"""Decide `correct`: the window's own served tokens against the plain
reference.

After the window has closed, the device peak has been read and the
program's state is freed, a sample of the requests the window finished is
drawn from the seed, always with the request that served the most tokens.
For each engine, every call those requests made to it (the prompt it was
given and the tokens it answered) is run once through the configuration's
float32 reference, one sequence at a time, and each served token's
reference logit is compared with the reference's best logit at that
position. The traffic decodes greedily, so a sound engine serves the
reference's best token up to rounding; the number compared, per engine, is
the widest such gap in the sample (`gap.<engine>`), against the limit the
configuration file states for that member.

With `control=True` the control takes the program's place: the same pass
also runs the reference in float8 (bench/reference), and the number
compared is the float32 gap of the token the float8 forward ranks first at
each of the same positions, against the same limit. A control that the
limit does not fail makes the limit worthless, so a control run must come
out not correct (bench/control.py).
"""
from __future__ import annotations

import functools
import json
import random
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def sample(served: Dict[int, list], finished: List[int], seed: int,
           k: int) -> List[int]:
    """k finished requests drawn from `seed`, the longest among them."""
    done = [i for i in finished if served.get(i)]
    if not done:
        return []
    longest = max(done, key=lambda i: (sum(len(t) for *_, t in served[i]),
                                       -i))
    rest = sorted(set(done) - {longest})
    random.Random(seed).shuffle(rest)
    return [longest] + rest[:k - 1]


def sequences(served: Dict[int, list], picked: List[int]
              ) -> Dict[str, List[Tuple[List[int], List[int]]]]:
    """engine -> [(prompt, served tokens)] of the picked requests."""
    out: Dict[str, list] = {}
    for i in picked:
        for engine, _, prompt, toks in served[i]:
            if toks:
                out.setdefault(engine, []).append((prompt, toks))
    return out


def _bucket(n: int, lo: int = 128) -> int:
    b = lo
    while b < n:
        b *= 2
    return b


@functools.lru_cache(maxsize=None)
def _reader(ref, hf_json: str, control: bool):
    hf = json.loads(hf_json)

    def run(params, tokens, served):
        logits = ref.reference_logits(hf, params, tokens)
        best = jnp.max(logits, axis=-1)
        at = jnp.take_along_axis(logits, served[:, None], axis=-1)[:, 0]
        out = {"best": best, "served": at}
        if control:
            low = ref.reference_logits(hf, params, tokens, control=True)
            pick = jnp.argmax(low, axis=-1)
            out["control"] = jnp.take_along_axis(logits, pick[:, None],
                                                 axis=-1)[:, 0]
        return out
    return jax.jit(run)


def widest_gap(ref, hf: dict, params, seqs, control: bool = False
               ) -> Dict[str, float]:
    """Widest gap (reference best logit minus the logit of the token at
    hand) over every served token of `seqs`; with `control`, also of the
    float8 forward's first-ranked tokens. Returns the gaps and the count."""
    run = _reader(ref, json.dumps(hf, sort_keys=True), control)
    gap, gap_c, n = 0.0, 0.0, 0
    for prompt, toks in seqs:
        full = list(prompt) + list(toks[:-1])
        S = _bucket(len(full))
        x = np.zeros((S,), np.int32)
        x[:len(full)] = full
        nxt = np.zeros((S,), np.int32)       # token each position predicts
        nxt[len(prompt) - 1:len(full)] = toks
        r = jax.device_get(run(params, jnp.asarray(x), jnp.asarray(nxt)))
        sl = slice(len(prompt) - 1, len(full))
        gap = max(gap, float(np.max(r["best"][sl] - r["served"][sl])))
        if control:
            gap_c = max(gap_c, float(np.max(r["best"][sl] - r["control"][sl])))
        n += len(toks)
    out = {"gap": gap, "tokens": n}
    if control:
        out["control_gap"] = gap_c
    return out


def compare(system, served, picked, seed: int, control: bool = False
            ) -> Dict[str, dict]:
    """Per engine `gap.<engine>`: {"value", "limit", "tokens"}. With
    `control`, "value" is the control's gap and "program" keeps the
    program's own. Rebuilds each member's weights from the seed (the
    program's state is freed first by the caller) and drops them before
    the next member."""
    from bench import fleet
    out = {}
    seqs = sequences(served, picked)
    for name, m in system.members.items():
        if name not in seqs:
            continue
        params = fleet.make_params(system, m, seed)
        r = widest_gap(system.ref, m.hf, params, seqs[name], control)
        del params
        entry = {"value": r["gap"], "limit": m.check["logit_gap"],
                 "tokens": r["tokens"]}
        if control:
            entry.update(value=r["control_gap"], program=r["gap"])
        out[f"gap.{name}"] = entry
    return out
