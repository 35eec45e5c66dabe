"""The one traffic generator: a mix file (bench/traffic/<mix>.json) in, a
seeded plan of requests out.

Every seed gets the same requests in the same order and, for an open
loop, the same arrival times: all are drawn from the mix's fixed
`size_seed`. The run's seed draws only the token ids (and, elsewhere, the
weights), so runs with different seeds do the same work under the same
bursts. (An order drawn from the seed moved the PICE cell's latencies by
10-20% from seed to seed: which long query meets which burst decides the
queue.)

Arrivals (`arrival`):
  {"process": "poisson", "rate_per_s": r}  open loop: exponential gaps at
      evenly spaced quantiles (a Poisson stream's gaps, stratified), due
      times measured from the window's opening;
  {"process": "closed", "clients": n}      closed loop: n clients, each
      sending its next request when the last one completes.

Requests (`requests.kind`):
  "pice":   user queries for `PICEPipeline.handle_async`, made from
            `template` with one word group from each of `slots`;
  "fanout": an expansion task as the pipeline hands it to an edge engine:
            a shared prefix and 2..n suffix groups, all with ids in
            `token_ids`, `max_new` = per_suffix_token x longest suffix +
            plus (the pipeline's own rule);
  "chat":   independent completions, prompt and output lengths
            log-uniform, ids over `token_ids` ("vocab": the whole
            vocabulary but id 0, the engine's end of sequence).
"""
from __future__ import annotations

import dataclasses
import math
import random
from typing import List, Optional

import numpy as np


@dataclasses.dataclass
class Item:
    due_s: Optional[float]      # offset from the window's opening (open loop)
    payload: dict


def _log_uniform_int(rng: random.Random, lo: int, hi: int) -> int:
    return int(round(math.exp(rng.uniform(math.log(lo), math.log(hi)))))


def _sizes(req: dict, n: int, size_seed: int) -> List[dict]:
    """The fixed set of n request sizes of this mix."""
    rng = random.Random(size_seed)
    kind = req["kind"]
    out = []
    for _ in range(n):
        if kind == "pice":
            words = [rng.choice(group) for group in req["slots"]]
            out.append({"query": req["template"].format(*words)})
        elif kind == "fanout":
            g = rng.randint(*req["groups"])
            out.append({
                "prefix": rng.randint(*req["prefix_tokens"]),
                "suffixes": [rng.randint(*req["suffix_tokens"])
                             for _ in range(g)]})
        elif kind == "chat":
            draw = (_log_uniform_int if req["distribution"] == "log_uniform"
                    else random.Random.randint)
            out.append({"prompt": draw(rng, *req["prompt_tokens"]),
                        "output": draw(rng, *req["output_tokens"])})
        else:
            raise ValueError(f"unknown request kind {kind!r}")
    return out


def _payload(req: dict, size: dict, ids: np.random.Generator,
             vocab: int) -> dict:
    kind = req["kind"]
    if kind == "pice":
        return {"query": size["query"], "category": req["category"],
                "max_new_tokens": req["max_new_tokens"]}
    lo, hi = (1, vocab - 1) if req["token_ids"] == "vocab" \
        else req["token_ids"]

    def toks(n):
        return ids.integers(lo, hi + 1, n).tolist()
    if kind == "fanout":
        rule = req["max_new"]
        return {"prefix": toks(size["prefix"]),
                "suffixes": [toks(n) for n in size["suffixes"]],
                "max_new": int(rule["per_suffix_token"]
                               * max(size["suffixes"])) + rule["plus"]}
    return {"prompt": toks(size["prompt"]), "max_tokens": size["output"]}


def arrivals(rate: float, seconds: float, size_seed: int) -> List[float]:
    """Due times in [0, seconds) of a stratified Poisson stream: gaps at
    evenly spaced quantiles of the exponential, in an order fixed by
    `size_seed`."""
    n = max(1, int(round(rate * seconds)))
    gaps = [-math.log(1.0 - (i + 0.5) / n) / rate for i in range(n)]
    random.Random(size_seed).shuffle(gaps)
    due, t = [], 0.0
    for g in gaps:
        if t >= seconds:
            break
        due.append(t)
        t += g
    return due


def plan(traffic: dict, seed: int, seconds: float, vocab: int,
         rate: Optional[float] = None) -> List[Item]:
    """The requests of one run. Open loop: one per arrival in the window.
    Closed loop: a pool of `pool` requests the clients cycle through."""
    arr = traffic["arrival"]
    size_seed = traffic["size_seed"]
    if arr["process"] == "poisson":
        due = arrivals(rate or arr["rate_per_s"], seconds, size_seed)
    elif arr["process"] == "closed":
        due = [None] * traffic["pool"]
    else:
        raise ValueError(f"unknown arrival process {arr['process']!r}")
    sizes = _sizes(traffic["requests"], len(due), size_seed)
    ids = np.random.default_rng(seed)
    return [Item(d, _payload(traffic["requests"], s, ids, vocab))
            for d, s in zip(due, sizes)]
