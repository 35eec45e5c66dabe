"""Smoke run of the PICE serving path on one TPU chip at published widths.

    python chip_smoke.py

Builds the "one-chip" pairing (qwen3-8b cut to 8 of its 36 layers as the
cloud LLM, two qwen2-1.5b edge SLMs) with seeded random weights through
`repro.launch.serve.build_engines` / `build_pipeline`, the code
`python -m repro.launch.serve` runs, and serves a few seeded requests
through `PICEPipeline.handle`. It fails unless every engine reads attention
through compiled Pallas kernels, some response took the progressive path
with edge tokens, no response was degraded, no edge member failed, every
engine generated tokens, and prefill plus a few decode steps of the cloud
and an edge engine agree with a float32 `transformer.forward` reference
that a wrong KV page would visibly move.

It runs in one process that owns the chip and starts no other. It refuses
to run without a TPU (it never falls back to the CPU or to Pallas interpret
mode). Every time it prints is a smoke reading, not a metric. The last line
of its output is the JSON result; nothing follows it.

Compiled programs go to $JAX_COMPILATION_CACHE_DIR when that is set, else
to `.jax_cache/` beside this file.
"""
from __future__ import annotations

import json
import os
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent
sys.path.insert(0, str(REPO / "src"))

SEED = 0
PAIRING = "one-chip"
N_REQUESTS = 4
# Request budget: the corpus "writing" queries predict ~262 tokens, so the
# predicted length is this cap, well above PICEConfig.short_answer_tokens
# (48): the scheduler then weighs the progressive path for every request.
MAX_NEW_TOKENS = 256
# Decode live widths the serving window reaches (prompts of ~50 tokens plus
# at most MAX_NEW_TOKENS generated, and the 168-token reference sequence);
# warmup compiles their buckets up front.
WARM_CONTEXT = 512
# Reference check: a 160-token prompt is two 128-token ingest chunks (the
# second reads the first through the paged kernel), then 8 decode steps.
REF_PROMPT_TOKENS = 160
REF_DECODE_STEPS = 8
# Largest |engine - reference| log-probability (nats) of the engine's own
# tokens. The engine computes in bfloat16 (8-bit mantissa: one rounding is a
# relative error up to 2**-9) with float32 accumulation, norms and softmax;
# the reference is float32 throughout at "highest" matmul precision. At
# these widths with seeded random weights, the same comparison on XLA:CPU
# (2 and 6 layers, 32768-token vocab) gave 0.009-0.022, flat in depth, and
# this smoke on one TPU v5e chip gave 0.0215 (cloud) and 0.0218 (edge): 0.1
# leaves ~4x room for that. The check must also see a read fault: a control
# replaces the prompt's first KV page with other tokens, which is what a
# kernel reading a wrong page would attend, and the smoke fails unless that
# moves the reference log-probabilities past the same tolerance.
LOGPROB_TOL = 0.1
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileLog:
    """Counts backend compiles (and their seconds) through jax.monitoring;
    `since(mark)` gives the count and seconds after an earlier `mark()`."""

    def __init__(self):
        import jax
        self.events = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.events.append(duration)

    def mark(self) -> int:
        return len(self.events)

    def since(self, mark: int):
        new = self.events[mark:]
        return len(new), sum(new)


def require_tpu():
    """The device JAX found, or exit non-zero when it is not a TPU."""
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (JAX platform is "
                 f"{dev.platform!r}); this smoke runs only on a TPU")
    from repro.kernels.runtime import default_interpret
    if default_interpret():
        sys.exit("chip_smoke: Pallas would run in interpret mode")
    return dev


def use_compile_cache():
    """$JAX_COMPILATION_CACHE_DIR when set (JAX reads it itself), else a
    fixed directory beside this file, so a rerun finds its programs."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        import jax
        jax.config.update("jax_compilation_cache_dir",
                          str(REPO / ".jax_cache"))


def smoke_requests(n: int, seed: int, max_new: int = MAX_NEW_TOKENS):
    """Seeded long-answer requests (corpus "writing" queries)."""
    from repro.data import corpus as corpus_lib
    from repro.serving.requests import Request
    return [Request(query=ex.query, category=ex.category,
                    max_new_tokens=max_new)
            for ex in corpus_lib.corpus(n, seed=seed, category="writing")]


def serve_all(pipe, requests):
    return [pipe.handle(r) for r in requests]


def pipeline_failures(pipe, engines, responses):
    """Every way the served run falls short of the smoke's contract."""
    bad = [f"{n} reads attention through the {e.read_path} path"
           + (f" ({e.read_path_note})" if e.read_path_note else "")
           for n, e in engines.items() if e.read_path != "pallas"]
    if not any(r.mode == "progressive" and r.edge_tokens > 0
               for r in responses):
        bad.append("no response took the progressive path with edge tokens")
    bad += [f"request {r.req_id} degraded: {r.degraded}"
            for r in responses if r.degraded]
    if pipe.monitor.edge_failures:
        bad.append(f"{pipe.monitor.edge_failures} edge member(s) failed")
    bad += [f"{n} generated no tokens" for n, e in engines.items()
            if e.tokens_generated <= 0]
    return bad


def reference_logprobs(cfg, params, tokens):
    """Float32 log p(tokens[t] | tokens[:t]) for t >= 1 from the plain
    `transformer.forward` (no kernels, no cache), at "highest" matmul
    precision so the TPU does not round the float32 matmuls to bf16. The
    served bf16 params are widened where they are used, so no float32 copy
    of the model sits beside the fleet's weights (compiled for v5e at the
    cloud model's width, this program needs ~0.1 GB of scratch)."""
    import jax
    import jax.numpy as jnp
    from repro.models import transformer
    rcfg = cfg.with_(dtype="float32", use_pallas=False)
    with jax.default_matmul_precision("highest"):
        logits, _ = transformer.forward(rcfg, params, tokens[None, :-1])
    logp = jax.nn.log_softmax(logits[0].astype(jnp.float32), axis=-1)
    return jnp.take_along_axis(logp, tokens[1:, None], axis=-1)[:, 0]


def reference_error(engine, n_prompt: int, n_decode: int, seed: int):
    """Serve one seeded prompt through `engine.generate` (chunked ingest,
    then fused decode steps). Returns the largest |engine - reference|
    log-probability over the tokens it generated; the largest shift of
    those reference log-probabilities when the prompt's first KV page holds
    other tokens (the control: what a wrong page read would do); and how
    many tokens it generated."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    vocab, page = engine.cfg.vocab_size, engine.page_size
    rng = np.random.default_rng(seed)
    prompt = rng.integers(1, vocab, n_prompt).tolist()
    (out, lps), = engine.generate([prompt], max_new=n_decode)
    seq = np.asarray(prompt + out, np.int32)
    wrong = seq.copy()
    wrong[:page] = rng.integers(1, vocab, page)
    ref_fn = jax.jit(reference_logprobs, static_argnums=0)
    ref, ref_wrong = (
        np.asarray(ref_fn(engine.cfg, engine.params, jnp.asarray(s)))
        [n_prompt - 1:n_prompt - 1 + len(out)] for s in (seq, wrong))
    return (float(np.max(np.abs(np.asarray(lps) - ref))),
            float(np.max(np.abs(ref_wrong - ref))), len(out))


def _param_bytes(params) -> int:
    import jax
    return sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(params))


def main() -> int:
    dev = require_tpu()
    use_compile_cache()
    import jax
    from repro.configs.pice_cloud_edge import PAIRINGS
    from repro.configs.registry import get_config
    from repro.launch import serve

    log = CompileLog()
    pair = PAIRINGS[PAIRING]
    reading = "smoke reading, not a metric"
    t0 = time.perf_counter()
    engines, caps = serve.build_engines(PAIRING, train_steps=0, seed=SEED)
    print(f"[{reading}] built {len(engines)} engines in "
          f"{time.perf_counter() - t0:.1f}s", flush=True)
    for name, eng in engines.items():
        cfg = eng.cfg
        mark, t = log.mark(), time.perf_counter()
        eng.warmup(max_context=WARM_CONTEXT)
        n_comp, s_comp = log.since(mark)
        print(f"engine {name}: config {cfg.name} d_model {cfg.d_model} "
              f"heads {cfg.n_heads}/{cfg.n_kv_heads} hd "
              f"{cfg.resolved_head_dim} vocab {cfg.vocab_size} layers "
              f"{cfg.n_layers}/{get_config(cfg.name).n_layers} held/published"
              f" | param bytes {_param_bytes(eng.params)} | read path "
              f"{eng.read_path} | [{reading}] warmup compiles {n_comp} "
              f"({s_comp:.1f}s compiling, {time.perf_counter() - t:.1f}s "
              f"wall) | device peak_bytes_in_use "
              f"{dev.memory_stats()['peak_bytes_in_use']}", flush=True)

    mark = log.mark()
    pipe = serve.build_pipeline(engines, caps, cloud=pair.cloud)
    responses = serve_all(pipe, smoke_requests(N_REQUESTS, SEED))
    for r in responses:
        print(f"request {r.req_id}: mode {r.mode} cloud_tokens "
              f"{r.cloud_tokens} edge_tokens {r.edge_tokens} degraded "
              f"{r.degraded or '-'} | [{reading}] latency {r.latency_s:.3f}s",
              flush=True)
    n_comp, s_comp = log.since(mark)
    print(f"[{reading}] compiles while profiling and serving: {n_comp} "
          f"({s_comp:.1f}s)", flush=True)
    bad = pipeline_failures(pipe, engines, responses)

    checked = [pair.cloud, next(iter(pair.edges))]
    for name in checked:
        err, shift, n = reference_error(engines[name], REF_PROMPT_TOKENS,
                                        REF_DECODE_STEPS, SEED)
        print(f"reference check {name}: {n} tokens after a "
              f"{REF_PROMPT_TOKENS}-token prompt, max |logprob error| "
              f"{err:.6f} nats (tolerance {LOGPROB_TOL}); control with a "
              f"wrong first KV page shifts them by {shift:.6f}", flush=True)
        if not err <= LOGPROB_TOL:
            bad.append(f"{name} log-probabilities off the float32 "
                       f"reference by {err:.6f} > {LOGPROB_TOL}")
        if not shift > LOGPROB_TOL:
            bad.append(f"{name}: a wrong first KV page shifts the reference "
                       f"log-probabilities by only {shift:.6f} <= "
                       f"{LOGPROB_TOL}, so the check cannot see a read fault")
        if n < REF_DECODE_STEPS:
            bad.append(f"{name} stopped after {n} of {REF_DECODE_STEPS} "
                       "reference tokens")
    print(f"device peak_bytes_in_use {dev.memory_stats()['peak_bytes_in_use']}"
          f" of bytes_limit {dev.memory_stats().get('bytes_limit')}",
          flush=True)
    if bad:
        for b in bad:
            print(f"FAIL: {b}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
