"""PICE serving launcher: build the cloud engine + edge fleet of a named
pairing and run the progressive pipeline on a stream of requests.

  PYTHONPATH=src python -m repro.launch.serve --requests 8 [--train-steps 150]
  python -m repro.launch.serve --pairing one-chip --requests 4 --train-steps 0

`--pairing` names an entry of `configs.pice_cloud_edge.PAIRINGS`: "tiny"
(the default, runnable on a CPU) or "one-chip" (qwen3-8b cut to 8 layers
plus two qwen2-1.5b edges at published widths, for one TPU v5e chip). With
--train-steps > 0 the models are first trained on the synthetic corpus so
sketches/expansions are meaningful (quality metrics are reported against the
corpus ground truth); with 0 they serve seeded random weights, and no
optimizer state is ever built.
"""
from __future__ import annotations

import argparse
import time
from typing import Union

import jax

from repro.configs.pice_cloud_edge import PAIRINGS, Pairing
from repro.core import metrics as metrics_lib
from repro.core.profiler import cost_coefficient, profile_engine
from repro.core.progressive import PICEConfig, PICEPipeline
from repro.core.scheduler import EdgeModelInfo
from repro.data import corpus as corpus_lib
from repro.data.pipeline import PackedDataset
from repro.models import transformer
from repro.serving.engine import InferenceEngine
from repro.serving.requests import Request
from repro.training import optimizer as opt_lib
from repro.training.train_loop import init_train_state, train

# every engine of a fleet: decode batch slots and context tokens per slot
MAX_BATCH = 8
MAX_LEN = 1024


def build_engines(pairing: Union[str, Pairing] = "tiny", train_steps: int = 0,
                  seed: int = 0, log_fn=print, names=None,
                  kv_backend: str = "paged"):
    """One InferenceEngine per member of `pairing` (a PAIRINGS name or a
    Pairing). Returns (engines, capabilities), both keyed by engine name.
    Serving builds params only, in the config's param_dtype; the AdamW
    state exists only while training."""
    pair = PAIRINGS[pairing] if isinstance(pairing, str) else pairing
    engines, caps = {}, {}
    text = corpus_lib.lm_text(2000, seed) if train_steps else ""
    init = jax.jit(transformer.init_params, static_argnums=0)
    for name, member in pair.members.items():
        if names and name not in names:
            continue
        cfg, mseed = member.cfg, seed + member.seed
        if train_steps:
            state = init_train_state(cfg, mseed)
            ds = PackedDataset(text, 192, 8, seed)
            opt_cfg = opt_lib.AdamWConfig(lr=2e-3, warmup_steps=20,
                                          total_steps=train_steps)
            log_fn(f"-- training {name} for {train_steps} steps")
            params = train(cfg, state, iter(ds), opt_cfg, train_steps,
                           log_every=max(train_steps // 2, 1),
                           log_fn=log_fn).params
        else:
            params = init(cfg, jax.random.PRNGKey(mseed))
        engines[name] = InferenceEngine(cfg, params, max_batch=MAX_BATCH,
                                        max_len=MAX_LEN, name=name,
                                        kv_backend=kv_backend)
        caps[name] = member.capability
    return engines, caps


def build_pipeline(engines, caps, cloud: str = "tiny-cloud", log_fn=print,
                   profile_lengths=(8, 16, 32)) -> PICEPipeline:
    """Profile every engine, then wire the PICE pipeline with `cloud` as
    the sketching LLM and the other engines as the edge fleet."""
    lm_cloud = profile_engine(engines[cloud], lengths=profile_lengths,
                              name=cloud)
    infos = []
    for name, eng in engines.items():
        if name == cloud:
            continue
        lm = profile_engine(eng, lengths=profile_lengths, name=name)
        c = cost_coefficient(lm_cloud, lm)
        log_fn(f"profiled {name}: rate={lm.rate:.1f} tok/s, c={c:.2f}")
        infos.append(EdgeModelInfo(name=name, latency=lm,
                                   capability=caps.get(name, 0.5)))
    edge_engines = {k: v for k, v in engines.items() if k != cloud}
    return PICEPipeline(engines[cloud], edge_engines, lm_cloud, infos,
                        cfg=PICEConfig(ensemble_size=2))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--pairing", choices=sorted(PAIRINGS), default="tiny")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--train-steps", type=int, default=150)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--kv-backend", choices=("dense", "paged"),
                    default="paged",
                    help="KV cache backend (paged = on-demand page pool)")
    args = ap.parse_args()

    engines, caps = build_engines(args.pairing, args.train_steps, args.seed,
                                  kv_backend=args.kv_backend)
    pipe = build_pipeline(engines, caps, cloud=PAIRINGS[args.pairing].cloud)
    examples = corpus_lib.corpus(args.requests, seed=args.seed + 7)
    t0 = time.time()
    quality = []
    for ex in examples:
        resp = pipe.handle(Request(query=ex.query, category=ex.category))
        q = metrics_lib.rouge_1(ex.answer, resp.text)[2]
        quality.append(q)
        print(f"[{resp.mode:12s}] lat={resp.latency_s:5.2f}s "
              f"cloud={resp.cloud_tokens:4d}t edge={resp.edge_tokens:4d}t "
              f"rouge1-f1={q:.3f} | {resp.text[:60]!r}")
    dt = time.time() - t0
    print(f"\n{args.requests} requests in {dt:.1f}s "
          f"({60*args.requests/dt:.1f} req/min); "
          f"mean quality={sum(quality)/len(quality):.3f}; stats={pipe.stats}")


if __name__ == "__main__":
    main()
