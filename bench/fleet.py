"""Build the system under test from a configuration file.

A configuration file (bench/configs/<name>.json) lists its members: each a
published model config (`hf`, the keys of its config.json), the seed offset
of its weights, and `program`, the serving system's own model-config fields
the published config does not state. `serving` gives the engine settings.
`kind` is "pice_fleet" (a cloud LLM plus edge SLMs behind `PICEPipeline`,
built as `python -m repro.launch.serve` builds them) or "engine" (one
`InferenceEngine` behind its `EngineFrontend`).

Weights come from the configuration's reference module (`make_params`):
the benchmark makes them from the seed in the served dtype, in one jitted
call per member, so the plain reference can rebuild the same weights after
the program's state is freed without taking anything the program made.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Dict

import jax
import jax.numpy as jnp

from bench import spec as spec_lib


@dataclasses.dataclass
class Member:
    name: str
    hf: dict
    cfg: object                 # the program's ModelConfig
    seed_offset: int
    capability: float
    check: dict


@dataclasses.dataclass
class System:
    kind: str
    members: Dict[str, Member]
    engines: Dict[str, object]
    ref: object                 # the configuration's reference module
    cloud: str = ""
    pipeline: object = None
    frontend: object = None     # "engine" kind: the one front-end
    times: Dict[str, float] = dataclasses.field(default_factory=dict)

    def free(self):
        """Drop every reference to the program's device state."""
        self.engines.clear()
        self.pipeline = self.frontend = None


def model_config(member: dict, serving: dict, ref):
    from repro.models.config import ModelConfig
    kw = dict(family="dense", **ref.program_kwargs(member["hf"]))
    kw.update(member.get("program", {}))
    kw.update(param_dtype=serving["param_dtype"],
              use_pallas=serving["use_pallas"],
              prefill_chunk=serving["prefill_chunk"])
    return ModelConfig(**kw)


def members_of(config: dict) -> Dict[str, Member]:
    ref = spec_lib.load_module("reference", config["reference"])
    out = {}
    for m in config["members"]:
        cfg = model_config(m, config["serving"], ref)
        out[m["name"]] = Member(m["name"], m["hf"], cfg, m["seed_offset"],
                                m.get("capability", 0.5), m.get("check", {}))
    return out


def check_pairing(config: dict, members: Dict[str, Member]) -> None:
    """A configuration that names a launcher pairing must be that pairing:
    the same members, model configs and engine sizes."""
    from repro.configs.pice_cloud_edge import PAIRINGS
    from repro.launch import serve
    pair = PAIRINGS[config["pairing"]]

    def need(ok, what):
        if not ok:
            raise ValueError(f"configuration {config['name']!r} is not the "
                             f"{config['pairing']!r} pairing: {what}")
    need(set(pair.members) == set(members), "members differ")
    need(pair.cloud == config["cloud"], "cloud differs")
    for name, fm in pair.members.items():
        m = members[name]
        need(m.cfg.with_(source=fm.cfg.source) == fm.cfg, f"{name} config")
        need((m.seed_offset, m.capability) == (fm.seed, fm.capability),
             f"{name} seed offset or capability")
    s = config["serving"]
    need((s["max_batch"], s["max_len"]) == (serve.MAX_BATCH, serve.MAX_LEN),
         "engine sizes differ")


@functools.lru_cache(maxsize=None)
def param_shapes(cfg):
    from repro.models import transformer
    return jax.eval_shape(functools.partial(transformer.init_params, cfg),
                          jax.random.PRNGKey(0))


def make_params(system_or_ref, member: Member, seed: int):
    ref = getattr(system_or_ref, "ref", system_or_ref)
    return ref.make_params(param_shapes(member.cfg), seed + member.seed_offset)


def warm_first_token(eng, ingest_rows) -> None:
    """Run once, on throwaway arrays, the eager ops the engine's ragged
    ingest applies to a finished prompt's logits: a key split, a one-row
    slice of each row bucket's logits, the sample and its log-prob. The
    engine's `warmup` compiles only its jitted step variants, so these would
    otherwise first be built inside the window."""
    from repro.serving.sampler import sample, token_logprob
    V = eng.cfg.vocab_size
    key, sub = jax.random.split(jax.random.PRNGKey(0))
    for rb in sorted({min(1 << max(0, n - 1).bit_length(), eng.max_batch)
                      for n in ingest_rows}):
        row = jnp.zeros((rb, V), eng.cfg.dtype)[0:1]
        tok = sample(row, sub, eng.sampler)
        jax.block_until_ready(token_logprob(row, tok))


def build(config: dict, traffic: dict, seed: int, log=print,
          frontend_cls=None, probe=None) -> System:
    """Engines (weights from `seed`), warmed for the traffic's shapes, and
    for a PICE fleet the pipeline `serve.build_pipeline` wires, with its
    profiling. `frontend_cls(engine, monitor, queue_max)` makes the
    front-ends the window drives."""
    from repro.serving.engine import InferenceEngine
    from repro.serving.frontend import EngineFrontend
    frontend_cls = frontend_cls or (
        lambda eng, monitor=None, queue_max=64:
        EngineFrontend(eng, monitor=monitor, queue_max=queue_max))
    members = members_of(config)
    if config.get("pairing"):
        check_pairing(config, members)
    ref = spec_lib.load_module("reference", config["reference"])
    s = config["serving"]
    times: Dict[str, float] = {}
    engines = {}
    t = time.perf_counter()
    for name, m in members.items():
        params = make_params(ref, m, seed)
        engines[name] = InferenceEngine(
            m.cfg, params, max_batch=s["max_batch"], max_len=s["max_len"],
            name=name, kv_backend=s["kv_backend"], page_size=s["page_size"])
        if probe is not None:
            probe.instrument(engines[name])
    jax.block_until_ready([e.params for e in engines.values()])
    times["weights_s"] = time.perf_counter() - t
    warm = traffic["warm"]
    t = time.perf_counter()
    for eng in engines.values():
        eng.warmup(max_context=warm["max_context"],
                   ingest_rows=tuple(warm["ingest_rows"]))
        warm_first_token(eng, warm["ingest_rows"])
    jax.block_until_ready([e.cache for e in engines.values()])
    times["warmup_s"] = time.perf_counter() - t
    system = System(config["kind"], members, engines, ref,
                    cloud=config.get("cloud", ""), times=times)
    if config["kind"] == "pice_fleet":
        from repro.launch import serve
        t = time.perf_counter()
        caps = {n: m.capability for n, m in members.items()}
        pipe = serve.build_pipeline(engines, caps, cloud=config["cloud"],
                                    log_fn=log)
        times["profile_s"] = time.perf_counter() - t
        # the pipeline accepts ready-made front-ends: hand it the
        # benchmark's, on the same engines and the pipeline's own monitor
        pipe.cloud = frontend_cls(engines[config["cloud"]], pipe.monitor)
        pipe.edges = {n: frontend_cls(e, pipe.monitor)
                      for n, e in engines.items() if n != config["cloud"]}
        system.pipeline = pipe
    else:
        (eng,) = engines.values()
        system.frontend = frontend_cls(eng, None,
                                       traffic.get("queue_max", 64))
    return system


def engine_dims(system: System) -> Dict[str, dict]:
    """Per engine: the sizes the FLOP and byte functions need."""
    out = {}
    for name, eng in system.engines.items():
        c = eng.cfg
        out[name] = dict(
            n_layers=c.n_layers, d_model=c.d_model, n_heads=c.n_heads,
            n_kv_heads=c.n_kv_heads, head_dim=c.resolved_head_dim,
            d_ff=c.d_ff, vocab=c.vocab_size, page=eng.page_size,
            kv_itemsize=jax.numpy.dtype(c.resolved_kv_dtype).itemsize,
            act_itemsize=jax.numpy.dtype(c.dtype).itemsize,
            max_batch=eng.max_batch)
    return out
