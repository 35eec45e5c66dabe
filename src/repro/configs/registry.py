"""Architecture registry + input shape suite.

Each assigned architecture has a module `repro.configs.<id>` exposing CONFIG
(the exact full-size config, with its source citation) — registered here under
its public --arch id. `input_specs(cfg, shape)` builds ShapeDtypeStruct
stand-ins for every model input of a (config, input-shape) pair: weak-type
correct, shardable, no device allocation.
"""
from __future__ import annotations

import dataclasses
import importlib
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.models import transformer
from repro.models.config import ModelConfig

ARCH_IDS = (
    "whisper_tiny",
    "qwen3_8b",
    "mixtral_8x7b",
    "xlstm_1p3b",
    "qwen3_moe_30b_a3b",
    "granite_3_8b",
    "zamba2_2p7b",
    "internvl2_2b",
    "minitron_8b",
    "qwen2_1p5b",
    "deepseek_v2_lite",
)

# public --arch names (hyphenated, as assigned) -> module name
ALIASES = {
    "whisper-tiny": "whisper_tiny",
    "qwen3-8b": "qwen3_8b",
    "mixtral-8x7b": "mixtral_8x7b",
    "xlstm-1.3b": "xlstm_1p3b",
    "qwen3-moe-30b-a3b": "qwen3_moe_30b_a3b",
    "granite-3-8b": "granite_3_8b",
    "zamba2-2.7b": "zamba2_2p7b",
    "internvl2-2b": "internvl2_2b",
    "minitron-8b": "minitron_8b",
    "qwen2-1.5b": "qwen2_1p5b",
    "deepseek-v2-lite": "deepseek_v2_lite",
}


def get_config(arch: str) -> ModelConfig:
    mod_name = ALIASES.get(arch, arch.replace("-", "_").replace(".", "p"))
    mod = importlib.import_module(f"repro.configs.{mod_name}")
    return mod.CONFIG


def all_configs() -> Dict[str, ModelConfig]:
    return {a: get_config(a) for a in ALIASES}


# ---------------------------------------------------------------------------
# Input shapes (assigned suite)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class InputShape:
    name: str
    seq_len: int
    global_batch: int
    kind: str        # "train" | "prefill" | "decode"


SHAPES = {
    "train_4k": InputShape("train_4k", 4096, 256, "train"),
    "prefill_32k": InputShape("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": InputShape("decode_32k", 32768, 128, "decode"),
    "long_500k": InputShape("long_500k", 524288, 1, "decode"),
}


def shape_supported(cfg: ModelConfig, shape: InputShape) -> Optional[str]:
    """None if supported; else a reason string for the documented skip."""
    if shape.name == "long_500k":
        if cfg.family == "encdec":
            return ("whisper decoder is pure full-attention with a 30s-audio "
                    "448-token model card; no meaningful sub-quadratic variant "
                    "(documented skip in DESIGN.md)")
    return None


def adapt_for_shape(cfg: ModelConfig, shape: InputShape) -> ModelConfig:
    """Shape-specific config adaptation (documented in DESIGN.md):

    long_500k requires sub-quadratic decode state. SSM/hybrid archs are
    natively O(1)/windowed; mixtral already uses SWA. Pure full-attention
    dense archs switch to their sliding-window variant (window 4096) for this
    shape only.
    """
    if shape.name == "long_500k" and cfg.family in ("dense", "vlm") and not cfg.sliding_window:
        return cfg.with_(sliding_window=4096)
    return cfg


def input_specs(cfg: ModelConfig, shape: InputShape, spec: bool = True) -> dict:
    """ShapeDtypeStruct stand-ins for every input of the step function.

    Returns kwargs for train_step / prefill_step / decode_step respectively.
    """
    mk = (lambda sh, dt: jax.ShapeDtypeStruct(sh, dt)) if spec else (
        lambda sh, dt: jnp.zeros(sh, dt))
    B, S = shape.global_batch, shape.seq_len
    adt = jnp.dtype(cfg.dtype)
    out: dict = {}
    if shape.kind == "train":
        out["tokens"] = mk((B, S), jnp.int32)
        out["targets"] = mk((B, S), jnp.int32)
        if cfg.family == "encdec":
            e = cfg.encoder
            out["enc_frames"] = mk((B, e.n_ctx, e.d_model), adt)
        if cfg.family == "vlm" and cfg.n_prefix_tokens:
            out["prefix_embeds"] = mk((B, cfg.n_prefix_tokens, cfg.d_model), adt)
        return out
    if shape.kind == "prefill":
        out["tokens"] = mk((B, S), jnp.int32)
        out["prompt_lengths"] = mk((B,), jnp.int32)
        cache_len = S
        if cfg.family == "encdec":
            e = cfg.encoder
            out["enc_frames"] = mk((B, e.n_ctx, e.d_model), adt)
        if cfg.family == "vlm" and cfg.n_prefix_tokens:
            out["prefix_embeds"] = mk((B, cfg.n_prefix_tokens, cfg.d_model), adt)
            cache_len = S + cfg.n_prefix_tokens   # patch prefix lives in cache
        out["cache"] = transformer.init_cache(cfg, B, cache_len, spec=spec)
        return out
    # decode: ONE new token against a seq_len cache
    out["tokens"] = mk((B, 1), jnp.int32)
    out["cache"] = transformer.init_cache(cfg, B, S, spec=spec)
    return out
