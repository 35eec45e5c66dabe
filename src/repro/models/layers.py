"""Common neural-net layers (pure JAX): norms, RoPE, MLPs, embeddings."""
from __future__ import annotations


import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig


def _dt(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


# ---------------------------------------------------------------------------
# Initializers
# ---------------------------------------------------------------------------

def dense_init(key, shape, in_axis: int = 0, dtype=jnp.float32):
    fan_in = shape[in_axis]
    scale = 1.0 / jnp.sqrt(jnp.asarray(fan_in, jnp.float32))
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def embed_init(key, shape, dtype=jnp.float32):
    return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def rmsnorm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32)).astype(dtype)


def layernorm(x: jax.Array, scale: jax.Array, bias: jax.Array, eps: float = 1e-5) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dtype)


def norm(cfg: ModelConfig, params: dict, x: jax.Array) -> jax.Array:
    if cfg.use_layernorm:
        return layernorm(x, params["scale"], params["bias"], cfg.norm_eps)
    return rmsnorm(x, params["scale"], cfg.norm_eps)


def init_norm(cfg: ModelConfig, d: int) -> dict:
    p = {"scale": jnp.ones((d,), jnp.dtype(cfg.param_dtype))}
    if cfg.use_layernorm:
        p["bias"] = jnp.zeros((d,), jnp.dtype(cfg.param_dtype))
    return p


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------

def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, hd); positions: broadcastable to (..., S)."""
    hd = x.shape[-1]
    freqs = rope_frequencies(hd, theta)                        # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    angles = angles[..., None, :]                              # (..., S, 1, hd/2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def init_mlp(cfg: ModelConfig, key, d_model: int, d_ff: int, gated: bool = True) -> dict:
    pd = jnp.dtype(cfg.param_dtype)
    k1, k2, k3 = jax.random.split(key, 3)
    if gated:
        return {
            "w_gate": dense_init(k1, (d_model, d_ff), dtype=pd),
            "w_up": dense_init(k2, (d_model, d_ff), dtype=pd),
            "w_down": dense_init(k3, (d_ff, d_model), dtype=pd),
        }
    return {
        "w_up": dense_init(k1, (d_model, d_ff), dtype=pd),
        "b_up": jnp.zeros((d_ff,), pd),
        "w_down": dense_init(k2, (d_ff, d_model), dtype=pd),
        "b_down": jnp.zeros((d_model,), pd),
    }


def mlp(cfg: ModelConfig, params: dict, x: jax.Array) -> jax.Array:
    dt = x.dtype
    if "w_gate" in params:  # SwiGLU
        g = x @ params["w_gate"].astype(dt)
        u = x @ params["w_up"].astype(dt)
        h = jax.nn.silu(g) * u
        return h @ params["w_down"].astype(dt)
    h = x @ params["w_up"].astype(dt) + params["b_up"].astype(dt)
    h = jax.nn.gelu(h)
    return h @ params["w_down"].astype(dt) + params["b_down"].astype(dt)


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------

def init_embedding(cfg: ModelConfig, key) -> dict:
    pd = jnp.dtype(cfg.param_dtype)
    k1, k2 = jax.random.split(key)
    p = {"tok": embed_init(k1, (cfg.vocab_size, cfg.d_model), pd)}
    if not cfg.tie_embeddings:
        p["unembed"] = dense_init(k2, (cfg.d_model, cfg.vocab_size), dtype=pd)
    return p


def embed(cfg: ModelConfig, params: dict, tokens: jax.Array) -> jax.Array:
    # gather, then cast: casting first converts the whole (vocab, d) table
    return params["tok"][tokens].astype(_dt(cfg))


def unembed(cfg: ModelConfig, params: dict, x: jax.Array) -> jax.Array:
    if cfg.tie_embeddings:
        w = params["tok"].astype(x.dtype).T
    else:
        w = params["unembed"].astype(x.dtype)
    return x @ w
