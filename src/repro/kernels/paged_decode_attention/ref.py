"""Pure-jnp oracle for paged decode attention: gather-then-attend.

This is exactly the serving engine's fallback read path — materialize each
slot's block table into the contiguous layout, then run masked attention —
kept as the numerics contract for the Pallas kernel. The deliberate
inefficiency (reading the full table width per step) is what the kernel
removes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import paged_cache as pc

NEG_INF = -1e30


def _attend(q, gk, gv, lengths):
    """Masked single-token SDPA over gathered contiguous K/V (B, S, Hkv,
    hd); zero-length rows return zeros (matching the kernel), not the
    uniform-softmax garbage of an all-masked SDPA."""
    B, _, Hq, hd = q.shape
    rep = Hq // gk.shape[2]
    S = gk.shape[1]
    k = jnp.repeat(gk, rep, axis=2) if rep > 1 else gk
    v = jnp.repeat(gv, rep, axis=2) if rep > 1 else gv
    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32))
    logits = jnp.einsum("bqnh,bknh->bnqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale       # (B,Hq,1,S)
    mask = (jnp.arange(S)[None, :] < lengths[:, None])[:, None, None]
    logits = jnp.where(mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bnqk,bknh->bqnh", probs.astype(v.dtype), v)
    return jnp.where((lengths > 0)[:, None, None, None], out,
                     jnp.zeros_like(out))


def paged_decode_attention_ref(q, k_pages, v_pages, block_table, lengths,
                               layer=0):
    """q: (B,1,Hq,hd); k/v_pages: (L, n_pages, Hkv, page, hd), read at layer
    `layer`; block_table: (B, P) int32 (-1 = unmapped); lengths: (B,) valid
    token counts. Returns (B,1,Hq,hd); zero-length rows return zeros."""
    return _attend(q, pc.gather_sequence(k_pages, block_table, layer),
                   pc.gather_sequence(v_pages, block_table, layer), lengths)


def paged_decode_attention_quant_ref(q, k_pages, v_pages, k_scales, v_scales,
                                     block_table, lengths, layer=0):
    """Quantized-pool oracle: dequantize-gather into the contiguous f32
    layout, then attend exactly as the float oracle. Kernel-vs-this is a
    reduction-order comparison (tight tolerance); this-vs-the-float-pool
    oracle is the quantization tolerance contract (docs/serving.md)."""
    gk = pc.gather_sequence_dequant(k_pages, k_scales, block_table, layer)
    gv = pc.gather_sequence_dequant(v_pages, v_scales, block_table, layer)
    return _attend(q, gk, gv, lengths).astype(q.dtype)


def latent_attend(q, g, qpos, total, rank: int, scale: float):
    """Latent attention over gathered rows. q: (R, T, H, W) absorbed
    queries at positions qpos (R, T); g: (R, S, 1, W) gathered latent rows,
    keys admitted below `total` (R,) and at or before each query's
    position. -> (R, T, H, rank) in f32; rows with no admitted key return
    zeros."""
    kv = g[:, :, 0].astype(jnp.float32)                       # (R, S, W)
    S = kv.shape[1]
    logits = jnp.einsum("rthw,rsw->rhts", q.astype(jnp.float32), kv) * scale
    kpos = jnp.arange(S)
    mask = ((kpos[None, None, :] <= qpos[:, :, None])
            & (kpos[None, None, :] < total[:, None, None]))[:, None]
    logits = jnp.where(mask, logits, NEG_INF)
    probs = jnp.where(mask, jax.nn.softmax(logits, axis=-1), 0.0)
    return jnp.einsum("rhts,rsc->rthc", probs, kv[..., :rank])


def mla_paged_decode_ref(q, pages, block_table, lengths, layer=0, *,
                         rank: int, scale: float):
    """Oracle of the latent-mode decode kernel: gather the rows, attend."""
    g = pc.gather_sequence(pages, block_table, layer)
    qpos = (lengths - 1)[:, None]
    return latent_attend(q, g, qpos, lengths, rank, scale).astype(q.dtype)
