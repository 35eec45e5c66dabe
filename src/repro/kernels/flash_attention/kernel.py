"""Pallas TPU flash attention (prefill): causal / sliding-window, GQA.

TPU adaptation of the FlashAttention-2 schedule: the KV loop is the minor
(sequential) grid axis; a VMEM scratch holds the running (m, l, acc) softmax
state per Q block — TPU grids execute minor-to-major in order, which replaces
the GPU's per-SM software loop. Block sizes default to (128, 128), matching
the MXU's 128x128 systolic tile; the (Bq, hd) accumulator and the (Bq, Bkv)
logits tile both live in VMEM.

Sliding-window support prunes KV blocks entirely outside the window at the
grid level (they are masked, contributing nothing) — with window w, only
ceil(w / Bkv) + 1 KV blocks per Q block do real work.

Grid: (B * Hq, nQ, nKV).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

NEG_INF = -1e30


def _fa_kernel(q_ref, k_ref, v_ref, o_ref,
               m_scr, l_scr, acc_scr,
               *, nkv: int, bq: int, bkv: int, causal: bool, window: int,
               softcap: float, scale: float):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * bq
    k_start = ki * bkv

    # Skip blocks that are fully masked (strictly above the diagonal, or
    # entirely left of the sliding window).
    run = ki >= 0
    if causal:
        run = run & (k_start <= q_start + bq - 1)
    if window:
        run = run & (k_start + bkv - 1 > q_start - window)

    @pl.when(run)
    def _body():
        q = q_ref[0].astype(jnp.float32)               # (bq, hd)
        k = k_ref[0].astype(jnp.float32)               # (bkv, hd)
        v = v_ref[0].astype(jnp.float32)               # (bkv, hd)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        if softcap:
            s = jnp.tanh(s / softcap) * softcap
        rows = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
        cols = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
        mask = jnp.ones((bq, bkv), jnp.bool_)
        if causal:
            mask = cols <= rows
        if window:
            mask = mask & (cols > rows - window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...][:, 0]                      # (bq,)
        l_prev = l_scr[...][:, 0]
        m_cur = jnp.max(s, axis=1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=1)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_scr[...] = m_new[:, None]
        l_scr[...] = l_new[:, None]

    @pl.when(ki == nkv - 1)
    def _finish():
        l = l_scr[...][:, 0]
        denom = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[...] / denom[:, None]).astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, *, causal: bool = True, window: int = 0,
                           softcap: float = 0.0, block_q: int = 128,
                           block_kv: int = 128, interpret: bool):
    """q: (B,S,Hq,hd), k/v: (B,S,Hkv,hd) -> (B,S,Hq,hd)."""
    B, S, Hq, hd = q.shape
    Hkv = k.shape[2]
    rep = Hq // Hkv
    bq = min(block_q, S)
    while S % bq:
        bq //= 2
    bkv = min(block_kv, S)
    while S % bkv:
        bkv //= 2
    nq, nkv = S // bq, S // bkv

    qf = jnp.moveaxis(q, 2, 1).reshape(B * Hq, S, hd)
    kf = jnp.moveaxis(k, 2, 1).reshape(B * Hkv, S, hd)
    vf = jnp.moveaxis(v, 2, 1).reshape(B * Hkv, S, hd)

    kernel = functools.partial(
        _fa_kernel, nkv=nkv, bq=bq, bkv=bkv, causal=causal, window=window,
        softcap=softcap, scale=1.0 / float(hd) ** 0.5)

    out = pl.pallas_call(
        kernel,
        grid=(B * Hq, nq, nkv),
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda g, qi, ki: (g, qi, 0)),
            pl.BlockSpec((1, bkv, hd), lambda g, qi, ki, rep=rep: (g // rep, ki, 0)),
            pl.BlockSpec((1, bkv, hd), lambda g, qi, ki, rep=rep: (g // rep, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda g, qi, ki: (g, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B * Hq, S, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf)
    return jnp.moveaxis(out.reshape(B, Hq, S, hd), 1, 2)
