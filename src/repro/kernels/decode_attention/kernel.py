"""Pallas TPU flash-decode kernel: one new token vs a long KV cache.

This is the hot spot PICE's sketch-shortening targets: at 32k context the
paper measures KV-cache reads at >50% of decode latency. On TPU the decode
step is HBM-bandwidth-bound — each generated token must stream the entire
(B, S, Hkv, hd) cache HBM->VMEM. The kernel:

  * processes all `q_per_kv` query heads of one KV head together, so each
    streamed KV block is reused q_per_kv times (GQA arithmetic-intensity win;
    the GPU analogue reuses via shared memory, here it is one VMEM tile);
  * walks the cache in (block_s, hd) VMEM tiles along the sequential minor
    grid axis with a running-softmax scratch (flash-decode);
  * prunes tail blocks past `lengths` with pl.when (ragged batches read only
    ceil(len / block_s) blocks);
  * a final block that overhangs S (S not a multiple of block_s) is masked
    in-kernel, NOT absorbed by shrinking block_s — e.g. S=300 must tile as
    2x256-class blocks, not 75 blocks of 4.

Grid: (B, Hkv, ceil(S / block_s)).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

NEG_INF = -1e30


def _dec_kernel(len_ref,                       # scalar prefetch: (B,) lengths
                q_ref, k_ref, v_ref, o_ref,
                m_scr, l_scr, acc_scr,
                *, ns: int, bs: int, scale: float):
    b = pl.program_id(0)
    si = pl.program_id(2)

    @pl.when(si == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = len_ref[b]
    s_start = si * bs

    @pl.when(s_start < length)
    def _body():
        kpos = s_start + jax.lax.broadcasted_iota(jnp.int32, (bs, 1), 0)
        valid = kpos < length                       # (bs, 1)
        q = q_ref[0, 0].astype(jnp.float32)         # (q_per_kv, hd)
        # zero invalid rows BEFORE the matmul: a ragged final block (S not a
        # multiple of bs) overhangs the cache and reads unspecified padding
        # that must not reach the MXU as NaN/inf
        k = jnp.where(valid, k_ref[0, 0].astype(jnp.float32), 0.0)
        v = jnp.where(valid, v_ref[0, 0].astype(jnp.float32), 0.0)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        s = jnp.where(valid[:, 0][None, :], s, NEG_INF)

        m_prev = m_scr[...][:, 0]
        l_prev = l_scr[...][:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = (l_prev * alpha + jnp.sum(p, axis=1))[:, None]
        acc_scr[...] = acc_scr[...] * alpha[:, None] + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_scr[...] = m_new[:, None]

    @pl.when(si == ns - 1)
    def _finish():
        l = l_scr[...][:, 0]
        denom = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scr[...] / denom[:, None]).astype(o_ref.dtype)


def decode_attention_pallas(q, k_cache, v_cache, lengths, *, block_s: int = 256,
                            interpret: bool):
    """q: (B,1,Hq,hd); k/v_cache: (B,S,Hkv,hd); lengths (B,). -> (B,1,Hq,hd)."""
    B, _, Hq, hd = q.shape
    S, Hkv = k_cache.shape[1], k_cache.shape[2]
    rep = Hq // Hkv
    # a non-power-of-two S keeps the full block size; the overhanging final
    # block is masked in-kernel (shrinking bs here degraded S=300 to bs=4)
    bs = min(block_s, S)
    ns = -(-S // bs)

    # (B, Hkv, q_per_kv, hd): group q heads by their kv head
    qg = q[:, 0].reshape(B, Hkv, rep, hd)
    kf = jnp.moveaxis(k_cache, 2, 1)               # (B, Hkv, S, hd)
    vf = jnp.moveaxis(v_cache, 2, 1)

    kernel = functools.partial(_dec_kernel, ns=ns, bs=bs,
                               scale=1.0 / float(hd) ** 0.5)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, Hkv, ns),
        in_specs=[
            pl.BlockSpec((1, 1, rep, hd), lambda b, h, s, *_: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bs, hd), lambda b, h, s, *_: (b, h, s, 0)),
            pl.BlockSpec((1, 1, bs, hd), lambda b, h, s, *_: (b, h, s, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, rep, hd), lambda b, h, s, *_: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((rep, 1), jnp.float32),
            pltpu.VMEM((rep, 1), jnp.float32),
            pltpu.VMEM((rep, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, rep, hd), q.dtype),
        interpret=interpret,
    )(lengths.astype(jnp.int32), qg, kf, vf)
    return out.reshape(B, 1, Hq, hd)
