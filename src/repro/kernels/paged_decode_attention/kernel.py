"""Pallas TPU paged flash-decode kernel: one new token vs a paged KV pool.

The serving engine's paged backend (models/paged_cache.py) stores KV in a
per-layer page pool `(n_pages, page_size, n_kv, hd)` addressed through a
block table `(B, P)`. The jnp oracle first *gathers* every slot's full
table width into a contiguous `(B, P * page_size, n_kv, hd)` buffer — per
layer, per token, sized by the table width rather than actual lengths —
and only then attends. At long context that double-pays the PICE decode
hot spot (KV reads are >50% of decode latency); this kernel removes the
gather entirely:

  * `(block_table, lengths)` are scalar-prefetched, and the block table IS
    the K/V `index_map`: grid step (b, h, p) streams physical page
    `block_table[b, p]` HBM->VMEM directly from the pool. No contiguous
    copy ever exists.
  * steps past a slot's live pages re-map to its last live page — Pallas
    elides the DMA for a revisited block — and `pl.when` skips their
    compute, so per-step read volume is O(sum ceil(len/page)) pages, not
    O(B * max_pages_per_seq).
  * unmapped (-1) pages and in-page positions past `length` are pruned /
    masked; COW-shared pages (fan-out forks) are just page ids that happen
    to repeat across rows — each reader streams the page once, instead of
    the gather re-materializing it N times.
  * all `q_per_kv` query heads of one KV head ride each streamed page tile
    (same GQA arithmetic-intensity reuse as the dense decode kernel), with
    a running-softmax scratch accumulated across pages (flash-decode).
  * a quantized (int8/fp8) pool streams at its storage width and each page
    tile is dequantized in VMEM with its per-(page, kv-head) scale, which
    rides the same clamped index map as its page.

Grid: (B, Hkv, P) with P = block-table width (callers should pre-trim it
to the live width). Rows with length 0 return zeros.

The pool is read through `lane_view`, so one grid step's K/V tile is
`(page_size, hd)`: the TPU compiler accepts it when hd % 128 == 0 and
page_size % 8 == 0 (the engine checks both before it picks this path).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

NEG_INF = -1e30


def lane_view(pages):
    """(n_pages, page, Hkv, hd) -> (n_pages, page, Hkv * hd), a free
    reshape. KV head h becomes lane block h of width hd, so a
    (1, page, hd) block indexed (page_id, 0, h) is one head's page tile
    whose last two dims meet the TPU (8, 128) block rule; a (.., 1, hd)
    block over the Hkv axis does not."""
    n, ps, hkv, hd = pages.shape
    return pages.reshape(n, ps, hkv * hd)


def scale_view(scales):
    """(n_pages, Hkv) dequant scales -> (n_pages, 1, Hkv): a (1, 1, Hkv)
    block then spans whole trailing dims, which the TPU block rule accepts
    (a (1, 1) block of the 2-D tensor does not, in VMEM or SMEM)."""
    return scales.reshape(scales.shape[0], 1, scales.shape[1])


def head_scale(s_ref, h):
    """KV head h's scale from a (1, 1, Hkv) block, as a (1, 1) array
    (a masked lane reduction: no dynamic lane indexing)."""
    row = s_ref[0]                                            # (1, Hkv)
    hit = jax.lax.broadcasted_iota(jnp.int32, row.shape, 1) == h
    return jnp.sum(jnp.where(hit, row, 0.0), axis=1, keepdims=True)


def page_tile(ref, valid, s_ref=None, h=None):
    """One page's (ps, hd) K or V tile in f32, dequantized when `s_ref` is
    given, with rows past the valid range zeroed BEFORE the MXU: they hold
    stale pool bytes that must not reach it as NaN/inf."""
    x = ref[0].astype(jnp.float32)
    if s_ref is not None:
        x = x * head_scale(s_ref, h)
    return jnp.where(valid, x, 0.0)


def init_softmax(m_scr, l_scr, acc_scr):
    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)


def softmax_update(q, k, v, mask, m_scr, l_scr, acc_scr, scale):
    """Fold one page tile into the running (flash) softmax.

    q: (N, hd) f32; k/v: (ps, hd) f32; mask: bool, broadcastable to
    (N, ps), True where a key is admissible. Every intermediate stays 2-D
    (N, x): 1-D row vectors make Mosaic relayout each one through VMEM,
    which put a 128-token chunk past the 16 MiB scoped-VMEM limit."""
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
    s = jnp.where(mask, s, NEG_INF)
    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    # rows with no admissible key yet keep zero weight
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_scr[...] = m_new


def softmax_result(l_scr, acc_scr):
    """acc / l, with rows that saw no key (l == 0) returning zeros."""
    l = l_scr[...]
    return acc_scr[...] / jnp.where(l == 0.0, 1.0, l)


def _paged_dec_kernel(tbl_ref,                 # scalar prefetch: (B, P) pages
                      len_ref,                 # scalar prefetch: (B,) lengths
                      q_ref, k_ref, v_ref, *refs,
                      np_: int, ps: int, scale: float, quant: bool):
    if quant:
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = refs
    else:
        (o_ref, m_scr, l_scr, acc_scr), ks_ref, vs_ref = refs, None, None
    b = pl.program_id(0)
    h = pl.program_id(1)
    pi = pl.program_id(2)

    @pl.when(pi == 0)
    def _init():
        init_softmax(m_scr, l_scr, acc_scr)

    length = len_ref[b]
    page = tbl_ref[b, pi]
    s_start = pi * ps

    # live page with tokens to attend: unmapped (-1) and past-length pages
    # contribute nothing and are skipped (their block was not re-fetched
    # either — see the clamped index_map in _paged_decode)
    @pl.when((s_start < length) & (page >= 0))
    def _body():
        valid = s_start + jax.lax.broadcasted_iota(jnp.int32, (ps, 1), 0) \
            < length                                            # (ps, 1)
        kmask = s_start + jax.lax.broadcasted_iota(jnp.int32, (1, ps), 1) \
            < length                                            # (1, ps)
        q = q_ref[0, 0].astype(jnp.float32)                     # (rep, hd)
        k = page_tile(k_ref, valid, ks_ref, h)
        v = page_tile(v_ref, valid, vs_ref, h)
        softmax_update(q, k, v, kmask, m_scr, l_scr, acc_scr, scale)

    @pl.when(pi == np_ - 1)
    def _finish():
        o_ref[0, 0] = softmax_result(l_scr, acc_scr).astype(o_ref.dtype)


def _paged_decode(q, k_pages, v_pages, scales, block_table, lengths,
                  interpret: bool):
    """Shared wrapper; `scales` is None or the (k_scales, v_scales) pair."""
    B, _, Hq, hd = q.shape
    ps, Hkv = k_pages.shape[1], k_pages.shape[2]
    P = block_table.shape[1]
    rep = Hq // Hkv
    table = block_table.astype(jnp.int32)
    lens = lengths.astype(jnp.int32)

    # (B, Hkv, q_per_kv, hd): group q heads by their kv head
    qg = q[:, 0].reshape(B, Hkv, rep, hd)

    def page_of(b, p, tbl_ref, len_ref):
        # steps past the live range re-stream the last live page: Pallas
        # skips the DMA for a block index equal to the previous step's, so
        # pruned pages cost neither bandwidth nor compute
        n_live = jax.lax.div(len_ref[b] + ps - 1, ps)
        pi = jnp.minimum(p, jnp.maximum(n_live - 1, 0))
        return jnp.maximum(tbl_ref[b, pi], 0)

    def kv_map(b, h, p, tbl_ref, len_ref):
        return (page_of(b, p, tbl_ref, len_ref), 0, h)

    def scale_map(b, h, p, tbl_ref, len_ref):
        return (page_of(b, p, tbl_ref, len_ref), 0, 0)

    in_specs = [
        pl.BlockSpec((1, 1, rep, hd), lambda b, h, p, *_: (b, h, 0, 0)),
        pl.BlockSpec((1, ps, hd), kv_map),
        pl.BlockSpec((1, ps, hd), kv_map),
    ]
    operands = [qg, lane_view(k_pages), lane_view(v_pages)]
    if scales is not None:
        in_specs += [pl.BlockSpec((1, 1, Hkv), scale_map)] * 2
        operands += [scale_view(s) for s in scales]
    kernel = functools.partial(_paged_dec_kernel, np_=P, ps=ps,
                               scale=1.0 / float(hd) ** 0.5,
                               quant=scales is not None)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, Hkv, P),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, rep, hd),
                               lambda b, h, p, *_: (b, h, 0, 0)),
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
    )(table, lens, *operands)
    return out.reshape(B, 1, Hq, hd)


def paged_decode_attention_pallas(q, k_pages, v_pages, block_table, lengths,
                                  *, interpret: bool):
    """q: (B,1,Hq,hd); k/v_pages: (n_pages, page, Hkv, hd);
    block_table: (B, P) int32 page ids (-1 = unmapped); lengths: (B,) valid
    token counts. -> (B,1,Hq,hd); zero-length rows return zeros."""
    return _paged_decode(q, k_pages, v_pages, None, block_table, lengths,
                         interpret)


def paged_decode_attention_quant_pallas(q, k_pages, v_pages, k_scales,
                                        v_scales, block_table, lengths,
                                        *, interpret: bool):
    """`paged_decode_attention_pallas` over a quantized pool.

    k/v_pages: (n_pages, page, Hkv, hd) int8 / fp8; k/v_scales: (n_pages,
    Hkv) f32 per-(page, kv-head) dequant scales, streamed as (1, 1, Hkv)
    blocks through the same clamped block-table index map as their page."""
    return _paged_decode(q, k_pages, v_pages, (k_scales, v_scales),
                         block_table, lengths, interpret)
