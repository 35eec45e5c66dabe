"""Pallas TPU paged chunked-prefill kernel: prompt chunks vs a paged KV
pool.

The serving engine's chunked prefill (models/transformer.prefill_ragged_paged
and prefill_chunk_paged) ingests prompts in fixed C-token chunks; each
chunk's queries attend causally within the chunk AND against every page its
slot already wrote — a ragged cross-chunk read the jnp oracle serves by
gathering the slot's whole block row into a contiguous buffer per layer per
chunk. This kernel removes the gather, mirroring the paged flash-decode
kernel (and sharing its page-tile and running-softmax helpers):

  * `(block_rows, [offset, chunk_len] per row)` are scalar-prefetched and
    the block rows ARE the K/V `index_map`: grid step (r, h, p) streams
    physical page `block_rows[r, p]` HBM->VMEM straight from the pool.
  * steps past a row's live range (`ceil((offset+chunk_len)/page)` pages)
    re-map to its last live page — Pallas elides the DMA for a revisited
    block — and `pl.when` prunes their compute along with unmapped (-1)
    pages and whole padding rows, so the read volume is
    O(sum_r (offset + chunk_len)), not O(R * P * page).
  * in-page positions past `offset+chunk_len` hold stale pool bytes and are
    zeroed before the MXU; the causal mask `kpos <= offset + (q mod C)`
    handles the intra-chunk triangle (the chunk's own K/V is written before
    the read, so self-attention within the chunk needs no special case).
  * the Q tile is the whole (q_per_kv * C, hd) chunk: every query head of
    one KV head rides each streamed page tile, with a running-softmax
    scratch accumulated across pages (flash style).
  * a quantized (int8/fp8) pool is dequantized in VMEM per page tile with
    its streamed per-(page, kv-head) scale, as in the decode kernel.

The single-slot entry points are the ragged kernel with one row. Query rows
past `chunk_len` are computed against whatever the mask admits and must be
discarded by the caller.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels.paged_decode_attention.kernel import (
    init_softmax, lane_view, page_tile, scale_view, softmax_result,
    softmax_update)


def _paged_pref_kernel(rows_ref,               # scalar prefetch: (R, P) pages
                       info_ref,               # scalar prefetch: (R, 2)
                       q_ref, k_ref, v_ref, *refs,
                       np_: int, ps: int, C: int, rep: int, scale: float,
                       quant: bool):
    if quant:
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = refs
    else:
        (o_ref, m_scr, l_scr, acc_scr), ks_ref, vs_ref = refs, None, None
    r = pl.program_id(0)
    h = pl.program_id(1)
    pi = pl.program_id(2)

    @pl.when(pi == 0)
    def _init():
        init_softmax(m_scr, l_scr, acc_scr)

    total = info_ref[r, 0] + info_ref[r, 1]    # offset + chunk_len
    page = rows_ref[r, pi]
    s_start = pi * ps

    # live mapped page of THIS row: pages past the row's covering range,
    # unmapped (-1) entries, and whole padding rows (len == 0 -> total ==
    # 0) contribute nothing and are skipped (their block was not re-fetched
    # either — see the clamped index_map in _paged_prefill)
    @pl.when((s_start < total) & (page >= 0))
    def _body():
        valid = s_start + jax.lax.broadcasted_iota(jnp.int32, (ps, 1), 0) \
            < total                                             # (ps, 1)
        kpos = s_start + jax.lax.broadcasted_iota(jnp.int32, (1, ps), 1)
        q = q_ref[0, 0].reshape(rep * C, -1).astype(jnp.float32)
        k = page_tile(k_ref, valid, ks_ref, h)
        v = page_tile(v_ref, valid, vs_ref, h)
        # causal: query row j is chunk position j mod C at absolute
        # position offsets[r] + (j mod C)
        qpos = info_ref[r, 0] + jax.lax.rem(
            jax.lax.broadcasted_iota(jnp.int32, (rep * C, 1), 0), C)
        mask = (kpos < total) & (kpos <= qpos)                  # (rep*C, ps)
        softmax_update(q, k, v, mask, m_scr, l_scr, acc_scr, scale)

    @pl.when(pi == np_ - 1)
    def _finish():
        hd = acc_scr.shape[-1]
        o_ref[0, 0] = softmax_result(l_scr, acc_scr).reshape(
            rep, C, hd).astype(o_ref.dtype)


def _paged_prefill(q, k_pages, v_pages, scales, block_rows, offsets, lens,
                   interpret: bool):
    """Shared ragged wrapper; `scales` is None or (k_scales, v_scales)."""
    R, C, Hq, hd = q.shape
    ps, Hkv = k_pages.shape[1], k_pages.shape[2]
    P = block_rows.shape[1]
    rep = Hq // Hkv
    rows = block_rows.astype(jnp.int32)
    info = jnp.stack([jnp.asarray(offsets, jnp.int32),
                      jnp.asarray(lens, jnp.int32)], axis=1)       # (R, 2)

    # (R, Hkv, rep, C, hd): group each row's q heads by their kv head
    qg = jnp.moveaxis(q, 2, 1).reshape(R, Hkv, rep, C, hd)

    def page_of(r, p, rows_ref, info_ref):
        # steps past row r's covering range re-stream its last live page:
        # Pallas skips the DMA for a block index equal to the previous
        # step's, so pruned pages cost neither bandwidth nor compute
        n_live = jax.lax.div(info_ref[r, 0] + info_ref[r, 1] + ps - 1, ps)
        pi = jnp.minimum(p, jnp.maximum(n_live - 1, 0))
        return jnp.maximum(rows_ref[r, pi], 0)

    def kv_map(r, h, p, rows_ref, info_ref):
        return (page_of(r, p, rows_ref, info_ref), 0, h)

    def scale_map(r, h, p, rows_ref, info_ref):
        return (page_of(r, p, rows_ref, info_ref), 0, 0)

    in_specs = [
        pl.BlockSpec((1, 1, rep, C, hd),
                     lambda r, h, p, *_: (r, h, 0, 0, 0)),
        pl.BlockSpec((1, ps, hd), kv_map),
        pl.BlockSpec((1, ps, hd), kv_map),
    ]
    operands = [qg, lane_view(k_pages), lane_view(v_pages)]
    if scales is not None:
        in_specs += [pl.BlockSpec((1, 1, Hkv), scale_map)] * 2
        operands += [scale_view(s) for s in scales]
    kernel = functools.partial(_paged_pref_kernel, np_=P, ps=ps, C=C,
                               rep=rep, scale=1.0 / float(hd) ** 0.5,
                               quant=scales is not None)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(R, Hkv, P),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, rep, C, hd),
                               lambda r, h, p, *_: (r, h, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((rep * C, 1), jnp.float32),
            pltpu.VMEM((rep * C, 1), jnp.float32),
            pltpu.VMEM((rep * C, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((R, Hkv, rep, C, hd), q.dtype),
        interpret=interpret,
    )(rows, info, *operands)
    # (R, Hkv, rep, C, hd) -> (R, C, Hq, hd) with head index h = kv*rep + r
    return jnp.moveaxis(out.reshape(R, Hq, C, hd), 1, 2)


def _one_row(block_row, offset, chunk_len):
    """Single-slot arguments as a one-row ragged batch."""
    return (block_row[None],
            jnp.asarray(offset, jnp.int32).reshape(1),
            jnp.asarray(chunk_len, jnp.int32).reshape(1))


def paged_prefill_attention_pallas(q, k_pages, v_pages, block_row, offset,
                                   chunk_len, *, interpret: bool):
    """q: (1, C, Hq, hd) one slot's chunk queries; k/v_pages: (n_pages,
    page, Hkv, hd) with the chunk already written; block_row: (P,) int32
    page ids (-1 = unmapped); offset/chunk_len: () int32. ->
    (1, C, Hq, hd); rows past chunk_len are unspecified."""
    return _paged_prefill(q, k_pages, v_pages, None,
                          *_one_row(block_row, offset, chunk_len), interpret)


def paged_prefill_attention_ragged_pallas(q, k_pages, v_pages, block_rows,
                                          offsets, lens, *,
                                          interpret: bool):
    """Multi-slot ragged chunk attention: the batched-ingest extension of
    `paged_prefill_attention_pallas`.

    q: (R, C, Hq, hd) — row r is one ingesting slot's chunk queries (chunk
    K/V already written); k/v_pages: (n_pages, page, Hkv, hd); block_rows:
    (R, P) int32 per-row page ids (-1 = unmapped); offsets/lens: (R,) int32.
    Grid (R, Hkv, P): the innermost axis walks row r's pages with a per-row
    scalar-prefetched clamp/prune, so the streamed volume is
    O(sum_r (offsets[r] + lens[r])). -> (R, C, Hq, hd); row r positions past
    lens[r] (and all of padding rows, lens[r] == 0) are unspecified."""
    return _paged_prefill(q, k_pages, v_pages, None, block_rows, offsets,
                          lens, interpret)


def paged_prefill_attention_quant_pallas(q, k_pages, v_pages, k_scales,
                                         v_scales, block_row, offset,
                                         chunk_len, *, interpret: bool):
    """`paged_prefill_attention_pallas` over a quantized pool (k/v_scales:
    (n_pages, Hkv) f32, streamed as (1, 1, Hkv) blocks through the same
    clamped block-row index map as their page)."""
    return _paged_prefill(q, k_pages, v_pages, (k_scales, v_scales),
                          *_one_row(block_row, offset, chunk_len), interpret)


def paged_prefill_attention_ragged_quant_pallas(q, k_pages, v_pages, k_scales,
                                                v_scales, block_rows, offsets,
                                                lens, *, interpret: bool):
    """`paged_prefill_attention_ragged_pallas` over a quantized pool."""
    return _paged_prefill(q, k_pages, v_pages, (k_scales, v_scales),
                          block_rows, offsets, lens, interpret)
