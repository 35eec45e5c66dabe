"""Pallas TPU paged chunked-prefill kernel: prompt chunks vs a paged KV
pool.

The serving engine's chunked prefill (models/transformer.prefill_ragged_paged)
ingests prompts in fixed C-token chunks, one row per ingesting slot; each
chunk's queries attend causally within the chunk AND against every page its
slot already wrote — a ragged cross-chunk read the jnp oracle serves by
gathering the slot's whole block row into a contiguous buffer per layer per
chunk. This kernel removes the gather, mirroring the paged flash-decode
kernel (and sharing its page-tile and running-softmax helpers):

  * `(layer, block_rows, [offset, chunk_len] per row)` are
    scalar-prefetched and the block rows ARE the K/V `index_map`: grid step
    (r, h, p) streams the `(page_size, hd)` tile of layer `layer`, physical
    page `block_rows[r, p]`, KV head h HBM->VMEM straight from the stacked
    head-major pool (no per-layer slice of it exists).
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

A single slot's chunk is a one-row batch. Query rows past `chunk_len` are
computed against whatever the mask admits and must be discarded by the
caller.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels.paged_decode_attention.kernel import (
    init_softmax, layer_operand, page_tile, page_tiles, pages_per_step,
    scale_view, softmax_result, softmax_update)


def _paged_pref_kernel(layer_ref,              # scalar prefetch: (1,) layer
                       rows_ref,               # scalar prefetch: (R, P) pages
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
                   layer, interpret: bool, name: str):
    """Shared ragged wrapper; `scales` is None or (k_scales, v_scales).
    `name` names the kernel in compiled programs and profiler traces (the
    public wrapper's name: `paged_prefill_attention_ragged[_quant]`)."""
    R, C, Hq, hd = q.shape
    Hkv, ps = k_pages.shape[2], k_pages.shape[3]
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

    def kv_map(r, h, p, layer_ref, rows_ref, info_ref):
        return (layer_ref[0], page_of(r, p, rows_ref, info_ref), h, 0, 0)

    def scale_map(r, h, p, layer_ref, rows_ref, info_ref):
        return (layer_ref[0], page_of(r, p, rows_ref, info_ref), 0, 0)

    in_specs = [
        pl.BlockSpec((1, 1, rep, C, hd),
                     lambda r, h, p, *_: (r, h, 0, 0, 0)),
        pl.BlockSpec((1, 1, 1, ps, hd), kv_map),
        pl.BlockSpec((1, 1, 1, ps, hd), kv_map),
    ]
    operands = [qg, k_pages, v_pages]
    if scales is not None:
        in_specs += [pl.BlockSpec((1, 1, 1, Hkv), scale_map)] * 2
        operands += [scale_view(s) for s in scales]
    kernel = functools.partial(_paged_pref_kernel, np_=P, ps=ps, C=C,
                               rep=rep, scale=1.0 / float(hd) ** 0.5,
                               quant=scales is not None)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
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
        name=name,
    )(layer_operand(layer), rows, info, *operands)
    # (R, Hkv, rep, C, hd) -> (R, C, Hq, hd) with head index h = kv*rep + r
    return jnp.moveaxis(out.reshape(R, Hq, C, hd), 1, 2)


def paged_prefill_attention_ragged_pallas(q, k_pages, v_pages, block_rows,
                                          offsets, lens, layer=0, *,
                                          interpret: bool):
    """Multi-slot ragged chunk attention, the engine's one chunk-ingest
    read (a single slot's chunk is a one-row batch).

    q: (R, C, Hq, hd) — row r is one ingesting slot's chunk queries (chunk
    K/V already written); k/v_pages: (L, n_pages, Hkv, page, hd), read at
    layer `layer`; block_rows:
    (R, P) int32 per-row page ids (-1 = unmapped); offsets/lens: (R,) int32.
    Grid (R, Hkv, P): the innermost axis walks row r's pages with a per-row
    scalar-prefetched clamp/prune, so the streamed volume is
    O(sum_r (offsets[r] + lens[r])). -> (R, C, Hq, hd); row r positions past
    lens[r] (and all of padding rows, lens[r] == 0) are unspecified."""
    return _paged_prefill(q, k_pages, v_pages, None, block_rows, offsets,
                          lens, layer, interpret,
                          "paged_prefill_attention_ragged")


def paged_prefill_attention_ragged_quant_pallas(q, k_pages, v_pages, k_scales,
                                                v_scales, block_rows, offsets,
                                                lens, layer=0, *,
                                                interpret: bool):
    """`paged_prefill_attention_ragged_pallas` over a quantized pool."""
    return _paged_prefill(q, k_pages, v_pages, (k_scales, v_scales),
                          block_rows, offsets, lens, layer, interpret,
                          "paged_prefill_attention_ragged_quant")


# ---------------------------------------------------------------------------
# Latent mode (multi-head latent attention): see the decode kernel's latent
# mode. One pool tile per page, scores over all its lanes, values its first
# `rank` lanes; all query heads on one KV "head". A grid step holds `hb`
# heads of one row's chunk (hb * C query rows) and G pages.
# ---------------------------------------------------------------------------

def _mla_pref_kernel(layer_ref, rows_ref, info_ref, q_ref, *refs, G: int,
                     ps: int, C: int, rank: int, scale: float):
    kv_refs = refs[:G]
    o_ref, m_scr, l_scr, acc_scr = refs[G:]
    r = pl.program_id(0)
    pj = pl.program_id(2)

    @pl.when(pj == 0)
    def _init():
        init_softmax(m_scr, l_scr, acc_scr)

    total = info_ref[r, 0] + info_ref[r, 1]    # offset + chunk_len
    start = pj * (G * ps)

    @pl.when(start < total)
    def _body():
        pages = [rows_ref[r, pj * G + j] for j in range(G)]
        (kv,), kmask = page_tiles(pages, start, total, ps, (kv_refs, None))
        q = q_ref[0, 0].astype(jnp.float32)                   # (hb*C, W)
        n = q.shape[0]
        # query row j is chunk position j mod C
        qpos = info_ref[r, 0] + jax.lax.rem(
            jax.lax.broadcasted_iota(jnp.int32, (n, 1), 0), C)
        kpos = start + jax.lax.broadcasted_iota(jnp.int32, (1, G * ps), 1)
        softmax_update(q, kv, kv[:, :rank], kmask & (kpos <= qpos), m_scr,
                       l_scr, acc_scr, scale)

    @pl.when(pj == pl.num_programs(2) - 1)
    def _finish():
        o_ref[0, 0] = softmax_result(l_scr, acc_scr).astype(o_ref.dtype)


def head_block(H: int, C: int, most_rows: int = 512) -> int:
    """Query heads per grid step: all of them, halved while the step's
    hb * C query rows exceed `most_rows` (VMEM holds their f32 scores and
    accumulators)."""
    hb = H
    while hb * C > most_rows and hb % 2 == 0:
        hb //= 2
    return hb


def mla_paged_prefill_pallas(q, pages, block_rows, offsets, lens, layer=0, *,
                             rank: int, scale: float, interpret: bool,
                             most_pages: int = 8):
    """Latent-mode ragged chunk attention. q: (R, C, H, W) absorbed chunk
    queries (each row's chunk rows already written to the pool); pages:
    (L, n_pages, 1, page, W); block_rows: (R, P) int32 (-1 = unmapped);
    offsets/lens: (R,) int32. -> (R, C, H, rank) latent outputs; row r
    positions past lens[r] (all of a padding row) are unspecified."""
    R, C, H, W = q.shape
    ps = pages.shape[3]
    P = block_rows.shape[1]
    G = pages_per_step(P, most_pages)
    hb = head_block(H, C)
    rows = block_rows.astype(jnp.int32)
    info = jnp.stack([jnp.asarray(offsets, jnp.int32),
                      jnp.asarray(lens, jnp.int32)], axis=1)       # (R, 2)
    # (R, H/hb, hb*C, W): each step's query rows head-major
    qg = jnp.moveaxis(q, 2, 1).reshape(R, H // hb, hb * C, W)

    def page_map(j):
        def index(r, h, p, layer_ref, rows_ref, info_ref):
            n_live = jax.lax.div(info_ref[r, 0] + info_ref[r, 1] + ps - 1, ps)
            pi = jnp.minimum(p * G + j, jnp.maximum(n_live - 1, 0))
            return (layer_ref[0], jnp.maximum(rows_ref[r, pi], 0), 0, 0, 0)
        return index

    in_specs = [pl.BlockSpec((1, 1, hb * C, W),
                             lambda r, h, p, *_: (r, h, 0, 0))]
    in_specs += [pl.BlockSpec((1, 1, 1, ps, W), page_map(j))
                 for j in range(G)]
    kernel = functools.partial(_mla_pref_kernel, G=G, ps=ps, C=C, rank=rank,
                               scale=float(scale))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(R, H // hb, P // G),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, hb * C, rank),
                               lambda r, h, p, *_: (r, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((hb * C, 1), jnp.float32),
            pltpu.VMEM((hb * C, 1), jnp.float32),
            pltpu.VMEM((hb * C, rank), jnp.float32),
        ],
    )
    # repro-analysis: disable=RA404 reason=head_block keeps hb * C at 512 query rows: ~6 MB of blocks and scratch at 640 lanes (tests/test_tpu_compile.py compiles it for a v5e)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((R, H // hb, hb * C, rank), q.dtype),
        interpret=interpret,
        name="mla_paged_prefill",
    )(layer_operand(layer), rows, info, qg, *([pages] * G))
    return jnp.moveaxis(out.reshape(R, H, C, rank), 1, 2)
