"""Pallas TPU paged flash-decode kernel: one new token vs a paged KV pool.

The serving engine's paged backend (models/paged_cache.py) stores KV in a
stacked page pool `(L, n_pages, n_kv, page_size, hd)` addressed through a
block table `(B, P)`. The jnp oracle first *gathers* every slot's full
table width into a contiguous `(B, P * page_size, n_kv, hd)` buffer — per
layer, per token, sized by the table width rather than actual lengths —
and only then attends. At long context that double-pays the PICE decode
hot spot (KV reads are >50% of decode latency); this kernel removes the
gather entirely:

  * `(layer, block_table, lengths)` are scalar-prefetched, and the block
    table IS the K/V `index_map`: each grid step (b, h, p) attends the
    `T = DECODE_PAGES` pages `p*T ...` of row b, KV head h, streamed
    HBM->VMEM directly from the stacked pool. The pool is passed G =
    min(P, T) times; operand j's index map picks the `(page_size, hd)`
    tile of layer `layer`, physical page `block_table[b, p*T + j]`. No
    contiguous copy, and no per-layer slice of the pool, ever exists: the
    model's layer loop hands every layer the whole stacked pool and its
    own (traced) layer index.
  * a grid step, and each block operand in it, has a fixed cost far above
    what one 32-row page tile takes to stream, so a step carries many
    pages, and a table narrower than T streams only its P pages (the
    tile's others are zeros). One tile of T pages at every width keeps
    the fold of the running softmax, and so the read, bit-identical at
    any covering width. A table width that T does not divide ends in a
    part step; a row's read ends at the table width, as the oracle's does.
  * a page past the row's live range or unmapped (-1) maps to one fixed
    block of the layer (page 0, head 0), the same for every row and step:
    Pallas elides the DMA for a block equal to the previous step's, so
    pruned pages cost no bandwidth, and a step with no live token skips
    its compute (`pl.when`). Per-step read volume is O(sum ceil(len/page))
    pages, not O(B * max_pages_per_seq).
  * the page tiles of a step are masked — rows past `length`, pages that
    are unmapped — and folded into a running-softmax scratch
    (flash-decode) as one `(T * page_size, hd)` tile; COW-shared pages (fan-out forks) are
    just page ids that repeat across rows — each reader streams the page
    once, instead of the gather re-materializing it N times.
  * all `q_per_kv` query heads of one KV head ride each step's tile (same
    GQA arithmetic-intensity reuse as the dense decode kernel).
  * a quantized (int8/fp8) pool streams at its storage width and each page
    tile is dequantized in VMEM with its per-(page, kv-head) scale, whose
    block rides the same index map as its page.

Grid: (B, Hkv, ceil(P / T)) with P = block-table width (callers should
pre-trim it to the live width). Rows with length 0 return zeros.

The pool is stored head-major, so each K/V block is the contiguous
`(page_size, hd)` tile `(1, 1, 1, page_size, hd)`: the TPU compiler accepts
it when hd % 128 == 0 and page_size % 8 == 0 (the engine checks both before
it picks this path).

The latent mode (multi-head latent attention) below shares the multi-page
tile routine (`page_tiles`) and the running softmax.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

NEG_INF = -1e30


def scale_view(scales):
    """(L, n_pages, Hkv) dequant scales -> (L, n_pages, 1, Hkv): a
    (1, 1, 1, Hkv) block then spans whole trailing dims, which the TPU
    block rule accepts (a (1, 1) block of the page and head axes does not,
    in VMEM or SMEM)."""
    return scales.reshape(*scales.shape[:2], 1, scales.shape[2])


def layer_operand(layer):
    """The layer index as the (1,) int32 scalar-prefetch operand."""
    return jnp.asarray(layer, jnp.int32).reshape(1)


def head_scale(s_ref, h):
    """KV head h's scale from a (1, 1, 1, Hkv) block, as a (1, 1) array
    (a masked lane reduction: no dynamic lane indexing)."""
    row = s_ref[0, 0]                                         # (1, Hkv)
    hit = jax.lax.broadcasted_iota(jnp.int32, row.shape, 1) == h
    return jnp.sum(jnp.where(hit, row, 0.0), axis=1, keepdims=True)


def page_tile(ref, valid, s_ref=None, h=None):
    """One page's (ps, hd) K or V tile, from a (1, 1, 1, ps, hd) block, in
    f32, dequantized when `s_ref` is given, with rows past the valid range
    zeroed BEFORE the MXU: they hold stale pool bytes that must not reach
    it as NaN/inf."""
    x = ref[0, 0, 0].astype(jnp.float32)
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


# Pages a decode grid step attends (the latent mode's: at most): 2 x 16
# bf16 tiles of (32, 128) are 256 KB a step, double-buffered 512 KB, well
# inside scoped VMEM.
DECODE_PAGES = 16


def pages_per_step(P: int, most: int) -> int:
    """Pages streamed per grid step: the largest power of two up to `most`
    that divides the block-table width P."""
    g = 1
    while g * 2 <= most and P % (g * 2) == 0:
        g *= 2
    return g


def decode_pages(P: int, latent: bool) -> int:
    """Pages a decode grid step streams at block-table width P: the GQA
    mode's up to DECODE_PAGES (the pages of its tile that P holds), the
    latent mode's largest power of two up to it that divides P."""
    return pages_per_step(P, DECODE_PAGES) if latent \
        else min(P, DECODE_PAGES)


def page_tiles(pages, start, total, ps, *operands, h=None, tile=None):
    """The page tiles of one grid step, each operand's as one (T*ps, W) f32
    tile, with rows past `total` or on an unmapped page zeroed, and the
    (1, T*ps) key mask that admits the rest.

    pages: the G page ids the step streams; tile: T, the pages the step
    attends (default G), the ones past G all zeros, which `total` must
    mask; operands: `(refs, s_refs)` pairs, the G page blocks
    (1, 1, 1, ps, W) of one pool (K, V or the latent rows) and their G
    scale blocks, or None for an unquantized pool; h: the KV head a scale
    is picked for."""
    G = len(pages)
    T = tile or G
    tiles = [[] for _ in operands]
    for j in range(G):
        row = start + j * ps + jax.lax.broadcasted_iota(jnp.int32, (ps, 1), 0)
        valid = (row < total) & (pages[j] >= 0)
        for out, (refs, s_refs) in zip(tiles, operands):
            out.append(page_tile(refs[j], valid,
                                 None if s_refs is None else s_refs[j], h))
    for out in tiles:
        out += [jnp.zeros_like(out[0])] * (T - G)
    kvs = [t[0] if T == 1 else jnp.concatenate(t, axis=0) for t in tiles]
    kidx = jax.lax.broadcasted_iota(jnp.int32, (1, T * ps), 1)
    kmask = start + kidx < total
    for j in range(G):
        kmask &= (kidx < j * ps) | (kidx >= (j + 1) * ps) | (pages[j] >= 0)
    return kvs, kmask


def _paged_dec_kernel(layer_ref,               # scalar prefetch: (1,) layer
                      tbl_ref,                 # scalar prefetch: (B, P) pages
                      len_ref,                 # scalar prefetch: (B,) lengths
                      q_ref, *refs, G: int, T: int, P: int, ps: int,
                      scale: float, quant: bool):
    k_refs, v_refs = refs[:G], refs[G:2 * G]
    if quant:
        ks_refs, vs_refs = refs[2 * G:3 * G], refs[3 * G:4 * G]
        refs = refs[4 * G:]
    else:
        ks_refs = vs_refs = None
        refs = refs[2 * G:]
    o_ref, m_scr, l_scr, acc_scr = refs
    b = pl.program_id(0)
    h = pl.program_id(1)
    pj = pl.program_id(2)

    @pl.when(pj == 0)
    def _init():
        init_softmax(m_scr, l_scr, acc_scr)

    length = jnp.minimum(len_ref[b], P * ps)
    start = pj * (T * ps)

    # a step with no token to attend is skipped (its blocks were not
    # fetched either — see the index maps in _paged_decode)
    @pl.when(start < length)
    def _body():
        pages = [tbl_ref[b, jnp.minimum(pj * T + j, P - 1)]
                 for j in range(G)]
        (k, v), kmask = page_tiles(pages, start, length, ps,
                                   (k_refs, ks_refs), (v_refs, vs_refs),
                                   h=h, tile=T)
        q = q_ref[0, 0].astype(jnp.float32)                     # (rep, hd)
        softmax_update(q, k, v, kmask, m_scr, l_scr, acc_scr, scale)

    @pl.when(pj == pl.num_programs(2) - 1)
    def _finish():
        o_ref[0, 0] = softmax_result(l_scr, acc_scr).astype(o_ref.dtype)


def _paged_decode(q, k_pages, v_pages, scales, block_table, lengths, layer,
                  interpret: bool, name: str):
    """Shared wrapper; `scales` is None or the (k_scales, v_scales) pair.
    `name` names the kernel in compiled programs and profiler traces (the
    public wrapper's name, so every variant starts with
    `paged_decode_attention`)."""
    B, _, Hq, hd = q.shape
    Hkv, ps = k_pages.shape[2], k_pages.shape[3]
    P = block_table.shape[1]
    G, T = decode_pages(P, latent=False), DECODE_PAGES
    rep = Hq // Hkv
    table = block_table.astype(jnp.int32)
    lens = lengths.astype(jnp.int32)

    # (B, Hkv, q_per_kv, hd): group q heads by their kv head
    qg = q[:, 0].reshape(B, Hkv, rep, hd)

    def page_map(j, heads: bool):
        # operand j streams page p*T + j of the row; a page past the live
        # range (past P included) or unmapped maps to one fixed block
        # (page 0, head 0) of the layer, which every such step shares, so
        # its DMA is elided
        def index(b, h, p, layer_ref, tbl_ref, len_ref):
            pi = p * T + j
            page = tbl_ref[b, jnp.minimum(pi, P - 1)]
            live = (pi * ps < jnp.minimum(len_ref[b], P * ps)) \
                & (page >= 0)
            at = (layer_ref[0], jnp.where(live, page, 0))
            return at + ((jnp.where(live, h, 0), 0, 0) if heads else (0, 0))
        return index

    in_specs = [pl.BlockSpec((1, 1, rep, hd),
                             lambda b, h, p, *_: (b, h, 0, 0))]
    in_specs += [pl.BlockSpec((1, 1, 1, ps, hd), page_map(j, True))
                 for j in range(G)] * 2
    operands = [qg] + [k_pages] * G + [v_pages] * G
    if scales is not None:
        in_specs += [pl.BlockSpec((1, 1, 1, Hkv), page_map(j, False))
                     for j in range(G)] * 2
        operands += [scale_view(s) for s in scales for _ in range(G)]
    kernel = functools.partial(_paged_dec_kernel, G=G, T=T, P=P, ps=ps,
                               scale=1.0 / float(hd) ** 0.5,
                               quant=scales is not None)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, Hkv, pl.cdiv(P, T)),
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
        name=name,
    )(layer_operand(layer), table, lens, *operands)
    return out.reshape(B, 1, Hq, hd)


def paged_decode_attention_pallas(q, k_pages, v_pages, block_table, lengths,
                                  layer=0, *, interpret: bool):
    """q: (B,1,Hq,hd); k/v_pages: (L, n_pages, Hkv, page, hd);
    block_table: (B, P) int32 page ids (-1 = unmapped); lengths: (B,) valid
    token counts; layer: () int32, the pool layer read. -> (B,1,Hq,hd);
    zero-length rows return zeros."""
    return _paged_decode(q, k_pages, v_pages, None, block_table, lengths,
                         layer, interpret, "paged_decode_attention")


def paged_decode_attention_quant_pallas(q, k_pages, v_pages, k_scales,
                                        v_scales, block_table, lengths,
                                        layer=0, *, interpret: bool):
    """`paged_decode_attention_pallas` over a quantized pool.

    k/v_pages: (L, n_pages, Hkv, page, hd) int8 / fp8; k/v_scales: (L,
    n_pages, Hkv) f32 per-(page, kv-head) dequant scales, streamed as
    (1, 1, 1, Hkv) blocks through the same block-table index map as their
    page."""
    return _paged_decode(q, k_pages, v_pages, (k_scales, v_scales),
                         block_table, lengths, layer, interpret,
                         "paged_decode_attention_quant")


# ---------------------------------------------------------------------------
# Latent mode (multi-head latent attention, DeepSeek-V2)
#
# The pool holds one row per token, (L, n_pages, 1, page, W): the
# normalised c_kv (`rank` lanes), the roped k_pe, zero lanes up to W. All
# query heads ride one KV "head". Queries arrive absorbed, W lanes each:
# q_nope through W_UK (rank lanes), q_pe, zeros. Scores are taken over all
# W lanes of a page tile; values are the first `rank` lanes of the SAME
# tile, so a page is streamed once. The output stays latent (rank lanes a
# head); the caller multiplies it through W_UV.
#
# A page of 32 rows is a small DMA, so each grid step streams `G` pages:
# the pool is passed G times, operand j's index map picking page p*G + j
# of the row (clamped to the row's last live page past its live range, so
# pruned pages cost no DMA), and the G tiles are attended as one
# (`page_tiles`, shared with the GQA mode).
# ---------------------------------------------------------------------------

def _mla_dec_kernel(layer_ref, tbl_ref, len_ref, q_ref, *refs, G: int,
                    ps: int, rank: int, scale: float):
    kv_refs = refs[:G]
    o_ref, m_scr, l_scr, acc_scr = refs[G:]
    b = pl.program_id(0)
    pj = pl.program_id(1)

    @pl.when(pj == 0)
    def _init():
        init_softmax(m_scr, l_scr, acc_scr)

    length = len_ref[b]
    start = pj * (G * ps)

    @pl.when(start < length)
    def _body():
        pages = [tbl_ref[b, pj * G + j] for j in range(G)]
        (kv,), kmask = page_tiles(pages, start, length, ps, (kv_refs, None))
        q = q_ref[0].astype(jnp.float32)                      # (H, W)
        softmax_update(q, kv, kv[:, :rank], kmask, m_scr, l_scr, acc_scr,
                       scale)

    @pl.when(pj == pl.num_programs(1) - 1)
    def _finish():
        o_ref[0] = softmax_result(l_scr, acc_scr).astype(o_ref.dtype)


def mla_paged_decode_pallas(q, pages, block_table, lengths, layer=0, *,
                            rank: int, scale: float, interpret: bool):
    """Latent-mode paged flash decode. q: (B, 1, H, W) absorbed queries;
    pages: (L, n_pages, 1, page, W) the latent pool; block_table: (B, P)
    int32 (-1 = unmapped); lengths: (B,) valid tokens; layer: () the pool
    layer read; rank: latent lanes that are values; scale: the softmax
    scale. -> (B, 1, H, rank) latent outputs; zero-length rows return
    zeros."""
    B, _, H, W = q.shape
    ps = pages.shape[3]
    P = block_table.shape[1]
    G = decode_pages(P, latent=True)
    table = block_table.astype(jnp.int32)
    lens = lengths.astype(jnp.int32)

    def page_map(j):
        def index(b, p, layer_ref, tbl_ref, len_ref):
            n_live = jax.lax.div(len_ref[b] + ps - 1, ps)
            pi = jnp.minimum(p * G + j, jnp.maximum(n_live - 1, 0))
            return (layer_ref[0], jnp.maximum(tbl_ref[b, pi], 0), 0, 0, 0)
        return index

    in_specs = [pl.BlockSpec((1, H, W), lambda b, p, *_: (b, 0, 0))]
    in_specs += [pl.BlockSpec((1, 1, 1, ps, W), page_map(j))
                 for j in range(G)]
    kernel = functools.partial(_mla_dec_kernel, G=G, ps=ps, rank=rank,
                               scale=float(scale))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, P // G),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, H, rank), lambda b, p, *_: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, rank), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, rank), q.dtype),
        interpret=interpret,
        name="mla_paged_decode",
    )(layer_operand(layer), table, lens, q[:, 0], *([pages] * G))
    return out[:, None]
