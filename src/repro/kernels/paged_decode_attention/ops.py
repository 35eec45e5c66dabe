"""Jitted public wrapper for paged flash-decode attention."""
from __future__ import annotations

import functools
from typing import Optional

import jax

from repro.kernels.paged_decode_attention import kernel as _kernel
from repro.kernels.runtime import resolve_interpret


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention(q, k_pages, v_pages, block_table, lengths,
                           layer=0, interpret: Optional[bool] = None):
    """Single-token GQA attention over a paged KV pool, streamed through
    the block table (no gather), `kernel.DECODE_PAGES` pages of one
    (row, KV head) a grid step.

    q: (B,1,Hq,hd); k/v_pages: (L, n_pages, Hkv, page_size, hd), the
    stacked head-major pool; block_table: (B, P) int32 page ids (-1 =
    unmapped); lengths: (B,) valid token counts; layer: () int32, the
    pool layer read (traced: one compile serves every layer). Pre-trim
    `block_table` to the live width (ceil(max(lengths)/page_size) columns)
    so the grid does not walk columns no slot uses; any covering width
    reads bit-identically.
    """
    return _kernel.paged_decode_attention_pallas(
        q, k_pages, v_pages, block_table, lengths, layer,
        interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention_quant(q, k_pages, v_pages, k_scales, v_scales,
                                 block_table, lengths, layer=0,
                                 interpret: Optional[bool] = None):
    """`paged_decode_attention` over an int8/fp8 pool: pages are streamed at
    the storage width, the same pages a grid step, and dequantized in-VMEM
    with their per-(page, kv-head) scales (k/v_scales: (L, n_pages, Hkv)
    f32), each streamed with its page. Numerics follow the quantized
    tolerance contract in docs/serving.md, not the bit-exact one."""
    return _kernel.paged_decode_attention_quant_pallas(
        q, k_pages, v_pages, k_scales, v_scales, block_table, lengths, layer,
        interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("rank", "scale", "interpret"))
def mla_paged_decode(q, pages, block_table, lengths, layer=0, *, rank: int,
                     scale: float, interpret: Optional[bool] = None):
    """Latent-mode paged decode (multi-head latent attention): absorbed
    queries q (B, 1, H, W) against the latent pool (L, n_pages, 1, page,
    W), streamed through the block table, up to `kernel.DECODE_PAGES`
    pages a grid step; scores over all W lanes, values the first `rank`
    lanes of the same rows. -> (B, 1, H, rank)."""
    return _kernel.mla_paged_decode_pallas(
        q, pages, block_table, lengths, layer, rank=rank, scale=scale,
        interpret=resolve_interpret(interpret))
