"""Jitted public wrapper for paged chunked-prefill attention."""
from __future__ import annotations

import functools
from typing import Optional

import jax

from repro.kernels.paged_prefill_attention import kernel as _kernel
from repro.kernels.runtime import resolve_interpret


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_prefill_attention_ragged(q, k_pages, v_pages, block_rows, offsets,
                                   lens, layer=0,
                                   interpret: Optional[bool] = None):
    """Batched ragged chunked-prefill GQA attention over a paged KV pool,
    streamed through the block rows (no gather): R slots' chunks against
    their own page chains in one call (the engine's chunk-ingest op; one
    slot's chunk is a one-row batch).

    q: (R, C, Hq, hd) — row r is slot r's next chunk queries (each row's
    chunk K/V already written to the pages); k/v_pages: (L, n_pages, Hkv,
    page_size, hd), read at layer `layer`; block_rows: (R, P) int32 per-row page ids (-1 = unmapped);
    offsets/lens: (R,) int32. Pre-trim `block_rows` to the shared live width
    (ceil(max(offsets + lens) / page_size) columns, bucketed) — each row
    still prunes down to its own covering range via scalar prefetch. Row r
    positions past lens[r] are unspecified, as are padding rows (lens == 0).
    """
    return _kernel.paged_prefill_attention_ragged_pallas(
        q, k_pages, v_pages, block_rows, offsets, lens, layer,
        interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_prefill_attention_ragged_quant(q, k_pages, v_pages, k_scales,
                                         v_scales, block_rows, offsets, lens,
                                         layer=0,
                                         interpret: Optional[bool] = None):
    """`paged_prefill_attention_ragged` over an int8/fp8 pool: pages stream
    at the storage width and are dequantized in-VMEM with their
    per-(page, kv-head) scales (k/v_scales: (L, n_pages, Hkv) f32).
    Numerics follow the quantized tolerance contract in docs/serving.md,
    not the bit-exact one."""
    return _kernel.paged_prefill_attention_ragged_quant_pallas(
        q, k_pages, v_pages, k_scales, v_scales, block_rows, offsets, lens,
        layer, interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("rank", "scale", "interpret"))
def mla_paged_prefill(q, pages, block_rows, offsets, lens, layer=0, *,
                      rank: int, scale: float,
                      interpret: Optional[bool] = None):
    """Latent-mode ragged chunk attention (multi-head latent attention): R
    slots' absorbed chunk queries q (R, C, H, W) against the latent pool
    (L, n_pages, 1, page, W) through their block rows (R, P); scores over
    all W lanes, values the first `rank`. -> (R, C, H, rank); positions
    past lens[r] are unspecified."""
    return _kernel.mla_paged_prefill_pallas(
        q, pages, block_rows, offsets, lens, layer, rank=rank, scale=scale,
        interpret=resolve_interpret(interpret))
