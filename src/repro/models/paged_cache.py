"""Paged KV cache (vLLM PagedAttention analogue, pure JAX).

Physical storage is a page pool per layer; sequences map to pages through a
block table, so slot memory is allocated on demand and freed on completion —
no per-slot max_len reservation. The decode read path is keyed on
cfg.use_pallas: kernels/paged_decode_attention streams mapped pages
HBM->VMEM directly through the block table (no contiguous copy); the
`gather_sequence` formulation below is its jnp oracle and the non-TPU
fallback. Callers should trim the table they read through to the live
width (ceil(max(lengths)/page_size) columns) so even the gather stops
paying for `max_pages_per_seq`.

Layout (head-major, the layout the Pallas kernels read, so no relayout
sits between the pool and a kernel):
  pages:       (L, n_pages, n_kv, page_size, hd)
  scales:      (L, n_pages, n_kv)            (int8 / fp8 pools only)
  block_table: (B, max_pages_per_seq) int32  (-1 = unmapped)
  lengths:     (B,)

Every reader and writer takes a segment's whole stacked pool and a `layer`
index into its leading axis, and touches only the pages it addresses: the
model's layer loop carries the pools and never slices a layer out.

A multi-head latent attention (MLA) model keeps one latent pool per
segment instead of K and V, under `LATENT`:
  latent_pages: (L, n_pages, 1, page_size, latent_width)
one row per token per layer, shared by every query head: the normalised
c_kv (kv_lora_rank lanes), the roped k_pe (qk_rope_head_dim lanes), then
zero lanes up to a multiple of 128. The plain writers take it as their K
pool with no V pool; it has no quantized form.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# Quantized KV pages (cfg.kv_dtype in {"int8", "fp8"}).
#
# Storage is the quantized pool plus a per-(page, kv-head) f32 scale tensor
# (L, n_pages, n_kv). Writes requantize whole pages: dequantize the touched
# page, overlay the new tokens in f32, recompute abs-max over the valid
# positions, rescale, and scatter page + scale together. Earlier tokens in
# a page are therefore re-rounded at most page_size times — a bounded error
# the tolerance contract in docs/serving.md covers. Reads dequantize either
# in-VMEM right after the page DMA (Pallas kernels) or via
# `gather_sequence_dequant` (oracle / non-TPU fallback).
# ---------------------------------------------------------------------------

KV_QMAX = {"int8": 127.0, "fp8": 448.0}

LATENT = "latent_pages"


def is_pool(seg: dict) -> bool:
    """Whether a segment cache holds paged pools (K/V or latent), rather
    than a recurrent segment's per-slot states."""
    return "k_pages" in seg or LATENT in seg


def kv_storage_dtype(kv_dtype: str):
    """jnp dtype a paged pool stores for a resolved kv_dtype string."""
    if kv_dtype == "int8":
        return jnp.int8
    if kv_dtype == "fp8":
        return jnp.float8_e4m3fn
    return jnp.dtype(kv_dtype)


def quant_scale(amax: jax.Array, kv_dtype: str) -> jax.Array:
    """Per-(page, kv-head) scale from the abs-max of its valid positions."""
    return jnp.where(amax > 0, amax / KV_QMAX[kv_dtype], 1.0)


def _quantize(x: jax.Array, scale: jax.Array, kv_dtype: str) -> jax.Array:
    """x: f32 (..., kv, page, hd); scale: (..., kv) -> storage dtype."""
    y = x / scale[..., None, None]
    if kv_dtype == "int8":
        return jnp.clip(jnp.round(y), -127.0, 127.0).astype(jnp.int8)
    return y.astype(jnp.float8_e4m3fn)


def _to_sequence(g: jax.Array) -> jax.Array:
    """Gathered pages (B, P, kv, page, hd) -> contiguous (B, P*page, kv, hd)."""
    B, P, kv, page, hd = g.shape
    return jnp.swapaxes(g, 2, 3).reshape(B, P * page, kv, hd)


def gather_sequence(pages: jax.Array, block_table: jax.Array,
                    layer=0) -> jax.Array:
    """pages: (L, n_pages, kv, page, hd); block_table: (B, P) -> layer
    `layer`'s contiguous (B, P*page, n_kv, hd). Unmapped (-1) pages read
    page 0 and must be masked by `lengths` downstream."""
    idx = jnp.maximum(block_table, 0)
    return _to_sequence(pages[layer, idx])


def gather_sequence_dequant(pages: jax.Array, scales: jax.Array,
                            block_table: jax.Array, layer=0) -> jax.Array:
    """`gather_sequence` for a quantized pool: dequantize per-(page, head)
    on read, returning contiguous f32 (B, P*page, n_kv, hd). scales:
    (L, n_pages, n_kv) f32."""
    idx = jnp.maximum(block_table, 0)
    g = pages[layer, idx].astype(jnp.float32)        # (B, P, kv, page, hd)
    g = g * scales[layer, idx][..., None, None]
    return _to_sequence(g)


def _scatter_rows(pages: jax.Array, layer, page_ids: jax.Array,
                  off: jax.Array, new: jax.Array) -> jax.Array:
    """Write one hd-wide row per (token, kv head) in place.

    page_ids/off: (...) per-token page id and in-page offset (page id
    n_pages drops the token); new: (..., kv, hd). Each scattered window is
    a single contiguous hd row of the head-major pool, which keeps the
    pool in the kernels' layout when the write sits inside a layer loop (a
    (kv, hd) window made XLA pick another layout for the loop's carry and
    relay the whole pool every layer). A pool of None (the V pool of a
    latent segment) writes nothing."""
    if pages is None:
        return None
    heads = jnp.arange(pages.shape[2])
    return pages.at[layer, page_ids[..., None], heads,
                    off[..., None]].set(new.astype(pages.dtype), mode="drop")


def write_token(pages_k: jax.Array, pages_v: jax.Array, block_table: jax.Array,
                lengths: jax.Array, new_k: jax.Array, new_v: jax.Array,
                active: Optional[jax.Array] = None, layer=0
                ) -> Tuple[jax.Array, jax.Array]:
    """Write one token per slot at its current length into layer `layer`.

    pages_*: (L, n_pages, kv, page, hd); new_*: (B, 1, kv, hd). `active`
    (B,) bool, when given, drops inactive rows' writes entirely — the
    engine's plan/run loop pushes freed rows' block-table clears lazily (at
    most one table transfer per step), so a freed slot's stale row may still
    map pages a COW sibling owns; masking here keeps those pages untouched."""
    n_pages, page_size = pages_k.shape[1], pages_k.shape[3]
    pos = lengths
    page_of = jnp.take_along_axis(block_table, (pos // page_size)[:, None],
                                  axis=1, mode="clip")[:, 0]    # (B,)
    off = pos % page_size
    # unmapped (-1) rows route to index n_pages, which mode="drop" discards —
    # crucial for freed slots whose pages may now belong to another request
    safe_page = jnp.where(page_of < 0, n_pages, page_of)
    if active is not None:
        safe_page = jnp.where(active, safe_page, n_pages)
    # cast-to-pool is a no-op at the default kv_dtype; kv_dtype="bfloat16"
    # stores a narrower non-quantized pool than the compute dtype
    return (_scatter_rows(pages_k, layer, safe_page, off, new_k[:, 0]),
            _scatter_rows(pages_v, layer, safe_page, off,
                          None if new_v is None else new_v[:, 0]))


def write_prompt(pages_k: jax.Array, pages_v: jax.Array, block_row: jax.Array,
                 new_k: jax.Array, new_v: jax.Array, prompt_len: jax.Array,
                 layer=0) -> Tuple[jax.Array, jax.Array]:
    """Scatter a monolithically prefilled prompt's K/V into one sequence's
    pages of layer `layer`, from logical position 0.

    pages_*: (L, n_pages, kv, page, hd); block_row: (P,) this sequence's
    block-table row; new_*: (1, S, kv, hd) right-padded; prompt_len: ()
    valid count in new_*.
    """
    n_pages, page_size = pages_k.shape[1], pages_k.shape[3]
    S = new_k.shape[1]
    pos = jnp.arange(S)
    page_of = jnp.take(block_row, pos // page_size, mode="clip")
    valid = (jnp.arange(S) < prompt_len) & (page_of >= 0)
    safe_page = jnp.where(valid, page_of, n_pages)       # OOB rows dropped
    off = pos % page_size
    return (_scatter_rows(pages_k, layer, safe_page, off, new_k[0]),
            _scatter_rows(pages_v, layer, safe_page, off,
                          None if new_v is None else new_v[0]))


def write_prompt_ragged(pages_k: jax.Array, pages_v: jax.Array,
                        block_rows: jax.Array, new_k: jax.Array,
                        new_v: jax.Array, lens: jax.Array, offsets: jax.Array,
                        layer=0) -> Tuple[jax.Array, jax.Array]:
    """Scatter R slots' prompt chunks into their pages in one shot.

    The batched-ingest analogue of `write_prompt`: row r holds slot r's next
    chunk, right-padded to C with `lens[r]` valid tokens, written at logical
    positions offsets[r]..offsets[r]+lens[r]-1 through that slot's block-table
    row. Distinct slots own distinct pages, so rows never collide and the
    scatter is order-independent — row r's writes are bitwise what a
    one-row call for that row alone would produce.

    pages_*: (L, n_pages, kv, page, hd); block_rows: (R, P); new_*: (R, C,
    kv, hd); lens/offsets: (R,). Padding rows (lens == 0) write nothing.
    """
    n_pages, page_size = pages_k.shape[1], pages_k.shape[3]
    R, C = new_k.shape[0], new_k.shape[1]
    pos = offsets[:, None] + jnp.arange(C)[None, :]            # (R, C)
    page_of = jnp.take_along_axis(block_rows, pos // page_size, axis=1,
                                  mode="clip")
    valid = (jnp.arange(C)[None, :] < lens[:, None]) & (page_of >= 0)
    safe_page = jnp.where(valid, page_of, n_pages)             # OOB dropped
    off = pos % page_size
    return (_scatter_rows(pages_k, layer, safe_page, off, new_k),
            _scatter_rows(pages_v, layer, safe_page, off, new_v))


def write_token_quant(pages_k: jax.Array, pages_v: jax.Array,
                      scales_k: jax.Array, scales_v: jax.Array,
                      block_table: jax.Array, lengths: jax.Array,
                      new_k: jax.Array, new_v: jax.Array, kv_dtype: str,
                      active: Optional[jax.Array] = None, layer=0
                      ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """`write_token` for a quantized pool: requantize each slot's tail page.

    The tail page is always uniquely owned (COW copies partial tails
    eagerly), so rewriting the whole page never clobbers a sibling. Garbage
    positions past the new token are zeroed out of both the abs-max and the
    stored page."""
    n_pages, page_size = pages_k.shape[1], pages_k.shape[3]
    B = lengths.shape[0]
    pos = lengths
    page_of = jnp.take_along_axis(block_table, (pos // page_size)[:, None],
                                  axis=1, mode="clip")[:, 0]    # (B,)
    off = pos % page_size
    safe_page = jnp.where(page_of < 0, n_pages, page_of)
    if active is not None:
        safe_page = jnp.where(active, safe_page, n_pages)
    idx = jnp.minimum(safe_page, n_pages - 1)
    valid = jnp.arange(page_size)[None, :] <= off[:, None]       # (B, page)

    def one(pages, scales, new):
        deq = pages[layer, idx].astype(jnp.float32)              # (B, kv, pg, hd)
        deq = deq * scales[layer, idx][:, :, None, None]
        deq = deq.at[jnp.arange(B), :, off].set(new[:, 0].astype(jnp.float32))
        deq = jnp.where(valid[:, None, :, None], deq, 0.0)
        amax = jnp.max(jnp.abs(deq), axis=(2, 3))                # (B, kv)
        scale = quant_scale(amax, kv_dtype)
        q = _quantize(deq, scale, kv_dtype)
        pages = pages.at[layer, safe_page].set(q, mode="drop")
        scales = scales.at[layer, safe_page].set(scale, mode="drop")
        return pages, scales

    pages_k, scales_k = one(pages_k, scales_k, new_k)
    pages_v, scales_v = one(pages_v, scales_v, new_v)
    return pages_k, pages_v, scales_k, scales_v


def _quant_chunk_scatter(pages, scales, layer, page_ids, newg, inchunk,
                         valid, kv_dtype):
    """Shared tail of the quantized prompt writes: dequantize the touched
    pages, overlay the chunk tokens, requantize over valid positions, and
    scatter pages + scales (rows with nothing to write are dropped).

    pages: (L, n_pages, kv, pg, hd); scales: (L, n_pages, kv); page_ids:
    (T,); newg: (T, pg, kv, hd) f32 chunk tokens aligned to the pages'
    positions; inchunk/valid: (T, pg) masks."""
    n_pages = pages.shape[1]
    idx = jnp.maximum(page_ids, 0)
    deq = pages[layer, idx].astype(jnp.float32) \
        * scales[layer, idx][:, :, None, None]                   # (T, kv, pg, hd)
    deq = jnp.where(inchunk[:, None, :, None], jnp.swapaxes(newg, 1, 2), deq)
    deq = jnp.where(valid[:, None, :, None], deq, 0.0)
    amax = jnp.max(jnp.abs(deq), axis=(2, 3))                    # (T, kv)
    scale = quant_scale(amax, kv_dtype)
    q = _quantize(deq, scale, kv_dtype)
    writes = jnp.any(inchunk, axis=1) & (page_ids >= 0)
    safe = jnp.where(writes, page_ids, n_pages)
    pages = pages.at[layer, safe].set(q, mode="drop")
    scales = scales.at[layer, safe].set(scale, mode="drop")
    return pages, scales


def write_prompt_quant(pages_k: jax.Array, pages_v: jax.Array,
                       scales_k: jax.Array, scales_v: jax.Array,
                       block_row: jax.Array, new_k: jax.Array,
                       new_v: jax.Array, prompt_len: jax.Array, kv_dtype: str,
                       layer=0
                       ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """`write_prompt` for a quantized pool: each touched page is quantized
    whole under its fresh scale."""
    page_size = pages_k.shape[3]
    S = new_k.shape[1]
    n_touch = -(-S // page_size)          # pages the padded prompt spans
    logical = jnp.arange(n_touch)                                # (T,)
    page_ids = jnp.take(block_row, logical, mode="clip")
    kpos = logical[:, None] * page_size + jnp.arange(page_size)[None, :]
    inchunk = kpos < prompt_len                                  # (T, pg)
    valid = inchunk & (page_ids >= 0)[:, None]
    cc = jnp.clip(kpos, 0, S - 1)

    def one(pages, scales, new):
        newg = new[0].astype(jnp.float32)[cc]                    # (T, pg, kv, hd)
        return _quant_chunk_scatter(pages, scales, layer, page_ids, newg,
                                    inchunk, valid, kv_dtype)

    pages_k, scales_k = one(pages_k, scales_k, new_k)
    pages_v, scales_v = one(pages_v, scales_v, new_v)
    return pages_k, pages_v, scales_k, scales_v


def write_prompt_ragged_quant(pages_k: jax.Array, pages_v: jax.Array,
                              scales_k: jax.Array, scales_v: jax.Array,
                              block_rows: jax.Array, new_k: jax.Array,
                              new_v: jax.Array, lens: jax.Array,
                              offsets: jax.Array, kv_dtype: str, layer=0
                              ) -> Tuple[jax.Array, jax.Array, jax.Array,
                                         jax.Array]:
    """`write_prompt_ragged` for a quantized pool: R slots' chunks in one
    shot. Distinct slots own distinct pages, so the flattened (R * touched)
    page rewrite never collides across rows and stays order-independent."""
    page_size = pages_k.shape[3]
    R, C = new_k.shape[0], new_k.shape[1]
    n_touch = C // page_size + 2
    first = offsets // page_size                                  # (R,)
    logical = first[:, None] + jnp.arange(n_touch)[None, :]       # (R, T)
    page_ids = jnp.take_along_axis(block_rows, jnp.minimum(
        logical, block_rows.shape[1] - 1), axis=1)
    page_ids = jnp.where(logical < block_rows.shape[1], page_ids, -1)
    kpos = logical[:, :, None] * page_size \
        + jnp.arange(page_size)[None, None, :]                    # (R, T, pg)
    chunk_idx = kpos - offsets[:, None, None]
    inchunk = (chunk_idx >= 0) & (chunk_idx < lens[:, None, None])
    valid = (kpos < (offsets + lens)[:, None, None]) \
        & (page_ids >= 0)[:, :, None]
    cc = jnp.clip(chunk_idx, 0, C - 1).reshape(R, n_touch * page_size)

    def one(pages, scales, new):
        newg = jnp.take_along_axis(new.astype(jnp.float32),
                                   cc[:, :, None, None], axis=1)
        newg = newg.reshape(R * n_touch, page_size, *new.shape[2:])
        return _quant_chunk_scatter(
            pages, scales, layer, page_ids.reshape(-1), newg,
            inchunk.reshape(R * n_touch, page_size),
            valid.reshape(R * n_touch, page_size), kv_dtype)

    pages_k, scales_k = one(pages_k, scales_k, new_k)
    pages_v, scales_v = one(pages_v, scales_v, new_v)
    return pages_k, pages_v, scales_k, scales_v


_WRITERS = {"token": (write_token, write_token_quant),
            "prompt": (write_prompt, write_prompt_quant),
            "prompt_ragged": (write_prompt_ragged, write_prompt_ragged_quant)}


def write_pools(pools: dict, layer, what: str, *args, **kw) -> dict:
    """Write new K/V into layer `layer` of one segment's pools
    ({"k_pages", "v_pages"[, "k_scale", "v_scale"]}) with the writer
    `what` names ("token", "prompt" or "prompt_ragged"), called with
    `*args, **kw`. A pool that carries scales is quantized, at the kv_dtype
    its storage dtype says, and takes the requantizing writer. A latent
    pool takes the plain writer with its rows as K and no V (the caller
    passes None for the V rows)."""
    plain, quant = _WRITERS[what]
    if LATENT in pools:
        rows, _ = plain(pools[LATENT], None, *args, layer=layer, **kw)
        return {LATENT: rows}
    if "k_scale" not in pools:
        k, v = plain(pools["k_pages"], pools["v_pages"], *args, layer=layer,
                     **kw)
        return {"k_pages": k, "v_pages": v}
    kv_dtype = "int8" if pools["k_pages"].dtype == jnp.int8 else "fp8"
    k, v, ks, vs = quant(pools["k_pages"], pools["v_pages"], pools["k_scale"],
                         pools["v_scale"], *args, kv_dtype, layer=layer, **kw)
    return {"k_pages": k, "v_pages": v, "k_scale": ks, "v_scale": vs}


def copy_page(pages: jax.Array, src: int, dst: int) -> jax.Array:
    """Copy one physical page across all layers of a segment's pool.

    pages: (count, n_pages, ...) — pools and their scales alike. src == dst
    is a no-op copy, used when a fork has no partial tail page to
    duplicate."""
    return pages.at[:, dst].set(pages[:, src])


@dataclasses.dataclass
class PageAllocator:
    """Host-side page bookkeeping: free list + per-slot page chains, with
    per-page refcounts so forks can share read-only prefix pages
    copy-on-write (`fork` / `cow_page`). A page returns to the free list
    only when its last reference is released."""
    n_pages: int
    page_size: int
    max_pages_per_seq: int

    def __post_init__(self):
        self.free: List[int] = list(range(self.n_pages))
        self.owned: dict = {}
        self.refcount: List[int] = [0] * self.n_pages
        # Host tier: req_id -> {"resident": [(logical_idx, page_id)],
        # "swapped_idx": [logical_idx]}. Demoted requests keep shared pages
        # resident (their reference is held, so siblings can't free them)
        # and surrender uniquely-owned pages to the free list once the
        # engine has snapshotted their bytes to host memory.
        self.hosted: Dict = {}

    def _take(self) -> int:
        p = self.free.pop()
        self.refcount[p] = 1
        return p

    def alloc_for(self, slot: int, n_tokens: int) -> List[int]:
        need = max(1, -(-n_tokens // self.page_size))
        assert need <= self.max_pages_per_seq, "sequence exceeds block table"
        if len(self.free) < need:
            raise MemoryError("page pool exhausted")
        pages = [self._take() for _ in range(need)]
        self.owned[slot] = pages
        return pages

    def extend(self, slot: int, new_len: int) -> Optional[int]:
        """Grow slot to cover new_len tokens; returns new page id if mapped."""
        pages = self.owned.get(slot, [])
        need = max(1, -(-new_len // self.page_size))
        if need <= len(pages):
            return None
        if not self.free:
            raise MemoryError("page pool exhausted")
        p = self._take()
        pages.append(p)
        self.owned[slot] = pages
        return p

    def fork(self, src_slot: int, dst_slot: int, n_tokens: int
             ) -> Tuple[List[int], int, int]:
        """Share src's first `n_tokens` of pages with dst copy-on-write.

        Full pages are shared (refcount++); a partial tail page — the page
        the next token write would land in — is copied into a fresh page so
        the fork can append without touching its siblings. Returns
        (dst_pages, tail_src, tail_dst); tail ids are equal when the prefix
        is page-aligned and nothing needs a device-side copy."""
        src_pages = self.owned[src_slot]
        assert dst_slot not in self.owned, "destination slot still owns pages"
        assert 0 < n_tokens <= len(src_pages) * self.page_size
        full = n_tokens // self.page_size
        shared = src_pages[:full]
        tail_src = tail_dst = 0
        if n_tokens % self.page_size:
            if not self.free:
                raise MemoryError("page pool exhausted")
            tail_src = src_pages[full]
            tail_dst = self._take()
        for p in shared:
            self.refcount[p] += 1
        dst_pages = list(shared)
        if tail_src != tail_dst:
            dst_pages.append(tail_dst)
        self.owned[dst_slot] = dst_pages
        return dst_pages, tail_src, tail_dst

    def fork_cost(self, n_tokens: int) -> int:
        """Free pages a fork of an n_tokens prefix consumes now (0 or 1)."""
        return 1 if n_tokens % self.page_size else 0

    def cow_page(self, slot: int, pos: int) -> Optional[Tuple[int, int]]:
        """Copy-on-write guard before writing token position `pos`: if the
        page holding it is shared, re-point the slot at a private copy.
        Returns (old_page, new_page) for the device-side copy, or None when
        the page is already uniquely owned (the common case — forks copy
        their partial tail eagerly, so this triggers only on exotic chains).
        """
        pages = self.owned.get(slot, [])
        idx = pos // self.page_size
        if idx >= len(pages):
            return None
        p = pages[idx]
        if self.refcount[p] <= 1:
            return None
        if not self.free:
            raise MemoryError("page pool exhausted")
        new = self._take()
        self.refcount[p] -= 1
        pages[idx] = new
        return p, new

    def demote(self, slot: int, req_id) -> List[Tuple[int, int]]:
        """Move a slot's chain to the host tier instead of freeing it.

        Uniquely-owned pages are freed for reuse and listed as swapped —
        the caller must snapshot their bytes from the *current* (immutable)
        cache value before dispatching anything that could rewrite them.
        Shared pages stay resident with this chain's reference held, so COW
        siblings cannot free them and `promote` re-shares them in place.
        Returns [(logical_idx, page_id)] for the swapped pages."""
        pages = self.owned.pop(slot)
        resident: List[Tuple[int, int]] = []
        swapped: List[Tuple[int, int]] = []
        for i, p in enumerate(pages):
            if self.refcount[p] == 1:
                swapped.append((i, p))
                self.refcount[p] = 0
                self.free.append(p)
            else:
                resident.append((i, p))
        self.hosted[req_id] = {"resident": resident,
                               "swapped_idx": [i for i, _ in swapped]}
        return swapped

    def promote(self, req_id, slot: int) -> List[Tuple[int, int]]:
        """Re-admit a demoted request into `slot`: fresh device pages for
        the swapped logical indices (MemoryError when the pool is dry),
        resident shared pages rejoin the chain with their held reference.
        Returns [(logical_idx, new_page_id)] upload targets for the host
        bytes, in logical order."""
        ent = self.hosted[req_id]
        assert slot not in self.owned, "destination slot still owns pages"
        if len(self.free) < len(ent["swapped_idx"]):
            raise MemoryError("page pool exhausted")
        uploads = [(i, self._take()) for i in ent["swapped_idx"]]
        chain = dict(uploads)
        chain.update(ent["resident"])
        self.owned[slot] = [chain[i] for i in sorted(chain)]
        del self.hosted[req_id]
        return uploads

    def drop_hosted(self, req_id) -> None:
        """Abandon a demoted request, releasing its held resident refs."""
        ent = self.hosted.pop(req_id, None)
        if ent is None:
            return
        for _, p in ent["resident"]:
            self.refcount[p] -= 1
            assert self.refcount[p] >= 0, "refcount underflow"
            if self.refcount[p] == 0:
                self.free.append(p)

    def hosted_pages(self, req_id) -> int:
        """Swapped page count a promote of req_id must allocate."""
        return len(self.hosted[req_id]["swapped_idx"])

    def release(self, slot: int) -> None:
        for p in self.owned.pop(slot, []):
            self.refcount[p] -= 1
            assert self.refcount[p] >= 0, "refcount underflow"
            if self.refcount[p] == 0:
                self.free.append(p)

    def unique_pages(self, slot: int) -> int:
        """Pages only this slot references — what releasing it would free."""
        return sum(1 for p in self.owned.get(slot, [])
                   if self.refcount[p] == 1)

    @property
    def pages_in_use(self) -> int:
        return self.n_pages - len(self.free)

    @property
    def pages_shared(self) -> int:
        """Physical pages referenced by more than one slot."""
        return sum(1 for c in self.refcount if c > 1)

    @property
    def logical_pages(self) -> int:
        """Sum of per-slot chain lengths (counts shared pages per reference);
        logical - in_use is the memory COW sharing is saving."""
        return sum(len(v) for v in self.owned.values())

    @property
    def utilization(self) -> float:
        return 1.0 - len(self.free) / self.n_pages
