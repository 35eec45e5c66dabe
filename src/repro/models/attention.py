"""GQA attention: training/prefill (full-sequence) and decode (cached) paths.

Pure-jnp reference implementations; `cfg.use_pallas=True` routes the hot paths
through the Pallas kernels in repro.kernels (flash_attention for prefill,
decode_attention for cached decode, the paged kernels for the paged cache).
Whether the cached-attention kernels can serve a config is decided once, by
`pallas_unsupported`; the cached paths below never fall back per call.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import cache as cache_lib
from repro.models.config import ModelConfig
from repro.models.layers import apply_rope, dense_init, rmsnorm

NEG_INF = -1e30


def pallas_unsupported(cfg: ModelConfig, paged: bool) -> str:
    """Why the cached-attention Pallas kernels (dense decode, or with
    `paged` the paged decode and chunk-ingest kernels) cannot serve `cfg`
    on this backend; '' when they can. The serving engine asks once, at
    construction, and reads through the jnp oracle when the answer is not
    empty."""
    if cfg.attn_logit_softcap:
        return "attn_logit_softcap is set and the kernels apply no softcap"
    if cfg.sliding_window:
        return "sliding_window is set and the kernels attend the whole cache"
    from repro.analysis.rules import LANE_MULTIPLE
    from repro.kernels.runtime import default_interpret
    hd = cfg.resolved_head_dim
    if paged and not default_interpret() and hd % LANE_MULTIPLE:
        return (f"head_dim {hd} is not a multiple of {LANE_MULTIPLE}, which "
                "the TPU block rule needs for a (page, head_dim) tile")
    return ""


def _require_kernels(cfg: ModelConfig, paged: bool = True) -> None:
    """Trace-time guard on the cached paths: use_pallas on a config the
    kernels cannot serve is an error, never a silent fallback."""
    why = pallas_unsupported(cfg, paged)
    if why:
        raise ValueError(f"{cfg.name}: use_pallas=True but {why}")


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------

def init_attention(cfg: ModelConfig, key, cross: bool = False,
                   kv_d_model: Optional[int] = None) -> dict:
    pd = jnp.dtype(cfg.param_dtype)
    d, hd = cfg.d_model, cfg.resolved_head_dim
    n_q, n_kv = cfg.n_heads, cfg.n_kv_heads
    kd = kv_d_model or d
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    p = {
        "wq": dense_init(k1, (d, n_q, hd), dtype=pd),
        "wk": dense_init(k2, (kd, n_kv, hd), dtype=pd),
        "wv": dense_init(k3, (kd, n_kv, hd), dtype=pd),
        "wo": dense_init(k4, (n_q, hd, d), in_axis=1, dtype=pd),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((n_q, hd), pd)
        p["bk"] = jnp.zeros((n_kv, hd), pd)
        p["bv"] = jnp.zeros((n_kv, hd), pd)
    if cfg.qk_norm and not cross:
        p["q_norm"] = jnp.ones((hd,), pd)
        p["k_norm"] = jnp.ones((hd,), pd)
    return p


def _project_qkv(cfg: ModelConfig, params: dict, x: jax.Array,
                 kv_x: Optional[jax.Array] = None):
    dt = x.dtype
    kv_x = x if kv_x is None else kv_x
    q = jnp.einsum("bsd,dnh->bsnh", x, params["wq"].astype(dt))
    k = jnp.einsum("bsd,dnh->bsnh", kv_x, params["wk"].astype(dt))
    v = jnp.einsum("bsd,dnh->bsnh", kv_x, params["wv"].astype(dt))
    if "bq" in params:
        q = q + params["bq"].astype(dt)
        k = k + params["bk"].astype(dt)
        v = v + params["bv"].astype(dt)
    if "q_norm" in params:
        q = rmsnorm(q, params["q_norm"], cfg.norm_eps)
        k = rmsnorm(k, params["k_norm"], cfg.norm_eps)
    return q, k, v


def _repeat_kv(k: jax.Array, q_per_kv: int) -> jax.Array:
    """(B,S,n_kv,hd) -> (B,S,n_q,hd) by repeating each kv head."""
    if q_per_kv == 1:
        return k
    return jnp.repeat(k, q_per_kv, axis=2)


def _sdpa(q, k, v, mask, softcap: float = 0.0):
    """q: (B,Tq,N,hd), k/v: (B,Tk,N,hd), mask broadcastable (B,1,Tq,Tk)."""
    hd = q.shape[-1]
    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32))
    logits = jnp.einsum("bqnh,bknh->bnqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if softcap:
        logits = jnp.tanh(logits / softcap) * softcap
    logits = jnp.where(mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bnqk,bknh->bqnh", probs.astype(v.dtype), v)
    return out


# Use q-blocked attention when the logits matrix would exceed this many
# elements per (batch, head) — avoids materializing S x S at long context.
CHUNK_THRESHOLD = 4096 * 4096
CHUNK_BQ = 512


def chunked_sdpa(q, k, v, *, causal: bool, window: int = 0,
                 kv_lengths: Optional[jax.Array] = None,
                 softcap: float = 0.0) -> jax.Array:
    """Q-blocked attention (flash-style, pure jnp, lax.map over q blocks).

    q: (B,Sq,N,hd), k/v: (B,Sk,N,hd) already head-repeated. Never materializes
    more than (B, bq, N, Sk_eff) logits; with a sliding window only a
    (window + bq) K/V slice is read per block (true sub-quadratic compute).
    """
    B, Sq, N, hd = q.shape
    Sk = k.shape[1]
    bq = min(CHUNK_BQ, Sq)
    while Sq % bq:
        bq //= 2
    nb = Sq // bq
    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32))

    use_window_slice = bool(window) and (window + bq) <= Sk

    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * bq, bq, axis=1)
        rows = i * bq + jnp.arange(bq)
        if use_window_slice:
            start = jnp.clip(i * bq + bq - (window + bq), 0, Sk - (window + bq))
            ki = jax.lax.dynamic_slice_in_dim(k, start, window + bq, axis=1)
            vi = jax.lax.dynamic_slice_in_dim(v, start, window + bq, axis=1)
            cols = start + jnp.arange(window + bq)
        else:
            ki, vi = k, v
            cols = jnp.arange(Sk)
        logits = jnp.einsum("bqnh,bknh->bnqk", qi.astype(jnp.float32),
                            ki.astype(jnp.float32)) * scale
        if softcap:
            logits = jnp.tanh(logits / softcap) * softcap
        m = jnp.ones((bq, cols.shape[0]), bool)
        if causal:
            m = m & (cols[None, :] <= rows[:, None])
        if window:
            m = m & (cols[None, :] > rows[:, None] - window)
        m = m[None, None]
        if kv_lengths is not None:
            m = m & (cols[None, None, None, :] < kv_lengths[:, None, None, None])
        logits = jnp.where(m, logits, NEG_INF)
        probs = jax.nn.softmax(logits, axis=-1)
        return jnp.einsum("bnqk,bknh->bqnh", probs.astype(v.dtype), vi)

    # checkpoint each q-block: the VJP otherwise stores every block's f32
    # probs — a full (B, N, Sq, Sk) attention matrix across the loop (§Perf:
    # 343 GB/device at granite train_4k). Recomputed in backward instead.
    outs = jax.lax.map(jax.checkpoint(block), jnp.arange(nb))
    return jnp.moveaxis(outs, 0, 1).reshape(B, Sq, N, hd)


def full_or_chunked_sdpa(q, k, v, *, causal: bool, window: int = 0,
                         kv_lengths: Optional[jax.Array] = None,
                         softcap: float = 0.0) -> jax.Array:
    """Dense SDPA for short sequences, q-blocked for long ones."""
    Sq, Sk = q.shape[1], k.shape[1]
    if Sq * Sk >= CHUNK_THRESHOLD and Sq > 1:
        return chunked_sdpa(q, k, v, causal=causal, window=window,
                            kv_lengths=kv_lengths, softcap=softcap)
    mask = jnp.ones((1, 1, Sq, Sk), bool)
    if causal and Sq == Sk:
        mask = causal_mask(Sq, Sk, window=window)
    if kv_lengths is not None:
        mask = mask & (jnp.arange(Sk)[None, None, None, :]
                       < kv_lengths[:, None, None, None])
    return _sdpa(q, k, v, mask, softcap)


def causal_mask(Tq: int, Tk: int, q_offset: int = 0,
                window: int = 0) -> jax.Array:
    """(1,1,Tq,Tk) bool; window>0 applies sliding-window causality."""
    qi = jnp.arange(Tq)[:, None] + q_offset
    ki = jnp.arange(Tk)[None, :]
    m = ki <= qi
    if window:
        m = m & (ki > qi - window)
    return m[None, None]


# ---------------------------------------------------------------------------
# Full-sequence (train / prefill)
# ---------------------------------------------------------------------------

def attention_fwd(cfg: ModelConfig, params: dict, x: jax.Array,
                  positions: jax.Array, *, causal: bool = True,
                  segment_mask: Optional[jax.Array] = None) -> jax.Array:
    """Self-attention over a full sequence. x: (B, S, D)."""
    B, S, _ = x.shape
    q, k, v = _project_qkv(cfg, params, x)
    if cfg.use_rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    if cfg.use_pallas:
        from repro.kernels.flash_attention import ops as fa_ops
        out = fa_ops.flash_attention(q, k, v, causal=causal,
                                     window=cfg.sliding_window,
                                     softcap=cfg.attn_logit_softcap)
    else:
        k = _repeat_kv(k, cfg.q_per_kv)
        v = _repeat_kv(v, cfg.q_per_kv)
        if segment_mask is not None:
            mask = causal_mask(S, S, window=cfg.sliding_window) if causal \
                else jnp.ones((1, 1, S, S), bool)
            out = _sdpa(q, k, v, mask & segment_mask, cfg.attn_logit_softcap)
        else:
            out = full_or_chunked_sdpa(q, k, v, causal=causal,
                                       window=cfg.sliding_window,
                                       softcap=cfg.attn_logit_softcap)
    dt = x.dtype
    return jnp.einsum("bsnh,nhd->bsd", out, params["wo"].astype(dt))


def cross_attention_fwd(cfg: ModelConfig, params: dict, x: jax.Array,
                        enc_out: jax.Array) -> jax.Array:
    """Cross-attention (whisper decoder): x (B,T,D) attends enc_out (B,Se,De)."""
    q, k, v = _project_qkv(cfg, params, x, kv_x=enc_out)
    k = _repeat_kv(k, cfg.q_per_kv)
    v = _repeat_kv(v, cfg.q_per_kv)
    out = full_or_chunked_sdpa(q, k, v, causal=False,
                               softcap=cfg.attn_logit_softcap)
    return jnp.einsum("bsnh,nhd->bsd", out, params["wo"].astype(x.dtype))


def cross_attention_cached(cfg: ModelConfig, params: dict, x: jax.Array,
                           ck: jax.Array, cv: jax.Array) -> jax.Array:
    """Decode-time cross-attention against precomputed enc K/V (B,Se,n_kv,hd)."""
    dt = x.dtype
    q = jnp.einsum("bsd,dnh->bsnh", x, params["wq"].astype(dt))
    if "bq" in params:
        q = q + params["bq"].astype(dt)
    k = _repeat_kv(ck, cfg.q_per_kv)
    v = _repeat_kv(cv, cfg.q_per_kv)
    out = full_or_chunked_sdpa(q, k, v, causal=False,
                               softcap=cfg.attn_logit_softcap)
    return jnp.einsum("bsnh,nhd->bsd", out, params["wo"].astype(dt))


# ---------------------------------------------------------------------------
# Decode (single or few new tokens against a cache)
# ---------------------------------------------------------------------------

def attention_decode(cfg: ModelConfig, params: dict, x: jax.Array,
                     layer_k: jax.Array, layer_v: jax.Array,
                     lengths: jax.Array, window: int = 0
                     ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Decode step. x: (B, T, D) with T new tokens (usually 1).

    layer_k/layer_v: (B, Scache, n_kv, hd); lengths: (B,) tokens already in
    cache. Returns (out, new_layer_k, new_layer_v).
    """
    B, T, _ = x.shape
    q, k, v = _project_qkv(cfg, params, x)
    positions = lengths[:, None] + jnp.arange(T)[None, :]
    if cfg.use_rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    layer_k, layer_v = cache_lib.update_layer_kv(layer_k, layer_v, lengths,
                                                 k, v, window=window)
    Sc = layer_k.shape[1]
    if cfg.use_pallas and T == 1:
        _require_kernels(cfg, paged=False)
        from repro.kernels.decode_attention import ops as da_ops
        out = da_ops.decode_attention(q, layer_k, layer_v, lengths + T)
    else:
        ki = jnp.arange(Sc)[None, None, :]                     # (1,1,Sc)
        qpos = positions[:, :, None]                           # (B,T,1)
        if window:
            # ring buffer: entry at slot s holds absolute position p iff
            # p % window == s and p <= qpos and p > qpos - window.
            # Reconstruct absolute position of each slot given current length.
            total = lengths[:, None, None] + T                 # tokens after write
            abs_pos = ki + ((total - 1 - ki) // window) * window
            valid = (abs_pos <= qpos) & (abs_pos > qpos - window) & (abs_pos >= 0)
            mask = valid[:, None]                              # (B,1,T,Sc)
        else:
            mask = (ki <= qpos)[:, None]
        out = _grouped_sdpa(q, layer_k, layer_v, mask, cfg.q_per_kv,
                            cfg.attn_logit_softcap)
    dt = x.dtype
    out = jnp.einsum("bsnh,nhd->bsd", out, params["wo"].astype(dt))
    return out, layer_k, layer_v


def attention_decode_paged(cfg: ModelConfig, params: dict, x: jax.Array,
                           k_pages: jax.Array, v_pages: jax.Array,
                           block_table: jax.Array, lengths: jax.Array,
                           live_pages: Optional[int] = None,
                           active: Optional[jax.Array] = None,
                           k_scales: Optional[jax.Array] = None,
                           v_scales: Optional[jax.Array] = None):
    """Decode step against a paged KV pool (vLLM-style block table).

    x: (B, 1, D); k_pages/v_pages: (n_pages, page, n_kv, hd) this layer's
    pools; block_table: (B, P) page ids (-1 = unmapped); lengths: (B,) tokens
    already cached per slot. Returns (out, new_k_pages, new_v_pages,
    new_k_scales, new_v_scales) — the scales are None unless
    cfg.kv_quantized, in which case k/v_scales: (n_pages, n_kv) f32 are the
    pool's per-(page, kv-head) dequant scales and the whole path follows the
    quantized tolerance contract (docs/serving.md) instead of bit-exactness.

    live_pages (static) trims the READ width to the first `live_pages`
    block-table columns — callers pass ceil((max(lengths)+1)/page_size),
    bucketed to bound recompilation. Trimmed columns are beyond every slot's
    valid positions, whose softmax weight is exactly zero, so outputs are
    bit-identical at any covering width; the token write uses the full table.

    The read path is keyed on cfg.use_pallas: the paged flash-decode kernel
    streams only mapped pages through the block table (per-step KV volume
    O(sum lengths)); the fallback/oracle gathers the (trimmed) table into
    the contiguous layout and runs the same masked grouped SDPA as the
    dense path, so dense and paged backends stay bit-identical on it.

    `active` (B,) bool, when given, drops inactive rows' K/V writes — the
    plan/run engine defers freed slots' block-table clears, so a stale row
    may still map pages a COW sibling owns (see pc.write_token).
    """
    from repro.models import paged_cache as pc
    B, T, _ = x.shape
    q, k, v = _project_qkv(cfg, params, x)
    positions = lengths[:, None] + jnp.arange(T)[None, :]
    if cfg.use_rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    dt = x.dtype
    table = block_table if live_pages is None \
        else block_table[:, :live_pages]
    if cfg.kv_quantized:
        k_pages, v_pages, k_scales, v_scales = pc.write_token_quant(
            k_pages, v_pages, k_scales, v_scales, block_table, lengths,
            k, v, cfg.kv_dtype, active=active)
        if cfg.use_pallas:
            _require_kernels(cfg)
            from repro.kernels.paged_decode_attention import ops as pda_ops
            out = pda_ops.paged_decode_attention_quant(
                q, k_pages, v_pages, k_scales, v_scales, table, lengths + T)
        else:
            gk = pc.gather_sequence_dequant(k_pages, k_scales, table)
            gv = pc.gather_sequence_dequant(v_pages, v_scales, table)
            Sc = gk.shape[1]
            ki = jnp.arange(Sc)[None, None, :]
            qpos = positions[:, :, None]
            mask = (ki <= qpos)[:, None]
            out = _grouped_sdpa(q.astype(jnp.float32), gk, gv, mask,
                                cfg.q_per_kv, cfg.attn_logit_softcap)
        out = out.astype(dt)
        out = jnp.einsum("bsnh,nhd->bsd", out, params["wo"].astype(dt))
        return out, k_pages, v_pages, k_scales, v_scales
    k_pages, v_pages = pc.write_token(k_pages, v_pages, block_table, lengths,
                                      k, v, active=active)
    if cfg.use_pallas:
        _require_kernels(cfg)
        from repro.kernels.paged_decode_attention import ops as pda_ops
        # the new token was just written at position `lengths`
        out = pda_ops.paged_decode_attention(q, k_pages, v_pages, table,
                                             lengths + T)
    else:
        gk = pc.gather_sequence(k_pages, table)
        gv = pc.gather_sequence(v_pages, table)
        Sc = gk.shape[1]
        ki = jnp.arange(Sc)[None, None, :]
        qpos = positions[:, :, None]
        mask = (ki <= qpos)[:, None]
        out = _grouped_sdpa(q, gk, gv, mask, cfg.q_per_kv,
                            cfg.attn_logit_softcap)
    out = jnp.einsum("bsnh,nhd->bsd", out, params["wo"].astype(dt))
    return out, k_pages, v_pages, None, None


def attention_prefill_chunk_paged(cfg: ModelConfig, params: dict, x: jax.Array,
                                  k_pages: jax.Array, v_pages: jax.Array,
                                  block_row: jax.Array, offset, chunk_len,
                                  live_pages: Optional[int] = None,
                                  k_scales: Optional[jax.Array] = None,
                                  v_scales: Optional[jax.Array] = None):
    """One prompt chunk against a paged KV pool (chunked prefill).

    x: (1, C, D) — C new tokens of ONE slot, right-padded to `chunk_len`
    valid; block_row: (P,) the slot's block-table row; offset: () tokens
    already written for this slot (the chunk's first logical position).
    Writes the chunk's K/V at offset..offset+chunk_len-1, then attends each
    chunk query causally within the chunk AND against everything the slot
    already holds (ragged cross-chunk read). Returns (out, k_pages, v_pages,
    k_scales, v_scales) — scales are None unless cfg.kv_quantized (see
    attention_decode_paged).

    The oracle/fallback reads through the same gather + `_grouped_sdpa`
    formulation as the paged decode step — deliberately: the grouped einsum
    is reduction-order stable across query counts, so a chunk of C tokens
    produces bitwise the outputs of C single-token decode steps (fork-suffix
    and eviction-resume replays stay bit-identical to uninterrupted decode),
    and at C > 1 it matches the monolithic `_prefill_block` SDPA bitwise.
    `cfg.use_pallas` routes the read through the paged-prefill Pallas kernel
    (kernels/paged_prefill_attention), which streams only the slot's mapped
    pages HBM->VMEM through the scalar-prefetched block row.
    """
    B, C, _ = x.shape
    q, k, v = _project_qkv(cfg, params, x)
    positions = jnp.asarray(offset, jnp.int32) + jnp.arange(C)[None]
    if cfg.use_rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    from repro.models import paged_cache as pc
    dt = x.dtype
    row = block_row if live_pages is None else block_row[:live_pages]
    if cfg.kv_quantized:
        k_pages, v_pages, k_scales, v_scales = pc.write_prompt_quant(
            k_pages, v_pages, k_scales, v_scales, block_row, k, v,
            chunk_len, cfg.kv_dtype, offset=offset)
        if cfg.use_pallas:
            _require_kernels(cfg)
            from repro.kernels.paged_prefill_attention import ops as ppa_ops
            out = ppa_ops.paged_prefill_attention_quant(
                q, k_pages, v_pages, k_scales, v_scales, row, offset,
                chunk_len)
        else:
            gk = pc.gather_sequence_dequant(k_pages, k_scales, row[None])
            gv = pc.gather_sequence_dequant(v_pages, v_scales, row[None])
            Sc = gk.shape[1]
            ki = jnp.arange(Sc)[None, None, :]
            qpos = positions[:, :, None]
            mask = (ki <= qpos)[:, None]
            out = _grouped_sdpa(q.astype(jnp.float32), gk, gv, mask,
                                cfg.q_per_kv, cfg.attn_logit_softcap)
        out = out.astype(dt)
        out = jnp.einsum("bsnh,nhd->bsd", out, params["wo"].astype(dt))
        return out, k_pages, v_pages, k_scales, v_scales
    k_pages, v_pages = pc.write_prompt(k_pages, v_pages, block_row, k, v,
                                       chunk_len, offset=offset)
    if cfg.use_pallas:
        _require_kernels(cfg)
        from repro.kernels.paged_prefill_attention import ops as ppa_ops
        out = ppa_ops.paged_prefill_attention(q, k_pages, v_pages, row,
                                              offset, chunk_len)
    else:
        gk = pc.gather_sequence(k_pages, row[None])
        gv = pc.gather_sequence(v_pages, row[None])
        Sc = gk.shape[1]
        ki = jnp.arange(Sc)[None, None, :]
        qpos = positions[:, :, None]
        mask = (ki <= qpos)[:, None]
        out = _grouped_sdpa(q, gk, gv, mask, cfg.q_per_kv,
                            cfg.attn_logit_softcap)
    out = jnp.einsum("bsnh,nhd->bsd", out, params["wo"].astype(dt))
    return out, k_pages, v_pages, None, None


def attention_prefill_ragged_paged(cfg: ModelConfig, params: dict,
                                   x: jax.Array, k_pages: jax.Array,
                                   v_pages: jax.Array, block_rows: jax.Array,
                                   offsets: jax.Array, lens: jax.Array,
                                   live_pages: Optional[int] = None,
                                   k_scales: Optional[jax.Array] = None,
                                   v_scales: Optional[jax.Array] = None):
    """R prompt chunks — one per ingesting slot — against a paged KV pool in
    a single call (batched ragged ingest).

    x: (R, C, D) — row r is slot r's next chunk, right-padded to `lens[r]`
    valid tokens; block_rows: (R, P) the slots' block-table rows (pre-trimmed
    to the shared live width); offsets: (R,) tokens already written per slot.
    Writes every row's chunk K/V (`pc.write_prompt_ragged` — distinct slots
    own distinct pages, so the scatter is collision-free), then attends each
    row's queries causally within its chunk AND against everything that slot
    already holds. Returns (out, k_pages, v_pages, k_scales, v_scales) —
    scales are None unless cfg.kv_quantized (see attention_decode_paged);
    row r positions past lens[r] are unspecified, as are padding rows
    (lens == 0).

    Numerics contract: both read paths are row-independent — the oracle is
    the same gather + `_grouped_sdpa` formulation as the single-slot chunk
    path (batching adds rows, never changes a row's reduction order), and the
    ragged Pallas kernel walks each row's pages exactly as the single-slot
    kernel does — so batched ingest is bitwise the one-chunk-per-step
    scheduler, which is in turn bitwise monolithic prefill.
    """
    R, C, _ = x.shape
    q, k, v = _project_qkv(cfg, params, x)
    positions = offsets[:, None] + jnp.arange(C)[None, :]          # (R, C)
    if cfg.use_rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    from repro.models import paged_cache as pc
    dt = x.dtype
    rows = block_rows if live_pages is None else block_rows[:, :live_pages]
    if cfg.kv_quantized:
        k_pages, v_pages, k_scales, v_scales = pc.write_prompt_ragged_quant(
            k_pages, v_pages, k_scales, v_scales, block_rows, k, v, lens,
            offsets, cfg.kv_dtype)
        if cfg.use_pallas:
            _require_kernels(cfg)
            from repro.kernels.paged_prefill_attention import ops as ppa_ops
            out = ppa_ops.paged_prefill_attention_ragged_quant(
                q, k_pages, v_pages, k_scales, v_scales, rows, offsets, lens)
        else:
            gk = pc.gather_sequence_dequant(k_pages, k_scales, rows)
            gv = pc.gather_sequence_dequant(v_pages, v_scales, rows)
            Sc = gk.shape[1]
            ki = jnp.arange(Sc)[None, None, :]
            qpos = positions[:, :, None]
            mask = (ki <= qpos)[:, None]
            out = _grouped_sdpa(q.astype(jnp.float32), gk, gv, mask,
                                cfg.q_per_kv, cfg.attn_logit_softcap)
        out = out.astype(dt)
        out = jnp.einsum("bsnh,nhd->bsd", out, params["wo"].astype(dt))
        return out, k_pages, v_pages, k_scales, v_scales
    k_pages, v_pages = pc.write_prompt_ragged(k_pages, v_pages, block_rows,
                                              k, v, lens, offsets)
    if cfg.use_pallas:
        _require_kernels(cfg)
        from repro.kernels.paged_prefill_attention import ops as ppa_ops
        out = ppa_ops.paged_prefill_attention_ragged(q, k_pages, v_pages,
                                                     rows, offsets, lens)
    else:
        gk = pc.gather_sequence(k_pages, rows)         # (R, P*page, kv, hd)
        gv = pc.gather_sequence(v_pages, rows)
        Sc = gk.shape[1]
        ki = jnp.arange(Sc)[None, None, :]
        qpos = positions[:, :, None]
        mask = (ki <= qpos)[:, None]
        out = _grouped_sdpa(q, gk, gv, mask, cfg.q_per_kv,
                            cfg.attn_logit_softcap)
    out = jnp.einsum("bsnh,nhd->bsd", out, params["wo"].astype(dt))
    return out, k_pages, v_pages, None, None


def _grouped_sdpa(q, k, v, mask, q_per_kv: int, softcap: float = 0.0):
    """GQA attention WITHOUT materializing repeated K/V.

    q: (B,Tq,Nq,hd) -> grouped (B,Tq,Nkv,g,hd); k/v: (B,Tk,Nkv,hd); mask
    broadcastable to (B,1,Tq,Tk). jnp.repeat of the cache forces GSPMD to
    reshard it (involuntary full-rematerialization all-gathers — §Perf:
    77 GB/step at qwen3-8b decode_32k); the grouped einsum keeps the cache
    sharding intact.
    """
    if q_per_kv == 1:
        return _sdpa(q, k, v, mask, softcap)
    B, Tq, Nq, hd = q.shape
    Nkv = k.shape[2]
    qg = q.reshape(B, Tq, Nkv, q_per_kv, hd)
    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32))
    # keep operands in their storage dtype (bf16) with f32 MXU accumulation:
    # upcasting the cache first would double any resharding traffic (§Perf)
    logits = jnp.einsum("bqngh,bknh->bngqk", qg, k,
                        preferred_element_type=jnp.float32) * scale
    if softcap:
        logits = jnp.tanh(logits / softcap) * softcap
    logits = jnp.where(mask[:, :, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bngqk,bknh->bqngh", probs.astype(v.dtype), v)
    return out.reshape(B, Tq, Nq, hd)
