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
from repro.models.layers import (apply_rope, apply_rope_freqs, dense_init,
                                 mla_rope_frequencies, mla_rope_mscale,
                                 mla_softmax_scale, rmsnorm)

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
    if cfg.is_mla:
        if not paged:
            return "latent attention has paged kernels only"
        return ""       # latent rows are padded to 128 lanes
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
    if cfg.is_mla:
        return init_mla(cfg, key)
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


def _paged_read(cfg: ModelConfig, q, pools: dict, layer, rows, positions,
                kernels, kernel_args):
    """Attention read shared by the paged decode / chunk / ragged paths:
    q (R, T, Hq, hd) at `positions` (R, T) against layer `layer` of
    `pools` through block-table `rows` (R, P), the new tokens already
    written. `cfg.use_pallas` streams the mapped pages through the
    (plain, quantized) kernel pair `kernels`, called with `kernel_args`
    after the pools; the oracle gathers `rows` into the contiguous layout
    and runs the masked grouped SDPA the dense path runs, so dense and
    paged backends stay bit-identical on it."""
    from repro.models import paged_cache as pc
    kp, vp = pools["k_pages"], pools["v_pages"]
    quant = "k_scale" in pools
    if cfg.use_pallas:
        _require_kernels(cfg)
        plain, quantized = kernels
        if quant:
            return quantized(q, kp, vp, pools["k_scale"], pools["v_scale"],
                             *kernel_args, layer=layer)
        return plain(q, kp, vp, *kernel_args, layer=layer)
    if quant:
        gk = pc.gather_sequence_dequant(kp, pools["k_scale"], rows, layer)
        gv = pc.gather_sequence_dequant(vp, pools["v_scale"], rows, layer)
        q = q.astype(jnp.float32)
    else:
        gk = pc.gather_sequence(kp, rows, layer)        # (R, P*page, kv, hd)
        gv = pc.gather_sequence(vp, rows, layer)
    ki = jnp.arange(gk.shape[1])[None, None, :]
    mask = (ki <= positions[:, :, None])[:, None]
    return _grouped_sdpa(q, gk, gv, mask, cfg.q_per_kv,
                         cfg.attn_logit_softcap)


def _paged_qkv(cfg: ModelConfig, params: dict, x: jax.Array, positions):
    q, k, v = _project_qkv(cfg, params, x)
    if cfg.use_rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _paged_out(params: dict, out: jax.Array, dt) -> jax.Array:
    return jnp.einsum("bsnh,nhd->bsd", out.astype(dt),
                      params["wo"].astype(dt))


def attention_decode_paged(cfg: ModelConfig, params: dict, x: jax.Array,
                           pools: dict, layer, block_table: jax.Array,
                           lengths: jax.Array,
                           live_pages: Optional[int] = None,
                           active: Optional[jax.Array] = None):
    """Decode step against a paged KV pool (vLLM-style block table).

    x: (B, 1, D); pools: one attention segment's stacked pools
    {"k_pages", "v_pages"} (L, n_pages, n_kv, page, hd), plus "k_scale" /
    "v_scale" (L, n_pages, n_kv) f32 when cfg.kv_quantized; layer: () the
    layer read and written (traced in the layer loop); block_table: (B, P)
    page ids (-1 = unmapped); lengths: (B,) tokens already cached per slot.
    Returns (out, pools) with the new token written in place; a quantized
    pool follows the quantized tolerance contract (docs/serving.md) instead
    of bit-exactness.

    live_pages (static) trims the READ width to the first `live_pages`
    block-table columns — callers pass ceil((max(lengths)+1)/page_size),
    bucketed to bound recompilation. Trimmed columns are beyond every slot's
    valid positions, whose softmax weight is exactly zero, so outputs are
    bit-identical at any covering width; the token write uses the full table.

    The read path is keyed on cfg.use_pallas: the paged flash-decode kernel
    streams only mapped pages through the block table (per-step KV volume
    O(sum lengths)); the fallback/oracle gathers the (trimmed) table (see
    `_paged_read`).

    `active` (B,) bool, when given, drops inactive rows' K/V writes — the
    plan/run engine defers freed slots' block-table clears, so a stale row
    may still map pages a COW sibling owns (see pc.write_token).
    """
    from repro.models import paged_cache as pc
    from repro.kernels.paged_decode_attention import ops as pda_ops
    B, T, _ = x.shape
    positions = lengths[:, None] + jnp.arange(T)[None, :]
    q, k, v = _paged_qkv(cfg, params, x, positions)
    pools = pc.write_pools(pools, layer, "token", block_table, lengths, k, v,
                           active=active)
    table = block_table if live_pages is None \
        else block_table[:, :live_pages]
    # the new token was just written at position `lengths`
    out = _paged_read(cfg, q, pools, layer, table, positions,
                      (pda_ops.paged_decode_attention,
                       pda_ops.paged_decode_attention_quant),
                      (table, lengths + T))
    return _paged_out(params, out, x.dtype), pools


def attention_prefill_ragged_paged(cfg: ModelConfig, params: dict,
                                   x: jax.Array, pools: dict, layer,
                                   block_rows: jax.Array, offsets: jax.Array,
                                   lens: jax.Array,
                                   live_pages: Optional[int] = None):
    """R prompt chunks — one per ingesting slot — against a paged KV pool in
    a single call (batched ragged ingest).

    x: (R, C, D) — row r is slot r's next chunk, right-padded to `lens[r]`
    valid tokens; pools/layer: as in attention_decode_paged; block_rows:
    (R, P) the slots' block-table rows; offsets: (R,) tokens already
    written per slot. Writes every row's chunk K/V (`pc.write_prompt_ragged`
    — distinct slots own distinct pages, so the scatter is collision-free),
    then attends each row's queries causally within its chunk AND against
    everything that slot already holds. Returns (out, pools); row r
    positions past lens[r] are unspecified, as are padding rows (lens == 0).

    Numerics contract: each row is independent of the others in the batch
    on both read paths — batching adds rows, never changes a row's
    reduction order, and the ragged Pallas kernel walks each row's pages
    alone. The oracle/fallback reads through the same gather +
    `_grouped_sdpa` formulation as the paged decode step — deliberately:
    the grouped einsum is reduction-order stable across query counts, so a
    chunk of C tokens produces bitwise the outputs of C single-token decode
    steps (fork-suffix and eviction-resume replays stay bit-identical to
    uninterrupted decode), and a chunked ingest matches monolithic prefill.
    `cfg.use_pallas` routes the read through the paged-prefill Pallas
    kernel (kernels/paged_prefill_attention), which streams only each
    row's mapped pages HBM->VMEM through its scalar-prefetched block row.
    """
    from repro.models import paged_cache as pc
    from repro.kernels.paged_prefill_attention import ops as ppa_ops
    R, C, _ = x.shape
    positions = offsets[:, None] + jnp.arange(C)[None, :]          # (R, C)
    q, k, v = _paged_qkv(cfg, params, x, positions)
    pools = pc.write_pools(pools, layer, "prompt_ragged", block_rows, k, v,
                           lens, offsets)
    rows = block_rows if live_pages is None else block_rows[:, :live_pages]
    out = _paged_read(cfg, q, pools, layer, rows, positions,
                      (ppa_ops.paged_prefill_attention_ragged,
                       ppa_ops.paged_prefill_attention_ragged_quant),
                      (rows, offsets, lens))
    return _paged_out(params, out, x.dtype), pools


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


# ---------------------------------------------------------------------------
# Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434), no q-LoRA
#
#   q = x W_q -> H x (nope + rope); [c_kv, k_pe] = x W_kva; c_kv normed;
#   [k_nope, v] = c_kv W_kvb -> H x (nope + v); rope on q_pe and the one
#   k_pe every head shares; scores (q_nope.k_nope + q_pe.k_pe) x scale;
#   out = softmax . v -> W_o.
#
# Rope is rotate-half over the rope dims at YaRN frequencies. (The
# published DeepseekV2 code de-interleaves the rope dims first: a fixed
# permutation of W_q's and W_kva's rope columns, so a checkpoint loads
# into this convention by permuting those columns.)
#
# The full-sequence path expands k_nope and v per head. The paged paths
# cache only [c_kv, k_pe] (paged_cache.LATENT) and absorb W_kvb: queries
# go through W_UK (q_lat = W_UK q_nope, rank lanes) and meet the cached
# rows directly, and the attended latent goes through W_UV.
# ---------------------------------------------------------------------------

def init_mla(cfg: ModelConfig, key) -> dict:
    pd = jnp.dtype(cfg.param_dtype)
    d, H, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    nope, rope, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "wq": dense_init(k1, (d, H, nope + rope), dtype=pd),
        "wkv_a": dense_init(k2, (d, r + rope), dtype=pd),
        "kv_norm": jnp.ones((r,), pd),
        "wkv_b": dense_init(k3, (r, H, nope + vd), dtype=pd),
        "wo": dense_init(k4, (H, vd, d), in_axis=1, dtype=pd),
    }


def _mla_rope(cfg: ModelConfig, x, positions):
    return apply_rope_freqs(x, positions, mla_rope_frequencies(cfg),
                            mla_rope_mscale(cfg))


def _mla_project(cfg: ModelConfig, params: dict, x: jax.Array, positions):
    """-> q_nope (B,S,H,nope), roped q_pe (B,S,H,rope), normed c_kv
    (B,S,rank) and roped k_pe (B,S,rope), in x's dtype."""
    dt = x.dtype
    nope, r = cfg.qk_nope_head_dim, cfg.kv_lora_rank
    q = jnp.einsum("bsd,dnh->bsnh", x, params["wq"].astype(dt))
    kva = x @ params["wkv_a"].astype(dt)
    c = rmsnorm(kva[..., :r], params["kv_norm"], cfg.norm_eps)
    q_pe = _mla_rope(cfg, q[..., nope:], positions)
    k_pe = _mla_rope(cfg, kva[..., None, r:], positions)[..., 0, :]
    return q[..., :nope], q_pe, c, k_pe


def mla_fwd(cfg: ModelConfig, params: dict, x: jax.Array,
            positions: jax.Array, causal: bool = True) -> jax.Array:
    """Latent attention over a full sequence (train / scoring), with k_nope
    and v expanded per head. x: (B, S, D)."""
    dt = x.dtype
    nope = cfg.qk_nope_head_dim
    q_nope, q_pe, c, k_pe = _mla_project(cfg, params, x, positions)
    kv = jnp.einsum("bsr,rnh->bsnh", c, params["wkv_b"].astype(dt))
    k_nope, v = kv[..., :nope], kv[..., nope:]
    f32 = jnp.float32
    logits = (jnp.einsum("bqnh,bknh->bnqk", q_nope.astype(f32),
                         k_nope.astype(f32))
              + jnp.einsum("bqnh,bkh->bnqk", q_pe.astype(f32),
                           k_pe.astype(f32))) * mla_softmax_scale(cfg)
    S = x.shape[1]
    if causal:
        logits = jnp.where(causal_mask(S, S), logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bnqk,bknh->bqnh", probs.astype(v.dtype), v)
    return jnp.einsum("bsnh,nhd->bsd", out, params["wo"].astype(dt))


def _mla_paged(cfg: ModelConfig, params: dict, x: jax.Array, positions,
               write, read):
    """Shared by the paged latent paths: project, write the new tokens'
    latent rows with `write(pools, rows)` (rows: (B, S, 1, W)), read with
    `read(pools, q)` (absorbed q: (B, S, H, W) -> latent out (B, S, H,
    rank)), then W_UV and W_o. Returns (out, pools)."""
    dt = x.dtype
    nope, r = cfg.qk_nope_head_dim, cfg.kv_lora_rank
    pad = cfg.latent_width - r - cfg.qk_rope_head_dim
    q_nope, q_pe, c, k_pe = _mla_project(cfg, params, x, positions)
    wkv_b = params["wkv_b"].astype(dt)
    rows = jnp.concatenate([c, k_pe, jnp.zeros(c.shape[:-1] + (pad,), dt)],
                           axis=-1)[..., None, :]
    pools = write(rows)
    q_lat = jnp.einsum("bsnh,rnh->bsnr", q_nope, wkv_b[..., :nope])
    q = jnp.concatenate(
        [q_lat, q_pe, jnp.zeros(q_pe.shape[:-1] + (pad,), dt)], axis=-1)
    lat = read(pools, q).astype(dt)
    out = jnp.einsum("bsnr,rnh->bsnh", lat, wkv_b[..., nope:])
    return jnp.einsum("bsnh,nhd->bsd", out, params["wo"].astype(dt)), pools


def _latent_oracle(cfg: ModelConfig, q, pages, layer, rows, positions,
                   total):
    """The paged latent read without a kernel: gather, then attend."""
    from repro.kernels.paged_decode_attention.ref import latent_attend
    from repro.models import paged_cache as pc
    g = pc.gather_sequence(pages, rows, layer)
    return latent_attend(q, g, positions, total, cfg.kv_lora_rank,
                         mla_softmax_scale(cfg))


def mla_decode_paged(cfg: ModelConfig, params: dict, x: jax.Array,
                     pools: dict, layer, block_table: jax.Array,
                     lengths: jax.Array, live_pages: Optional[int] = None,
                     active: Optional[jax.Array] = None):
    """`attention_decode_paged` for latent attention: one latent row per
    new token into layer `layer` of the latent pool, then the absorbed read
    (kernels/paged_decode_attention latent mode under cfg.use_pallas).
    Returns (out, pools)."""
    from repro.models import paged_cache as pc
    from repro.kernels.paged_decode_attention import ops as pda_ops
    positions = lengths[:, None] + jnp.arange(x.shape[1])[None, :]
    table = block_table if live_pages is None \
        else block_table[:, :live_pages]

    def write(rows):
        return pc.write_pools(pools, layer, "token", block_table, lengths,
                              rows, None, active=active)

    def read(pools, q):
        pages = pools[pc.LATENT]
        if cfg.use_pallas:
            return pda_ops.mla_paged_decode(
                q, pages, table, lengths + 1, layer, rank=cfg.kv_lora_rank,
                scale=mla_softmax_scale(cfg))
        return _latent_oracle(cfg, q, pages, layer, table, positions,
                              lengths + 1)
    return _mla_paged(cfg, params, x, positions, write, read)


def mla_prefill_ragged_paged(cfg: ModelConfig, params: dict, x: jax.Array,
                             pools: dict, layer, block_rows: jax.Array,
                             offsets: jax.Array, lens: jax.Array,
                             live_pages: Optional[int] = None):
    """`attention_prefill_ragged_paged` for latent attention: R slots'
    chunks' latent rows written, then each row's queries attend causally
    within its chunk and to what its slot holds (kernels/
    paged_prefill_attention latent mode under cfg.use_pallas). Returns
    (out, pools)."""
    from repro.models import paged_cache as pc
    from repro.kernels.paged_prefill_attention import ops as ppa_ops
    C = x.shape[1]
    positions = offsets[:, None] + jnp.arange(C)[None, :]
    rows_t = block_rows if live_pages is None \
        else block_rows[:, :live_pages]

    def write(rows):
        return pc.write_pools(pools, layer, "prompt_ragged", block_rows,
                              rows, None, lens, offsets)

    def read(pools, q):
        pages = pools[pc.LATENT]
        if cfg.use_pallas:
            return ppa_ops.mla_paged_prefill(
                q, pages, rows_t, offsets, lens, layer,
                rank=cfg.kv_lora_rank, scale=mla_softmax_scale(cfg))
        return _latent_oracle(cfg, q, pages, layer, rows_t, positions,
                              offsets + lens)
    return _mla_paged(cfg, params, x, positions, write, read)
