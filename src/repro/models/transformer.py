"""Model stacks: decoder-only / MoE / SSM / xLSTM / hybrid / enc-dec / VLM.

Layers are grouped into homogeneous *segments* (contiguous runs of the same
block kind). Each segment's params are stacked on a leading layer axis and
executed with lax.scan (cfg.scan_layers=False unrolls — used by the dry-run
so XLA cost analysis sees every layer's FLOPs).

Public entry points:
  init_params(cfg, key)
  forward(cfg, params, tokens, ...)         -> logits (train / scoring)
  prefill(cfg, params, tokens, cache, ...)  -> (logits, cache)
  decode_step(cfg, params, tokens, cache)   -> (logits, cache)
  init_cache(cfg, batch, max_len) / cache_specs(...)
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.distributed import sharding as shd
from repro.models import attention as attn_lib
from repro.models import moe as moe_lib
from repro.models import ssm as ssm_lib
from repro.models import xlstm as xlstm_lib
from repro.models.config import (ATTN,
                                 MAMBA2,
                                 MLSTM,
                                 MOE,
                                 SHARED_ATTN,
                                 SLSTM,
                                 ModelConfig)
from repro.models.layers import (dense_init, embed, embed_init, init_embedding,
                                 init_mlp, init_norm, mlp, norm, unembed)


# ---------------------------------------------------------------------------
# Segments
# ---------------------------------------------------------------------------

def segments_of(cfg: ModelConfig) -> List[Tuple[str, int]]:
    pat = cfg.block_pattern()
    segs: List[Tuple[str, int]] = []
    for kind in pat:
        if segs and segs[-1][0] == kind:
            segs[-1] = (kind, segs[-1][1] + 1)
        else:
            segs.append((kind, 1))
    return segs


# ---------------------------------------------------------------------------
# Per-block init
# ---------------------------------------------------------------------------

def _init_block(cfg: ModelConfig, key, kind: str, cross: bool = False) -> dict:
    ks = jax.random.split(key, 4)
    d = cfg.d_model
    if kind == ATTN or kind == SHARED_ATTN:
        p = {
            "norm1": init_norm(cfg, d),
            "attn": attn_lib.init_attention(cfg, ks[0]),
            "norm2": init_norm(cfg, d),
            "mlp": init_mlp(cfg, ks[1], d, cfg.d_ff, gated=not cfg.use_layernorm),
        }
        if cross:
            p["norm_x"] = init_norm(cfg, d)
            p["xattn"] = attn_lib.init_attention(
                cfg, ks[2], cross=True, kv_d_model=cfg.encoder.d_model)
        return p
    if kind == MOE:
        return {
            "norm1": init_norm(cfg, d),
            "attn": attn_lib.init_attention(cfg, ks[0]),
            "norm2": init_norm(cfg, d),
            "moe": moe_lib.init_moe(cfg, ks[1]),
        }
    if kind == MAMBA2:
        return {"norm1": init_norm(cfg, d), "mamba": ssm_lib.init_mamba2(cfg, ks[0])}
    if kind == MLSTM:
        return {"norm1": init_norm(cfg, d), "mlstm": xlstm_lib.init_mlstm(cfg, ks[0])}
    if kind == SLSTM:
        return {"norm1": init_norm(cfg, d), "slstm": xlstm_lib.init_slstm(cfg, ks[0])}
    raise ValueError(kind)


def _stack_init(cfg: ModelConfig, key, kind: str, count: int, cross: bool) -> dict:
    keys = jax.random.split(key, count)
    return jax.vmap(lambda k: _init_block(cfg, k, kind, cross))(keys)


# ---------------------------------------------------------------------------
# Whisper-style encoder (over stubbed frame/patch embeddings)
# ---------------------------------------------------------------------------

def _enc_cfg_as_model(cfg: ModelConfig) -> ModelConfig:
    e = cfg.encoder
    return cfg.with_(d_model=e.d_model, n_heads=e.n_heads, n_kv_heads=e.n_kv_heads,
                     d_ff=e.d_ff, n_layers=e.n_layers, use_rope=False,
                     sliding_window=0, qk_norm=False, qkv_bias=cfg.qkv_bias)


def _init_encoder(cfg: ModelConfig, key) -> dict:
    ecfg = _enc_cfg_as_model(cfg)
    e = cfg.encoder
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "pos": embed_init(k1, (e.n_ctx, e.d_model), jnp.dtype(cfg.param_dtype)),
        "blocks": _stack_init(ecfg, k2, ATTN, e.n_layers, cross=False),
        "final_norm": init_norm(cfg, e.d_model),
    }


def encode(cfg: ModelConfig, params: dict, frames: jax.Array,
           mesh=None) -> jax.Array:
    """frames: (B, n_ctx, d_enc) stub embeddings -> encoder output."""
    ecfg = _enc_cfg_as_model(cfg)
    x = frames + params["pos"].astype(frames.dtype)[None, : frames.shape[1]]
    positions = jnp.arange(frames.shape[1])[None]

    def body(x, blk):
        h = attn_lib.attention_fwd(ecfg, blk["attn"],
                                   norm(ecfg, blk["norm1"], x), positions,
                                   causal=False)
        x = x + h
        x = x + mlp(ecfg, blk["mlp"], norm(ecfg, blk["norm2"], x))
        return x, None

    x, _ = _run_segment(ecfg, params["blocks"], x, body, mesh)
    return norm(ecfg, params["final_norm"], x)


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def init_params(cfg: ModelConfig, key) -> dict:
    cfg.validate()
    segs = segments_of(cfg)
    keys = jax.random.split(key, len(segs) + 5)
    cross = cfg.family == "encdec"
    p: Dict[str, Any] = {"embed": init_embedding(cfg, keys[0])}
    seg_params = []
    shared_done = False
    for i, (kind, count) in enumerate(segs):
        if kind == SHARED_ATTN:
            if not shared_done:
                p["shared"] = _init_block(cfg, keys[i + 1], SHARED_ATTN)
                shared_done = True
            seg_params.append({})  # weights live in p["shared"]
        else:
            seg_params.append(_stack_init(cfg, keys[i + 1], kind, count, cross))
    p["segments"] = seg_params
    p["final_norm"] = init_norm(cfg, cfg.d_model)
    if cfg.family == "encdec":
        p["encoder"] = _init_encoder(cfg, keys[-1])
        p["dec_pos"] = embed_init(keys[-2], (cfg.max_seq_len, cfg.d_model),
                                  jnp.dtype(cfg.param_dtype))
    if cfg.length_buckets:
        p["length_head"] = dense_init(keys[-3], (cfg.d_model, cfg.length_buckets))
    return p


# ---------------------------------------------------------------------------
# Segment runner (scan or unroll, with optional remat + sharding constraint)
# ---------------------------------------------------------------------------

def _act_axes(cfg: ModelConfig, mesh):
    if mesh is None:
        return None
    b = shd.batch_axes(mesh)
    if cfg.act_shard == "batch_seq":
        return (b, "model", None)
    if cfg.act_shard == "batch_model":
        return (b, None, "model")
    return (b, None, None)


def _constrain(cfg: ModelConfig, mesh, x):
    axes = _act_axes(cfg, mesh)
    if axes is None or mesh is None:
        return x
    return shd.constraint(x, mesh, axes)


def _scan_or_unroll(cfg: ModelConfig, fn, init, xs):
    """lax.scan over stacked layers, or a python unroll (cfg.scan_layers=False,
    used by the dry-run so XLA cost analysis sees every layer)."""
    if cfg.scan_layers:
        return jax.lax.scan(fn, init, xs)
    n = jax.tree_util.tree_leaves(xs)[0].shape[0]
    carry, ys = init, []
    for i in range(n):
        sl = jax.tree.map(lambda a: a[i], xs)
        carry, y = fn(carry, sl)
        ys.append(y)
    if ys and ys[0] is not None:
        ys = jax.tree.map(lambda *a: jnp.stack(a), *ys)
    else:
        ys = None
    return carry, ys


def _run_segment(cfg: ModelConfig, stacked: dict, x: jax.Array, body, mesh):
    """Run body over stacked layer params. body: (x, blk) -> (x, aux|None)."""
    def fn(x, blk):
        x = _constrain(cfg, mesh, x)
        return body(x, blk)

    if cfg.remat:
        fn = jax.checkpoint(fn)
    return _scan_or_unroll(cfg, fn, x, stacked)


# ---------------------------------------------------------------------------
# Block bodies (full-sequence)
# ---------------------------------------------------------------------------

def _block_fwd_full(cfg: ModelConfig, kind: str, blk: dict, x, positions,
                    enc_out=None, mesh=None):
    """Returns (x, aux) for one block over a full sequence."""
    aux = jnp.zeros((), jnp.float32)
    if kind in (ATTN, SHARED_ATTN, MOE):
        attend = attn_lib.mla_fwd if cfg.is_mla else attn_lib.attention_fwd
        h = attend(cfg, blk["attn"], norm(cfg, blk["norm1"], x), positions,
                   causal=True)
        x = x + h
        if enc_out is not None and "xattn" in blk:
            x = x + attn_lib.cross_attention_fwd(
                cfg, blk["xattn"], norm(cfg, blk["norm_x"], x), enc_out)
        if kind == MOE and cfg.moe_dropless:
            h = moe_lib.moe_serve(cfg, blk["moe"], norm(cfg, blk["norm2"], x))
        elif kind == MOE:
            h, aux = moe_lib.moe_fwd(cfg, blk["moe"], norm(cfg, blk["norm2"], x),
                                     mesh=mesh)
        else:
            h = mlp(cfg, blk["mlp"], norm(cfg, blk["norm2"], x))
        return x + h, aux
    if kind == MAMBA2:
        return x + ssm_lib.mamba2_fwd(cfg, blk["mamba"],
                                      norm(cfg, blk["norm1"], x)), aux
    if kind == MLSTM:
        return x + xlstm_lib.mlstm_fwd(cfg, blk["mlstm"],
                                       norm(cfg, blk["norm1"], x)), aux
    if kind == SLSTM:
        return x + xlstm_lib.slstm_fwd(cfg, blk["slstm"],
                                       norm(cfg, blk["norm1"], x)), aux
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# Forward (train / full-sequence scoring)
# ---------------------------------------------------------------------------

def forward(cfg: ModelConfig, params: dict, tokens: jax.Array,
            prefix_embeds: Optional[jax.Array] = None,
            enc_frames: Optional[jax.Array] = None,
            mesh=None, return_hidden: bool = False):
    """tokens: (B, S) int32.

    prefix_embeds: VLM stub patch embeddings (B, n_prefix, D) prepended.
    enc_frames: whisper stub frame embeddings (B, n_ctx, d_enc).
    Returns (logits, aux_loss[, hidden]).
    """
    x = embed(cfg, params["embed"], tokens)
    if prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
    B, S, _ = x.shape
    positions = jnp.arange(S)[None]
    enc_out = None
    if cfg.family == "encdec":
        enc_out = encode(cfg, params["encoder"], enc_frames, mesh)
        x = x + params["dec_pos"].astype(x.dtype)[None, :S]
    x = _constrain(cfg, mesh, x)

    aux_total = jnp.zeros((), jnp.float32)
    for (kind, count), seg in zip(segments_of(cfg), params["segments"]):
        if kind == SHARED_ATTN:
            x, aux = _block_fwd_full(cfg, kind, params["shared"], x, positions,
                                     enc_out, mesh)
            aux_total = aux_total + aux
            continue

        def body(x, blk, kind=kind):
            return _block_fwd_full(cfg, kind, blk, x, positions, enc_out, mesh)

        x, auxs = _run_segment(cfg, seg, x, body, mesh)
        if auxs is not None:
            aux_total = aux_total + jnp.sum(auxs)

    x = norm(cfg, params["final_norm"], x)
    logits = unembed(cfg, params["embed"], x)
    if mesh is not None:
        logits = shd.constraint(logits, mesh,
                                (shd.batch_axes(mesh), None, "model"))
    if return_hidden:
        return logits, aux_total, x
    return logits, aux_total


def predict_length(cfg: ModelConfig, params: dict, hidden: jax.Array) -> jax.Array:
    """PICE response-length head: mean-pooled hidden -> bucket logits."""
    pooled = jnp.mean(hidden.astype(jnp.float32), axis=1)
    return pooled @ params["length_head"].astype(jnp.float32)


# ---------------------------------------------------------------------------
# Cache init / specs
# ---------------------------------------------------------------------------

def _seg_cache(cfg: ModelConfig, kind: str, count: int, batch: int,
               max_len: int, spec: bool):
    assert not (cfg.is_mla and kind in (ATTN, MOE)), \
        "latent attention caches through the paged backend only"
    hd = cfg.resolved_head_dim
    adt = jnp.dtype(cfg.dtype)
    mk = (lambda sh, dt: jax.ShapeDtypeStruct(sh, dt)) if spec else (
        lambda sh, dt: jnp.zeros(sh, dt))
    if kind in (ATTN, MOE, SHARED_ATTN):
        w = cfg.sliding_window
        size = w if w else max_len
        c = {"k": mk((count, batch, size, cfg.n_kv_heads, hd), adt),
             "v": mk((count, batch, size, cfg.n_kv_heads, hd), adt)}
        if cfg.family == "encdec":
            c["cross_k"] = mk((count, batch, cfg.encoder.n_ctx, cfg.n_kv_heads, hd), adt)
            c["cross_v"] = mk((count, batch, cfg.encoder.n_ctx, cfg.n_kv_heads, hd), adt)
        return c
    if kind == MAMBA2:
        inner, H, P, N = ssm_lib.ssm_dims(cfg)
        return {"conv": mk((count, batch, cfg.ssm_conv - 1, inner), adt),
                "ssd": mk((count, batch, H, P, N), jnp.float32)}
    if kind == MLSTM:
        inner, H, hdm = xlstm_lib.mlstm_dims(cfg)
        return {"C": mk((count, batch, H, hdm, hdm), jnp.float32),
                "n": mk((count, batch, H, hdm), jnp.float32),
                "m": mk((count, batch, H), jnp.float32)}
    if kind == SLSTM:
        d = cfg.d_model
        return {k: mk((count, batch, d), jnp.float32) for k in ("h", "c", "n", "m")}
    raise ValueError(kind)


def init_cache(cfg: ModelConfig, batch: int, max_len: int, spec: bool = False) -> dict:
    mk = (lambda sh, dt: jax.ShapeDtypeStruct(sh, dt)) if spec else (
        lambda sh, dt: jnp.zeros(sh, dt))
    return {
        "lengths": mk((batch,), jnp.int32),
        "segments": [
            _seg_cache(cfg, kind, count, batch, max_len, spec)
            for kind, count in segments_of(cfg)
        ],
    }


# ---------------------------------------------------------------------------
# Prefill
# ---------------------------------------------------------------------------

def prefill(cfg: ModelConfig, params: dict, tokens: jax.Array, cache: dict,
            prefix_embeds: Optional[jax.Array] = None,
            enc_frames: Optional[jax.Array] = None,
            prompt_lengths: Optional[jax.Array] = None,
            mesh=None) -> Tuple[jax.Array, dict]:
    """Process the prompt, fill the cache, return last-position logits.

    tokens: (B, S) right-padded to S; prompt_lengths: (B,) actual lengths
    (defaults to S).
    """
    x = embed(cfg, params["embed"], tokens)
    if prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
    B, S, _ = x.shape
    if prompt_lengths is None:
        prompt_lengths = jnp.full((B,), S, jnp.int32)
    elif prefix_embeds is not None:
        prompt_lengths = prompt_lengths + prefix_embeds.shape[1]
    positions = jnp.arange(S)[None]
    enc_out = None
    if cfg.family == "encdec":
        enc_out = encode(cfg, params["encoder"], enc_frames, mesh)
        x = x + params["dec_pos"].astype(x.dtype)[None, :S]
    x = _constrain(cfg, mesh, x)

    new_segs = []
    for (kind, count), seg, segc in zip(segments_of(cfg), params["segments"],
                                        cache["segments"]):
        if kind == SHARED_ATTN:
            x, newc = _prefill_block(cfg, kind, params["shared"],
                                     jax.tree.map(lambda a: a[0], segc), x,
                                     positions, prompt_lengths, enc_out, mesh)
            newc = jax.tree.map(lambda a: a[None], newc)
        else:
            def scan_body(x, inp, kind=kind):
                blk, c = inp
                x = _constrain(cfg, mesh, x)
                x, newc = _prefill_block(cfg, kind, blk, c, x, positions,
                                         prompt_lengths, enc_out, mesh)
                return x, newc
            x, newc = _scan_or_unroll(cfg, scan_body, x, (seg, segc))
        new_segs.append(newc)

    x = norm(cfg, params["final_norm"], x)
    # logits at the last real token of each prompt
    idx = jnp.clip(prompt_lengths - 1, 0, S - 1)
    last_h = jax.vmap(lambda h, i: h[i])(x, idx)
    logits = unembed(cfg, params["embed"], last_h[:, None])[:, 0]
    new_cache = {"lengths": prompt_lengths, "segments": new_segs}
    return logits, new_cache


def _prefill_block(cfg: ModelConfig, kind: str, blk: dict, c: dict, x,
                   positions, prompt_lengths, enc_out, mesh=None,
                   kv_writer=None, layer=None):
    """Full-sequence pass that also produces the cache entry for this layer.

    kv_writer: optional (c, k, v) -> newc override for the attention-KV cache
    entry (the paged backend scatters into its page pool here); the compute
    path is shared so dense and paged prefill produce identical activations.
    The paged backend's `layer` reads a held-expert stack (_block_tail).
    """
    B, S, _ = x.shape
    hd = cfg.resolved_head_dim
    if kind in (ATTN, MOE, SHARED_ATTN):
        xin = norm(cfg, blk["norm1"], x)
        q, k, v = attn_lib._project_qkv(cfg, blk["attn"], xin)
        if cfg.use_rope:
            q = attn_lib.apply_rope(q, positions, cfg.rope_theta)
            k = attn_lib.apply_rope(k, positions, cfg.rope_theta)
        kf = attn_lib._repeat_kv(k, cfg.q_per_kv)
        vf = attn_lib._repeat_kv(v, cfg.q_per_kv)
        h = attn_lib.full_or_chunked_sdpa(
            q, kf, vf, causal=True, window=cfg.sliding_window,
            kv_lengths=prompt_lengths, softcap=cfg.attn_logit_softcap)
        h = jnp.einsum("bsnh,nhd->bsd", h, blk["attn"]["wo"].astype(x.dtype))
        x = x + h
        if kv_writer is not None:
            newc = kv_writer(c, k, v)
            return _block_tail(cfg, kind, blk, x, newc, enc_out, mesh,
                               layer=layer)
        newc = dict(c)
        if cfg.sliding_window:
            w = cfg.sliding_window
            # keep the last `w` positions (assumes S >= w or pads zeros)
            if S >= w:
                newc["k"], newc["v"] = k[:, S - w:], v[:, S - w:]
                # ring layout: slot = pos % w
                roll = (-(S % w)) % w
                newc["k"] = jnp.roll(newc["k"], -roll, axis=1)
                newc["v"] = jnp.roll(newc["v"], -roll, axis=1)
            else:
                pad_k = jnp.zeros((B, w - S, cfg.n_kv_heads, hd), k.dtype)
                newc["k"] = jnp.concatenate([k, pad_k], axis=1)
                newc["v"] = jnp.concatenate([v, pad_k], axis=1)
        else:
            newc["k"] = jnp.zeros_like(c["k"]).at[:, :S].set(k)
            newc["v"] = jnp.zeros_like(c["v"]).at[:, :S].set(v)
        return _block_tail(cfg, kind, blk, x, newc, enc_out, mesh)
    if kind == MAMBA2:
        out, conv_s, ssd_s = ssm_lib.mamba2_fwd(
            cfg, blk["mamba"], norm(cfg, blk["norm1"], x), return_state=True)
        return x + out, {"conv": conv_s.astype(c["conv"].dtype), "ssd": ssd_s}
    if kind == MLSTM:
        out, st = xlstm_lib.mlstm_fwd(cfg, blk["mlstm"],
                                      norm(cfg, blk["norm1"], x),
                                      return_state=True)
        return x + out, st
    if kind == SLSTM:
        out, st = xlstm_lib.slstm_fwd(cfg, blk["slstm"],
                                      norm(cfg, blk["norm1"], x),
                                      return_state=True)
        return x + out, st
    raise ValueError(kind)


def _block_tail(cfg: ModelConfig, kind: str, blk: dict, x, newc,
                enc_out, mesh=None, stats=None, layer=None):
    """Post-attention tail of an attention block, shared by dense and paged
    prefill and the paged ingest and decode steps: optional cross-attention
    cache, then the MoE/MLP block (the serving expert layer, which fills
    `stats`, when cfg.moe_dropless; its experts are the whole stack of the
    layer loop, read at the traced `layer`, where _paged_layers hands it
    that)."""
    if enc_out is not None and "xattn" in blk:
        xin2 = norm(cfg, blk["norm_x"], x)
        _, ck, cv = attn_lib._project_qkv(cfg, blk["xattn"], xin2,
                                          kv_x=enc_out)
        newc["cross_k"], newc["cross_v"] = ck, cv
        x = x + attn_lib.cross_attention_cached(cfg, blk["xattn"], xin2, ck, cv)
    if kind == MOE and cfg.moe_dropless:
        h = moe_lib.moe_serve(cfg, blk["moe"], norm(cfg, blk["norm2"], x),
                              stats, layer)
    elif kind == MOE:
        h, _ = moe_lib.moe_fwd(cfg, blk["moe"], norm(cfg, blk["norm2"], x),
                               mesh=mesh)
    else:
        h = mlp(cfg, blk["mlp"], norm(cfg, blk["norm2"], x))
    return x + h, newc


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------

def _advance_lengths(lengths: jax.Array,
                     active: Optional[jax.Array]) -> jax.Array:
    """Post-decode length update: only active rows consumed a token. Without
    the mask, freed slots' lengths drift past max_len between requests and
    keep issuing clipped cache writes."""
    if active is None:
        return lengths + 1
    return lengths + active.astype(lengths.dtype)


def decode_step(cfg: ModelConfig, params: dict, tokens: jax.Array, cache: dict,
                mesh=None, active: Optional[jax.Array] = None
                ) -> Tuple[jax.Array, dict]:
    """tokens: (B, 1) -> (logits (B, vocab), updated cache).

    active: optional (B,) bool mask of live slots; inactive rows keep their
    cached length (their writes land in freed space and are overwritten on
    slot reuse)."""
    x = embed(cfg, params["embed"], tokens)
    lengths = cache["lengths"]
    if cfg.family == "encdec":
        pos = jnp.clip(lengths, 0, cfg.max_seq_len - 1)
        x = x + params["dec_pos"].astype(x.dtype)[pos][:, None]
    x = _constrain(cfg, mesh, x)

    new_segs = []
    for (kind, count), seg, segc in zip(segments_of(cfg), params["segments"],
                                        cache["segments"]):
        if kind == SHARED_ATTN:
            x, newc = _decode_block(cfg, kind, params["shared"],
                                    jax.tree.map(lambda a: a[0], segc), x,
                                    lengths, mesh)
            newc = jax.tree.map(lambda a: a[None], newc)
        else:
            def scan_body(x, inp, kind=kind):
                blk, c = inp
                x = _constrain(cfg, mesh, x)
                x, newc = _decode_block(cfg, kind, blk, c, x, lengths, mesh)
                return x, newc
            x, newc = _scan_or_unroll(cfg, scan_body, x, (seg, segc))
        new_segs.append(newc)

    x = norm(cfg, params["final_norm"], x)
    logits = unembed(cfg, params["embed"], x)[:, 0]
    if mesh is not None:
        logits = shd.constraint(logits, mesh, (shd.batch_axes(mesh), "model"))
    new_cache = {"lengths": _advance_lengths(lengths, active),
                 "segments": new_segs}
    return logits, new_cache


# ---------------------------------------------------------------------------
# Paged KV cache (vLLM-style): init / prefill / decode
# ---------------------------------------------------------------------------

def _check_paged_support(cfg: ModelConfig) -> None:
    assert cfg.sliding_window == 0, \
        "paged KV cache supports full attention only (sliding_window=0)"
    assert cfg.family != "encdec", \
        "paged KV cache does not support cross-attention caches"


def _check_attention_only(cfg: ModelConfig) -> None:
    assert all(kind in (ATTN, MOE, SHARED_ATTN)
               for kind, _ in segments_of(cfg)), \
        "chunked prefill supports attention-only stacks"


def init_paged_cache(cfg: ModelConfig, batch: int, n_pages: int,
                     page_size: int, max_pages_per_seq: int,
                     spec: bool = False) -> dict:
    """Paged cache pytree: attention segments store stacked page pools
    addressed through one shared block table; recurrent segments (SSM/xLSTM)
    keep their O(1) per-slot dense states.

      k_pages/v_pages: (count, n_pages, n_kv, page_size, hd)
      latent_pages:    (count, n_pages, 1, page_size, latent_width), in
                       place of K and V for latent attention (cfg.is_mla)
      block_table:     (batch, max_pages_per_seq) int32, -1 = unmapped
      lengths:         (batch,)

    The pools are head-major, the layout the paged Pallas kernels read: one
    (page_size, hd) tile is contiguous, so nothing relays a pool between
    the layer loop and a kernel.

    cfg.kv_quantized stores the pools at the int8/fp8 storage dtype and adds
    per-(page, kv-head) f32 scale leaves k_scale/v_scale: (count, n_pages,
    n_kv), initialized to ones so unwritten pages dequantize to zeros.
    """
    _check_paged_support(cfg)
    from repro.models import paged_cache as pc
    hd = cfg.resolved_head_dim
    adt = pc.kv_storage_dtype(cfg.resolved_kv_dtype)
    mk = (lambda sh, dt: jax.ShapeDtypeStruct(sh, dt)) if spec else (
        lambda sh, dt: jnp.zeros(sh, dt))
    assert not (cfg.is_mla and cfg.kv_quantized), \
        "the latent pool keeps no dequant scales: int8/fp8 latent pages " \
        "are not supported"
    segs = []
    for kind, count in segments_of(cfg):
        if kind in (ATTN, MOE, SHARED_ATTN) and cfg.is_mla:
            segs.append({pc.LATENT: mk(
                (count, n_pages, 1, page_size, cfg.latent_width), adt)})
        elif kind in (ATTN, MOE, SHARED_ATTN):
            sh = (count, n_pages, cfg.n_kv_heads, page_size, hd)
            seg = {"k_pages": mk(sh, adt), "v_pages": mk(sh, adt)}
            if cfg.kv_quantized:
                sh = (count, n_pages, cfg.n_kv_heads)
                seg["k_scale"] = (mk(sh, jnp.float32) if spec
                                  else jnp.ones(sh, jnp.float32))
                seg["v_scale"] = (mk(sh, jnp.float32) if spec
                                  else jnp.ones(sh, jnp.float32))
            segs.append(seg)
        else:
            segs.append(_seg_cache(cfg, kind, count, batch, 0, spec))
    table = (jax.ShapeDtypeStruct((batch, max_pages_per_seq), jnp.int32)
             if spec else jnp.full((batch, max_pages_per_seq), -1, jnp.int32))
    return {"lengths": mk((batch,), jnp.int32), "block_table": table,
            "segments": segs}


def _expert_stack(cfg: ModelConfig, kind: str, seg: dict, dtype):
    """(the segment's params without its held-expert weights, those
    weights stacked over the segment's layers) for a serving expert
    segment whose weights are stored in the activations' dtype; else
    (seg, None): those read one layer's weights, cast to `dtype`."""
    if kind != MOE or not cfg.moe_dropless \
            or seg["moe"]["w_gate"].dtype != dtype:
        return seg, None
    moe = dict(seg["moe"])
    experts = {k: moe.pop(k) for k in moe_lib.EXPERT_WEIGHTS}
    return {**seg, "moe": moe}, experts


def _paged_layers(cfg: ModelConfig, params: dict, cache: dict, x, attn,
                  other=None, mesh=None, stats=None):
    """Run every layer of a paged-cache stack over `x`; returns (x, the new
    segment caches).

    Each attention segment's stacked pools ride the layer loop's CARRY, and
    `attn(x, blk, pools, layer, kind, stats) -> (x, pools)` reads and
    writes layer `layer` (traced) of them in place. Scanned as xs/ys
    instead, the pools would be sliced out of the stack and written back
    whole every layer, and XLA would hold a second stacked pool as scratch.
    Recurrent segments' O(1) per-slot states scan as usual through
    `other(x, blk, state, kind) -> (x, state)`.

    The serving expert layers' held-expert weights stay out of the scanned
    xs for the same reason (_expert_stack): `blk` holds the segment's whole
    stack of them, loop-invariant and read-only, and the grouped matmul
    reads layer `layer` of it in place. Scanned, each layer's experts
    would be sliced out of the stack and copied whole, since XLA
    materialises a custom call's operand.

    `stats`, a dict, gets "expert_rows": the assignments each held expert
    computed, summed over the serving expert layers (cfg.moe_dropless)."""
    new_segs = []
    rows = []
    for (kind, count), seg, segc in zip(segments_of(cfg), params["segments"],
                                        cache["segments"]):
        if kind == SHARED_ATTN:
            x, segc = attn(x, params["shared"], segc, 0, kind, None)
        elif kind in (ATTN, MOE):
            seg, experts = _expert_stack(cfg, kind, seg, x.dtype)

            def body(carry, inp, kind=kind, experts=experts):
                x, pools = carry
                blk, layer = inp
                if experts:
                    blk = {**blk, "moe": {**blk["moe"], **experts}}
                x = _constrain(cfg, mesh, x)
                st = {}
                out = attn(x, blk, pools, layer, kind, st)
                return out, st.get("expert_rows")
            (x, segc), ys = _scan_or_unroll(cfg, body, (x, segc),
                                            (seg, jnp.arange(count)))
            if ys is not None:
                rows.append(jnp.sum(ys, axis=0))
        else:
            def body(x, inp, kind=kind):
                blk, c = inp
                x = _constrain(cfg, mesh, x)
                return other(x, blk, c, kind)
            x, segc = _scan_or_unroll(cfg, body, x, (seg, segc))
        new_segs.append(segc)
    if stats is not None and rows:
        stats["expert_rows"] = sum(rows)
    return x, new_segs


def prefill_paged(cfg: ModelConfig, params: dict, tokens: jax.Array,
                  cache: dict, slot, prompt_len, mesh=None
                  ) -> Tuple[jax.Array, dict]:
    """Prefill one request (tokens: (1, S) right-padded) directly into the
    shared paged cache at batch row `slot`, whose block-table row must already
    map enough pages for `prompt_len` tokens. Returns (logits (1, V), cache).
    """
    _check_paged_support(cfg)
    from repro.models import paged_cache as pc
    x = embed(cfg, params["embed"], tokens)
    B, S, _ = x.shape
    plen = jnp.asarray(prompt_len, jnp.int32).reshape(())
    plens = plen[None]
    positions = jnp.arange(S)[None]
    block_row = cache["block_table"][slot]
    x = _constrain(cfg, mesh, x)

    assert not cfg.is_mla, \
        "latent attention ingests through chunked prefill (prefill_chunk)"

    def attn(x, blk, pools, layer, kind, stats):
        def write(pools, k, v):
            return pc.write_pools(pools, layer, "prompt", block_row, k, v,
                                  plen)
        return _prefill_block(cfg, kind, blk, pools, x, positions, plens,
                              None, mesh, kv_writer=write, layer=layer)

    def other(x, blk, c, kind):
        x, one = _prefill_block(cfg, kind, blk, c, x, positions, plens,
                                None, mesh)
        return x, jax.tree.map(
            lambda bg, on: jax.lax.dynamic_update_slice(
                bg, on.astype(bg.dtype), (slot,) + (0,) * (bg.ndim - 1)),
            c, one)

    x, new_segs = _paged_layers(cfg, params, cache, x, attn, other, mesh)
    x = norm(cfg, params["final_norm"], x)
    idx = jnp.clip(plens - 1, 0, S - 1)
    last_h = jax.vmap(lambda h, i: h[i])(x, idx)
    logits = unembed(cfg, params["embed"], last_h[:, None])[:, 0]
    new_cache = {"lengths": cache["lengths"].at[slot].set(plen),
                 "block_table": cache["block_table"], "segments": new_segs}
    return logits, new_cache


def prefill_ragged_paged(cfg: ModelConfig, params: dict, tokens: jax.Array,
                         cache: dict, slots, offsets, lens,
                         live_pages: Optional[int] = None, mesh=None,
                         stats=None) -> Tuple[jax.Array, dict]:
    """Batched ragged chunk ingest: R slots' next prompt chunks in ONE call.

    tokens: (R, C) — row r is slot `slots[r]`'s next chunk, right-padded to
    `lens[r]` valid tokens starting at logical position `offsets[r]`; slots/
    offsets/lens: (R,) int32. Padding rows (the engine buckets R) carry
    slots[r] == batch (out of range): their cache scatters drop and their
    block-table gathers clip to a live row whose results are discarded.
    Each row's block-table entry must already map pages through
    offsets[r] + lens[r] tokens. Returns (logits (R, V) at each row's last
    valid chunk token, cache); padding rows' logits are unspecified.

    This is the plan/run engine's one chunk-ingest call (flashinfer's
    BatchPrefillWithPagedKVCacheWrapper layout): the scheduler plans (slot,
    offset, len) rows on the host, then every ingesting slot advances
    together; a single slot's chunk (a parked prefix's) is a one-row batch.
    Each row is independent of the others in the batch — batching adds
    rows, never changes a row's reduction order — and a chunked ingest
    matches monolithic prefill (identical greedy tokens, logprobs within
    float32 rounding).

    Chunked ingestion requires an attention-only stack: recurrent segments
    (SSM / xLSTM) would need their scan state carried across chunks, which
    their fwd paths do not expose — the engine falls back to monolithic
    prefill for those families.
    """
    _check_paged_support(cfg)
    _check_attention_only(cfg)
    x = embed(cfg, params["embed"], tokens)
    R, C, _ = x.shape
    slots = jnp.asarray(slots, jnp.int32)
    offsets = jnp.asarray(offsets, jnp.int32)
    lens = jnp.asarray(lens, jnp.int32)
    block_rows = jnp.take(cache["block_table"], slots, axis=0, mode="clip")
    x = _constrain(cfg, mesh, x)

    ragged = attn_lib.mla_prefill_ragged_paged if cfg.is_mla \
        else attn_lib.attention_prefill_ragged_paged

    def attn(x, blk, pools, layer, kind, stats):
        h, pools = ragged(cfg, blk["attn"], norm(cfg, blk["norm1"], x), pools,
                          layer, block_rows, offsets, lens,
                          live_pages=live_pages)
        return _block_tail(cfg, kind, blk, x + h, pools, None, mesh, stats,
                           layer)

    x, new_segs = _paged_layers(cfg, params, cache, x, attn, mesh=mesh,
                                stats=stats)
    x = norm(cfg, params["final_norm"], x)
    idx = jnp.clip(lens - 1, 0, C - 1)
    last_h = jax.vmap(lambda h, i: h[i])(x, idx)               # (R, D)
    # per-row unembed via lax.map, NOT one (R, 1, D) einsum: XLA collapses
    # the latter into an M=R GEMM whose accumulation can differ from an
    # M=1 matvec by an ulp (opt-level dependent); mapping keeps every row
    # the exact (1, 1, D) shape whatever R is, preserving the bitwise
    # row-independence contract. R is small (bucketed batch rows) and only
    # one row per request ever seeds a sample, so the serialization is
    # negligible.
    logits = jax.lax.map(
        lambda h: unembed(cfg, params["embed"], h[None, None])[0, 0], last_h)
    # padding rows target index `batch` and are dropped
    lengths = cache["lengths"].at[slots].set(offsets + lens, mode="drop")
    new_cache = {"lengths": lengths,
                 "block_table": cache["block_table"], "segments": new_segs}
    return logits, new_cache


def decode_step_paged(cfg: ModelConfig, params: dict, tokens: jax.Array,
                      cache: dict, mesh=None,
                      active: Optional[jax.Array] = None,
                      live_pages: Optional[int] = None, stats=None
                      ) -> Tuple[jax.Array, dict]:
    """tokens: (B, 1) -> (logits (B, vocab), updated paged cache).

    Attention layers append the new token into their page pools through the
    block table and read either the Pallas paged flash-decode kernel
    (cfg.use_pallas) or the gather oracle; recurrent layers are identical
    to the dense decode. `active` masks freed rows' length advance AND their
    K/V writes — the plan/run engine pushes block-table clears lazily, so a
    freed row's stale table entry may still map a COW sibling's pages.
    `live_pages` (static) bounds the attention READ to the first live
    block-table columns — see attention_decode_paged.
    """
    _check_paged_support(cfg)
    x = embed(cfg, params["embed"], tokens)
    lengths = cache["lengths"]
    table = cache["block_table"]
    x = _constrain(cfg, mesh, x)

    decode = attn_lib.mla_decode_paged if cfg.is_mla \
        else attn_lib.attention_decode_paged

    def attn(x, blk, pools, layer, kind, stats):
        h, pools = decode(cfg, blk["attn"], norm(cfg, blk["norm1"], x), pools,
                          layer, table, lengths, live_pages=live_pages,
                          active=active)
        return _block_tail(cfg, kind, blk, x + h, pools, None, mesh, stats,
                           layer)

    def other(x, blk, c, kind):
        return _decode_block(cfg, kind, blk, c, x, lengths, mesh)

    x, new_segs = _paged_layers(cfg, params, cache, x, attn, other, mesh,
                                stats)
    x = norm(cfg, params["final_norm"], x)
    logits = unembed(cfg, params["embed"], x)[:, 0]
    if mesh is not None:
        logits = shd.constraint(logits, mesh, (shd.batch_axes(mesh), "model"))
    new_cache = {"lengths": _advance_lengths(lengths, active),
                 "block_table": table, "segments": new_segs}
    return logits, new_cache


def fork_slot_paged(cfg: ModelConfig, cache: dict, src_slot, dst_slot,
                    tail_src_page, tail_dst_page) -> dict:
    """Device-side state duplication behind copy-on-write prefix sharing.

    Full prefix pages are shared through the block table (host side, see
    `PageAllocator.fork`); this op copies only what cannot be shared — the
    partial tail page of every attention layer (pass tail_src_page ==
    tail_dst_page for a no-op when the prefix is page-aligned) and the O(1)
    per-slot recurrent states — then mirrors the source row's cached length.
    A latent pool's tail page is copied the same way.
    Also serves plain COW page copies: call with src_slot == dst_slot and
    the (old, new) page pair from `PageAllocator.cow_page`.
    """
    _check_paged_support(cfg)
    from repro.models import paged_cache as pc
    new_segs = []
    for (kind, count), segc in zip(segments_of(cfg), cache["segments"]):
        if kind in (ATTN, MOE, SHARED_ATTN):
            # copy_page is generic over (count, n_pages, ...) leaves: K and
            # V pools, a latent pool, and the tail page's dequant scales
            new_segs.append({k: pc.copy_page(a, tail_src_page, tail_dst_page)
                             for k, a in segc.items()})
        else:
            new_segs.append(jax.tree.map(
                lambda a: a.at[:, dst_slot].set(a[:, src_slot]), segc))
    lengths = cache["lengths"].at[dst_slot].set(cache["lengths"][src_slot])
    return {"lengths": lengths, "block_table": cache["block_table"],
            "segments": new_segs}


def promote_slot_paged(cfg: ModelConfig, cache: dict, upload_ids,
                       payloads, slot, ctx_len) -> dict:
    """Swap-in (host-tier promote): scatter a demoted request's snapshotted
    pages back into every attention segment's pool and restore its cached
    length, so decode re-enters directly — no replay.

    upload_ids: (U,) int32 physical page targets, padded with n_pages
    (dropped); payloads: one dict per attention segment holding k_pages/
    v_pages (count, U, page, n_kv, hd) at the pool's storage dtype (plus
    k_scale/v_scale (count, U, n_kv) for quantized pools); slot/ctx_len:
    traced scalars. The block table is pushed separately by the engine's
    host mirror. Swap is gated to attention-only stacks (recurrent segments
    would need their dense states snapshotted too), so non-attention
    segments pass through untouched."""
    _check_paged_support(cfg)
    new_segs = []
    pi = 0
    for (kind, count), segc in zip(segments_of(cfg), cache["segments"]):
        if kind in (ATTN, MOE, SHARED_ATTN):
            pay = payloads[pi]
            pi += 1
            new_segs.append({
                key: segc[key].at[:, upload_ids].set(
                    pay[key].astype(segc[key].dtype), mode="drop")
                for key in segc
            })
        else:
            new_segs.append(segc)
    lengths = cache["lengths"].at[slot].set(
        jnp.asarray(ctx_len, jnp.int32))
    return {"lengths": lengths, "block_table": cache["block_table"],
            "segments": new_segs}


def _decode_block(cfg: ModelConfig, kind: str, blk: dict, c: dict, x, lengths,
                  mesh=None):
    if kind in (ATTN, MOE, SHARED_ATTN):
        assert not cfg.is_mla, \
            "latent attention decodes through the paged backend only"
        xin = norm(cfg, blk["norm1"], x)
        h, nk, nv = attn_lib.attention_decode(cfg, blk["attn"], xin, c["k"],
                                              c["v"], lengths,
                                              window=cfg.sliding_window)
        x = x + h
        newc = dict(c)
        newc["k"], newc["v"] = nk, nv
        if "cross_k" in c and "xattn" in blk:
            xin2 = norm(cfg, blk["norm_x"], x)
            x = x + attn_lib.cross_attention_cached(cfg, blk["xattn"], xin2,
                                                    c["cross_k"], c["cross_v"])
        if kind == MOE and cfg.moe_dropless:
            h = moe_lib.moe_serve(cfg, blk["moe"], norm(cfg, blk["norm2"], x))
        elif kind == MOE:
            h, _ = moe_lib.moe_fwd(cfg, blk["moe"], norm(cfg, blk["norm2"], x),
                                   mesh=mesh)
        else:
            h = mlp(cfg, blk["mlp"], norm(cfg, blk["norm2"], x))
        return x + h, newc
    if kind == MAMBA2:
        out, conv_s, ssd_s = ssm_lib.mamba2_decode(
            cfg, blk["mamba"], norm(cfg, blk["norm1"], x),
            c["conv"], c["ssd"])
        return x + out, {"conv": conv_s.astype(c["conv"].dtype), "ssd": ssd_s}
    if kind == MLSTM:
        out, st = xlstm_lib.mlstm_decode(cfg, blk["mlstm"],
                                         norm(cfg, blk["norm1"], x), c)
        return x + out, st
    if kind == SLSTM:
        out, st = xlstm_lib.slstm_decode(cfg, blk["slstm"],
                                         norm(cfg, blk["norm1"], x), c)
        return x + out, st
    raise ValueError(kind)
