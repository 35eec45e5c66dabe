"""Pure-jnp oracle for paged chunked-prefill attention: gather-then-attend.

This is exactly the serving engine's fallback read path for one prompt
chunk — materialize the slot's block row into the contiguous layout, write
the chunk first, then run the causal grouped SDPA — kept as the numerics
contract for the Pallas kernel. The oracle deliberately uses the same
grouped-einsum formulation as the paged decode step: it is reduction-order
stable across query counts, which is what lets a C-token chunk reproduce C
single-token decode steps bitwise (the engine's fork-suffix / resume
replays rely on that).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import paged_cache as pc

NEG_INF = -1e30


def _attend_rows(q, gk, gv, offsets, lens):
    """Causal chunk SDPA of R rows over gathered contiguous K/V (R, S, Hkv,
    hd): row r's queries sit at offsets[r].. and see keys below
    offsets[r] + lens[r]."""
    R, C, Hq, hd = q.shape
    rep = Hq // gk.shape[2]
    S = gk.shape[1]
    k = jnp.repeat(gk, rep, axis=2) if rep > 1 else gk
    v = jnp.repeat(gv, rep, axis=2) if rep > 1 else gv
    qpos = offsets[:, None] + jnp.arange(C)[None, :]              # (R, C)
    kpos = jnp.arange(S)
    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32))
    logits = jnp.einsum("bqnh,bknh->bnqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale            # (R,Hq,C,S)
    total = (offsets + lens)[:, None, None]                       # (R, 1, 1)
    mask = ((kpos[None, None, :] <= qpos[:, :, None])
            & (kpos[None, None, :] < total))[:, None]             # (R,1,C,S)
    logits = jnp.where(mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bnqk,bknh->bqnh", probs.astype(v.dtype), v)


def paged_prefill_attention_ragged_ref(q, k_pages, v_pages, block_rows,
                                       offsets, lens, layer=0):
    """Ragged multi-slot oracle: R independent chunk reads in one batch.

    q: (R, C, Hq, hd) — row r is slot r's chunk queries (RoPE applied, chunk
    K/V already written); k/v_pages: (L, n_pages, Hkv, page, hd), read at
    layer `layer`; block_rows: (R, P) per-row block-table rows;
    offsets/lens: (R,). Returns (R, C, Hq, hd); row r positions past
    lens[r] are unspecified (callers discard them), as is every position of
    padding rows (lens[r] == 0).
    """
    return _attend_rows(q, pc.gather_sequence(k_pages, block_rows, layer),
                        pc.gather_sequence(v_pages, block_rows, layer),
                        offsets, lens)


def paged_prefill_attention_ragged_quant_ref(q, k_pages, v_pages, k_scales,
                                             v_scales, block_rows, offsets,
                                             lens, layer=0):
    """Quantized-pool oracle for the ragged multi-slot chunk kernel:
    dequantize-gather into the contiguous f32 layout, then attend exactly
    as the float oracle (docs/serving.md tolerance contract)."""
    gk = pc.gather_sequence_dequant(k_pages, k_scales, block_rows, layer)
    gv = pc.gather_sequence_dequant(v_pages, v_scales, block_rows, layer)
    return _attend_rows(q, gk, gv, offsets, lens).astype(q.dtype)


def mla_paged_prefill_ref(q, pages, block_rows, offsets, lens, layer=0, *,
                          rank: int, scale: float):
    """Oracle of the latent-mode ragged chunk kernel: gather each row's
    latent rows, attend causally within the chunk and to what the slot
    already holds."""
    from repro.kernels.paged_decode_attention.ref import latent_attend
    g = pc.gather_sequence(pages, block_rows, layer)
    C = q.shape[1]
    qpos = offsets[:, None] + jnp.arange(C)[None, :]
    return latent_attend(q, g, qpos, offsets + lens, rank,
                         scale).astype(q.dtype)
