"""Operations and bytes the served work needs, from shapes alone.

`dims` is one engine's sizes (bench/fleet.py `engine_dims`). Attention
counts 2 operations per multiply-add: QK^T and PV give 4 * heads *
head_dim operations per (query, key) pair. Bytes are the least a kernel
must move: every K and V page it attends (the paged kernels stream whole
pages), the queries it reads and the outputs it writes.
"""
from __future__ import annotations

from typing import Iterable, Tuple


def _pages(n_tokens: int, page: int) -> int:
    return -(-n_tokens // page)


def matmul_params(d: dict) -> int:
    """Weights one token multiplies through: every layer's projections and
    MLP, and the output head (the embedding lookup is a gather)."""
    D, H, Hkv, hd, F = (d["d_model"], d["n_heads"], d["n_kv_heads"],
                        d["head_dim"], d["d_ff"])
    layer = D * H * hd + 2 * D * Hkv * hd + H * hd * D + 3 * D * F
    return d["n_layers"] * layer + D * d["vocab"]


def attention_pairs_prefill(offset: int, length: int) -> int:
    """(query, key) pairs of a causal chunk of `length` tokens written after
    `offset` cached ones."""
    return length * offset + length * (length + 1) // 2


def decode_call(d: dict, ctx_lens: Iterable[int]) -> Tuple[float, float]:
    """(operations, bytes) of one paged-decode kernel call (one layer) over
    rows whose caches hold `ctx_lens` tokens, the new one included."""
    H, Hkv, hd = d["n_heads"], d["n_kv_heads"], d["head_dim"]
    ctx = list(ctx_lens)
    flops = 4.0 * H * hd * sum(ctx)
    kv = sum(_pages(c, d["page"]) for c in ctx) * d["page"] * Hkv * hd * 2
    qo = len(ctx) * H * hd * 2
    return flops, float(kv * d["kv_itemsize"] + qo * d["act_itemsize"])


def prefill_call(d: dict, rows: Iterable[Tuple[int, int]]
                 ) -> Tuple[float, float]:
    """(operations, bytes) of one paged-prefill kernel call (one layer) over
    rows of (offset, length): each row attends its chunk causally and every
    cached token before it."""
    H, Hkv, hd = d["n_heads"], d["n_kv_heads"], d["head_dim"]
    rows = list(rows)
    flops = 4.0 * H * hd * sum(attention_pairs_prefill(o, n) for o, n in rows)
    kv = sum(_pages(o + n, d["page"]) for o, n in rows) * d["page"] \
        * Hkv * hd * 2
    qo = sum(n for _, n in rows) * H * hd * 2
    return flops, float(kv * d["kv_itemsize"] + qo * d["act_itemsize"])


def least_seconds(flops: float, nbytes: float, peaks: dict
                  ) -> Tuple[float, str]:
    """The roofline: the larger of compute time at peak and transfer time
    at peak bandwidth, and which of the two bounds it."""
    t_c = flops / peaks["flops_bf16"]
    t_m = nbytes / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")


def model_flops_decode(d: dict, ctx_lens: Iterable[int]) -> float:
    """Model operations of one decode step over rows at `ctx_lens`."""
    ctx = list(ctx_lens)
    per_layer_attn = 4.0 * d["n_heads"] * d["head_dim"]
    return sum(2.0 * matmul_params(d) + d["n_layers"] * per_layer_attn * c
               for c in ctx)


def model_flops_prefill(d: dict, rows: Iterable[Tuple[int, int]]) -> float:
    """Model operations of ingesting chunks (offset, length): every token
    through every layer, attention over its context, and the output head
    once per row (the ingest returns the row's last logits)."""
    head = 2.0 * d["d_model"] * d["vocab"]
    body = 2.0 * (matmul_params(d) - d["d_model"] * d["vocab"])
    attn = 4.0 * d["n_heads"] * d["head_dim"] * d["n_layers"]
    return sum(n * body + head + attn * attention_pairs_prefill(o, n)
               for o, n in rows if n)
