"""Operations and bytes of latent attention (MLA) and of a chip's share of
routed experts, from shapes alone (see bench/flops.py for the dense
forms).

`fleet.engine_dims` carries dense sizes only, so these take the MLA and
expert sizes from the configuration file of the engine they read:
`sizes(engine)` finds the member of that name under bench/configs/ (and
the CPU twins' bench/tests/data/mla/configs/) and reads its hf keys.

- Attention, absorbed as the served path runs it: a (query, key) pair
  costs 2 * heads * (rank + rope) operations for the scores over the
  latent row and 2 * heads * rank for the values, per layer. The least
  bytes are the latent rows of every page attended, rank + rope lanes a
  token (the pool pads its rows to a multiple of 128 lanes: the padding
  shows as lost share), plus the queries in and latent outputs out.
- Experts: a routed assignment costs 2 * 3 * d_model * width operations;
  a token has experts_per_token x held / router-width assignments here on
  average (the router's expected share: which tokens the router sends
  here is not recorded).
- Model operations per token, the chip's share: every projection, the
  dense MLP, the router, the shared expert, the expected routed
  assignments and the output head, plus attention per pair of each layer.
"""
from __future__ import annotations

import functools
import json
from pathlib import Path
from typing import Iterable, Tuple

BENCH = Path(__file__).resolve().parent
CONFIG_DIRS = (BENCH / "configs", BENCH / "tests" / "data" / "mla" / "configs")


@functools.lru_cache(maxsize=None)
def sizes(engine: str):
    """The MLA and expert sizes of the member named `engine`, or None when
    no configuration file holds a latent-attention member of that name."""
    for d in CONFIG_DIRS:
        for f in sorted(d.glob("*.json")):
            for m in json.loads(f.read_text()).get("members", []):
                hf = m["hf"]
                if m["name"] == engine and "kv_lora_rank" in hf:
                    return _sizes(hf)
    return None


def _sizes(hf: dict) -> dict:
    held = hf["n_routed_experts"]
    return dict(
        n_layers=hf["num_hidden_layers"], d_model=hf["hidden_size"],
        heads=hf["num_attention_heads"], rank=hf["kv_lora_rank"],
        rope=hf["qk_rope_head_dim"], nope=hf["qk_nope_head_dim"],
        v=hf["v_head_dim"], vocab=hf["vocab_size"],
        dense_ff=hf["intermediate_size"],
        dense_layers=hf["first_k_dense_replace"],
        moe_layers=hf["num_hidden_layers"] - hf["first_k_dense_replace"],
        width=hf["moe_intermediate_size"], shared=hf["n_shared_experts"],
        held=held, router=held * hf.get("ep_size", 1),
        top_k=hf["num_experts_per_tok"])


def _pages(n_tokens: int, page: int) -> int:
    return -(-n_tokens // page)


def pair_flops(s: dict) -> float:
    """Operations of one (query, key) pair in one layer."""
    return 2.0 * s["heads"] * (2 * s["rank"] + s["rope"])


def decode_call(s: dict, page: int, itemsize: int,
                ctx_lens: Iterable[int]) -> Tuple[float, float]:
    """(operations, bytes) of one latent decode kernel call (one layer)
    over rows whose caches hold `ctx_lens` tokens, the new one included."""
    ctx = list(ctx_lens)
    lanes = s["rank"] + s["rope"]
    flops = pair_flops(s) * sum(ctx)
    kv = sum(_pages(c, page) for c in ctx) * page * lanes
    qo = len(ctx) * s["heads"] * (lanes + s["rank"])
    return flops, float((kv + qo) * itemsize)


def prefill_call(s: dict, page: int, itemsize: int,
                 rows: Iterable[Tuple[int, int]]) -> Tuple[float, float]:
    """(operations, bytes) of one latent chunk kernel call (one layer) over
    rows of (offset, length): each row attends its chunk causally and every
    cached token before it."""
    rows = list(rows)
    lanes = s["rank"] + s["rope"]
    pairs = sum(n * o + n * (n + 1) // 2 for o, n in rows)
    kv = sum(_pages(o + n, page) for o, n in rows) * page * lanes
    qo = sum(n for _, n in rows) * s["heads"] * (lanes + s["rank"])
    return pair_flops(s) * pairs, float((kv + qo) * itemsize)


def routed_per_token(s: dict) -> float:
    """Expected assignments a token sends to the experts held here."""
    return s["top_k"] * s["held"] / s["router"]


def token_flops(s: dict, head: bool = True) -> float:
    """Model operations of one token through every layer (and the output
    head with `head`), attention pairs aside: the chip's share."""
    D, H, r = s["d_model"], s["heads"], s["rank"]
    attn = (D * H * (s["nope"] + s["rope"]) + D * (r + s["rope"])
            + H * s["nope"] * r + H * r * s["v"] + H * s["v"] * D)
    moe = (D * s["router"] + 3 * D * s["width"] * s["shared"]
           + 3 * D * s["width"] * routed_per_token(s))
    return 2.0 * (s["n_layers"] * attn + s["dense_layers"] * 3 * D
                  * s["dense_ff"] + s["moe_layers"] * moe
                  + (D * s["vocab"] if head else 0))


def model_flops_decode(s: dict, ctx_lens: Iterable[int]) -> float:
    """Model operations of one decode step over rows at `ctx_lens`."""
    ctx = list(ctx_lens)
    return sum(token_flops(s) + s["n_layers"] * pair_flops(s) * c
               for c in ctx)


def model_flops_prefill(s: dict, rows: Iterable[Tuple[int, int]]) -> float:
    """Model operations of ingesting chunks (offset, length): every token
    through every layer, attention over its context, and the output head
    once per row (the ingest returns the row's last logits)."""
    return sum(n * token_flops(s, head=False) + 2.0 * s["d_model"]
               * s["vocab"] + s["n_layers"] * pair_flops(s)
               * (n * o + n * (n + 1) // 2) for o, n in rows if n)
