"""Mixture-of-Experts FFN: the training layer with capacity-based dispatch
(drop-on-overflow, `moe_fwd`), and the serving layer (`moe_serve`).

Scatter/gather dispatch (no (T,E,C) one-hot einsum) so it scales to
128-expert x 1M-token training batches:

  1. router top-k -> (expert_id, weight) per assignment, T*k assignments
  2. position-in-expert via cumsum over a (T*k, E) one-hot
  3. scatter tokens into an (E, C, D) buffer (overflow drops)
  4. per-expert SwiGLU: (E,C,D) x (E,D,F)
  5. gather + weighted combine back to (T, D)

The router load-balance auxiliary loss follows Switch/Mixtral:
  aux = E * sum_e( frac_tokens_e * mean_router_prob_e ).
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.models.layers import dense_init, init_mlp, mlp


EXPERT_WEIGHTS = ("w_gate", "w_up", "w_down")


def init_moe(cfg: ModelConfig, key) -> dict:
    """The router over all n_experts; the weights of the experts this chip
    holds (all of them unless cfg.ep_size > 1); the shared expert, one
    SwiGLU of n_shared_experts x the expert width, when there is one."""
    pd = jnp.dtype(cfg.param_dtype)
    d, f, e = cfg.d_model, cfg.expert_d_ff, cfg.n_experts
    held = cfg.held_experts
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    p = {
        "router": dense_init(k1, (d, e), dtype=pd),
        "w_gate": dense_init(k2, (held, d, f), in_axis=1, dtype=pd),
        "w_up": dense_init(k3, (held, d, f), in_axis=1, dtype=pd),
        "w_down": dense_init(k4, (held, f, d), in_axis=1, dtype=pd),
    }
    if cfg.n_shared_experts:
        p["shared"] = init_mlp(cfg, k5, d, f * cfg.n_shared_experts)
    return p


def moe_capacity(n_tokens: int, cfg: ModelConfig) -> int:
    per = n_tokens * cfg.experts_per_token / cfg.n_experts
    cap = int(math.ceil(per * cfg.capacity_factor))
    return max(cap, cfg.experts_per_token, 4)


def _ep_constraint(mesh, arr, expert_axis_ok: bool):
    """Shard dim 0 (experts) over `model` when divisible (expert parallel)."""
    if mesh is None or not expert_axis_ok:
        return arr
    from repro.distributed import sharding as shd
    return shd.constraint(arr, mesh, ["model"] + [None] * (arr.ndim - 1))


def moe_fwd(cfg: ModelConfig, params: dict, x: jax.Array, mesh=None
            ) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, D) -> (out (B,S,D), aux_loss scalar)."""
    B, S, D = x.shape
    T = B * S
    E, K = cfg.n_experts, cfg.experts_per_token
    C = moe_capacity(T, cfg)
    dt = x.dtype
    xf = x.reshape(T, D)

    logits = (xf @ params["router"].astype(dt)).astype(jnp.float32)   # (T,E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = jax.lax.top_k(probs, K)                             # (T,K)
    top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)             # renorm over top-k

    # load-balance aux loss
    frac = jnp.mean(jax.nn.one_hot(top_e[:, 0], E, dtype=jnp.float32), axis=0)
    for j in range(1, K):
        frac = frac + jnp.mean(jax.nn.one_hot(top_e[:, j], E, dtype=jnp.float32), axis=0)
    frac = frac / K
    aux = E * jnp.sum(frac * jnp.mean(probs, axis=0))

    # --- dispatch ---------------------------------------------------------
    flat_e = top_e.reshape(T * K)                                      # (A,)
    flat_w = top_w.reshape(T * K).astype(dt)
    if cfg.moe_sort_dispatch:
        # position-in-expert via a stable argsort over expert ids: O(A log A)
        # instead of the (A, E) one-hot cumsum, which XLA lowers to a
        # quadratic reduce-window (dominates HLO FLOPs at 128 experts).
        A = T * K
        order = jnp.argsort(flat_e, stable=True)                       # (A,)
        sorted_e = flat_e[order]
        run_start = jnp.searchsorted(sorted_e, jnp.arange(E),
                                     side="left")                      # (E,)
        pos_sorted = jnp.arange(A) - run_start[sorted_e]
        flat_pos = jnp.zeros((A,), jnp.int32).at[order].set(
            pos_sorted.astype(jnp.int32))
    else:
        onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)            # (A,E)
        pos = jnp.cumsum(onehot, axis=0) - onehot                      # rank in expert
        flat_pos = jnp.sum(pos * onehot, axis=-1)                      # (A,)
    keep = flat_pos < C
    # scatter tokens into (E, C, D); dropped assignments go to a trash row
    safe_e = jnp.where(keep, flat_e, E)
    safe_p = jnp.where(keep, flat_pos, 0)
    buf = jnp.zeros((E + 1, C, D), dt)
    token_idx = jnp.repeat(jnp.arange(T), K)
    buf = buf.at[safe_e, safe_p].set(xf[token_idx], mode="drop")
    buf = buf[:E]                                                      # (E,C,D)

    # --- expert compute ----------------------------------------------------
    ep = cfg.moe_ep and mesh is not None and E % mesh.shape.get("model", 1) == 0
    buf = _ep_constraint(mesh, buf, ep)
    g = jnp.einsum("ecd,edf->ecf", buf, params["w_gate"].astype(dt))
    u = jnp.einsum("ecd,edf->ecf", buf, params["w_up"].astype(dt))
    h = jax.nn.silu(_ep_constraint(mesh, g, ep)) * _ep_constraint(mesh, u, ep)
    y = jnp.einsum("ecf,efd->ecd", h, params["w_down"].astype(dt))     # (E,C,D)
    y = _ep_constraint(mesh, y, ep)

    # --- combine ------------------------------------------------------------
    gathered = y[safe_e, safe_p]                                       # (A,D)
    gathered = jnp.where(keep[:, None], gathered, 0.0)
    out = jnp.zeros((T, D), dt).at[token_idx].add(gathered * flat_w[:, None])
    return out.reshape(B, S, D), aux


def route(cfg: ModelConfig, router: jax.Array, xf: jax.Array):
    """Router over all n_experts, in f32: softmax, greedy top-k, optionally
    renormalised, times routed_scaling_factor. -> (weights (T, K) f32,
    experts (T, K) int32)."""
    logits = jnp.dot(xf, router.astype(xf.dtype),
                     preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = jax.lax.top_k(probs, cfg.experts_per_token)
    if cfg.norm_topk_prob:
        top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    return top_w * cfg.routed_scaling_factor, top_e


def moe_serve(cfg: ModelConfig, params: dict, x: jax.Array, stats=None,
              layer=None) -> jax.Array:
    """The serving expert layer. x: (..., D) -> (..., D).

    Dropless and grouped: the router picks top-k of all n_experts per
    token; the assignments to the experts this chip holds (its
    expert-parallel share, cfg.ep_rank of cfg.ep_size) are sorted by
    expert and run as one grouped matmul per projection
    (kernels/moe_grouped_matmul under cfg.use_pallas, else
    `jax.lax.ragged_dot`), weighted and summed back into their tokens.
    Every held assignment is computed, however unevenly the router spreads
    them: the sorted buffer has room for every token's k assignments. An
    assignment to an expert held elsewhere adds nothing here: on one chip
    the layer runs without its exchange, and this partial sum is what the
    layer passes on. The shared expert is added once.

    params' expert weights are one layer's (held, k, n), or a layer loop's
    whole stack (L, held, k, n) in x's dtype, which the grouped matmul
    reads at `layer` (a traced int32) in place.

    `stats`, a dict, gets "expert_rows": (held,) int32, the assignments
    each held expert computed."""
    shape = x.shape
    D = shape[-1]
    xf = x.reshape(-1, D)
    T = xf.shape[0]
    K, held = cfg.experts_per_token, cfg.held_experts
    top_w, top_e = route(cfg, params["router"], xf)
    local = top_e.reshape(-1) - cfg.ep_rank * held              # (T*K,)
    mine = (local >= 0) & (local < held)
    key = jnp.where(mine, local, held)                          # held: none
    sizes = jnp.zeros((held + 1,), jnp.int32).at[key].add(1)[:held]
    # each token has at most min(K, held) assignments here: room for all
    M = T * min(K, held)
    order = jnp.argsort(key, stable=True)[:M]
    tok = order // K
    w = jnp.where(mine[order], top_w.reshape(-1)[order], 0.0)
    rows = xf[tok]                                              # (M, D)
    dt = x.dtype
    stack = [params[k] for k in EXPERT_WEIGHTS]
    if stack[0].ndim == 3:              # one layer's: a stack of one
        stack, layer = [a[None].astype(dt) for a in stack], 0
    assert layer is not None and all(a.dtype == dt for a in stack)
    if cfg.use_pallas:
        from repro.kernels.moe_grouped_matmul import ops as gmm_ops
        gmm = gmm_ops.moe_grouped_matmul
    else:
        def gmm(lhs, rhs, gs, layer):
            return jax.lax.ragged_dot(lhs, rhs[layer], gs)
    w_gate, w_up, w_down = stack
    g = gmm(rows, w_gate, sizes, layer)
    u = gmm(rows, w_up, sizes, layer)
    y = gmm(jax.nn.silu(g) * u, w_down, sizes, layer)
    valid = jnp.arange(M) < jnp.sum(sizes)
    y = jnp.where(valid[:, None], y.astype(jnp.float32) * w[:, None], 0.0)
    out = jnp.zeros((T, D), jnp.float32).at[tok].add(y).astype(dt)
    if "shared" in params:
        out = out + mlp(cfg, params["shared"], xf)
    if stats is not None:
        stats["expert_rows"] = sizes
    return out.reshape(shape)
