"""Plain reference of DeepSeek-V2 (multi-head latent attention and routed
plus shared experts), and the benchmark's weights for it.

Imports nothing of the program. It follows the published architecture
(arXiv:2405.04434; hf:deepseek-ai/DeepSeek-V2-Lite config.json):

- token embedding, untied output head; RMSNorm (eps `rms_norm_eps`)
  before attention and before the MLP, and once at the end;
- attention, no q-LoRA: q = x W_q -> H x (nope + rope); [c_kv, k_pe] =
  x W_kva; c_kv = RMSNorm(c_kv); [k_nope, v] = c_kv W_kvb -> H x (nope +
  v); rope on q_pe and on the one k_pe all heads share; scores
  (q_nope . k_nope + q_pe . k_pe) x (nope + rope)^-0.5 x m^2, m =
  0.1 mscale_all_dim ln(factor) + 1; causal softmax; o = p . v -> W_o;
- YaRN rope over the rope dims, base `rope_theta`: base frequencies above
  the correction range, base / factor below it, a linear ramp between the
  dims that turn `beta_fast` and `beta_slow` times over
  `original_max_position_embeddings`; cos and sin scaled by
  mscale(mscale) / mscale(mscale_all_dim);
- the first `first_k_dense_replace` layers: SwiGLU MLP of
  `intermediate_size`; the rest: router softmax over all experts in f32,
  greedy top-k, renormalised only with `norm_topk_prob`, times
  `routed_scaling_factor`; the routed output sum_e s_e SwiGLU_e(x) of
  width `moe_intermediate_size`; plus one shared SwiGLU of
  n_shared_experts x that width.

Everything is float32 at "highest" matmul precision, over the whole
sequence, with no cache, kernel or batching. Each expert is applied to
every token and weighted by its routing weight (zero where not chosen).

Departures:
- rope is rotate-half over the rope dims as they come out of W_q and
  W_kva. The published code de-interleaves them first, a fixed
  permutation of those weights' rope columns; with seeded weights the two
  are the same model.
- the expert-parallel share (`ep_size`, `ep_rank` in the hf keys):
  `n_routed_experts` counts the experts held here, the router keeps all
  n_routed_experts x ep_size outputs, and an assignment to an expert held
  elsewhere adds nothing, as in the program (the partial sum one chip of
  the deployment passes on).
- weights are seeded random numbers, not the trained ones (norm scales
  too, so those paths carry signal).

Weights are held in the serving system's parameter layout (stacked layers
under `segments`: the dense layers, then the expert layers), in the dtype
they are served in; `make_params` builds them from a seed in one jitted
call.

`reference_logits(..., control=True)` is the check's control: the same
forward with every weight and every activation entering a projection, the
router, an expert, the MLP or the head rounded to float8 e4m3
(per-output-channel and per-token scales).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

F8_MAX = 448.0   # largest finite float8_e4m3fn


def program_kwargs(hf: dict) -> dict:
    """The serving system's model-config fields this published config
    fixes. (`family` goes in the configuration file's `program` fields.)"""
    rs = hf["rope_scaling"]
    ep = hf.get("ep_size", 1)
    return dict(
        n_layers=hf["num_hidden_layers"], d_model=hf["hidden_size"],
        n_heads=hf["num_attention_heads"],
        n_kv_heads=hf["num_key_value_heads"],
        head_dim=hf["v_head_dim"],
        d_ff=hf["intermediate_size"], vocab_size=hf["vocab_size"],
        rope_theta=float(hf["rope_theta"]), norm_eps=hf["rms_norm_eps"],
        tie_embeddings=hf["tie_word_embeddings"],
        kv_lora_rank=hf["kv_lora_rank"],
        qk_nope_head_dim=hf["qk_nope_head_dim"],
        qk_rope_head_dim=hf["qk_rope_head_dim"],
        v_head_dim=hf["v_head_dim"],
        yarn_factor=float(rs["factor"]),
        yarn_original_max_pos=rs["original_max_position_embeddings"],
        yarn_beta_fast=float(rs["beta_fast"]),
        yarn_beta_slow=float(rs["beta_slow"]),
        yarn_mscale=float(rs["mscale"]),
        yarn_mscale_all_dim=float(rs["mscale_all_dim"]),
        n_experts=hf["n_routed_experts"] * ep, ep_size=ep,
        ep_rank=hf.get("ep_rank", 0),
        experts_per_token=hf["num_experts_per_tok"],
        moe_d_ff=hf["moe_intermediate_size"],
        n_shared_experts=hf["n_shared_experts"],
        first_k_dense=hf["first_k_dense_replace"],
        norm_topk_prob=hf["norm_topk_prob"],
        routed_scaling_factor=float(hf["routed_scaling_factor"]),
        moe_dropless=True)


# leaf name -> how the benchmark draws it: ("normal", std), ("fan_in",
# axes counted from the end that the leaf contracts over), or ("one_plus",
# std) for norm scales
WEIGHT_RULES = {
    "tok": ("normal", 0.02),
    "unembed": ("fan_in", (-2,)),
    "wq": ("fan_in", (-3,)), "wkv_a": ("fan_in", (-2,)),
    "wkv_b": ("fan_in", (-3,)), "wo": ("fan_in", (-3, -2)),
    "router": ("fan_in", (-2,)),
    "w_gate": ("fan_in", (-2,)), "w_up": ("fan_in", (-2,)),
    "w_down": ("fan_in", (-2,)),
    "scale": ("one_plus", 0.1), "kv_norm": ("one_plus", 0.1),
}


def _leaf(path, spec, key):
    name = path[-1]
    if name not in WEIGHT_RULES:
        raise KeyError(f"no weight rule for parameter {'/'.join(path)}")
    kind, arg = WEIGHT_RULES[name]
    z = jax.random.normal(key, spec.shape, jnp.float32)
    if kind == "normal":
        w = z * arg
    elif kind == "one_plus":
        w = 1.0 + z * arg
    else:
        fan_in = 1
        for ax in arg:
            fan_in *= spec.shape[ax]
        w = z / jnp.sqrt(jnp.float32(fan_in))
    return w.astype(spec.dtype)


def _path_names(path):
    return tuple(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)


_builders = {}


def make_params(shapes, seed: int):
    """Weights of the tree `shapes` (ShapeDtypeStructs in the serving
    layout) drawn from `seed`, on the default device, in one jitted call."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    sig = (treedef, tuple((p, s.shape, str(s.dtype)) for p, s in leaves))
    if sig not in _builders:
        def build(key):
            keys = jax.random.split(key, len(leaves))
            return jax.tree_util.tree_unflatten(
                treedef, [_leaf(_path_names(p), s, k)
                          for (p, s), k in zip(leaves, keys)])
        _builders[sig] = jax.jit(build)
    key = jax.random.fold_in(jax.random.PRNGKey(seed % 2 ** 31),
                             seed // 2 ** 31)
    return _builders[sig](key)


def _f8(x, axis):
    """Round to float8 e4m3 with one scale per slice along `axis`."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    s = jnp.where(amax > 0, amax / F8_MAX, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _rms(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def yarn_inv_freq(hf: dict) -> np.ndarray:
    """YaRN inverse frequencies of the rope dims (numbers, float64)."""
    rs = hf["rope_scaling"]
    dim, base = hf["qk_rope_head_dim"], float(hf["rope_theta"])
    factor = float(rs["factor"])
    orig = rs["original_max_position_embeddings"]
    extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    inter = extra / factor

    def corr(turns):            # dim index that turns `turns` times
        return dim * math.log(orig / (turns * 2 * math.pi)) \
            / (2 * math.log(base))
    low = max(math.floor(corr(rs["beta_fast"])), 0)
    high = min(math.ceil(corr(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    return inter * ramp + extra * (1.0 - ramp)


def _mscale(factor, m):
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def softmax_scale(hf: dict) -> float:
    rs = hf["rope_scaling"]
    s = (hf["qk_nope_head_dim"] + hf["qk_rope_head_dim"]) ** -0.5
    m = _mscale(float(rs["factor"]), float(rs["mscale_all_dim"]))
    return s * m * m


def _rope(x, hf):
    """x: (S, H, d), rotate-half at the YaRN frequencies."""
    rs = hf["rope_scaling"]
    S = x.shape[0]
    half = x.shape[-1] // 2
    inv = jnp.asarray(yarn_inv_freq(hf), jnp.float32)
    ms = _mscale(float(rs["factor"]), float(rs["mscale"])) \
        / _mscale(float(rs["factor"]), float(rs["mscale_all_dim"]))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :] * ms, jnp.sin(ang)[:, None, :] * ms
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def reference_logits(hf: dict, params, tokens, control: bool = False):
    """tokens: (S,) int32 -> float32 logits (S, vocab) of the next token at
    every position."""
    f32 = jnp.float32
    eps = hf["rms_norm_eps"]
    H = hf["num_attention_heads"]
    nope, rope = hf["qk_nope_head_dim"], hf["qk_rope_head_dim"]
    r, vd = hf["kv_lora_rank"], hf["v_head_dim"]
    K = hf["num_experts_per_tok"]
    held = hf["n_routed_experts"]
    first = hf.get("ep_rank", 0) * held        # global id of expert 0 here
    scale = softmax_scale(hf)

    def w(a):                       # a weight, contracted over its axis -2
        a = a.astype(f32)
        return _f8(a, axis=tuple(range(a.ndim - 1))) if control else a

    def act(x):                     # an activation entering a matmul
        return _f8(x, axis=-1) if control else x

    def swiglu(h, m):
        g = jax.nn.silu(h @ w(m["w_gate"])) * (h @ w(m["w_up"]))
        return act(g) @ w(m["w_down"])

    with jax.default_matmul_precision("highest"):
        x = params["embed"]["tok"][tokens].astype(f32)
        S = x.shape[0]
        causal = jnp.tril(jnp.ones((S, S), bool))

        def attention(x, a):
            h = act(_rms(x, a["norm1"]["scale"].astype(f32), eps))
            a = a["attn"]
            D = h.shape[-1]
            q = (h @ w(a["wq"].reshape(D, -1))).reshape(S, H, nope + rope)
            kva = h @ w(a["wkv_a"])
            c = act(_rms(kva[:, :r], a["kv_norm"].astype(f32), eps))
            kv = (c @ w(a["wkv_b"].reshape(r, -1))).reshape(S, H, nope + vd)
            q_pe = _rope(q[..., nope:], hf)
            k_pe = _rope(kva[:, None, r:], hf)[:, 0]
            s = (jnp.einsum("qnh,knh->nqk", q[..., :nope], kv[..., :nope])
                 + jnp.einsum("qnh,kh->nqk", q_pe, k_pe)) * scale
            s = jnp.where(causal[None], s, -jnp.inf)
            o = jnp.einsum("nqk,knh->qnh", jax.nn.softmax(s, axis=-1),
                           kv[..., nope:])
            wo = a["wo"]
            return x + act(o.reshape(S, -1)) @ w(wo.reshape(-1, wo.shape[-1]))

        def dense_layer(x, blk):
            x = attention(x, blk)
            h = act(_rms(x, blk["norm2"]["scale"].astype(f32), eps))
            return x + swiglu(h, blk["mlp"]), None

        def expert_layer(x, blk):
            x = attention(x, blk)
            m = blk["moe"]
            h = act(_rms(x, blk["norm2"]["scale"].astype(f32), eps))
            probs = jax.nn.softmax(h @ w(m["router"]), axis=-1)
            top_w, top_e = jax.lax.top_k(probs, K)
            if hf["norm_topk_prob"]:
                top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
            top_w = top_w * float(hf["routed_scaling_factor"])

            def one(y, e):          # expert e of those held here
                gate = jnp.sum(jnp.where(top_e == first + e, top_w, 0.0),
                               axis=-1)
                ex = {k: m[k][e] for k in ("w_gate", "w_up", "w_down")}
                return y + gate[:, None] * swiglu(h, ex), None
            y, _ = jax.lax.scan(one, jnp.zeros_like(x), jnp.arange(held))
            return x + y + swiglu(h, m["shared"]), None

        dense, experts = params["segments"]
        x, _ = jax.lax.scan(dense_layer, x, dense)
        x, _ = jax.lax.scan(expert_layer, x, experts)
        x = act(_rms(x, params["final_norm"]["scale"].astype(f32), eps))
        return x @ w(params["embed"]["unembed"])
