"""Plain reference of the Qwen2 / Qwen3 dense decoders, and the benchmark's
weights for them.

Imports nothing of the program. It follows the published architecture
(Qwen2: arXiv:2407.10671 and hf:Qwen/Qwen2-1.5B; Qwen3: hf:Qwen/Qwen3-8B):
token embedding; per layer RMSNorm -> GQA self-attention (Qwen2: bias on
q/k/v; Qwen3: per-head RMSNorm on q and k before RoPE) with rotary
positions (rotate-half, base `rope_theta`) -> residual -> RMSNorm -> SwiGLU
MLP -> residual; final RMSNorm; output head (tied to the embedding when
`tie_word_embeddings`). Everything is float32 at "highest" matmul
precision, over the whole sequence, with no cache, kernel or batching.

Weights are held in the serving system's parameter layout (stacked layers
under `segments[0]`), in the dtype they are served in; `make_params` builds
them from a seed in one jitted call. Departure from the checkpoints: the
weights are seeded random numbers, not the trained ones (norm scales and
q/k/v biases are random too, so those paths carry signal).

`reference_logits(..., control=True)` is the check's control: the same
forward with every weight and every activation entering a projection, the
MLP or the head rounded to float8 e4m3 (per-output-channel and per-token
scales), the precision a later change might be tempted to serve in.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F8_MAX = 448.0   # largest finite float8_e4m3fn


def program_kwargs(hf: dict) -> dict:
    """The serving system's model-config fields this published config
    fixes (widths, depth, attention options, norms, positions)."""
    qwen3 = hf["model_type"] == "qwen3"
    return dict(
        n_layers=hf["num_hidden_layers"], d_model=hf["hidden_size"],
        n_heads=hf["num_attention_heads"],
        n_kv_heads=hf["num_key_value_heads"],
        head_dim=hf.get("head_dim",
                        hf["hidden_size"] // hf["num_attention_heads"]),
        d_ff=hf["intermediate_size"], vocab_size=hf["vocab_size"],
        qk_norm=qwen3,
        qkv_bias=hf.get("attention_bias", not qwen3),
        rope_theta=float(hf["rope_theta"]), norm_eps=hf["rms_norm_eps"],
        tie_embeddings=hf["tie_word_embeddings"])


# leaf name -> how the benchmark draws it: ("normal", std), or
# ("fan_in", axes counted from the end that the leaf contracts over), or
# ("one_plus", std) for norm scales
WEIGHT_RULES = {
    "tok": ("normal", 0.02),
    "unembed": ("fan_in", (-2,)),
    "wq": ("fan_in", (-3,)), "wk": ("fan_in", (-3,)), "wv": ("fan_in", (-3,)),
    "wo": ("fan_in", (-3, -2)),
    "w_gate": ("fan_in", (-2,)), "w_up": ("fan_in", (-2,)),
    "w_down": ("fan_in", (-2,)),
    "bq": ("normal", 0.1), "bk": ("normal", 0.1), "bv": ("normal", 0.1),
    "scale": ("one_plus", 0.1), "q_norm": ("one_plus", 0.1),
    "k_norm": ("one_plus", 0.1),
    "length_head": ("fan_in", (-2,)),
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


def _rope(x, theta):
    """x: (S, H, hd), rotate-half convention."""
    S, _, hd = x.shape
    half = hd // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv      # (S, hd/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def reference_logits(hf: dict, params, tokens, control: bool = False):
    """tokens: (S,) int32 -> float32 logits (S, vocab) of the next token at
    every position."""
    f32 = jnp.float32
    eps, theta = hf["rms_norm_eps"], float(hf["rope_theta"])
    n_q = hf["num_attention_heads"]
    n_kv = hf["num_key_value_heads"]
    qwen3 = hf["model_type"] == "qwen3"

    def w(a):                       # a weight, contracted over its axis -2
        a = a.astype(f32)
        return _f8(a, axis=tuple(range(a.ndim - 1))) if control else a

    def act(x):                     # an activation entering a matmul
        return _f8(x, axis=-1) if control else x

    with jax.default_matmul_precision("highest"):
        x = params["embed"]["tok"][tokens].astype(f32)
        S = x.shape[0]
        causal = jnp.tril(jnp.ones((S, S), bool))

        def proj(h, wt, b=None):            # (S, D) x (D, n, hd)
            D, n, hd = wt.shape
            out = (act(h) @ w(wt.reshape(D, n * hd))).reshape(S, n, hd)
            return out if b is None else out + b.astype(f32)

        def layer(x, blk):
            a = blk["attn"]
            h = _rms(x, blk["norm1"]["scale"].astype(f32), eps)
            q = proj(h, a["wq"], a.get("bq"))
            k = proj(h, a["wk"], a.get("bk"))
            v = proj(h, a["wv"], a.get("bv"))
            if qwen3:
                q = _rms(q, a["q_norm"].astype(f32), eps)
                k = _rms(k, a["k_norm"].astype(f32), eps)
            q, k = _rope(q, theta), _rope(k, theta)
            hd = q.shape[-1]
            k = jnp.repeat(k, n_q // n_kv, axis=1)
            v = jnp.repeat(v, n_q // n_kv, axis=1)
            s = jnp.einsum("qnh,knh->nqk", q, k) / jnp.sqrt(f32(hd))
            s = jnp.where(causal[None], s, -jnp.inf)
            o = jnp.einsum("nqk,knh->qnh", jax.nn.softmax(s, axis=-1), v)
            wo = a["wo"]
            x = x + act(o.reshape(S, -1)) @ w(wo.reshape(-1, wo.shape[-1]))
            m = blk["mlp"]
            h = act(_rms(x, blk["norm2"]["scale"].astype(f32), eps))
            g = jax.nn.silu(h @ w(m["w_gate"])) * (h @ w(m["w_up"]))
            return x + act(g) @ w(m["w_down"]), None

        x, _ = jax.lax.scan(layer, x, params["segments"][0])
        x = act(_rms(x, params["final_norm"]["scale"].astype(f32), eps))
        if hf["tie_word_embeddings"]:
            head = params["embed"]["tok"].astype(f32)          # (V, D)
            head = _f8(head, axis=(1,)) if control else head
            return x @ head.T
        return x @ w(params["embed"]["unembed"])
