"""Per-architecture smoke tests (reduced variants): one forward + one train
step on CPU, asserting output shapes and no NaNs; plus decode consistency."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import ALIASES, all_configs
from repro.launch import steps as steps_lib
from repro.models import transformer
from repro.training import optimizer as opt_lib

CONFIGS = all_configs()

# init_params costs seconds per arch on CPU; the three per-arch test families
# use shape-identical reduced configs, so share one init per (arch, overrides
# that change param shapes — here: none do).
_PARAMS_CACHE = {}


def _params_for(arch, r, key):
    if arch not in _PARAMS_CACHE:
        _PARAMS_CACHE[arch] = transformer.init_params(r, key)
    return _PARAMS_CACHE[arch]


def _batch_for(r, key, B=2, S=32):
    toks = jax.random.randint(key, (B, S), 0, r.vocab_size)
    batch = {"tokens": toks, "targets": jnp.roll(toks, -1, axis=1)}
    if r.family == "encdec":
        batch["enc_frames"] = jax.random.normal(
            key, (B, r.encoder.n_ctx, r.encoder.d_model), jnp.bfloat16)
    if r.family == "vlm":
        batch["prefix_embeds"] = jax.random.normal(
            key, (B, r.n_prefix_tokens, r.d_model), jnp.bfloat16)
    return batch


@pytest.mark.parametrize("arch", sorted(ALIASES))
def test_arch_forward_shapes_no_nan(arch, rng_key):
    r = CONFIGS[arch].reduced(remat=False)
    params = _params_for(arch, r, rng_key)
    batch = _batch_for(r, rng_key)
    logits, aux = jax.jit(lambda p, b: transformer.forward(
        r, p, b["tokens"],
        prefix_embeds=b.get("prefix_embeds"),
        enc_frames=b.get("enc_frames")))(params, batch)
    B, S = batch["tokens"].shape
    extra = r.n_prefix_tokens if r.family == "vlm" else 0
    assert logits.shape == (B, S + extra, r.vocab_size)
    assert not bool(jnp.any(jnp.isnan(logits.astype(jnp.float32))))
    assert np.isfinite(float(aux))


# One representative per family in tier-1 (train-step compiles are the most
# expensive thing in this file); the remaining archs run under -m slow.
_TRAIN_FAMILY_REPS = {"qwen2-1.5b", "mixtral-8x7b", "xlstm-1.3b",
                      "zamba2-2.7b", "whisper-tiny", "internvl2-2b"}


@pytest.mark.parametrize("arch", [
    a if a in _TRAIN_FAMILY_REPS else pytest.param(a, marks=pytest.mark.slow)
    for a in sorted(ALIASES)])
def test_arch_train_step(arch, rng_key):
    r = CONFIGS[arch].reduced(remat=False)
    params = _params_for(arch, r, rng_key)
    opt_state = opt_lib.init_opt_state(params)
    step = jax.jit(steps_lib.make_train_step(r, opt_lib.AdamWConfig(lr=1e-3)))
    batch = _batch_for(r, rng_key, B=2, S=16)
    params2, opt_state2, metrics = step(params, opt_state, batch)
    loss = float(metrics["loss"])
    assert np.isfinite(loss) and loss > 0
    # params actually changed
    delta = jax.tree.reduce(
        lambda a, b: a + b,
        jax.tree.map(lambda a, b: float(jnp.sum(jnp.abs(
            a.astype(jnp.float32) - b.astype(jnp.float32)))), params, params2))
    assert delta > 0
    assert int(opt_state2.step) == 1


@pytest.mark.parametrize("arch", ["qwen3-8b", "mixtral-8x7b", "xlstm-1.3b",
                                  "zamba2-2.7b", "whisper-tiny",
                                  "internvl2-2b", "qwen2-1.5b"])
def test_decode_matches_forward(arch, rng_key):
    """Step-by-step decode with cache == teacher-forced forward (f32)."""
    over = dict(remat=False, dtype="float32")
    if CONFIGS[arch].is_moe:
        over["capacity_factor"] = 8.0        # no token dropping
    r = CONFIGS[arch].reduced(**over)
    params = _params_for(arch, r, rng_key)
    B, S0, N, MAX = 2, 8, 5, 64
    toks = jax.random.randint(rng_key, (B, S0 + N), 0, r.vocab_size)
    kw = {}
    if r.family == "encdec":
        kw["enc_frames"] = jax.random.normal(
            rng_key, (B, r.encoder.n_ctx, r.encoder.d_model), jnp.float32)
    if r.family == "vlm":
        kw["prefix_embeds"] = jax.random.normal(
            rng_key, (B, r.n_prefix_tokens, r.d_model), jnp.float32)
    cache = transformer.init_cache(r, B, MAX)
    logits, cache = jax.jit(
        lambda p, t, c: transformer.prefill(r, p, t, c, **kw))(
        params, toks[:, :S0], cache)
    decode = jax.jit(lambda p, t, c: transformer.decode_step(r, p, t, c))
    outs = [logits]
    for i in range(N):
        logits, cache = decode(params, toks[:, S0 + i:S0 + i + 1], cache)
        outs.append(logits)
    dec = jnp.stack(outs[:-1], 1)
    fw, _ = transformer.forward(r, params, toks, **kw)
    extra = r.n_prefix_tokens if r.family == "vlm" else 0
    ref = fw[:, extra + S0 - 1: extra + S0 + N - 1]
    np.testing.assert_allclose(np.asarray(dec), np.asarray(ref), rtol=2e-4,
                               atol=2e-4)


def test_sliding_window_cache_ring_buffer(rng_key):
    """Windowed decode must equal full-cache decode restricted to the window."""
    r = CONFIGS["mixtral-8x7b"].reduced(remat=False, dtype="float32",
                                        sliding_window=8, capacity_factor=8.0)
    params = _params_for("mixtral-8x7b", r, rng_key)
    B, S0, N = 1, 12, 8              # crosses the window boundary
    toks = jax.random.randint(rng_key, (B, S0 + N), 0, r.vocab_size)
    cache = transformer.init_cache(r, B, 64)
    logits, cache = jax.jit(
        lambda p, t, c: transformer.prefill(r, p, t, c))(
        params, toks[:, :S0], cache)
    decode = jax.jit(lambda p, t, c: transformer.decode_step(r, p, t, c))
    outs = [logits]
    for i in range(N):
        logits, cache = decode(params, toks[:, S0 + i:S0 + i + 1], cache)
        outs.append(logits)
    dec = jnp.stack(outs[:-1], 1)
    fw, _ = transformer.forward(r, params, toks)
    ref = fw[:, S0 - 1: S0 + N - 1]
    np.testing.assert_allclose(np.asarray(dec), np.asarray(ref), rtol=5e-4,
                               atol=5e-4)


def test_variable_prompt_lengths(rng_key):
    """Right-padded prefill must match per-request unpadded prefill."""
    r = CONFIGS["qwen2-1.5b"].reduced(remat=False, dtype="float32")
    params = _params_for("qwen2-1.5b", r, rng_key)
    toks = jax.random.randint(rng_key, (2, 12), 0, r.vocab_size)
    lens = jnp.array([7, 12])
    cache = transformer.init_cache(r, 2, 32)
    padded = toks.at[0, 7:].set(0)
    logits, cache2 = transformer.prefill(r, params, padded, cache,
                                         prompt_lengths=lens)
    # reference: prefill request 0 alone at its true length
    cache1 = transformer.init_cache(r, 1, 32)
    ref_logits, _ = transformer.prefill(r, params, toks[:1, :7], cache1)
    np.testing.assert_allclose(np.asarray(logits[0]), np.asarray(ref_logits[0]),
                               rtol=2e-4, atol=2e-4)
    assert int(cache2["lengths"][0]) == 7 and int(cache2["lengths"][1]) == 12


def test_param_counts_match_assignment():
    """Full-size configs should land near their nameplate parameter counts."""
    expect = {"qwen3-8b": (7, 10), "mixtral-8x7b": (40, 50),
              "qwen3-moe-30b-a3b": (27, 33), "granite-3-8b": (7, 10),
              "minitron-8b": (7, 11), "qwen2-1.5b": (1.2, 2.2),
              "deepseek-v2-lite": (15.5, 16.0)}
    for arch, (lo, hi) in expect.items():
        b = CONFIGS[arch].param_count() / 1e9
        assert lo <= b <= hi, f"{arch}: {b:.2f}B outside [{lo},{hi}]"
