"""Latent attention (MLA) and the serving expert layer, on the CPU at tiny
sizes: the latent-mode paged kernels and the grouped expert matmul in
interpret mode against their oracles (pruned and unmapped pages, empty
groups), the expert-parallel shares adding up to the uncut layer, the
layer staying dropless when the router sends every token the same way, a
latent pool through fork and host swap, and the YaRN frequencies."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.moe_grouped_matmul import ops as gmm_ops
from repro.kernels.moe_grouped_matmul import ref as gmm_ref
from repro.kernels.paged_decode_attention import ops as pda_ops
from repro.kernels.paged_decode_attention import ref as pda_ref
from repro.kernels.paged_prefill_attention import ops as ppa_ops
from repro.kernels.paged_prefill_attention import ref as ppa_ref
from repro.models import moe as moe_lib
from repro.models import transformer
from repro.models.config import ModelConfig
from repro.models.layers import (mla_rope_frequencies, mla_softmax_scale,
                                 yarn_frequencies)
from repro.serving.engine import InferenceEngine

# the tiny twin of deepseek-v2-lite-ep8 (bench/tests/data/mla): 16 routed
# experts of which a rank holds 4, top-3, one shared expert
TINY = ModelConfig(
    name="tiny-mla", family="moe", n_layers=3, d_model=64, n_heads=4,
    n_kv_heads=4, d_ff=96, moe_d_ff=32, vocab_size=256, max_seq_len=4096,
    n_experts=16, experts_per_token=3, moe_dropless=True,
    n_shared_experts=1, first_k_dense=1, norm_topk_prob=False, ep_size=4,
    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=16, v_head_dim=16,
    rope_theta=10000.0, yarn_factor=40.0, yarn_original_max_pos=4096,
    yarn_mscale=0.707, yarn_mscale_all_dim=0.707, dtype="float32",
    remat=False, prefill_chunk=16)

# f32 oracles against f32 kernels: the two differ in summation order only
TOL = dict(rtol=2e-5, atol=2e-5)
W, RANK, PS = 128, 96, 8        # latent lanes, value lanes, page size


def _pool(key, L=2, n_pages=12):
    return jax.random.normal(key, (L, n_pages, 1, PS, W), jnp.float32)


@pytest.mark.parametrize("lengths,width", [([5, 17, 0, 24], 4),
                                           ([1, 8, 9, 31], 8)])
def test_latent_decode_kernel_matches_oracle(lengths, width):
    """Rows at 0, 1 and several pages, a table wider than the live range
    (pruned pages), and an unmapped (-1) column past every row's end."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    pages = _pool(k1)
    q = jax.random.normal(k2, (4, 1, 4, W), jnp.float32)
    table = np.full((4, width), -1, np.int32)
    ids = iter(np.random.default_rng(0).permutation(12))
    for b, n in enumerate(lengths):
        for p in range(-(-n // PS)):
            table[b, p] = next(ids)
    lens = jnp.asarray(lengths, jnp.int32)
    for layer in (0, 1):
        got = pda_ops.mla_paged_decode(q, pages, jnp.asarray(table), lens,
                                       layer, rank=RANK, scale=0.3)
        want = pda_ref.mla_paged_decode_ref(q, pages, jnp.asarray(table),
                                            lens, layer, rank=RANK, scale=0.3)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)
    for b, n in enumerate(lengths):
        if n == 0:                                # a zero-length row
            assert not np.any(np.asarray(got)[b])


def test_latent_prefill_kernel_matches_oracle():
    """Ragged rows: a first chunk, a chunk after cached context spanning
    page boundaries, a short last chunk, and a padding row (len 0)."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(1))
    pages = _pool(k1, n_pages=16)
    C = 8
    q = jax.random.normal(k2, (4, C, 4, W), jnp.float32)
    rows = np.full((4, 8), -1, np.int32)
    rows[0, :1] = [3]
    rows[1, :3] = [5, 0, 9]
    rows[2, :4] = [1, 2, 4, 6]
    offsets = jnp.asarray([0, 13, 24, 0], jnp.int32)
    lens = jnp.asarray([8, 8, 5, 0], jnp.int32)
    got = ppa_ops.mla_paged_prefill(q, pages, jnp.asarray(rows), offsets,
                                    lens, 1, rank=RANK, scale=0.3)
    want = ppa_ref.mla_paged_prefill_ref(q, pages, jnp.asarray(rows),
                                         offsets, lens, 1, rank=RANK,
                                         scale=0.3)
    for r, n in enumerate([8, 8, 5]):
        np.testing.assert_allclose(np.asarray(got)[r, :n],
                                   np.asarray(want)[r, :n], **TOL)


@pytest.mark.parametrize("layer", [None, 0, 1, 2])
@pytest.mark.parametrize("sizes", [[0, 130, 0, 100], [0, 0, 0, 0],
                                   [300, 0, 0, 0], [7, 1, 0, 40]])
def test_grouped_matmul_matches_oracle(sizes, layer):
    """Empty groups, no rows at all, one group holding every row, groups
    sharing a row tile; rows past the last group are the caller's. One
    layer's weights, or a stack of three read at `layer` in place."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(2))
    lhs = jax.random.normal(k1, (300, 64), jnp.float32)
    gs = jnp.asarray(sizes, jnp.int32)
    if layer is None:
        rhs = jax.random.normal(k2, (4, 64, 48), jnp.float32)
        got = gmm_ops.moe_grouped_matmul(lhs, rhs, gs)
    else:
        stack = jax.random.normal(k2, (3, 4, 64, 48), jnp.float32)
        got = gmm_ops.moe_grouped_matmul(lhs, stack, gs,
                                         jnp.asarray(layer, jnp.int32))
        rhs = stack[layer]
    want = gmm_ref.moe_grouped_matmul_ref(lhs, rhs, gs)
    n = sum(sizes)
    np.testing.assert_allclose(np.asarray(got)[:n], np.asarray(want)[:n],
                               rtol=1e-4, atol=1e-4)


def _moe_params(cfg, seed=0):
    return moe_lib.init_moe(cfg, jax.random.PRNGKey(seed))


@pytest.mark.parametrize("use_pallas", [False, True])
def test_expert_layer_reads_a_stack_at_its_layer(use_pallas):
    """The serving expert layer handed a three-layer stack of held-expert
    weights and a traced layer serves what it serves from that layer's own
    weights, bit for bit (the kernel in interpret mode, or ragged_dot)."""
    cfg = TINY.with_(use_pallas=use_pallas)
    layers = [_moe_params(cfg, seed) for seed in range(3)]
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 9, 64), jnp.float32)
    serve = jax.jit(lambda p, x, layer: moe_lib.moe_serve(
        cfg, p, x, layer=layer))
    for layer in range(3):
        stacked = dict(layers[layer])
        for k in moe_lib.EXPERT_WEIGHTS:
            stacked[k] = jnp.stack([p[k] for p in layers])
        got = serve(stacked, x, jnp.asarray(layer, jnp.int32))
        want = serve(layers[layer], x, None)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _share(full, cfg_share):
    """Rank `cfg_share.ep_rank`'s slice of an uncut layer's experts."""
    n = cfg_share.held_experts
    lo = cfg_share.ep_rank * n
    p = dict(full)
    for k in ("w_gate", "w_up", "w_down"):
        p[k] = full[k][lo:lo + n]
    return p


@pytest.mark.parametrize("use_pallas", [False, True])
def test_expert_shares_add_up_to_the_uncut_layer(use_pallas):
    """Every rank's partial sum, the shared expert counted once, is the
    layer with all 16 experts held (the deployment the share stands for)."""
    uncut = TINY.with_(ep_size=1, use_pallas=use_pallas)
    full = _moe_params(uncut)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 9, 64), jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole = moe_lib.moe_serve(uncut, full, x)
        shared = moe_lib.mlp(uncut, full["shared"], x)
        parts = [moe_lib.moe_serve(
            TINY.with_(ep_rank=r, use_pallas=use_pallas),
            _share(full, TINY.with_(ep_rank=r)), x) for r in range(4)]
    total = sum(parts) - 3 * shared
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("use_pallas", [False, True])
def test_every_token_to_the_same_experts_is_dropless(use_pallas):
    """A router that sends all 40 tokens to experts 0-2 (ties go to the
    lowest ids): each of them computes every row, where a capacity layer
    would drop all but ~10 of each expert's 40."""
    cfg = TINY.with_(use_pallas=use_pallas)
    p = _moe_params(cfg)
    p["router"] = jnp.zeros_like(p["router"])
    x = jax.random.normal(jax.random.PRNGKey(4), (40, 64), jnp.float32)
    st = {}
    with jax.default_matmul_precision("highest"):
        got = moe_lib.moe_serve(cfg, p, x, st)
        want = moe_lib.mlp(cfg, p["shared"], x)
        for e in range(3):
            ex = {k: p[k][e] for k in ("w_gate", "w_up", "w_down")}
            want = want + moe_lib.mlp(cfg, ex, x) / 16.0
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    assert np.asarray(st["expert_rows"]).tolist() == [40, 40, 40, 0]
    assert moe_lib.moe_capacity(40, TINY) < 40


def test_yarn_frequencies_and_scale_of_deepseek_v2_lite():
    """Correction range 10..23 (dims turning 32 and 1 times over 4096
    positions), base frequencies below it, base / 40 above it, and the
    softmax scale 192^-0.5 x (0.1 x 0.707 x ln 40 + 1)^2."""
    from repro.configs.registry import get_config
    cfg = get_config("deepseek-v2-lite")
    inv = mla_rope_frequencies(cfg)
    base = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    corr = [64 * math.log(4096 / (2 * math.pi * n)) / (2 * math.log(1e4))
            for n in (32, 1)]
    assert (math.floor(corr[0]), math.ceil(corr[1])) == (10, 23)
    np.testing.assert_allclose(inv[:11], base[:11], rtol=1e-6)
    np.testing.assert_allclose(inv[23:], base[23:] / 40, rtol=1e-6)
    ramp = (16 - 10) / 13
    np.testing.assert_allclose(inv[16], base[16] / 40 * ramp
                               + base[16] * (1 - ramp), rtol=1e-6)
    assert abs(mla_softmax_scale(cfg) - 0.114721) < 1e-6
    np.testing.assert_array_equal(
        yarn_frequencies(64, 1e4, 40, 4096, 32, 1), inv)


@pytest.fixture(scope="module")
def tiny_params():
    return transformer.init_params(TINY, jax.random.PRNGKey(5))


def _greedy(eng, prompt, n):
    (out,) = eng.generate([prompt], max_new=n)
    return out


@pytest.mark.parametrize("attention", ["latent", "gqa"])
@pytest.mark.parametrize("use_pallas", [False, True])
def test_engine_reads_the_expert_stack_as_per_layer_weights(
        tiny_params, monkeypatch, use_pallas, attention):
    """Greedy tokens of the paged engine, ingesting two prompts (latent:
    in 16-token chunks, the ragged ingest; GQA: whole, the monolithic
    paged prefill) and decoding 8 tokens each, and their
    log-probabilities, are exactly what it serves when each expert layer
    is handed its own weights sliced out of the stack: the same matmuls on
    the same tiles. (The tiny model's tokens alone barely see the experts:
    its log-probabilities do.)"""
    rng = np.random.default_rng(8)
    prompts = [rng.integers(1, 256, n).tolist() for n in (37, 20)]
    kw = dict(max_batch=2, max_len=64, kv_backend="paged", page_size=8,
              eos_id=-1)
    cfg = TINY.with_(use_pallas=use_pallas)
    params = tiny_params
    if attention == "gqa":
        cfg = cfg.with_(name="tiny-gqa-moe", kv_lora_rank=0, prefill_chunk=0)
        params = transformer.init_params(cfg, jax.random.PRNGKey(5))
    stacked = InferenceEngine(cfg, params, **kw).generate(
        prompts, max_new=8)
    # a config of its own name compiles its own programs
    monkeypatch.setattr(transformer, "_expert_stack",
                        lambda cfg, kind, seg, dtype: (seg, None))
    per_layer = InferenceEngine(cfg.with_(name=cfg.name + "-per-layer"),
                                params, **kw).generate(prompts, max_new=8)
    assert stacked == per_layer             # tokens and log-probabilities


def test_latent_pool_survives_fork_and_host_swap(tiny_params):
    """A fan-out over a shared prefix (COW fork of the latent pool's tail
    page), and a pool small enough that decode growth demotes a request to
    the host tier and promotes it back, serve the tokens an uncontended
    engine serves."""
    rng = np.random.default_rng(6)
    prefix = rng.integers(1, 256, 21).tolist()
    sufs = [rng.integers(1, 256, 5).tolist() for _ in range(3)]
    shorts = [rng.integers(1, 256, n).tolist() for n in (4, 2, 3)]
    kw = dict(max_len=64, kv_backend="paged", page_size=8, eos_id=-1)
    with jax.default_matmul_precision("highest"):
        def alone(p, n):
            return _greedy(InferenceEngine(TINY, tiny_params, max_batch=1,
                                           **kw), p, n)[0]
        fan = InferenceEngine(TINY, tiny_params, max_batch=4, **kw)
        outs = fan.generate_fanout(prefix, sufs, max_new=6)
        assert [t for t, _ in outs] == [alone(prefix + s, 6) for s in sufs]
        squeezed = InferenceEngine(TINY, tiny_params, max_batch=3, n_pages=6,
                                   **kw)
        got = squeezed.generate(shorts, max_new=24)
        assert squeezed.swap_outs > 0 and squeezed.swap_ins > 0
        assert [t for t, _ in got] == [alone(p, 24) for p in shorts]
