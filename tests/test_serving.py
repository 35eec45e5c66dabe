"""Serving engine tests: continuous batching, cache insertion, scoring."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.pice_cloud_edge import TINY_EDGE_A
from repro.models import transformer
from repro.serving.engine import InferenceEngine
from repro.serving.sampler import SamplerConfig


@pytest.fixture(scope="module")
def engine():
    cfg = TINY_EDGE_A.with_(dtype="float32")
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    return InferenceEngine(cfg, params, max_batch=4, max_len=256,
                           name="test"), cfg, params


def test_generate_lengths(engine):
    eng, _, _ = engine
    outs = eng.generate([[65, 66, 67], [70, 71]], max_new=12)
    assert len(outs) == 2
    for toks, lps in outs:
        assert 1 <= len(toks) <= 12
        assert len(lps) == len(toks)
        assert all(lp <= 0.0 for lp in lps)


def test_continuous_batching_slot_reuse(engine):
    eng, _, _ = engine
    # more requests than slots forces recycling
    prompts = [[65 + i, 66, 67] for i in range(9)]
    outs = eng.generate(prompts, max_new=6)
    assert len(outs) == 9
    assert all(len(t) >= 1 for t, _ in outs)
    assert len(eng.free_slots()) == eng.max_batch


def test_batched_equals_single(engine):
    """Greedy decode of a request must be identical whether it shares the
    batch with other requests or runs alone (continuous-batching isolation)."""
    eng, cfg, params = engine
    a = [65, 66, 67, 68]
    b = [80, 81]
    solo = InferenceEngine(cfg, params, max_batch=1, max_len=256)
    (ref, _), = solo.generate([a], max_new=8)
    outs = eng.generate([b, a, b], max_new=8)
    assert outs[1][0] == ref


def test_score_is_teacher_forced_logprob(engine):
    eng, cfg, params = engine
    seq = [65, 66, 67, 68, 69]
    mean_lp, per = eng.score(seq)
    assert per.shape[0] == len(seq) - 1
    assert mean_lp <= 0.0
    logits, _ = transformer.forward(cfg, params,
                                    jnp.asarray([seq[:-1]], jnp.int32))
    logp = jax.nn.log_softmax(logits[0].astype(jnp.float32), -1)
    want = np.asarray([float(logp[i, seq[i + 1]]) for i in range(len(seq) - 1)])
    np.testing.assert_allclose(per, want, rtol=1e-4, atol=1e-4)


def test_sampler_greedy_vs_temperature(engine):
    eng, cfg, params = engine
    hot = InferenceEngine(cfg, params, max_batch=1, max_len=256,
                          sampler=SamplerConfig(temperature=1.0, top_k=8))
    (g1, _), = eng.generate([[65, 66]], max_new=10)
    (g2, _), = eng.generate([[65, 66]], max_new=10)
    assert g1 == g2, "greedy must be deterministic"
    (h1, _), = hot.generate([[65, 66]], max_new=10)
    assert len(h1) >= 1


@pytest.mark.parametrize("param_dtype", ["float32", "bfloat16"])
def test_build_engines_serves_params_only(monkeypatch, param_dtype):
    """Serving (train_steps=0) builds params in the config's param_dtype
    and never the optimizer state (which at qwen3-8b width would not fit
    one chip beside the fleet)."""
    from repro.configs.pice_cloud_edge import FleetMember, Pairing
    from repro.launch import serve

    def no_optimizer(*args, **kwargs):
        raise AssertionError("optimizer state built for serving")
    monkeypatch.setattr(serve, "init_train_state", no_optimizer)
    monkeypatch.setattr(serve, "MAX_BATCH", 2)
    monkeypatch.setattr(serve, "MAX_LEN", 64)
    cfg = TINY_EDGE_A.with_(param_dtype=param_dtype)
    pair = Pairing(cloud="c", members={"c": FleetMember(cfg, 0.9),
                                       "e": FleetMember(cfg, 0.7, seed=1)})
    engines, caps = serve.build_engines(pair, train_steps=0, seed=3)
    assert set(engines) == {"c", "e"} and caps == {"c": 0.9, "e": 0.7}
    leaves = jax.tree.leaves(engines["c"].params)
    assert {leaf.dtype for leaf in leaves} == {jnp.dtype(param_dtype)}
    # members draw their weights from the launch seed plus their own offset
    expect = jax.jit(transformer.init_params, static_argnums=0)(
        cfg, jax.random.PRNGKey(4))
    np.testing.assert_array_equal(
        np.asarray(engines["e"].params["embed"]["tok"], np.float32),
        np.asarray(expect["embed"]["tok"], np.float32))
