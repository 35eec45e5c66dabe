"""The DeepSeek-V2 configuration's benchmark pieces on the CPU, on its tiny
twin (bench/tests/data/mla: 3 layers of which 1 dense, d 64, 4 heads, rank
32, rope 16, 16 routed experts of which 4 held, top-3, 1 shared): the plain
reference (bench/reference/deepseek_v2.py) against the program's float32
forward and against what the engine serves through chunked ingest and
decode on the latent pool; the operation and byte counts by hand; and the
cell end to end through the harness, `correct` going false when a served
token is altered."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import fleet, flops_mla
from bench import spec as spec_lib
from bench.tests.helpers import DATA

MLA = DATA / "mla"
CELL = "cloud.docsketch.steady"


def member():
    cfg = json.loads((MLA / "configs" / "tiny-mla.json").read_text())
    (m,) = fleet.members_of(cfg).values()
    return m


def reference():
    return spec_lib.load_module("reference", "deepseek_v2")


def test_reference_matches_program_forward():
    """Both in float32 at the highest matmul precision over the
    benchmark's (bf16) weights: they differ in summation order only, so
    2e-4 on logits of magnitude ~3. The float8 control moves them by far
    more."""
    from repro.models import transformer
    m, ref = member(), reference()
    params = fleet.make_params(ref, m, seed=2 ** 31 + 77)
    shapes = fleet.param_shapes(m.cfg)
    assert jax.tree.structure(params) == jax.tree.structure(shapes)
    toks = jnp.asarray(np.random.default_rng(0).integers(1, 256, 40),
                       jnp.int32)
    cfg32 = m.cfg.with_(dtype="float32", use_pallas=False)
    with jax.default_matmul_precision("highest"):
        prog, _ = transformer.forward(cfg32, params, toks[None])
    mine = ref.reference_logits(m.hf, params, toks)
    np.testing.assert_allclose(np.asarray(mine), np.asarray(prog[0]),
                               atol=2e-4, rtol=2e-4)
    low = ref.reference_logits(m.hf, params, toks, control=True)
    assert float(jnp.max(jnp.abs(low - mine))) > 100 * float(
        jnp.max(jnp.abs(np.asarray(prog[0]) - mine)))


@pytest.mark.parametrize("use_pallas", [False, True])
def test_engine_ingest_and_decode_match_the_reference(use_pallas):
    """A 45-token prompt ingested in three 16-token chunks, then 8 greedy
    decode steps, all through the latent pool (the kernels in interpret
    mode, or the gather oracle): each served token's log-probability is
    the reference's at that position over the whole sequence. float32
    throughout, the absorbed read against the expanded reference: 1e-4."""
    from repro.serving.engine import InferenceEngine
    m, ref = member(), reference()
    params = fleet.make_params(ref, m, seed=11)
    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    cfg = m.cfg.with_(dtype="float32", use_pallas=use_pallas)
    prompt = np.random.default_rng(1).integers(1, 256, 45).tolist()
    with jax.default_matmul_precision("highest"):
        eng = InferenceEngine(cfg, params, max_batch=2, max_len=128,
                              kv_backend="paged", page_size=8, eos_id=-1)
        ((toks, lps),) = eng.generate([prompt], max_new=8)
        logits = ref.reference_logits(m.hf, params,
                                      jnp.asarray(prompt + toks[:-1]))
    logp = np.asarray(jax.nn.log_softmax(logits, axis=-1))
    want = [logp[len(prompt) - 1 + i, t] for i, t in enumerate(toks)]
    np.testing.assert_allclose(lps, want, atol=1e-4, rtol=0)
    assert toks == [int(np.argmax(logp[len(prompt) - 1 + i]))
                    for i in range(len(toks))]


def test_sizes_come_from_the_configuration_files():
    s = flops_mla.sizes("deepseek-v2-lite-ep8")
    assert (s["heads"], s["rank"], s["rope"], s["held"], s["router"],
            s["top_k"], s["moe_layers"]) == (16, 512, 64, 8, 64, 6, 26)
    assert flops_mla.sizes("tiny-mla")["held"] == 4
    assert flops_mla.sizes("qwen2-1.5b") is None


def test_latent_and_expert_counts_by_hand():
    s = flops_mla.sizes("deepseek-v2-lite-ep8")
    assert flops_mla.pair_flops(s) == 2 * 16 * (576 + 512)
    f, b = flops_mla.decode_call(s, 32, 2, [1, 33])
    assert f == 2 * 16 * 1088 * 34
    # 1 + 2 pages of 32 rows x 576 lanes, queries in (576) and latent out
    # (512) per head, bf16
    assert b == (3 * 32 * 576 + 2 * 16 * (576 + 512)) * 2
    f, b = flops_mla.prefill_call(s, 32, 2, [(100, 28)])
    assert f == 2 * 16 * 1088 * (28 * 100 + 28 * 29 // 2)
    assert b == (4 * 32 * 576 + 28 * 16 * 1088) * 2
    # a token sends 6 x 8 / 64 = 0.75 assignments here
    assert flops_mla.routed_per_token(s) == 0.75
    attn = (2048 * 16 * 192 + 2048 * 576 + 16 * 128 * 512 + 16 * 512 * 128
            + 16 * 128 * 2048)
    moe = 2048 * 64 + 3 * 2048 * 1408 * 2 + 3 * 2048 * 1408 * 0.75
    assert flops_mla.token_flops(s) == 2 * (27 * attn + 3 * 2048 * 10944
                                            + 26 * moe + 2048 * 102400)


def tiny_spec():
    return spec_lib.Spec(MLA / "BENCHMARK.json", MLA / "traffic")


def run_twin(seed, trace=False):
    from bench import run
    spec = tiny_spec()
    cell = spec.cell(CELL)
    r = run.run_cell(spec, cell, seed, 2.0, trace)
    return r, run.report(cell, spec, r, trace)


def test_twin_cell_end_to_end_and_altered_tokens(monkeypatch):
    r, res = run_twin(2 ** 31 + 5)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 3 and r["compiles"] == 0
    assert set(res["metrics"]) == {"latency_p50_s", "latency_p90_s",
                                   "setup_s"}
    c = res["compared"]["gap.tiny-mla"]
    assert c["value"] <= c["limit"]
    from repro.serving.engine import InferenceEngine
    commit = InferenceEngine._commit

    def bad(self, slot, tok, lp):
        return commit(self, slot, (tok + 1) % self.cfg.vocab_size or 1, lp)
    monkeypatch.setattr(InferenceEngine, "_commit", bad)
    _, res = run_twin(2 ** 31 + 5)
    assert res["correct"] is False


def test_readers_on_a_hand_built_trace():
    """On a hand-built trace: 1 ms of each kernel in a 1 s span of 4
    engine steps, one decode call at contexts 100 and 200 and one
    128-token chunk at offset 256, for the real configuration's sizes. The
    rooflines divide the calls' least time by the kernel's trace time."""
    from types import SimpleNamespace

    from bench import flops, layers
    from bench.peaks import peaks_for
    eng = "deepseek-v2-lite-ep8"
    probe = SimpleNamespace(decode_calls=[(eng, [100, 200])],
                            prefill_calls=[(eng, [(256, 128)])],
                            steps={eng: 4})
    dims = {eng: dict(page=32, kv_itemsize=2, act_itemsize=2,
                      n_layers=27)}
    ops = [(name, 0, 10 ** 6) for name in
           ("mla_paged_decode.4", "mla_paged_prefill.2",
            "moe_grouped_matmul", "paged_decode_attention")]
    p = peaks_for("TPU v5 lite")
    ctx = layers.Context(probe, None, dims, p, ops, 0, 10 ** 9)
    s = flops_mla.sizes(eng)

    def read(metric):
        return spec_lib.reader(metric + ".steady").read(ctx)
    least = 27 * flops.least_seconds(
        *flops_mla.decode_call(s, 32, 2, [100, 200]), p)[0]
    assert read("mla_decode_roofline") == pytest.approx(100 * least / 1e-3)
    least = 27 * flops.least_seconds(
        *flops_mla.prefill_call(s, 32, 2, [(256, 128)]), p)[0]
    assert read("mla_prefill_roofline") == pytest.approx(100 * least / 1e-3)
    # 1 ms of the grouped matmul over the engine's 4 steps
    assert read("moe_experts_ms") == pytest.approx(0.25)
    work = (flops_mla.model_flops_decode(s, [100, 200])
            + flops_mla.model_flops_prefill(s, [(256, 128)]))
    assert read("moe_step_mfu") == pytest.approx(100 * work / p["flops_bf16"])
    # a dense engine's calls read nothing here
    probe.decode_calls = [("qwen2-1.5b", [100])]
    probe.prefill_calls = []
    probe.steps = {"qwen2-1.5b": 4}
    assert read("mla_decode_roofline") is None
    assert read("moe_experts_ms") is None
    assert read("moe_step_mfu") is None
