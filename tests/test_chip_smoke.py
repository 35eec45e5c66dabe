"""CPU rehearsal of chip_smoke.py: its pipeline and reference checks run on
a tiny twin of the one-chip pairing (a cloud model and two edge members of
one config from different seeds, paged KV, chunked ragged ingest, Pallas in
interpret mode), so the smoke's control flow is exercised on every run; and
the script itself refuses a CPU."""
import importlib.util
import os
import subprocess
import sys
import types
from pathlib import Path

import pytest

from repro.configs.pice_cloud_edge import (TINY_CLOUD, TINY_EDGE_A,
                                           FleetMember, Pairing)
from repro.launch import serve
from repro.serving.engine import InferenceEngine

ROOT = Path(__file__).resolve().parents[1]
TWIN_SERVING = dict(use_pallas=True, prefill_chunk=32)
TWIN = Pairing(cloud="tiny-cloud", members={
    "tiny-cloud": FleetMember(TINY_CLOUD.with_(**TWIN_SERVING), 0.9),
    "tiny-edge-a1": FleetMember(TINY_EDGE_A.with_(**TWIN_SERVING), 0.7,
                                seed=1),
    "tiny-edge-a2": FleetMember(TINY_EDGE_A.with_(**TWIN_SERVING), 0.6,
                                seed=2),
})
# Pallas interpret mode walks every kernel grid step in Python-built XLA
# loops (~ms each), so the rehearsal serves short answers.
TWIN_MAX_NEW = 64


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def fleet():
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(serve, "MAX_BATCH", 2)
        mp.setattr(serve, "MAX_LEN", 256)
        return serve.build_engines(TWIN, train_steps=0, seed=0)


def test_smoke_pipeline_checks_pass_on_tiny_twin(smoke, fleet):
    engines, caps = fleet
    pipe = serve.build_pipeline(engines, caps, cloud=TWIN.cloud,
                                log_fn=lambda s: None)
    responses = smoke.serve_all(
        pipe, smoke.smoke_requests(2, smoke.SEED, max_new=TWIN_MAX_NEW))
    assert smoke.pipeline_failures(pipe, engines, responses) == []


@pytest.mark.parametrize("name", ["tiny-cloud", "tiny-edge-a1"])
def test_smoke_reference_check_passes_on_tiny_twin(smoke, fleet, name):
    engines, _ = fleet
    # 40 prompt tokens span two 32-token ingest chunks; a 256-token vocab
    # can draw EOS early, so at least the prefill's token is compared
    err, shift, n = smoke.reference_error(engines[name], 40, 4, smoke.SEED)
    assert n >= 1
    assert err <= smoke.LOGPROB_TOL < shift


def test_smoke_reference_check_catches_a_skipped_page(smoke, fleet,
                                                      monkeypatch):
    """A paged decode kernel that skips every slot's first page (a read
    fault the served text would not reveal) must push the reference check
    past its tolerance."""
    from repro.kernels.paged_decode_attention import ops as pda_ops
    good = fleet[0]["tiny-edge-a1"]
    read = pda_ops.paged_decode_attention

    def skip_first_page(q, k_pages, v_pages, table, lengths):
        return read(q, k_pages, v_pages, table.at[:, 0].set(-1), lengths)
    monkeypatch.setattr(pda_ops, "paged_decode_attention", skip_first_page)
    # a config of its own, so this engine traces its steps with the fault
    cfg = good.cfg.with_(name="tiny-edge-a1-read-fault")
    eng = InferenceEngine(cfg, good.params, max_batch=2, max_len=256,
                          kv_backend="paged")
    err, _, n = smoke.reference_error(eng, 40, 4, smoke.SEED)
    assert n > 1, "no decode step read the cache"
    assert err > smoke.LOGPROB_TOL


def test_smoke_pipeline_checks_catch_each_failure(smoke):
    pipe = types.SimpleNamespace(
        monitor=types.SimpleNamespace(edge_failures=1))
    oracle = InferenceEngine(TINY_EDGE_A, None, max_batch=1, max_len=64,
                             kv_backend="paged", page_size=16)
    bad = smoke.pipeline_failures(pipe, {"oracle": oracle}, [])
    assert any("oracle path" in b for b in bad)
    assert any("progressive" in b for b in bad)
    assert any("edge member" in b for b in bad)
    assert any("generated no tokens" in b for b in bad)


def test_smoke_script_refuses_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(ROOT / "src"))
    out = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                         capture_output=True, text=True, env=env,
                         timeout=120)
    assert out.returncode != 0
    assert "no TPU found" in out.stderr
    assert '"ok"' not in out.stdout


def test_smoke_compile_cache_placement(smoke, monkeypatch):
    """$JAX_COMPILATION_CACHE_DIR wins when set (the smoke sets nothing);
    unset, the cache is the fixed `.jax_cache` beside the script."""
    import jax
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        jax.config.update("jax_compilation_cache_dir", None)
        smoke.use_compile_cache()
        assert jax.config.jax_compilation_cache_dir is None
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        smoke.use_compile_cache()
        assert jax.config.jax_compilation_cache_dir == str(
            ROOT / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
