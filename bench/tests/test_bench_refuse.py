"""bench/run.py refuses a host without a TPU, and a checkout that holds
only the benchmark, with a clear message and no result line."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def run_bench(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "pice.long.steady",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, capture_output=True, text=True, env=env, timeout=300)


def test_refuses_without_a_tpu():
    out = run_bench(ROOT)
    assert out.returncode != 0
    assert "no TPU found" in out.stderr
    assert '"correct"' not in out.stdout


def test_refuses_in_a_checkout_of_only_the_benchmark(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = run_bench(tmp_path)
    assert out.returncode != 0
    assert '"correct"' not in out.stdout


def test_unknown_workload_is_refused():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "no.such.cell",
         "--seed", "1", "--seconds", "1"], cwd=ROOT, capture_output=True,
        text=True, env=env, timeout=300)
    assert out.returncode != 0 and "no workload" in out.stderr
