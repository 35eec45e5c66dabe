"""Real-width compiles for a TPU v5e chip, without the chip.

The interpret-mode parity tests run the kernel bodies in Python; they never
ask the TPU compiler whether it accepts the kernels' block shapes, VMEM use
or layouts. These tests compile the main path's Pallas kernels at
qwen2-1.5b and qwen3-8b head shapes (12/2 and 32/8 heads, head_dim 128,
page 32, 128-token chunks) for one chip of a *described* v5e:2x2 topology,
and check that the compiled program holds the Mosaic kernel
(`tpu_custom_call`). One more compiles the served decode step of the whole
qwen2-1.5b edge model and checks it fits a 16 GB chip.

The topology is described inside a module fixture (never at import: one
process at a time may load the TPU library), which skips where it cannot be
described.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention import kernel as fa_kernel
from repro.kernels.paged_decode_attention import kernel as pda_kernel
from repro.kernels.paged_prefill_attention import kernel as ppa_kernel

HEADS = {"qwen2-1.5b": (12, 2), "qwen3-8b": (32, 8)}     # (Hq, Hkv)
HD, PAGE, N_PAGES, BATCH, TABLE_W, CHUNK, ROWS = 128, 32, 256, 8, 32, 128, 8
POOL_DTYPES = {"bf16": jnp.bfloat16, "int8": jnp.int8}
V5E_HBM_BYTES = 16 * 1024 ** 3


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to a persistent cache but
    # cannot be read back without the chip: keep the cache out of it
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", cache_on)


@pytest.fixture(scope="module")
def spec(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return make


def _compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("pool", sorted(POOL_DTYPES))
@pytest.mark.parametrize("model", sorted(HEADS))
def test_paged_decode_attention_compiles_for_v5e(spec, model, pool):
    hq, hkv = HEADS[model]
    q = spec((BATCH, 1, hq, HD), jnp.bfloat16)
    pages = spec((N_PAGES, PAGE, hkv, HD), POOL_DTYPES[pool])
    table = spec((BATCH, TABLE_W), jnp.int32)
    lens = spec((BATCH,), jnp.int32)
    if pool == "bf16":
        text = _compiled_text(
            lambda *a: pda_kernel.paged_decode_attention_pallas(
                *a, interpret=False), q, pages, pages, table, lens)
    else:
        scales = spec((N_PAGES, hkv), jnp.float32)
        text = _compiled_text(
            lambda *a: pda_kernel.paged_decode_attention_quant_pallas(
                *a, interpret=False),
            q, pages, pages, scales, scales, table, lens)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("pool", sorted(POOL_DTYPES))
@pytest.mark.parametrize("model", sorted(HEADS))
def test_paged_prefill_attention_ragged_compiles_for_v5e(spec, model, pool):
    hq, hkv = HEADS[model]
    q = spec((ROWS, CHUNK, hq, HD), jnp.bfloat16)
    pages = spec((N_PAGES, PAGE, hkv, HD), POOL_DTYPES[pool])
    rows = spec((ROWS, TABLE_W), jnp.int32)
    offs = spec((ROWS,), jnp.int32)
    if pool == "bf16":
        text = _compiled_text(
            lambda *a: ppa_kernel.paged_prefill_attention_ragged_pallas(
                *a, interpret=False), q, pages, pages, rows, offs, offs)
    else:
        scales = spec((N_PAGES, hkv), jnp.float32)
        text = _compiled_text(
            lambda *a: ppa_kernel.paged_prefill_attention_ragged_quant_pallas(
                *a, interpret=False),
            q, pages, pages, scales, scales, rows, offs, offs)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("model", sorted(HEADS))
def test_flash_attention_compiles_for_v5e(spec, model):
    hq, hkv = HEADS[model]
    q = spec((1, 1024, hq, HD), jnp.bfloat16)
    kv = spec((1, 1024, hkv, HD), jnp.bfloat16)
    text = _compiled_text(
        lambda *a: fa_kernel.flash_attention_pallas(*a, interpret=False),
        q, kv, kv)
    assert "tpu_custom_call" in text


def test_served_edge_decode_step_compiles_within_v5e_hbm(spec, monkeypatch):
    """The one-chip pairing's edge engine (qwen2-1.5b, all 28 layers, bf16)
    fused decode step, from shapes: kernels compiled, arguments plus
    scratch under one chip's HBM."""
    from repro.configs.pice_cloud_edge import PAIRINGS
    from repro.kernels import runtime
    from repro.launch import serve
    from repro.models import transformer
    from repro.serving import engine as engine_lib
    from repro.serving.sampler import SamplerConfig
    # the host backend is the CPU; the program is for the described chip
    monkeypatch.setattr(runtime, "default_interpret", lambda: False)
    cfg = next(iter(PAIRINGS["one-chip"].edges.values())).cfg
    B, L, ps = serve.MAX_BATCH, serve.MAX_LEN, PAGE

    def place(tree):
        return jax.tree.map(lambda a: spec(a.shape, a.dtype), tree)
    params = place(jax.eval_shape(
        lambda: transformer.init_params(cfg, jax.random.PRNGKey(0))))
    cache = place(transformer.init_paged_cache(cfg, B, B * L // ps, ps,
                                               L // ps, spec=True))
    step = engine_lib._jitted(cfg, "decode_paged_run", SamplerConfig())
    compiled = step.lower(4, params, spec((B, 1), jnp.int32), cache,
                          spec((B,), jnp.bool_),
                          spec((2,), jnp.uint32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        < V5E_HBM_BYTES
