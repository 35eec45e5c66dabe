"""Real-width compiles for a TPU v5e chip, without the chip.

The interpret-mode parity tests run the kernel bodies in Python; they never
ask the TPU compiler whether it accepts the kernels' block shapes, VMEM use
or layouts. These tests compile the main path's Pallas kernels at
qwen2-1.5b and qwen3-8b head shapes (12/2 and 32/8 heads, head_dim 128,
page 32, 128-token chunks) for one chip of a *described* v5e:2x2 topology,
and check that the compiled program holds the Mosaic kernel
(`tpu_custom_call`). The served decode step of the whole qwen2-1.5b edge
model is compiled too: it must fit a 16 GB chip, and at the benchmark's
edge pool its decode and ragged ingest steps must read and write the
stacked KV pool in place, with no pool-sized scratch, slice or relayout.
The DeepSeek-V2-Lite share's steps must likewise read the held-expert
weight stack in place, with no per-layer slice of it.

The topology is described inside a module fixture (never at import: one
process at a time may load the TPU library), which skips where it cannot be
described.
"""
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention import kernel as fa_kernel
from repro.kernels.moe_grouped_matmul import kernel as gmm_kernel
from repro.kernels.paged_decode_attention import kernel as pda_kernel
from repro.kernels.paged_prefill_attention import kernel as ppa_kernel

HEADS = {"qwen2-1.5b": (12, 2), "qwen3-8b": (32, 8)}     # (Hq, Hkv)
HD, PAGE, N_PAGES, BATCH, TABLE_W, CHUNK, ROWS = 128, 32, 256, 8, 32, 128, 8
LAYERS = 4                               # stacked pool; the layer is traced
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


def _pallas_grids(fn, *args):
    """The grid of each Pallas kernel in fn's program."""
    return [e.params["grid_mapping"].grid
            for e in jax.make_jaxpr(fn)(*args).jaxpr.eqns
            if e.primitive.name == "pallas_call"]


# (Hq, Hkv, slots, table width, layers, pool pages) of each decode read;
# the fan-out cell's edge engine reads 64 slots of a 28-layer pool of
# 64 x 2048 tokens, at a live width of 32 pages
DECODE_READS = {m: (*HEADS[m], BATCH, TABLE_W, LAYERS, N_PAGES)
                for m in HEADS}
DECODE_READS["qwen2-1.5b-fanout"] = (12, 2, 64, 32, 28, 4096)


@pytest.mark.parametrize("pool", sorted(POOL_DTYPES))
@pytest.mark.parametrize("model", sorted(DECODE_READS))
def test_paged_decode_attention_compiles_for_v5e(spec, model, pool):
    """The GQA decode kernel lowers for a v5e, streaming G = DECODE_PAGES
    pages a grid step at these widths: the page axis of its grid is the
    table width over G."""
    hq, hkv, batch, width, layers, n_pages = DECODE_READS[model]
    q = spec((batch, 1, hq, HD), jnp.bfloat16)
    pages = spec((layers, n_pages, hkv, PAGE, HD), POOL_DTYPES[pool])
    table = spec((batch, width), jnp.int32)
    lens = spec((batch,), jnp.int32)
    layer = spec((), jnp.int32)
    if pool == "bf16":
        fn = functools.partial(pda_kernel.paged_decode_attention_pallas,
                               interpret=False)
        args = (q, pages, pages, table, lens, layer)
    else:
        scales = spec((layers, n_pages, hkv), jnp.float32)
        fn = functools.partial(
            pda_kernel.paged_decode_attention_quant_pallas, interpret=False)
        args = (q, pages, pages, scales, scales, table, lens, layer)
    G = pda_kernel.decode_pages(width, latent=False)
    assert G == pda_kernel.DECODE_PAGES > 1 and width % G == 0
    assert _pallas_grids(fn, *args) == [(batch, hkv, width // G)]
    assert "tpu_custom_call" in _compiled_text(fn, *args)


@pytest.mark.parametrize("pool", sorted(POOL_DTYPES))
@pytest.mark.parametrize("model", sorted(HEADS))
def test_paged_prefill_attention_ragged_compiles_for_v5e(spec, model, pool):
    hq, hkv = HEADS[model]
    q = spec((ROWS, CHUNK, hq, HD), jnp.bfloat16)
    pages = spec((LAYERS, N_PAGES, hkv, PAGE, HD), POOL_DTYPES[pool])
    rows = spec((ROWS, TABLE_W), jnp.int32)
    offs = spec((ROWS,), jnp.int32)
    layer = spec((), jnp.int32)
    if pool == "bf16":
        text = _compiled_text(
            lambda *a: ppa_kernel.paged_prefill_attention_ragged_pallas(
                *a, interpret=False), q, pages, pages, rows, offs, offs,
            layer)
    else:
        scales = spec((LAYERS, N_PAGES, hkv), jnp.float32)
        text = _compiled_text(
            lambda *a: ppa_kernel.paged_prefill_attention_ragged_quant_pallas(
                *a, interpret=False),
            q, pages, pages, scales, scales, rows, offs, offs, layer)
    assert "tpu_custom_call" in text


# deepseek-v2-lite: 16 query heads on one latent row of 512 + 64 lanes
# padded to 640; 8 held experts of width 1408 at d_model 2048
MLA_H, MLA_W, MLA_RANK = 16, 640, 512


def test_latent_paged_kernels_compile_for_v5e(spec):
    pages = spec((LAYERS, N_PAGES, 1, PAGE, MLA_W), jnp.bfloat16)
    lens = spec((BATCH,), jnp.int32)
    layer = spec((), jnp.int32)
    text = _compiled_text(
        lambda *a: pda_kernel.mla_paged_decode_pallas(
            *a, rank=MLA_RANK, scale=0.1147, interpret=False),
        spec((BATCH, 1, MLA_H, MLA_W), jnp.bfloat16), pages,
        spec((BATCH, TABLE_W), jnp.int32), lens, layer)
    assert "tpu_custom_call" in text and "mla_paged_decode" in text
    offs = spec((ROWS,), jnp.int32)
    text = _compiled_text(
        lambda *a: ppa_kernel.mla_paged_prefill_pallas(
            *a, rank=MLA_RANK, scale=0.1147, interpret=False),
        spec((ROWS, CHUNK, MLA_H, MLA_W), jnp.bfloat16), pages,
        spec((ROWS, TABLE_W), jnp.int32), offs, offs, layer)
    assert "tpu_custom_call" in text and "mla_paged_prefill" in text


@pytest.mark.parametrize("rows", [288, 6144])
def test_grouped_expert_matmul_compiles_for_v5e(spec, rows):
    """A decode step's 48 x 6 and a 1024-token ingest's assignment rows,
    through the gate and down projections of 8 held experts."""
    sizes = spec((8,), jnp.int32)
    for k, n in ((2048, 1408), (1408, 2048)):
        text = _compiled_text(
            lambda *a: gmm_kernel.moe_grouped_matmul_pallas(
                *a, interpret=False),
            spec((rows, k), jnp.bfloat16), spec((8, k, n), jnp.bfloat16),
            sizes)
        assert "tpu_custom_call" in text and "moe_grouped_matmul" in text


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


def _computations(text):
    """HLO text -> {computation name: its instruction lines}."""
    comps, name = {}, None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?(%[\w.-]+) .*\{$", line)
        if head:
            name = head.group(1)
            comps[name] = []
        elif name is not None and line.startswith("  "):
            comps[name].append(line.strip())
    return comps


# what may hold a pool-sized value: the loop carry moving through the
# program, free views of it, and the in-place scatter of the new tokens
CARRY_OPS = {"parameter", "get-tuple-element", "tuple", "while", "bitcast"}


def _pool_sized(text, dims):
    """Instructions outside fusion bodies whose array result has the pool's
    trailing dims `dims` (one layer's pool, or the stack), other than the
    carry's ops and scatters (a fusion counts as one when its body's root
    is a scatter)."""
    comps = _computations(text)
    fused = set(re.findall(r"calls=(%[\w.-]+)", text))
    bad = []
    for name, lines in comps.items():
        if name in fused:
            continue
        for line in lines:
            m = re.match(r"(?:ROOT )?%[\w.-]+ = \w+\[([\d,]*)\]\S* "
                         r"([\w-]+)\(", line)
            if m is None or not m.group(1).endswith(dims):
                continue
            op = m.group(2)
            if op in CARRY_OPS or op == "scatter":
                continue
            body = re.search(r"calls=(%[\w.-]+)", line)
            if op == "fusion" and body and any(
                    ln.startswith("ROOT") and " scatter(" in ln
                    for ln in comps.get(body.group(1), [])):
                continue
            bad.append(line[:200])
    return bad


@pytest.mark.parametrize("step", ["decode", "ingest"])
def test_served_edge_steps_keep_the_kv_pool_in_place(spec, monkeypatch,
                                                     step):
    """The fan-out cell's edge engine (qwen2-1.5b, 64 slots x 2048 tokens,
    page 32: two 1.88 GB pools): its decode step and its ragged ingest
    step carry the stacked pools through the layer loop and scatter each
    layer's new tokens in place. Compiled for a v5e, neither holds more
    than 1% of the pools' bytes as scratch, and no instruction other than
    the carry and its scatter has a pool-sized shape: no per-layer slice,
    write-back or relayout copy of a pool."""
    from repro.configs.pice_cloud_edge import PAIRINGS
    from repro.kernels import runtime
    from repro.models import transformer
    from repro.serving import engine as engine_lib
    from repro.serving.sampler import SamplerConfig
    monkeypatch.setattr(runtime, "default_interpret", lambda: False)
    cfg = next(iter(PAIRINGS["one-chip"].edges.values())).cfg
    B, L, live = 64, 2048, 32

    def place(tree):
        return jax.tree.map(lambda a: spec(a.shape, a.dtype), tree)
    params = place(jax.eval_shape(
        lambda: transformer.init_params(cfg, jax.random.PRNGKey(0))))
    cache = place(transformer.init_paged_cache(cfg, B, B * L // PAGE, PAGE,
                                               L // PAGE, spec=True))
    i32 = jnp.int32
    if step == "decode":
        fn = engine_lib._jitted(cfg, "decode_paged_run", SamplerConfig())
        args = (spec((B, 1), i32), cache, spec((B,), jnp.bool_),
                spec((2,), jnp.uint32))
    else:
        fn = engine_lib._jitted(cfg, "prefill_ragged")
        args = (spec((ROWS, CHUNK), i32), cache, spec((ROWS,), i32),
                spec((ROWS,), i32), spec((ROWS,), i32))
    compiled = fn.lower(live, params, *args).compile()
    pools = [seg[k] for seg in cache["segments"] for k in seg]
    pool_bytes = sum(a.size * a.dtype.itemsize for a in pools)
    assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes / 100
    dims = ",".join(map(str, pools[0].shape[1:]))
    assert _pool_sized(compiled.as_text(), dims) == []


@pytest.mark.parametrize("step", ["decode", "ingest"])
def test_served_expert_steps_read_the_expert_stack_in_place(spec,
                                                            monkeypatch,
                                                            step):
    """The DeepSeek-V2-Lite share (rank 0 of 8-way expert parallelism: 8
    held experts of width 1408 at d_model 2048), cut to 4 layers so three
    expert layers share the layer loop, at the cell's 48 x 4096 latent
    pool, page 32, 128-token chunks. Compiled for a v5e, its decode and
    ragged ingest steps hand the grouped matmul the segment's whole stack
    of held-expert weights and the layer: no instruction outside a fusion
    body, other than the carry, holds one layer's experts (a per-layer
    slice of the stack, 46 MB a projection)."""
    from repro.configs.registry import get_config
    from repro.kernels import runtime
    from repro.models import transformer
    from repro.serving import engine as engine_lib
    from repro.serving.sampler import SamplerConfig
    monkeypatch.setattr(runtime, "default_interpret", lambda: False)
    cfg = get_config("deepseek-v2-lite").with_(
        n_layers=4, ep_size=8, ep_rank=0, param_dtype="bfloat16",
        use_pallas=True, prefill_chunk=CHUNK)
    B, L = 48, 4096

    def place(tree):
        return jax.tree.map(lambda a: spec(a.shape, a.dtype), tree)
    params = place(jax.eval_shape(
        lambda: transformer.init_params(cfg, jax.random.PRNGKey(0))))
    cache = place(transformer.init_paged_cache(cfg, B, B * L // PAGE, PAGE,
                                               L // PAGE, spec=True))
    i32 = jnp.int32
    if step == "decode":
        fn = engine_lib._jitted(cfg, "decode_paged_run", SamplerConfig())
        args = (spec((B, 1), i32), cache, spec((B,), jnp.bool_),
                spec((2,), jnp.uint32))
    else:
        fn = engine_lib._jitted(cfg, "prefill_ragged")
        args = (spec((ROWS, CHUNK), i32), cache, spec((ROWS,), i32),
                spec((ROWS,), i32), spec((ROWS,), i32))
    text = fn.lower(L // PAGE, params, *args).compile().as_text()
    assert "moe_grouped_matmul" in text
    for dims in ("8,2048,1408", "8,1408,2048"):
        assert _pool_sized(text, dims) == []
