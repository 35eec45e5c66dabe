"""Paged flash-decode kernel (kernels/paged_decode_attention) vs the
gather oracle: parity across page sizes, ragged lengths (including a
length-0 slot), unmapped tail pages, COW-forked block tables, and the
live-width trim + use_pallas wiring in models/attention."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.paged_decode_attention import ops as pda_ops
from repro.kernels.paged_decode_attention import ref as pda_ref
from repro.models import attention as attn_lib
from repro.models.config import ModelConfig


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else \
        dict(rtol=2e-5, atol=2e-5)


def _pools(key, n_pages, page, Hkv, hd, dtype):
    k1, k2 = jax.random.split(key)
    return (jax.random.normal(k1, (n_pages, page, Hkv, hd), dtype),
            jax.random.normal(k2, (n_pages, page, Hkv, hd), dtype))


def _chained_table(lens, page, P, start=0):
    """Disjoint page chains covering each row's length; tail stays -1."""
    tbl = np.full((len(lens), P), -1, np.int64)
    nxt = start
    for b, ln in enumerate(lens):
        live = -(-int(ln) // page)
        tbl[b, :live] = np.arange(nxt, nxt + live)
        nxt += live
    return jnp.asarray(tbl, jnp.int32)


# ---------------------------------------------------------------------------
# kernel vs gather oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("page", [8, 32])
@pytest.mark.parametrize("B,Hq,Hkv,hd,P", [
    (3, 8, 2, 32, 6),
    (2, 4, 4, 64, 4),
    # the wide-head case adds compile wall time, not coverage, on CPU
    pytest.param(2, 16, 4, 128, 3, marks=pytest.mark.slow),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_decode_parity(page, B, Hq, Hkv, hd, P, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, 1, Hq, hd), dtype)
    kp, vp = _pools(ks[1], B * P + 2, page, Hkv, hd, dtype)
    # ragged: always include a length-0 slot and a mid-page partial length
    lens = np.array(jax.random.randint(ks[2], (B,), 1, P * page + 1))
    lens[0] = 0
    lens[-1] = page + page // 2 if P > 1 else page // 2
    table = _chained_table(lens, page, P)
    lens = jnp.asarray(lens, jnp.int32)
    out = pda_ops.paged_decode_attention(q, kp, vp, table, lens)
    ref = pda_ref.paged_decode_attention_ref(q, kp, vp, table, lens)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))
    assert not np.any(np.isnan(np.asarray(out, np.float32)))
    np.testing.assert_array_equal(np.asarray(out[0], np.float32), 0.0)


def test_paged_decode_unmapped_tail_pages():
    """Garbage in unmapped (-1) and past-length pages must not leak."""
    B, Hq, Hkv, hd, page, P = 2, 4, 2, 32, 8, 5
    ks = jax.random.split(jax.random.PRNGKey(1), 2)
    q = jax.random.normal(ks[0], (B, 1, Hq, hd))
    kp, vp = _pools(ks[1], B * P, page, Hkv, hd, jnp.float32)
    lens = jnp.array([12, 30], jnp.int32)
    table = _chained_table(np.asarray(lens), page, P)
    out1 = pda_ops.paged_decode_attention(q, kp, vp, table, lens)
    # poison every page no row reads through its chain, and the in-page
    # tail beyond each row's length
    used = set(int(p) for p in np.asarray(table).ravel() if p >= 0)
    kp2, vp2 = np.array(kp), np.array(vp)
    for pg in range(kp2.shape[0]):
        if pg not in used:
            kp2[pg], vp2[pg] = 999.0, -999.0
    kp2[1, 12 % page:], vp2[1, 12 % page:] = 999.0, -999.0   # row 0 tail
    out2 = pda_ops.paged_decode_attention(q, jnp.asarray(kp2),
                                          jnp.asarray(vp2), table, lens)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2), atol=1e-6)


def test_paged_decode_cow_forked_table():
    """Two slots whose tables share prefix pages (COW fan-out) must each
    read the shared pages correctly — parity vs the oracle AND vs an
    unshared copy of the same logical layout."""
    Hq, Hkv, hd, page, P = 8, 2, 32, 8, 4
    ks = jax.random.split(jax.random.PRNGKey(2), 2)
    q = jax.random.normal(ks[0], (2, 1, Hq, hd))
    kp, vp = _pools(ks[1], 12, page, Hkv, hd, jnp.float32)
    # rows share pages [0,1] (the prefix), then diverge on private tails
    table = jnp.asarray([[0, 1, 2, -1], [0, 1, 3, 4]], jnp.int32)
    lens = jnp.array([20, 28], jnp.int32)
    out = pda_ops.paged_decode_attention(q, kp, vp, table, lens)
    ref = pda_ref.paged_decode_attention_ref(q, kp, vp, table, lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    # unshared equivalent: copy the shared prefix pages to fresh ids
    kp2 = kp.at[6].set(kp[0]).at[7].set(kp[1])
    vp2 = vp.at[6].set(vp[0]).at[7].set(vp[1])
    t2 = jnp.asarray([[0, 1, 2, -1], [6, 7, 3, 4]], jnp.int32)
    out2 = pda_ops.paged_decode_attention(q, kp2, vp2, t2, lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out2), atol=1e-6)


def test_paged_decode_trimmed_table_identical():
    """Reading through a live-trimmed table is exactly the full-width read
    (trimmed columns carry zero attention weight)."""
    B, Hq, Hkv, hd, page, P = 3, 4, 2, 32, 8, 6
    ks = jax.random.split(jax.random.PRNGKey(3), 2)
    q = jax.random.normal(ks[0], (B, 1, Hq, hd))
    kp, vp = _pools(ks[1], B * P, page, Hkv, hd, jnp.float32)
    lens_np = np.array([5, 16, 9])
    table = _chained_table(lens_np, page, P)
    lens = jnp.asarray(lens_np, jnp.int32)
    live = max(1, -(-int(lens_np.max()) // page))
    full = pda_ops.paged_decode_attention(q, kp, vp, table, lens)
    trim = pda_ops.paged_decode_attention(q, kp, vp, table[:, :live], lens)
    np.testing.assert_array_equal(np.asarray(full), np.asarray(trim))
    # same for the oracle read path
    rfull = pda_ref.paged_decode_attention_ref(q, kp, vp, table, lens)
    rtrim = pda_ref.paged_decode_attention_ref(q, kp, vp, table[:, :live],
                                               lens)
    np.testing.assert_array_equal(np.asarray(rfull), np.asarray(rtrim))


# ---------------------------------------------------------------------------
# wiring: attention_decode_paged keyed on use_pallas
# ---------------------------------------------------------------------------

def _paged_attn_setup(use_pallas, seed=4):
    cfg = ModelConfig(n_layers=1, d_model=128, n_heads=4, n_kv_heads=2,
                      head_dim=32, d_ff=256, vocab_size=64,
                      dtype="float32", use_pallas=use_pallas)
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    params = attn_lib.init_attention(cfg, ks[0])
    return cfg, params, ks


@pytest.mark.parametrize("live_pages", [None, 4])
def test_attention_decode_paged_kernel_matches_oracle(live_pages):
    """cfg.use_pallas routes the paged decode read through the kernel;
    outputs match the gather oracle within the dense decode kernel's
    tolerance, at full and trimmed read widths."""
    B, page, P, n_pages = 2, 8, 6, 16
    cfg, params, ks = _paged_attn_setup(False)
    hd, Hkv = cfg.resolved_head_dim, cfg.n_kv_heads
    x = jax.random.normal(ks[1], (B, 1, cfg.d_model), jnp.float32)
    kp, vp = _pools(ks[2], n_pages, page, Hkv, hd, jnp.float32)
    lens_np = np.array([13, 25])
    table = _chained_table(lens_np, page, P, start=1)
    lens = jnp.asarray(lens_np, jnp.int32)

    out_ref, kr, vr, _, _ = attn_lib.attention_decode_paged(
        cfg, params, x, kp, vp, table, lens, live_pages=live_pages)
    out_pal, kk, vk, _, _ = attn_lib.attention_decode_paged(
        cfg.with_(use_pallas=True), params, x, kp, vp, table, lens,
        live_pages=live_pages)
    np.testing.assert_allclose(np.asarray(out_pal), np.asarray(out_ref),
                               rtol=2e-5, atol=2e-5)
    # both paths write the token identically
    np.testing.assert_array_equal(np.asarray(kr), np.asarray(kk))
    np.testing.assert_array_equal(np.asarray(vr), np.asarray(vk))


def test_attention_decode_paged_trim_bit_identical():
    """The default (oracle) read path must stay bit-identical under the
    live-width trim — this is what keeps the engine's dense<->paged
    equivalence suite exact."""
    B, page, P, n_pages = 2, 8, 8, 20
    cfg, params, ks = _paged_attn_setup(False, seed=5)
    hd, Hkv = cfg.resolved_head_dim, cfg.n_kv_heads
    x = jax.random.normal(ks[1], (B, 1, cfg.d_model), jnp.float32)
    kp, vp = _pools(ks[2], n_pages, page, Hkv, hd, jnp.float32)
    lens_np = np.array([9, 21])
    table = _chained_table(lens_np, page, P)
    lens = jnp.asarray(lens_np, jnp.int32)
    full, _, _, _, _ = attn_lib.attention_decode_paged(cfg, params, x, kp, vp,
                                                       table, lens)
    live = -(-int(lens_np.max() + 1) // page)
    trim, _, _, _, _ = attn_lib.attention_decode_paged(cfg, params, x, kp, vp,
                                                       table, lens,
                                                       live_pages=live)
    np.testing.assert_array_equal(np.asarray(full), np.asarray(trim))


def test_attention_paged_refuses_a_config_the_kernels_cannot_serve():
    """use_pallas on a softcapped config is an error at trace time, never
    a silent per-call fallback to the oracle."""
    B, page, P, n_pages = 2, 8, 4, 8
    cfg, params, ks = _paged_attn_setup(True)
    cfg = cfg.with_(attn_logit_softcap=30.0)
    x = jax.random.normal(ks[1], (B, 1, cfg.d_model), jnp.float32)
    kp, vp = _pools(ks[2], n_pages, page, cfg.n_kv_heads,
                    cfg.resolved_head_dim, jnp.float32)
    table = _chained_table(np.array([5, 9]), page, P)
    with pytest.raises(ValueError, match="softcap"):
        attn_lib.attention_decode_paged(cfg, params, x, kp, vp, table,
                                        jnp.asarray([5, 9], jnp.int32))


@pytest.mark.parametrize("override,path", [
    (dict(), "pallas"),
    (dict(attn_logit_softcap=30.0), "oracle"),
    (dict(use_pallas=False), "oracle"),
])
def test_engine_picks_its_read_path_at_construction(override, path):
    """The engine decides Pallas vs oracle once and says why it did not
    take the kernels when the config asked for them."""
    from repro.serving.engine import InferenceEngine
    cfg, _, _ = _paged_attn_setup(True)
    cfg = cfg.with_(**override)
    if path == "oracle" and cfg.use_pallas:
        with pytest.warns(UserWarning, match="softcap"):
            eng = InferenceEngine(cfg, None, max_batch=2, max_len=64,
                                  kv_backend="paged", page_size=8)
        assert "softcap" in eng.read_path_note
    else:
        eng = InferenceEngine(cfg, None, max_batch=2, max_len=64,
                              kv_backend="paged", page_size=8)
        assert eng.read_path_note == ""
    assert eng.read_path == path
    assert eng.cfg.use_pallas == (path == "pallas")


@pytest.mark.parametrize("kv_backend,path", [("paged", "oracle"),
                                             ("dense", "pallas")])
def test_head_dim_rule_binds_only_the_paged_read_path(monkeypatch,
                                                       kv_backend, path):
    """On a TPU (compiled Pallas) a head_dim off the 128-lane tile rules out
    the paged kernels' (page, head_dim) blocks, but not the dense decode
    kernel, so a dense engine keeps its kernels."""
    from repro.kernels import runtime
    from repro.serving.engine import InferenceEngine
    monkeypatch.setattr(runtime, "default_interpret", lambda: False)
    cfg, _, _ = _paged_attn_setup(True)          # head_dim 32
    if path == "oracle":
        with pytest.warns(UserWarning, match="head_dim 32"):
            eng = InferenceEngine(cfg, None, max_batch=2, max_len=64,
                                  kv_backend=kv_backend, page_size=8)
    else:
        eng = InferenceEngine(cfg, None, max_batch=2, max_len=64,
                              kv_backend=kv_backend, page_size=8)
        assert eng.read_path_note == ""
    assert eng.read_path == path


def test_validate_paged_alignment():
    cfg = ModelConfig()
    cfg.validate_paged(16, 256)
    with pytest.raises(AssertionError):
        cfg.validate_paged(24, 100)          # max_len not page-aligned
    with pytest.raises(AssertionError):
        cfg.with_(use_pallas=True).validate_paged(12, 240)  # sublane align
    cfg.with_(use_pallas=True).validate_paged(16, 256)
