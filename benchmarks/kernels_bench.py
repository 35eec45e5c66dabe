"""Kernel microbenchmarks: Pallas (interpret) vs jnp reference.

NOTE: interpret-mode wall time on CPU says nothing about TPU performance —
the derived column carries the structural numbers that matter (FLOPs, bytes,
arithmetic intensity); wall time is reported only to satisfy the CSV
contract and catch pathological regressions.

  PYTHONPATH=src python -m benchmarks.kernels_bench [--paged-smoke]

--paged-smoke runs only the paged decode A/B at tiny sizes (CI: parity +
the per-step KV read-volume accounting must not regress)."""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, timed


def paged_decode_case(smoke: bool = False):
    """Paged decode read path A/B: Pallas block-table streaming kernel vs
    the gather oracle (full table width) vs the live-trimmed gather the
    engine's fallback now uses.

    Lengths are skewed (one near-max straggler, short rest), which is where
    the gather pays for `max_pages_per_seq` on every slot: its per-step KV
    read volume is O(B * max_pages * page), the kernel's is O(sum
    ceil(len/page) * page). The emitted kv_bytes column carries exactly
    that accounting."""
    from repro.kernels.paged_decode_attention import ops as pda
    from repro.kernels.paged_decode_attention import ref as pda_ref

    key = jax.random.PRNGKey(3)
    if smoke:
        B, Hq, Hkv, hd, page, P = 4, 8, 2, 32, 8, 8
    else:
        B, Hq, Hkv, hd, page, P = 8, 8, 2, 64, 32, 64
    n_pages = B * P
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, 1, Hq, hd), jnp.float32)
    # one layer of the stacked head-major pool the engine carries
    pages_k = jax.random.normal(ks[1], (1, n_pages, Hkv, page, hd),
                                jnp.float32)
    pages_v = jax.random.normal(ks[2], (1, n_pages, Hkv, page, hd),
                                jnp.float32)
    # length-skewed batch: one straggler at half the table width, the rest
    # short — so all three read strategies differ: the trim drops the
    # columns NO slot uses, the kernel additionally skips per-row dead width
    max_tok = P * page
    lens_np = np.full((B,), max(page // 2, 1), np.int64)
    lens_np[0] = max_tok // 2 - page // 2
    lens = jnp.asarray(lens_np, jnp.int32)
    table_np = np.full((B, P), -1, np.int64)
    nxt = 0
    for b in range(B):
        live = -(-int(lens_np[b]) // page)
        table_np[b, :live] = np.arange(nxt, nxt + live)
        nxt += live
    table = jnp.asarray(table_np, jnp.int32)
    live_w = max(1, -(-int(lens_np.max()) // page))

    tok_bytes = 2 * Hkv * hd * 4                      # K+V, f32
    bytes_gather = B * P * page * tok_bytes           # full table width
    bytes_trim = B * live_w * page * tok_bytes        # live-trimmed gather
    pages_live = sum(-(-int(l) // page) for l in lens_np)
    bytes_kernel = pages_live * page * tok_bytes      # only mapped pages

    out_k, us_k = timed(lambda: jax.block_until_ready(
        pda.paged_decode_attention(q, pages_k, pages_v, table, lens)))
    out_k, us_k = timed(lambda: jax.block_until_ready(
        pda.paged_decode_attention(q, pages_k, pages_v, table, lens)))
    emit("kernels/paged_decode_pallas", us_k,
         f"kv_bytes={bytes_kernel:.2e};page={page};pages_read={pages_live}")

    oracle = jax.jit(pda_ref.paged_decode_attention_ref)
    out_o, _ = timed(lambda: jax.block_until_ready(
        oracle(q, pages_k, pages_v, table, lens)))
    out_o, us_o = timed(lambda: jax.block_until_ready(
        oracle(q, pages_k, pages_v, table, lens)))
    emit("kernels/paged_decode_gather_oracle", us_o,
         f"kv_bytes={bytes_gather:.2e};page={page};pages_read={B * P}")

    trimmed = jax.jit(pda_ref.paged_decode_attention_ref)
    tt = table[:, :live_w]
    _, _ = timed(lambda: jax.block_until_ready(
        trimmed(q, pages_k, pages_v, tt, lens)))
    out_t, us_t = timed(lambda: jax.block_until_ready(
        trimmed(q, pages_k, pages_v, tt, lens)))
    emit("kernels/paged_decode_gather_trimmed", us_t,
         f"kv_bytes={bytes_trim:.2e};page={page};pages_read={B * live_w}")

    # regression guards: the kernel must match the oracle, and the
    # trimmed read must actually shrink the per-step volume
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_o),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(out_t), np.asarray(out_o),
                               rtol=2e-5, atol=2e-5)
    assert bytes_kernel < bytes_trim <= bytes_gather
    print(f"# paged decode: kernel reads {pages_live} pages/step "
          f"({bytes_kernel / bytes_gather:.0%} of the gather's {B * P}); "
          f"gather full={us_o:.0f}us trimmed={us_t:.0f}us "
          f"(x{us_o / max(us_t, 1e-9):.2f} at this length skew)")


def run():
    key = jax.random.PRNGKey(0)

    # flash attention: prefill tile
    from repro.kernels.flash_attention import ops as fa, ref as fa_ref
    B, S, H, hd = 1, 512, 4, 64
    q = jax.random.normal(key, (B, S, H, hd), jnp.float32)
    k = jax.random.normal(key, (B, S, H, hd), jnp.float32)
    v = jax.random.normal(key, (B, S, H, hd), jnp.float32)
    flops = 4 * B * H * S * S * hd
    _, us = timed(lambda: jax.block_until_ready(
        fa.flash_attention(q, k, v, block_q=128, block_kv=128)))
    emit("kernels/flash_attention_pallas", us, f"flops={flops:.2e}")
    _, us = timed(lambda: jax.block_until_ready(fa_ref.mha_ref(q, k, v)))
    emit("kernels/flash_attention_ref", us, f"flops={flops:.2e}")

    # decode attention: the PICE hotspot (KV streaming)
    from repro.kernels.decode_attention import ops as da, ref as da_ref
    B, S, Hq, Hkv, hd = 4, 4096, 8, 2, 64
    q1 = jax.random.normal(key, (B, 1, Hq, hd), jnp.float32)
    kc = jax.random.normal(key, (B, S, Hkv, hd), jnp.float32)
    vc = jax.random.normal(key, (B, S, Hkv, hd), jnp.float32)
    lens = jnp.full((B,), S, jnp.int32)
    bytes_ = 2 * B * S * Hkv * hd * 4
    _, us = timed(lambda: jax.block_until_ready(
        da.decode_attention(q1, kc, vc, lens, block_s=512)))
    emit("kernels/decode_attention_pallas", us,
         f"kv_bytes={bytes_:.2e};ai={4*Hq*hd/(2*Hkv*hd*4):.2f}flops_per_byte")
    _, us = timed(lambda: jax.block_until_ready(
        da_ref.decode_attention_ref(q1, kc, vc, lens)))
    emit("kernels/decode_attention_ref", us, f"kv_bytes={bytes_:.2e}")

    # paged decode read path: Pallas block-table streaming kernel vs the
    # gather oracle (full and live-trimmed widths) at skewed lengths
    paged_decode_case()

    # rmsnorm
    from repro.kernels.rmsnorm import ops as rn, ref as rn_ref
    x = jax.random.normal(key, (4096, 1024), jnp.bfloat16)
    s = jax.random.normal(key, (1024,))
    _, us = timed(lambda: jax.block_until_ready(rn.rmsnorm(x, s)))
    emit("kernels/rmsnorm_pallas", us, f"bytes={x.size*2*2:.2e}")
    _, us = timed(lambda: jax.block_until_ready(rn_ref.rmsnorm_ref(x, s)))
    emit("kernels/rmsnorm_ref", us, f"bytes={x.size*2*2:.2e}")

    # ssd scan
    from repro.kernels.ssm_scan import ops as ssm, ref as ssm_ref
    Bb, S, H, P, N = 2, 1024, 4, 64, 64
    x = jax.random.normal(key, (Bb, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(key, (Bb, S, H))) * 0.1
    A = -jnp.exp(jax.random.normal(key, (H,)))
    Bm = jax.random.normal(key, (Bb, S, N)) * 0.3
    Cm = jax.random.normal(key, (Bb, S, N)) * 0.3
    flops = 2 * Bb * S * H * P * N * 3
    _, us = timed(lambda: jax.block_until_ready(
        ssm.ssm_scan(x, dt, A, Bm, Cm, chunk=128)[0]))
    emit("kernels/ssm_scan_pallas", us, f"flops={flops:.2e}")
    _, us = timed(lambda: jax.block_until_ready(
        ssm_ref.ssd_chunked_ref(x, dt, A, Bm, Cm, chunk=128)[0]))
    emit("kernels/ssm_scan_ref", us, f"flops={flops:.2e}")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--paged-smoke", action="store_true",
                    help="only the paged decode A/B at tiny sizes (CI)")
    if ap.parse_args().paged_smoke:
        paged_decode_case(smoke=True)
    else:
        run()
