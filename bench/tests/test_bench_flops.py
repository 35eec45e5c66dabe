"""The operation and byte functions against hand arithmetic at qwen2-1.5b
widths (d_model 1536, 12 query / 2 KV heads of 128, d_ff 8960, vocab
151936, 28 layers, bf16 pool, page 32)."""
from bench import flops
from bench.peaks import peaks_for

Q2 = dict(n_layers=28, d_model=1536, n_heads=12, n_kv_heads=2, head_dim=128,
          d_ff=8960, vocab=151936, page=32, kv_itemsize=2, act_itemsize=2,
          max_batch=64)


def test_matmul_params_of_qwen2_1p5b():
    per_layer = (1536 * 12 * 128 + 2 * 1536 * 2 * 128 + 12 * 128 * 1536
                 + 3 * 1536 * 8960)
    assert per_layer == 46_792_704   # x 28 + embedding = 1.54 B (published)
    assert flops.matmul_params(Q2) == 28 * 46_792_704 + 1536 * 151936


def test_decode_call_counts_pages_and_pairs():
    f, b = flops.decode_call(Q2, [1, 32, 33])
    assert f == 4 * 12 * 128 * (1 + 32 + 33)
    # 1 + 1 + 2 pages of 32 tokens x 2 KV heads x 128 x (K and V) x 2 B,
    # plus q in and out: 3 rows x 12 heads x 128 x 2 x 2 B
    assert b == 4 * 32 * 2 * 128 * 2 * 2 + 3 * 12 * 128 * 2 * 2


def test_prefill_call_counts_causal_pairs():
    f, b = flops.prefill_call(Q2, [(0, 128), (100, 28)])
    pairs = 128 * 129 // 2 + (28 * 100 + 28 * 29 // 2)
    assert f == 4 * 12 * 128 * pairs
    pages = 4 + 4          # 128 tokens, then 128 tokens (100 + 28)
    assert b == pages * 32 * 2 * 128 * 2 * 2 + (128 + 28) * 12 * 128 * 2 * 2


def test_least_seconds_picks_the_binding_roof():
    p = peaks_for("TPU v5 lite")
    t, bound = flops.least_seconds(819e9, 819e9, p)
    assert bound == "memory" and abs(t - 1.0) < 1e-12
    t, bound = flops.least_seconds(197e12 * 2, 1.0, p)
    assert bound == "compute" and abs(t - 2.0) < 1e-12


def test_model_flops_decode_and_prefill():
    d = flops.model_flops_decode(Q2, [10])
    assert d == 2 * flops.matmul_params(Q2) + 28 * 4 * 12 * 128 * 10
    p = flops.model_flops_prefill(Q2, [(0, 4)])
    body = 2 * (flops.matmul_params(Q2) - 1536 * 151936)
    assert p == 4 * body + 2 * 1536 * 151936 + 28 * 4 * 12 * 128 * 10


def test_unknown_device_kind_is_an_error():
    import pytest
    with pytest.raises(KeyError):
        peaks_for("cpu")
