"""PICE's own serving configuration: the cloud LLM + edge SLM fleet pairing.

The paper's testbed pairs Qwen2.5-72B/Llama3-70B on a cloud A100 server with
<8B SLMs on Jetson edge devices, recommending LLM >= 10x SLM. Full-size
configs reference the assigned archs (qwen3-8b cloud, qwen2-1.5b/xlstm/zamba2
edge ensemble = 5.3-8x parameter gap, the closest available pairing). TINY_*
variants are runnable-on-CPU models used by the examples and the real-compute
serving benchmarks; they keep the >=10x size ratio the paper recommends.

`PAIRINGS` names the fleets `launch/serve.py` can build: "tiny" (the CPU
default) and "one-chip" (published widths on one TPU v5e chip).
"""
import dataclasses
from typing import Dict

from repro.configs.registry import get_config
from repro.models.config import ModelConfig


def cloud_config() -> ModelConfig:
    return get_config("qwen3-8b").with_(length_buckets=16)


def edge_configs() -> dict:
    return {
        "qwen2-1.5b": get_config("qwen2-1.5b"),
        "xlstm-1.3b": get_config("xlstm-1.3b"),
        "zamba2-2.7b": get_config("zamba2-2.7b"),
    }


# ---------------------------------------------------------------------------
# One TPU v5e chip (16 GB HBM) at published widths.
#
# Cloud: qwen3-8b (d_model 4096, 32/8 heads, head_dim 128, d_ff 12288, vocab
# 151936, qk_norm) holds 8 of its 36 published layers. Whole it is ~8.19 B
# params (16.4 GB in bf16) and cannot share a chip with anything; each layer
# is ~193 M params (0.39 GB), the embedding and head 2.5 GB. The 28 layers
# left out would sit on further chips as pipeline stages; this chip holds the
# first stage plus the embedding and head, ~2.79 B params (5.6 GB).
# Edge: qwen2-1.5b whole (28 layers, input embedding tied to the output
# head as published: ~1.54 B params, 3.09 GB), twice, from different seeds,
# so the ensemble has two members. Weights ~11.75 GB in all; the paged KV
# pools (8 slots x 1024 tokens each; 28 KiB/token for qwen2-1.5b, 4 KiB per
# layer per token for qwen3-8b) add ~0.73 GB.
# Serving settings: bf16 params, Pallas kernels, page 32 (the engine's
# default), and 128-token chunked prefill so the ragged ingest kernel is on
# the path.
# ---------------------------------------------------------------------------

ONE_CHIP_SERVING = dict(param_dtype="bfloat16", use_pallas=True,
                        prefill_chunk=128)
# `name` stays the registered one, so get_config(cfg.name) is the published
# model this cut comes from
ONE_CHIP_CLOUD = cloud_config().with_(
    n_layers=8, **ONE_CHIP_SERVING,
    source="hf:Qwen/Qwen3-8B; reduced: n_layers 36 -> 8 (first pipeline "
           "stage of a 36-layer deployment; the rest would sit on further "
           "chips); random weights")
ONE_CHIP_EDGE = get_config("qwen2-1.5b").with_(
    tie_embeddings=True, **ONE_CHIP_SERVING,
    source="hf:Qwen/Qwen2-1.5B (tie_word_embeddings=true); whole; random "
           "weights")


@dataclasses.dataclass(frozen=True)
class FleetMember:
    """One engine of a served fleet."""
    cfg: ModelConfig
    capability: float      # quality proxy in (0, 1) the scheduler ranks by
    seed: int = 0          # added to the launch seed for this member's weights


@dataclasses.dataclass(frozen=True)
class Pairing:
    """A cloud model plus its edge fleet, keyed by engine name."""
    cloud: str
    members: Dict[str, FleetMember]

    @property
    def edges(self) -> Dict[str, FleetMember]:
        return {k: m for k, m in self.members.items() if k != self.cloud}


# ---------------------------------------------------------------------------
# Tiny (CPU-runnable) variants — same families, >=10x cloud/edge param ratio.
# ---------------------------------------------------------------------------

TINY_CLOUD = ModelConfig(
    name="tiny-cloud",
    family="dense",
    n_layers=6,
    d_model=256,
    n_heads=8,
    n_kv_heads=4,
    head_dim=32,
    d_ff=1024,
    vocab_size=256,          # byte tokenizer
    max_seq_len=2048,
    qk_norm=True,
    length_buckets=16,
    remat=False,
    source="tiny qwen3-style cloud model for CPU testbed",
)

TINY_EDGE_A = ModelConfig(
    name="tiny-edge-a",
    family="dense",
    n_layers=2,
    d_model=128,
    n_heads=4,
    n_kv_heads=2,
    head_dim=32,
    d_ff=256,
    vocab_size=256,
    max_seq_len=2048,
    qkv_bias=True,
    remat=False,
    source="tiny qwen2-style edge SLM",
)

TINY_EDGE_B = ModelConfig(
    name="tiny-edge-b",
    family="dense",
    n_layers=2,
    d_model=96,
    n_heads=4,
    n_kv_heads=4,
    head_dim=24,
    d_ff=192,
    vocab_size=256,
    max_seq_len=2048,
    remat=False,
    source="tiny llama-style edge SLM",
)

TINY_EDGE_C = ModelConfig(
    name="tiny-edge-c",
    family="ssm",
    n_layers=2,
    d_model=128,
    n_heads=4,
    n_kv_heads=4,
    d_ff=0,
    vocab_size=256,
    max_seq_len=2048,
    ssm_state=16,
    ssm_chunk=64,
    remat=False,
    source="tiny mamba2-style edge SLM (O(1) decode state)",
)

TINY_EDGE_CONFIGS = {
    "tiny-edge-a": TINY_EDGE_A,
    "tiny-edge-b": TINY_EDGE_B,
    "tiny-edge-c": TINY_EDGE_C,
}


PAIRINGS = {
    "tiny": Pairing(cloud="tiny-cloud", members={
        "tiny-cloud": FleetMember(TINY_CLOUD, 0.9),
        "tiny-edge-a": FleetMember(TINY_EDGE_A, 0.7),
        "tiny-edge-b": FleetMember(TINY_EDGE_B, 0.55),
        "tiny-edge-c": FleetMember(TINY_EDGE_C, 0.6),
    }),
    "one-chip": Pairing(cloud="qwen3-8b-8of36", members={
        "qwen3-8b-8of36": FleetMember(ONE_CHIP_CLOUD, 0.9),
        "qwen2-1.5b-a": FleetMember(ONE_CHIP_EDGE, 0.7, seed=1),
        "qwen2-1.5b-b": FleetMember(ONE_CHIP_EDGE, 0.6, seed=2),
    }),
}
