"""paged_prefill_roofline: the paged prefill kernel's share of its
roofline, in % (layer: kernels, kernels/paged_prefill_attention). It serves
both the batched ragged ingest and the single-slot chunks a fan-out's
shared prefix is ingested with. The least time of every recorded call (rows
of (offset, length), bench/flops.py) over the kernel's summed time in the
trace; 128-token chunks over short contexts are memory bound, long
contexts turn it toward compute."""
from bench import flops
from bench.layers import PREFILL_KERNEL


def read(ctx):
    return ctx.roofline_share(PREFILL_KERNEL, ctx.probe.prefill_calls,
                              flops.prefill_call)
