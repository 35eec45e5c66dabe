"""paged_decode_roofline: the paged flash-decode kernel's share of its
roofline, in % (layer: kernels, kernels/paged_decode_attention). The least
time of every call the harness recorded in the traced span (each layer's call:
operations and bytes from the rows' context lengths, bench/flops.py) over
the kernel's summed time in the trace. Decode reads every cached page once
per step, so the memory bound applies."""
from bench import flops
from bench.layers import DECODE_KERNEL


def read(ctx):
    return ctx.roofline_share(DECODE_KERNEL, ctx.probe.decode_calls,
                              flops.decode_call)
