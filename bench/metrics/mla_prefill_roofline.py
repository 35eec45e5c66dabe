"""mla_prefill_roofline: the latent-mode paged chunk kernel's share of its
roofline, in % (layer: kernels, kernels/paged_prefill_attention, the
`mla_paged_prefill` call), over the batched ragged ingest and single-slot
chunks alike. The least time of every recorded call (rows of (offset,
length), bench/flops_mla.py) over the kernel's summed time in the trace.
None in a cell without latent-attention engines."""
from bench import flops, flops_mla

KERNEL = "mla_paged_prefill"


def read(ctx):
    t = ctx.kernel_s(KERNEL)
    if t <= 0 or ctx.peaks is None:
        return None
    least = 0.0
    for eng, rows in ctx.probe.prefill_calls:
        s = flops_mla.sizes(eng)
        if s is None:
            continue
        d = ctx.dims[eng]
        least += s["n_layers"] * flops.least_seconds(*flops_mla.prefill_call(
            s, d["page"], d["kv_itemsize"], rows), ctx.peaks)[0]
    return 100.0 * least / t if least else None
