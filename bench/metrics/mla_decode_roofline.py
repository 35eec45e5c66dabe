"""mla_decode_roofline: the latent-mode paged decode kernel's share of its
roofline, in % (layer: kernels, kernels/paged_decode_attention, the
`mla_paged_decode` call). The least time of every decode call the harness
recorded in the traced span (each layer's: operations and bytes from the
rows' context lengths, bench/flops_mla.py) over the kernel's summed time
in the trace. None in a cell without latent-attention engines."""
from bench import flops, flops_mla

KERNEL = "mla_paged_decode"


def read(ctx):
    t = ctx.kernel_s(KERNEL)
    if t <= 0 or ctx.peaks is None:
        return None
    least = 0.0
    for eng, rows in ctx.probe.decode_calls:
        s = flops_mla.sizes(eng)
        if s is None:
            continue
        d = ctx.dims[eng]
        least += s["n_layers"] * flops.least_seconds(*flops_mla.decode_call(
            s, d["page"], d["kv_itemsize"], rows), ctx.peaks)[0]
    return 100.0 * least / t if least else None
