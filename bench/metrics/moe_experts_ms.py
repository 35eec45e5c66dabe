"""moe_experts_ms: the grouped expert matmul's device time per engine
step, in ms (layer: experts, kernels/moe_grouped_matmul): the summed time
of the `moe_grouped_matmul` calls in the trace over the steps that the
latent-attention expert engines took in the traced span. None in a cell
without such engines.

A time and not a share of the roofline: the kernel streams the weights of
the held experts that have rows in a call and skips the rest, so its least
time depends on where the router sent each call's tokens, which the
harness does not record (the engine spans' `expert_rows` carry it once
the program's tracer runs in the traced span)."""
from bench import flops_mla

KERNEL = "moe_grouped_matmul"


def read(ctx):
    t = ctx.kernel_s(KERNEL)
    steps = sum(n for eng, n in ctx.probe.steps.items()
                if (s := flops_mla.sizes(eng)) is not None
                and s["moe_layers"])
    if t <= 0 or not steps:
        return None
    return 1000.0 * t / steps
