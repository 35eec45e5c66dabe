"""moe_step_mfu: model operations of the tokens prefilled and decoded in
the traced span by latent-attention expert engines, over (span x the
chip's bf16 peak), in % (layer: whole step). Operations are the chip's
share (bench/flops_mla.py): attention, the dense layers, the shared expert
and the expected routed assignments to the experts held here. None in a
cell without such engines."""
from bench import flops_mla


def read(ctx):
    p = ctx.probe
    if ctx.peaks is None or ctx.hi <= ctx.lo:
        return None
    work = 0.0
    for eng, rows in p.decode_calls:
        s = flops_mla.sizes(eng)
        if s is not None:
            work += flops_mla.model_flops_decode(s, rows)
    for eng, rows in p.prefill_calls:
        s = flops_mla.sizes(eng)
        if s is not None:
            work += flops_mla.model_flops_prefill(s, rows)
    if not work:
        return None
    return 100.0 * work / (ctx.window_s * ctx.peaks["flops_bf16"])
