"""step_mfu: model operations of the tokens prefilled and decoded in the
traced span over (span x the chip's bf16 peak), in % (layer: the whole
step). Tokens and their context lengths from the harness's records of
every decode and ingest call; operations from bench/flops.py."""
from bench import flops


def read(ctx):
    p = ctx.probe
    if ctx.peaks is None or ctx.hi <= ctx.lo or not (
            p.decode_calls or p.prefill_calls):
        return None
    work = sum(flops.model_flops_decode(ctx.dims[e], rows)
               for e, rows in p.decode_calls)
    work += sum(flops.model_flops_prefill(ctx.dims[e], rows)
                for e, rows in p.prefill_calls)
    return 100.0 * work / (ctx.window_s * ctx.peaks["flops_bf16"])
