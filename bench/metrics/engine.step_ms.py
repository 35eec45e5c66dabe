"""engine.step_ms: the traced span's length divided by the steps all
engines took in it, in ms (layer: engine). Steps counted at step_hook; the
span is the harness's `bench.span` annotation on the profiler's clock."""


def read(ctx):
    steps = sum(ctx.probe.steps.values())
    if not steps or ctx.hi <= ctx.lo:
        return None
    return 1000.0 * ctx.window_s / steps
