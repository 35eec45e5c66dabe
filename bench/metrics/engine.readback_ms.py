"""engine.readback_ms: mean ms per engine step spent in its
`engine.readback` descendants (the harvest's `jax.device_get` of the last
step's tokens, and the first-token one), over the `engine.step` spans that
end inside the traced span, per step of every engine (layer: front-end
and engine). The host waits there for the device. Read from the
program's tracer records (bench/spans.py); None where the context holds
none."""
import statistics

from bench import spans


def read(ctx):
    split = spans.step_split(ctx)
    if not split:
        return None
    return statistics.fmean(rb / 1e6 for _, rb in split)
