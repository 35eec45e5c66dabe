"""engine.host_ms: mean ms per engine step spent other than reading
results back: each `engine.step` span that ends inside the traced span,
less its `engine.readback` descendants (the harvest's and the first
token's `jax.device_get`), per step of every engine (layer: front-end and
engine). Read from the program's tracer records (bench/spans.py); None
where the context holds none."""
import statistics

from bench import spans


def read(ctx):
    split = spans.step_split(ctx)
    if not split:
        return None
    return statistics.fmean((s.end_ns - s.start_ns - rb) / 1e6
                            for s, rb in split)
