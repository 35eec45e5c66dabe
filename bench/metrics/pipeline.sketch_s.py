"""pipeline.sketch_s: mean seconds from a request's arrival to its cloud
sketch's return, over the `pipeline.sketch` intervals that end inside the
traced span (layer: pipeline, core/progressive.py). Read from the
program's tracer records (bench/spans.py); None where the context holds
none."""
from bench import spans


def read(ctx):
    return spans.mean_s(ctx, "pipeline.sketch")
