"""frontend.queue_wait_s: mean seconds a sub-request waited for an engine
slot, from its arrival at the front-end (a fan-out fork: its
`generate_fanout_async` call) to the slot `try_admit` gave it, over the
`frontend.queued` intervals that end inside the traced span (layer:
front-end and engine). Read from the program's tracer records
(bench/spans.py); None where the context holds none."""
from bench import spans


def read(ctx):
    return spans.mean_s(ctx, "frontend.queued")
