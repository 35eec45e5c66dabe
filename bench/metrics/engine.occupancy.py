"""engine.occupancy: mean share of an engine's slots active per step, in %,
over every step of every engine in the traced span (layer: front-end and
engine). Counted at each engine's step_hook."""


def read(ctx):
    p = ctx.probe
    steps = sum(p.steps.values())
    if not steps:
        return None
    return 100.0 * sum(p.active_share_sum.values()) / steps
