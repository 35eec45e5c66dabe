"""pipeline.edge_groups: expansion groups run per request, summed over the
ensemble members (0 for a cloud_full answer), mean over the window's
requests that completed (layer: pipeline). Counted by the harness's
front-ends at each generate_fanout_async call."""


def read(ctx):
    done = ctx.finished()
    if not done:
        return None
    return sum(ctx.probe.groups.get(d.index, 0) for d in done) / len(done)
