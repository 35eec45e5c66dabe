"""pipeline.expand_s: mean seconds from the cloud sketch's return to the
stitched answer, over the window's progressive requests that completed
(layer: pipeline, core/progressive.py). Read from the harness's front-end
records."""


def read(ctx):
    p = ctx.probe
    xs = [d.end - p.sketch_done[d.index] for d in ctx.finished()
          if d.mode == "progressive" and d.index in p.sketch_done]
    return sum(xs) / len(xs) if xs else None
