"""Per-layer metric `alloc_iters`: mean outer iterations of the in-trace
eq. (28) solve per round, from the telemetry ring flushed at the traced
window's segment boundaries."""
import math


def read(ctx):
    iters = [float(r.alloc_iters) for r in ctx.telemetry
             if getattr(r, 'alloc_iters', None) is not None]
    iters = [x for x in iters if math.isfinite(x)]
    return sum(iters) / len(iters) if iters else None
