"""Per-layer metric `alloc_dual_rounds`: mean tree rounds of the eq. (28)
solve's dual bisection per traced round that solved, from the telemetry
ring's `alloc_dual_rounds`.  None on a program whose telemetry has no
such field."""
from bench.harness.program_obs import alloc_steps


def read(ctx):
    rounds = [getattr(r, 'alloc_dual_rounds', None) for r in ctx.telemetry]
    steps = alloc_steps(ctx)
    if not rounds or steps is None or any(x is None for x in rounds):
        return None
    solved = [float(x) for x, s in zip(rounds, steps) if s > 0]
    return sum(solved) / len(solved) if solved else None
