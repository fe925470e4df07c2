"""Per-layer metric `alloc_steps`: mean trips of the eq. (28) solve's
innermost serial loops (golden-section steps of the alternating solve)
per traced round that solved, from the telemetry ring's `alloc_steps`."""
from bench.harness.program_obs import alloc_steps


def read(ctx):
    steps = [s for s in alloc_steps(ctx) or [] if s > 0]
    return sum(steps) / len(steps) if steps else None
