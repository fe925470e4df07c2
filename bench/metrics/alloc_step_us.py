"""Per-layer metric `alloc_step_us`: device self time of the segment's ops
under the program's `obs/alloc_solve` scope, in microseconds, over the
`alloc_steps` the traced rounds took: the cost of one solver step."""
from bench.harness.program_obs import alloc_steps, scope_seconds


def read(ctx):
    seconds = scope_seconds(ctx, 'alloc_solve')
    steps = sum(alloc_steps(ctx) or [])
    if seconds is None or steps <= 0:
        return None
    return 1e6 * seconds / steps
