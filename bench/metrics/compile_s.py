"""Per-layer metric `compile_s`: the seconds the program spent tracing,
lowering and backend-compiling up to the time of reading, from its own
compile counters.  No compile falls in the window, so this is set-up."""
from bench.harness.program_obs import compile_seconds


def read(ctx):
    return compile_seconds()
