"""Per-layer metric `unscoped_ms`: device self time per round, in ms, of the
segment's ops whose HLO op_name lies under no `obs/` stage scope (the
scan's own loop and copies, and whatever no stage claims)."""
from bench.harness.program_obs import unscoped_seconds


def read(ctx):
    return ctx.per_round_ms(unscoped_seconds(ctx))
