"""Per-layer metric `flush_host_ms`: median over the run's telemetry flushes
of the host time, in ms, after the flush's device_get (`flush.get`) ended,
from the program's own host spans.  In a closed loop the next segment is
dispatched only after the flush returns, so the device idles through it."""
import statistics

from bench.harness.program_obs import flush_tail_ms, program_spans


def read(ctx):
    tails = flush_tail_ms(program_spans() or [])
    return statistics.median(tails) if tails else None
