"""Per-layer metric `transport_ms`: device self time per round, in ms, of the ops
whose HLO source file lies in the modules listed in `transport_ms.json`."""
from bench.harness.readers import module_ms


def read(ctx):
    return module_ms(ctx, 'transport_ms')
