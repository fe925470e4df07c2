"""Per-layer metric `kernel_ms`: device self time per round, in ms, of the ops
whose HLO source file lies in the modules listed in `kernel_ms.json`."""
from bench.harness.readers import module_ms


def read(ctx):
    return module_ms(ctx, 'kernel_ms')
