"""On-chip benchmark of the SP-FL system.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of BENCHMARK.json on the chips of this machine (it needs a
TPU and exits non-zero without one), and prints one JSON result line
last on standard output.  See PERF.md for the cells and metrics.
"""
import time

T0 = time.time()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
# the persistent compilation cache lives at a fixed path in the checkout
os.environ['JAX_COMPILATION_CACHE_DIR'] = str(ROOT / '.jax_cache')
sys.path[:0] = [str(ROOT), str(ROOT / 'src')]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split('\n\n')[0])
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seed', type=int, required=True)
    ap.add_argument('--seconds', type=float, required=True)
    ap.add_argument('--trace', type=int, choices=(0, 1), default=0)
    ap.add_argument('--keep-trace', default=None,
                    help='keep the profiler trace in this directory')
    args = ap.parse_args(argv)
    from bench.harness import runner
    return runner.run(args.workload, args.seed, args.seconds,
                      bool(args.trace), T0, keep_trace=args.keep_trace)


if __name__ == '__main__':
    sys.exit(main())
