"""Readings behind the limits of `correct`: the numbers the check compares,
for many seeds, for the program, for the control (the reference in
bfloat16 put in the program's place) or for the program with a fault
planted underneath.  It drives the benchmark's own first segment and
comparison (`bench/harness/runner.py`) and skips the measured window.

    python3 bench/readings.py --workload <cell> --seeds 1,2,3 \\
        --mode program|control|fault:<name> [--phase both|chip|reference] \\
        [--dir <directory>]

`--phase chip` runs the program's first segments and keeps what they
produced in `--dir`; `--phase reference` (no chip) compares them there, so
the reference of one seed can run beside the next seed's compile.  Prints
one JSON line per seed.
"""
import time

T0 = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pickle  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
os.environ['JAX_COMPILATION_CACHE_DIR'] = str(ROOT / '.jax_cache')
sys.path[:0] = [str(ROOT), str(ROOT / 'src')]


def produce(spec, seed: int, mode: str) -> tuple:
    """-> (cell, what the run under check produced)."""
    from bench.harness import runner
    fault = mode.split(':', 1)[1] if mode.startswith('fault:') else None
    cell = spec.adapter().Cell(spec.config, spec.traffic, seed, fault)
    if mode == 'control':
        return cell, cell.control()
    _, produced = runner.first_segment(cell)
    cell.release()
    return cell, produced


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split('\n\n')[0])
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seeds', required=True)
    ap.add_argument('--mode', default='program')
    ap.add_argument('--phase', default='both',
                    choices=('both', 'chip', 'reference'))
    ap.add_argument('--dir', default=None)
    args = ap.parse_args(argv)
    from bench.harness import runner
    from bench.harness.spec import Spec
    from repro.launch import env as launch_env
    launch_env.configure()
    spec = Spec(args.workload)
    for seed in (int(s) for s in args.seeds.split(',')):
        t0 = time.perf_counter()
        kept = Path(args.dir or '.') / f'{args.workload}.{args.mode}.{seed}.pkl'
        if args.phase == 'reference':
            cell = spec.adapter().Cell(spec.config, spec.traffic, seed)
            produced = pickle.loads(kept.read_bytes())
        else:
            cell, produced = produce(spec, seed, args.mode)
        if args.phase == 'chip':
            kept.write_bytes(pickle.dumps(produced))
            print(json.dumps({'seed': seed, 'kept': str(kept),
                              'seconds': time.perf_counter() - t0}),
                  flush=True)
            continue
        readings, checks, correct = runner.compare(spec, cell, produced)
        print(json.dumps({
            'workload': args.workload, 'seed': seed, 'mode': args.mode,
            'correct': correct, 'readings': readings,
            'alloc_iters': produced.get('alloc_iters'),
            'mod_ok': [int(m.sum()) for m in produced['mod_ok']],
            'seconds': time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
