"""§Perf hillclimb driver — hypothesis -> change -> re-lower -> measure.

Runs named variants of a (arch x shape) pair through the dry-run pipeline
(depth-1/2 unrolled extrapolation; single-pod mesh) and reports the three
roofline terms per variant, so EXPERIMENTS.md §Perf can log each iteration
with before/after numbers.

  PYTHONPATH=src:benchmarks python benchmarks/hillclimb.py \
      --arch mixtral-8x7b --shape train_4k \
      --variants baseline bf16_uplink remat_dots

Variants (composable with '+'):
  baseline      paper-faithful (fp32 uplink reduce, full remat, q_chunk 1024)
  bf16_uplink   cross-client all-reduce in bf16 (payload already b-bit)
  remat_dots    checkpoint_dots remat policy (save matmuls, less recompute)
  qchunk_256 / qchunk_4096   attention query-chunk retune
"""
import repro.launch.dryrun as dr   # noqa: E402  (sets XLA_FLAGS first)

import argparse
import dataclasses
import json
import os
import time

import jax

from repro.configs.base import FLConfig
from repro.configs.registry import get_arch, get_shape
from repro.launch.mesh import make_production_mesh

PEAK_FLOPS = 197e12
HBM_BW = 819e9
LINK_BW = 50e9

OUT_DIR = os.path.join(os.path.dirname(__file__), '..', 'experiments',
                       'hillclimb')


def apply_variant(cfg, fl, name: str):
    for part in name.split('+'):
        if part == 'baseline':
            continue
        elif part == 'bf16_uplink':
            fl = dataclasses.replace(fl, uplink_reduce_dtype='bfloat16')
        elif part == 'remat_dots':
            cfg = dataclasses.replace(cfg, remat_policy='dots')
        elif part == 'remat_none':
            cfg = dataclasses.replace(cfg, remat_policy='none')
        elif part.startswith('qchunk_'):
            cfg = dataclasses.replace(cfg, q_chunk=int(part.split('_')[1]))
        elif part.startswith('cf_'):     # MoE capacity factor
            cfg = dataclasses.replace(cfg,
                                      capacity_factor=float(part[3:]))
        elif part == 'moe_grouped':      # per-row dispatch (EP all-to-all)
            cfg = dataclasses.replace(cfg, moe_dispatch='grouped')
        elif part == 'cache_batch':      # device-local decode attention
            cfg = dataclasses.replace(cfg, decode_cache_layout='batch')
        else:
            raise ValueError(f'unknown variant part {part!r}')
    return cfg, fl


def measure(cfg, fl, shape, mesh) -> dict:
    g_full = cfg.n_layers // len(cfg.layer_pattern)
    with jax.set_mesh(mesh):
        d1 = dr._compile_and_analyze(dr._depth_clone(cfg, 1), shape, mesh,
                                     fl, unroll=True)
        d2 = dr._compile_and_analyze(dr._depth_clone(cfg, 2), shape, mesh,
                                     fl, unroll=True)
    cost = dr._affine_extrapolate(d1.get('cost_analysis') or {},
                                  d2.get('cost_analysis') or {}, g_full)
    coll = {}
    for c in dr._COLLECTIVES:
        coll[c] = dr._affine_extrapolate(
            {'x': d1['collectives'][c]['bytes']},
            {'x': d2['collectives'][c]['bytes']}, g_full)['x']
    flops = cost.get('flops', 0.0)
    mem = cost.get('bytes accessed', 0.0)
    cbytes = sum(coll.values())
    return {
        'flops_per_dev': flops,
        'bytes_per_dev': mem,
        'collective_bytes_per_dev': cbytes,
        'collectives': coll,
        'compute_s': flops / PEAK_FLOPS,
        'memory_s': mem / HBM_BW,
        'collective_s': cbytes / LINK_BW,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--arch', required=True)
    ap.add_argument('--shape', required=True)
    ap.add_argument('--variants', nargs='+', default=['baseline'])
    args = ap.parse_args()

    base_cfg = get_arch(args.arch)
    shape = get_shape(args.shape)
    mesh = make_production_mesh(multi_pod=False)
    os.makedirs(OUT_DIR, exist_ok=True)

    print(f'{"variant":28s} {"compute_s":>11} {"memory_s":>11} '
          f'{"collect_s":>11}  dominant', flush=True)
    for name in args.variants:
        fl = FLConfig(n_devices=16)
        cfg, fl = apply_variant(base_cfg, fl, name)
        t0 = time.time()
        m = measure(cfg, fl, shape, mesh)
        m['variant'] = name
        m['arch'] = args.arch
        m['shape'] = args.shape
        m['wall_s'] = time.time() - t0
        dom = max(('compute', 'memory', 'collective'),
                  key=lambda k: m[f'{k}_s'])
        m['dominant'] = dom
        path = os.path.join(
            OUT_DIR, f'{args.arch}__{args.shape}__{name}.json')
        with open(path, 'w') as f:
            json.dump(m, f, indent=1)
        print(f'{name:28s} {m["compute_s"]:11.4e} {m["memory_s"]:11.4e} '
              f'{m["collective_s"]:11.4e}  {dom}', flush=True)


if __name__ == '__main__':
    main()
