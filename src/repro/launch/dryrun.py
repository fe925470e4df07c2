import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512").strip()
# ^ MUST precede any jax import: jax locks the device count on first init.

"""Multi-pod dry-run — deliverable (e).

NOTE: the two os.environ lines above intentionally precede every other
import (jax locks the device count on first init); hence no
``from __future__`` here.

For every (architecture x input shape) and both production meshes, build
the jitted step with full production shardings, ``.lower().compile()`` it
against ShapeDtypeStruct inputs (no allocation), and record:

  * memory_analysis()        — per-device bytes (proves it fits)
  * cost_analysis()          — HLO FLOPs / bytes for §Roofline
  * collective inventory     — parsed from the optimized (SPMD) HLO:
    per-device bytes of all-gather / all-reduce / reduce-scatter /
    all-to-all / collective-permute

Artifacts land in experiments/dryrun/<arch>__<shape>__<mesh>.json and feed
benchmarks/roofline.py.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch smollm-135m --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod-only|--single-pod-only]
"""
import argparse
import json
import re
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs.base import FLConfig, ModelConfig, ShapeConfig
from repro.configs.registry import applicable, get_arch, get_shape, ARCHITECTURES
from repro.configs.base import INPUT_SHAPES
from repro.launch import shardings as sh
from repro.launch.mesh import client_axes, make_production_mesh, n_clients
from repro.models import transformer as tf
from repro.training import distributed as dist

ARTIFACT_DIR = os.path.join(os.path.dirname(__file__), '..', '..', '..',
                            'experiments', 'dryrun')

_DTYPE_BYTES = {
    'f64': 8, 'f32': 4, 'bf16': 2, 'f16': 2, 'f8e4m3fn': 1, 'f8e5m2': 1,
    's64': 8, 'u64': 8, 's32': 4, 'u32': 4, 's16': 2, 'u16': 2,
    's8': 1, 'u8': 1, 'pred': 1, 'c64': 8, 'c128': 16,
}

_COLLECTIVES = ('all-gather', 'all-reduce', 'reduce-scatter', 'all-to-all',
                'collective-permute')


# ---------------------------------------------------------------------------
# HLO collective parsing
# ---------------------------------------------------------------------------

_SHAPE_RE = re.compile(r'(\w+)\[([\d,]*)\]')


def _shape_bytes(shape_str: str) -> int:
    """'f32[16,128]' -> bytes; tuple shapes handled by the caller."""
    total = 0
    for m in _SHAPE_RE.finditer(shape_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(','):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def parse_collectives(hlo_text: str) -> dict:
    """Per-device collective bytes from optimized SPMD HLO.

    Counts the RESULT shape bytes of every collective op line (the
    per-partition payload); async start/done pairs are counted once via
    the -start op.
    """
    out = {c: {'count': 0, 'bytes': 0} for c in _COLLECTIVES}
    for line in hlo_text.splitlines():
        s = line.strip()
        if '=' not in s:
            continue
        rhs = s.split('=', 1)[1].strip()
        for c in _COLLECTIVES:
            idx = -1
            for tok in (f' {c}-start(', f' {c}('):
                idx = rhs.find(tok)
                if idx != -1:
                    break
            if idx == -1:
                continue
            out[c]['count'] += 1
            out[c]['bytes'] += _shape_bytes(rhs[:idx])
            break
    return out


# ---------------------------------------------------------------------------
# step builders (lowered, never executed)
# ---------------------------------------------------------------------------

def _abstract_params(cfg: ModelConfig):
    return jax.eval_shape(
        lambda k: tf.init_params(cfg, k), jax.random.PRNGKey(0))


def input_specs(cfg: ModelConfig, shape: ShapeConfig, mesh) -> dict:
    """ShapeDtypeStruct stand-ins for every model input of this shape.

    This is the deliverable-(f) entry point: weak-type-correct, shardable,
    no device allocation.  Audio/VLM frontends follow the harness carve-out
    (precomputed token/patch embeddings).
    """
    K = n_clients(mesh)
    if shape.kind == 'train':
        if cfg.name.startswith('arctic-480b'):
            spec = {'tokens': jax.ShapeDtypeStruct(
                (shape.global_batch, shape.seq_len), jnp.int32)}
        else:
            spec = dist.client_batch_shapes(cfg, K, shape.global_batch,
                                            shape.seq_len)
        return spec
    if shape.kind == 'prefill':
        spec = {'tokens': jax.ShapeDtypeStruct(
            (shape.global_batch, shape.seq_len), jnp.int32)}
        if cfg.frontend == 'vision' and cfg.n_prefix_tokens:
            spec['prefix'] = jax.ShapeDtypeStruct(
                (shape.global_batch, cfg.n_prefix_tokens,
                 cfg.frontend_embed_dim), jnp.bfloat16)
        return spec
    # decode: ONE new token against a cache of seq_len
    return {'token': jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32),
            'pos': jax.ShapeDtypeStruct((), jnp.int32)}


def build_lowered(cfg: ModelConfig, shape: ShapeConfig, mesh,
                  fl: FLConfig, unroll: bool = False):
    """Returns (lowered, meta) for the right step of this shape.kind."""
    params_shape = _abstract_params(cfg)
    pspecs = sh.param_shardings(cfg, mesh, params_shape)
    repl = sh.replicated(mesh)
    key_spec = jax.ShapeDtypeStruct((2,), jnp.uint32)
    K = n_clients(mesh)

    if shape.kind == 'train':
        batch_spec = input_specs(cfg, shape, mesh)
        if cfg.name.startswith('arctic-480b'):
            step = dist.make_standard_train_step(cfg, fl, unroll=unroll)
            ca = client_axes(mesh)
            lead = ca if len(ca) > 1 else ca[0]
            batch_sh = {'tokens': jax.sharding.NamedSharding(
                mesh, jax.sharding.PartitionSpec(lead, None))}
            jitted = jax.jit(step,
                             in_shardings=(pspecs, batch_sh, repl),
                             out_shardings=(pspecs, repl))
            lowered = jitted.lower(params_shape, batch_spec, key_spec)
            return lowered, {'step': 'standard_train', 'clients': 0}
        step = dist.make_fl_train_step(cfg, fl, 'spfl', unroll=unroll)
        gbar_shape = jax.eval_shape(dist.init_gbar, params_shape)
        gbar_sh = sh.param_shardings(cfg, mesh, gbar_shape)
        batch_sh = sh.to_shardings(
            mesh, sh.train_batch_specs(cfg, mesh, per_client=True))
        kq = jax.ShapeDtypeStruct((K,), jnp.float32)
        jitted = jax.jit(
            step,
            in_shardings=(pspecs, batch_sh, gbar_sh, repl, repl, repl),
            out_shardings=(pspecs, gbar_sh, repl))
        lowered = jitted.lower(params_shape, batch_spec, gbar_shape,
                               kq, kq, key_spec)
        return lowered, {'step': 'fl_train', 'clients': K}

    if shape.kind == 'prefill':
        batch_spec = input_specs(cfg, shape, mesh)
        batch_sh = sh.to_shardings(mesh, sh.prefill_batch_spec(cfg, mesh))

        def prefill_step(params, batch):
            return tf.prefill(params, cfg, batch['tokens'], shape.seq_len,
                              prefix_embeds=batch.get('prefix'),
                              unroll=unroll)

        cache_shape = jax.eval_shape(prefill_step, params_shape, batch_spec)[1]
        cache_sh = sh.to_shardings(
            mesh, sh.cache_specs(cfg, mesh, shape, cache_shape))
        jitted = jax.jit(prefill_step, in_shardings=(pspecs, batch_sh),
                         out_shardings=(repl, cache_sh))
        lowered = jitted.lower(params_shape, batch_spec)
        return lowered, {'step': 'prefill', 'clients': 0}

    # decode
    cache_shape = jax.eval_shape(
        lambda: tf.init_cache(cfg, shape.global_batch, shape.seq_len,
                              jnp.bfloat16))
    cache_sh = sh.to_shardings(
        mesh, sh.cache_specs(cfg, mesh, shape, cache_shape))
    tok_sh = jax.sharding.NamedSharding(mesh, sh.decode_token_spec(cfg, mesh, shape))
    spec = input_specs(cfg, shape, mesh)

    def decode(params, cache, token, pos):
        return tf.decode_step(params, cfg, cache, token, pos, unroll=unroll)

    logits_spec = (jax.sharding.PartitionSpec(None, None, 'model')
                   if shape.global_batch < mesh.shape['data'] else
                   jax.sharding.PartitionSpec(
                       client_axes(mesh) if len(client_axes(mesh)) > 1
                       else client_axes(mesh)[0], None, 'model'))
    logits_spec = sh.sanitize_spec(
        logits_spec, (shape.global_batch, 1, cfg.vocab_size), mesh)
    jitted = jax.jit(
        decode,
        in_shardings=(pspecs, cache_sh, tok_sh, sh.replicated(mesh)),
        out_shardings=(jax.sharding.NamedSharding(mesh, logits_spec),
                       cache_sh),
        donate_argnums=(1,))   # in-place cache update (no copy)
    lowered = jitted.lower(params_shape, cache_shape, spec['token'],
                           spec['pos'])
    return lowered, {'step': 'decode', 'clients': 0}


# ---------------------------------------------------------------------------
# runner
# ---------------------------------------------------------------------------

def _depth_clone(cfg: ModelConfig, n_periods: int) -> ModelConfig:
    import dataclasses
    return dataclasses.replace(
        cfg, n_layers=n_periods * len(cfg.layer_pattern))


def _compile_and_analyze(cfg, shape, mesh, fl, unroll):
    lowered, meta = build_lowered(cfg, shape, mesh, fl, unroll=unroll)
    compiled = lowered.compile()
    rec = dict(meta)
    try:
        mem = compiled.memory_analysis()
        rec['memory_analysis'] = {
            k: getattr(mem, k) for k in
            ('argument_size_in_bytes', 'output_size_in_bytes',
             'temp_size_in_bytes', 'generated_code_size_in_bytes',
             'alias_size_in_bytes')
            if hasattr(mem, k)} if mem is not None else None
    except Exception as e:               # CPU backend may not support
        rec['memory_analysis'] = f'unavailable: {e}'
    try:
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0]
        rec['cost_analysis'] = {
            k: float(v) for k, v in cost.items()
            if isinstance(v, (int, float)) and
            k in ('flops', 'transcendentals', 'bytes accessed')}
    except Exception as e:
        rec['cost_analysis'] = f'unavailable: {e}'
    try:
        text = compiled.as_text()
    except Exception:
        text = lowered.as_text()
    rec['collectives'] = parse_collectives(text)
    return rec


def _affine_extrapolate(c1: dict, c2: dict, g_full: int) -> dict:
    """cost(G) is affine in the group count G for identical layer groups:
    cost(G) = c1 + (c2 - c1) * (G - 1), slope clamped nonnegative."""
    out = {}
    for k in set(c1) | set(c2):
        a, b = float(c1.get(k, 0.0)), float(c2.get(k, 0.0))
        out[k] = a + max(b - a, 0.0) * (g_full - 1)
    return out


def run_one(arch: str, shape_name: str, multi_pod: bool,
            out_dir: str = ARTIFACT_DIR, detail: bool = None) -> dict:
    """One (arch, shape, mesh) dry-run.

    Always: full-depth scanned model -> lower + compile + memory_analysis
    (the "it lowers, it fits" proof for this mesh).
    detail (default: single-pod only): additionally compile depth-1 and
    depth-2 UNROLLED clones and affine-extrapolate exact per-device HLO
    flops/bytes/collectives to full depth for §Roofline — XLA cost_analysis
    counts a scanned while-body once, so the scanned executable alone
    undercounts compute by ~n_layers.
    """
    cfg = get_arch(arch)
    shape = get_shape(shape_name)
    ok, why = applicable(cfg, shape)
    mesh_name = 'pod2x16x16' if multi_pod else 'pod16x16'
    detail = (not multi_pod) if detail is None else detail
    record = {
        'arch': arch, 'shape': shape_name, 'mesh': mesh_name,
        'applicable': ok, 'skip_reason': why,
        'params': cfg.param_count(), 'active_params': cfg.active_param_count(),
        'n_layers': cfg.n_layers,
    }
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f'{arch}__{shape_name}__{mesh_name}.json')
    if not ok:
        with open(path, 'w') as f:
            json.dump(record, f, indent=1)
        return record

    fl = FLConfig(n_devices=32 if multi_pod else 16)
    mesh = make_production_mesh(multi_pod=multi_pod)
    with jax.set_mesh(mesh):
        t0 = time.time()
        full = _compile_and_analyze(cfg, shape, mesh, fl, unroll=False)
        record.update(full)
        record['compile_s'] = time.time() - t0
        record['n_devices'] = mesh.size
        if detail:
            g_full = cfg.n_layers // len(cfg.layer_pattern)
            t1 = time.time()
            d1 = _compile_and_analyze(_depth_clone(cfg, 1), shape, mesh, fl,
                                      unroll=True)
            d2 = _compile_and_analyze(_depth_clone(cfg, 2), shape, mesh, fl,
                                      unroll=True)
            cost = _affine_extrapolate(
                d1.get('cost_analysis') or {},
                d2.get('cost_analysis') or {}, g_full)
            col1, col2 = d1['collectives'], d2['collectives']
            coll = {c: {k: _affine_extrapolate({'x': col1[c][k]},
                                               {'x': col2[c][k]},
                                               g_full)['x']
                        for k in ('count', 'bytes')}
                    for c in _COLLECTIVES}
            record['hlo_estimate'] = {
                'method': 'affine depth-1/depth-2 unrolled extrapolation',
                'cost_analysis': cost,
                'collectives': coll,
                'depth1': {'cost': d1.get('cost_analysis'),
                           'collectives': col1},
                'depth2': {'cost': d2.get('cost_analysis'),
                           'collectives': col2},
                'detail_compile_s': time.time() - t1,
            }

    with open(path, 'w') as f:
        json.dump(record, f, indent=1)
    record['artifact'] = path
    return record


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--arch', default=None)
    ap.add_argument('--shape', default=None)
    ap.add_argument('--all', action='store_true')
    ap.add_argument('--multi-pod', action='store_true')
    ap.add_argument('--single-pod', action='store_true')
    ap.add_argument('--out-dir', default=ARTIFACT_DIR)
    ap.add_argument('--resume', action='store_true',
                    help='skip combos whose artifact already exists')
    args = ap.parse_args()

    meshes = []
    if args.multi_pod or not args.single_pod:
        meshes.append(True)
    if args.single_pod or not args.multi_pod:
        meshes.append(False)
    meshes = sorted(set(meshes))   # False (single) first

    combos = []
    if args.all:
        for a in ARCHITECTURES:
            for s in INPUT_SHAPES:
                combos.append((a, s))
    else:
        assert args.arch and args.shape, '--arch and --shape (or --all)'
        combos.append((args.arch, args.shape))

    failures = 0
    for a, s in combos:
        for mp in meshes:
            tag = f'{a} x {s} x {"2x16x16" if mp else "16x16"}'
            if args.resume:
                mesh_name = 'pod2x16x16' if mp else 'pod16x16'
                p = os.path.join(args.out_dir, f'{a}__{s}__{mesh_name}.json')
                if os.path.exists(p):
                    print(f'[HAVE] {tag}', flush=True)
                    continue
            try:
                rec = run_one(a, s, mp, out_dir=args.out_dir)
                if not rec['applicable']:
                    print(f'[SKIP] {tag}: {rec["skip_reason"]}', flush=True)
                    continue
                est = rec.get('hlo_estimate', {}).get('cost_analysis', {})
                fl_est = est.get('flops')
                print(f'[OK]   {tag}: compile {rec.get("compile_s", 0):.1f}s'
                      + (f' est-flops/dev {fl_est:.3e}' if fl_est else ''),
                      flush=True)
            except Exception as e:
                failures += 1
                print(f'[FAIL] {tag}: {e}', flush=True)
                traceback.print_exc()
    if failures:
        raise SystemExit(f'{failures} dry-run failures')


if __name__ == '__main__':
    main()
