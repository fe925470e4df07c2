"""One benchmark run: set-up, the measured window, the optional trace,
and the comparison with the reference that decides `correct`.

The window drives the cell's compiled segment back to back, flushing the
telemetry ring at every segment boundary as the program's scan driver
does, and ends at the first boundary after `seconds`.  Set-up (imports,
inputs, build, compile or cache load, and the first segment, which is
also the one the reference checks) ends where the window begins.
"""
from __future__ import annotations

import contextlib
import gc
import json
import math
import shutil
import sys
import tempfile
import time

import numpy as np

from bench.harness.spec import ROOT, Spec

GIB = 2.0 ** 30
# a traced run profiles the window's first segment (a segment's device
# trace runs to tens of MB) and keeps measuring
TRACED_SEGMENTS = 1


class CompileCounter:
    """Counts XLA compilations (and persistent-cache loads) by the events
    JAX records around them."""

    def __init__(self):
        import jax
        self.compiles = 0
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, name, secs, **_):
        if name == '/jax/core/compile/backend_compile_duration':
            self.compiles += 1

    def _event(self, name, **_):
        if name == '/jax/compilation_cache/cache_hits':
            self.cache_hits += 1
        elif name == '/jax/compilation_cache/cache_misses':
            self.cache_misses += 1

    def snapshot(self) -> dict:
        return {'compiles': self.compiles, 'cache_hits': self.cache_hits,
                'cache_misses': self.cache_misses}


def _annotate(enabled: bool, name: str):
    if not enabled:
        return contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation(name)


def _peak_bytes(devices) -> int:
    peaks = [(d.memory_stats() or {}).get('peak_bytes_in_use', 0)
             for d in devices]
    return int(max(peaks))


def first_segment(cell):
    """Build and compile the cell's segment and drive it through its first
    segment: -> (carry, what that segment produced)."""
    carry = cell.build()
    carry, losses = cell.dispatch(carry, 0)
    carry, rows = cell.flush(carry)
    return carry, cell.program_outputs(carry, losses, rows)


def compare(spec, cell, produced: dict, log=None):
    """Run the reference after ``produced`` and hold every number to the
    cell's limits: -> (readings, checks, correct)."""
    t_ref = time.perf_counter()
    readings = cell.readings(produced, cell.reference(produced))
    if log is not None:
        log(f'bench: reference {time.perf_counter() - t_ref:.3f} s; '
            f'readings {json.dumps(readings)}')
    checks = {name: {'value': readings[name], 'limit': limit}
              for name, limit in spec.limits['limits'].items()}
    return readings, checks, all(c['value'] <= c['limit']
                                 for c in checks.values())


def run(workload: str, seed: int, seconds: float, trace: bool, t0: float,
        require_chip: bool = True, root=ROOT, fault=None,
        keep_trace: str | None = None, out=None, err=None) -> int:
    out = out or sys.stdout
    err = err or sys.stderr

    def log(msg):
        print(msg, file=err, flush=True)

    spec = Spec(workload, root)
    import jax
    from repro.launch import env as launch_env
    launch_env.configure()
    devices = jax.devices()
    if require_chip and (devices[0].platform != 'tpu'
                         or len(devices) < spec.chips):
        log(f'bench: needs {spec.chips} TPU chip(s); JAX found '
            f'{len(devices)} {devices[0].platform!r} device(s)')
        return 3
    devices = devices[:spec.chips]
    counter = CompileCounter()
    cell = spec.adapter().Cell(spec.config, spec.traffic, seed, fault)

    # ---- set-up: build, compile, and the checked first segment ----------
    carry, prog = first_segment(cell)
    built = counter.snapshot()
    t_window = time.time()
    setup_s = t_window - t0
    log(f'bench: {workload} seed {seed}: set-up {setup_s:.3f} s '
        f'(build compiles {built["compiles"]}, persistent-cache hits '
        f'{built["cache_hits"]}, misses {built["cache_misses"]})')

    # ---- the measured window ----------------------------------------------
    trace_dir = None
    tracing = trace
    if trace:
        trace_dir = keep_trace or tempfile.mkdtemp(prefix='bench_trace_')
        jax.profiler.start_trace(trace_dir)
    before = counter.snapshot()
    rounds = failed = traced_rounds = 0
    telemetry = []
    index = 1
    w0 = time.perf_counter()
    while True:
        with _annotate(tracing, 'bench/segment'):
            carry, losses = cell.dispatch(carry, index)
        with _annotate(tracing, 'bench/flush'):
            carry, rows = cell.flush(carry)
            seg_losses = [float(x) for x in np.asarray(losses)]
        index += 1
        rounds += len(seg_losses)
        failed += sum(1 for x in seg_losses if not math.isfinite(x))
        if tracing:
            telemetry.extend(rows)
            traced_rounds += len(seg_losses)
            if index > TRACED_SEGMENTS:
                jax.profiler.stop_trace()
                tracing = False
        if time.perf_counter() - w0 >= seconds:
            break
    jax.block_until_ready(carry)
    window_s = time.perf_counter() - w0
    if tracing:
        jax.profiler.stop_trace()
    after = counter.snapshot()
    in_window = after['compiles'] - before['compiles']
    peak = _peak_bytes(devices)
    log(f'bench: window {window_s:.3f} s, {rounds} rounds, compiles in '
        f'window {in_window}, peak bytes {peak}')

    metrics, breakdown, device_extra = {}, None, {}
    if trace:
        from bench.harness import trace as trace_mod
        hlo = cell.hlo_text()
        if keep_trace is not None:
            with open(f'{keep_trace}/program.hlo.txt', 'w') as f:
                f.write(hlo)
        ctx = trace_mod.Context(spec, cell, trace_dir, traced_rounds,
                                devices, telemetry, hlo)
        for m in spec.per_layer():
            value = spec.metric_reader(m['name']).read(ctx)
            if value is not None:
                metrics[m['name']] = {'value': value, 'unit': m['unit']}
        busy = ctx.busy_s()
        device_extra = {'busy_s': busy, 'window_s': ctx.traced_window_s()}
        breakdown = ctx.breakdown()
        if keep_trace is None:
            shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        values = {'rounds_per_s': rounds / window_s,
                  'peak_hbm_gib': peak / GIB,
                  'setup_s': setup_s}
        for m in spec.end_to_end():
            metrics[m['name']] = {'value': values[m['name']],
                                  'unit': m['unit']}

    # ---- correctness: free the program, run the reference, compare -------
    del carry, losses
    cell.release()
    gc.collect()
    _, checks, correct = compare(spec, cell, prog, log)
    if in_window:
        correct = False
        checks['compiles_in_window'] = {'value': in_window, 'limit': 0}
    for name, c in checks.items():
        log(f'check {name} {c["value"]!r} limit {c["limit"]!r} '
            f'{"ok" if c["value"] <= c["limit"] else "FAIL"}')

    dev = devices[0]
    result = {
        'correct': bool(correct), 'attempted': rounds, 'failed': failed,
        'metrics': metrics,
        'device': {'platform': dev.platform, 'kind': dev.device_kind,
                   'count': len(devices), 'memory_peak_bytes': peak,
                   **device_extra},
    }
    if breakdown is not None:
        result['breakdown'] = breakdown
    result['checks'] = checks
    print(json.dumps(result), file=out, flush=True)
    return 0
