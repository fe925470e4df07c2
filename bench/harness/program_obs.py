"""Readers of what the program records about itself (`repro.obs`): the
stage scopes that name its device ops, the solver step counter in its
telemetry ring, its host spans and its compile counters.  A program that
lacks one of them reads as None and does not raise."""
from __future__ import annotations

import importlib


def in_scope(op_name: str, scope: str) -> bool:
    """Whether an HLO ``op_name`` lies under the scope ``obs/<scope>``."""
    return f'/obs/{scope}/' in f'/{op_name}/'


def segment_ops(ctx):
    """[(HLO op, op_name, device self seconds)] of the traced window's ops
    that the compiled segment's HLO names."""
    return [(op, ctx.sources[op][1], t) for op, t in ctx._self.items()
            if op in ctx.sources]


def scope_seconds(ctx, scope: str):
    """Device self seconds of the segment's ops under ``obs/<scope>``;
    None when no op lies there."""
    times = [t for _, name, t in segment_ops(ctx) if in_scope(name, scope)]
    return sum(times) if times else None


def unscoped_seconds(ctx):
    """Device self seconds of the segment's ops under no ``obs/`` scope;
    None when the program does not scope its round (no op under
    ``obs/grad``), where every stage would read as unscoped."""
    ops = segment_ops(ctx)
    if not any(in_scope(name, 'grad') for _, name, _ in ops):
        return None
    return sum(t for _, name, t in ops if '/obs/' not in f'/{name}')


def alloc_steps(ctx):
    """``alloc_steps`` of every traced round, oldest first; None when the
    program's telemetry has no such field."""
    steps = [getattr(r, 'alloc_steps', None) for r in ctx.telemetry]
    if not steps or any(s is None for s in steps):
        return None
    return [float(s) for s in steps]


def _trace_module():
    return importlib.import_module('repro.obs.trace')


def program_spans():
    """The program's finished host spans (``repro.obs.trace.SPANS``), or
    None when it keeps no span record."""
    record = getattr(_trace_module(), 'SPANS', None)
    spans = getattr(record, 'spans', None)
    return None if spans is None else spans()


def flush_tail_ms(spans):
    """Per ``flush`` span, the host milliseconds after its ``flush.get``
    child ended: unstacking the records and resetting the ring."""
    gets = {s.parent: s for s in spans if s.name == 'flush.get'}
    return [(s.end_ns - gets[s.id].end_ns) * 1e-6 for s in spans
            if s.name == 'flush' and s.id in gets]


def compile_seconds():
    """The program's trace + lower + backend-compile seconds so far
    (``repro.obs.trace.compile_counters``); None when it counts nothing."""
    counters = getattr(_trace_module(), 'compile_counters', None)
    if counters is None:
        return None
    snap = counters()
    if not any(snap[k]['count'] for k in ('trace', 'lower',
                                          'backend_compile')):
        return None
    return snap['total_s']
