"""The per-layer metrics that read what the program records about itself:
its stage scopes (`alloc_step_us`, `unscoped_ms`), its solver step counter
(`alloc_steps`), its host spans (`flush_host_ms`) and its compile counters
(`compile_s`), on synthetic traces, rows and span records."""
from types import SimpleNamespace

import pytest

from bench.harness import trace as T
from bench.harness.spec import ROOT, Spec

CELL = 'paper-cnn.k20.packed'
SEG = 'jit(<lambda>)/while/body/closed_call'


def _hlo(ops):
    return ''.join(f'%{op} = f32[] fusion(), metadata={{op_name="{name}"}}\n'
                   for op, name in ops)


def _ctx(ops, names, rows=(), rounds=2):
    """A one-device context: ``ops`` are (HLO op, start, end) device
    events, ``names`` the op_name of each op in the segment's HLO."""
    class Cell:
        pass
    tr = T.Trace.from_events(ops, [], 0.0, 100.0)
    return T.Context(None, Cell(), None, rounds, [object()],
                     telemetry=list(rows), hlo_text=_hlo(names.items()),
                     trace=tr)


def _read(metric, ctx):
    return Spec(CELL, ROOT).metric_reader(metric).read(ctx)


ROUND = {
    'fusion.1': f'{SEG}/obs/grad/jit(per_client_grads)/conv',
    'fusion.2': f'{SEG}/obs/alloc_solve/while/body/obs/x/mul',
    'fusion.3': f'{SEG}/obs/alloc_solve/while/body/add',
    'while.4': 'jit(<lambda>)/while',
    'copy.5': SEG,
    'fusion.6': f'{SEG}/jit(run_transport)/obs/decode_aggregate/add',
}
EVENTS = [('while.4', 0.0, 10.0), ('fusion.1', 1.0, 3.0),
          ('fusion.2', 3.0, 4.0), ('fusion.3', 4.0, 4.5),
          ('copy.5', 5.0, 5.25), ('fusion.6', 6.0, 7.0),
          ('fusion.9', 8.0, 9.0)]          # in no program's HLO here


def _row(steps):
    return SimpleNamespace(alloc_steps=steps, alloc_iters=1)


def test_alloc_steps_is_the_mean_over_rounds_that_solved():
    ctx = _ctx(EVENTS, ROUND, rows=[_row(0), _row(3024), _row(9072)])
    assert _read('alloc_steps', ctx) == pytest.approx(6048.0)


def test_alloc_steps_none_without_a_solved_round_or_the_field():
    assert _read('alloc_steps', _ctx(EVENTS, ROUND, [_row(0)])) is None
    old = SimpleNamespace(alloc_iters=2)
    assert _read('alloc_steps', _ctx(EVENTS, ROUND, [old])) is None


def test_alloc_step_us_divides_scoped_time_by_the_steps():
    ctx = _ctx(EVENTS, ROUND, rows=[_row(500), _row(1000)])
    # fusion.2 (1 s, nested scope) + fusion.3 (0.5 s) over 1500 steps
    assert _read('alloc_step_us', ctx) == pytest.approx(1e6 * 1.5 / 1500)


def test_alloc_step_us_none_without_scope_or_steps():
    bare = {op: name.replace('/obs/alloc_solve', '')
            for op, name in ROUND.items()}
    assert _read('alloc_step_us', _ctx(EVENTS, bare, [_row(10)])) is None
    assert _read('alloc_step_us', _ctx(EVENTS, ROUND, [_row(0)])) is None


def test_unscoped_ms_counts_the_segment_ops_under_no_scope():
    ctx = _ctx(EVENTS, ROUND, rounds=2)
    # while.4's self time is 10 s less the 5.75 s of the ops nested in
    # it, plus copy.5's 0.25 s; fusion.9 is not in the segment's HLO
    assert _read('unscoped_ms', ctx) == pytest.approx(1e3 * 4.5 / 2)


def test_unscoped_ms_none_when_the_round_has_no_scopes():
    bare = {op: name.replace('obs/grad/', '') for op, name in ROUND.items()}
    assert _read('unscoped_ms', _ctx(EVENTS, bare)) is None


def _span_record(spans):
    return SimpleNamespace(spans=lambda: list(spans))


def _flush(sid, start, get_end, end):
    S = SimpleNamespace
    return [S(id=sid + 1, name='flush.get', parent=sid, start_ns=start,
              end_ns=get_end),
            S(id=sid + 2, name='flush.unstack', parent=sid,
              start_ns=get_end, end_ns=end),
            S(id=sid, name='flush', parent=None, start_ns=start,
              end_ns=end)]


def test_flush_host_ms_is_the_median_tail_after_the_get(monkeypatch):
    from repro.obs import trace as obs_trace
    spans = (_flush(0, 0, 4_000_000, 4_200_000)
             + _flush(10, 9_000_000, 13_000_000, 13_500_000)
             + _flush(20, 20_000_000, 24_000_000, 24_100_000))
    monkeypatch.setattr(obs_trace, 'SPANS', _span_record(spans))
    assert _read('flush_host_ms', _ctx(EVENTS, ROUND)) == pytest.approx(0.2)


def test_flush_host_ms_none_without_flush_or_record(monkeypatch):
    from repro.obs import trace as obs_trace
    monkeypatch.setattr(obs_trace, 'SPANS', _span_record([]))
    assert _read('flush_host_ms', _ctx(EVENTS, ROUND)) is None
    monkeypatch.delattr(obs_trace, 'SPANS')
    assert _read('flush_host_ms', _ctx(EVENTS, ROUND)) is None


def test_compile_s_reads_the_program_counters(monkeypatch):
    from repro.obs import trace as obs_trace
    snap = {'trace': {'count': 3, 'seconds': 1.5},
            'lower': {'count': 1, 'seconds': 2.0},
            'backend_compile': {'count': 1, 'seconds': 30.0},
            'total_s': 33.5}
    monkeypatch.setattr(obs_trace, 'compile_counters', lambda: snap)
    assert _read('compile_s', _ctx(EVENTS, ROUND)) == pytest.approx(33.5)
    none = {k: {'count': 0, 'seconds': 0.0}
            for k in ('trace', 'lower', 'backend_compile')}
    monkeypatch.setattr(obs_trace, 'compile_counters',
                        lambda: dict(none, total_s=0.0))
    assert _read('compile_s', _ctx(EVENTS, ROUND)) is None
    monkeypatch.delattr(obs_trace, 'compile_counters')
    assert _read('compile_s', _ctx(EVENTS, ROUND)) is None
