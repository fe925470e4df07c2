"""The reduction from a trace to device busy time, module attribution and
roofline shares."""
import gzip
from pathlib import Path

import pytest

from bench.harness import trace as T

DATA = Path(__file__).resolve().parent / 'data'


def test_union_and_merge_of_intervals():
    iv = [(0.0, 1.0), (0.5, 2.0), (3.0, 4.0), (3.5, 3.6)]
    assert T.union_length(iv) == pytest.approx(3.0)
    assert T.merged(iv) == [[0.0, 2.0], [3.0, 4.0]]


def test_self_time_subtracts_nested_events():
    ev = [('while', 0.0, 10.0), ('a', 1.0, 3.0), ('b', 4.0, 5.0),
          ('c', 12.0, 13.0)]
    got = dict(T.self_times(ev))
    assert got == pytest.approx({'while': 7.0, 'a': 2.0, 'b': 1.0,
                                 'c': 1.0})


def test_hlo_metadata_maps_instructions_to_source_files():
    text = (
        'HloModule jit_f\n'
        '  %fusion.3 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, '
        'calls=%fused_computation.3, metadata={op_name="jit(f)/mul" '
        'source_file="/x/src/repro/models/cnn.py" source_line=40}\n'
        '  ROOT %custom-call.1 = u32[4]{0} custom-call(%fusion.3), '
        'custom_call_target="tpu_custom_call", metadata={op_name="jit(f)/'
        'pallas_call" source_file="/x/src/repro/kernels/ops.py" '
        'source_line=7}\n'
        '  %copy.2 = f32[8]{0} copy(%fusion.3)\n')
    src = T.hlo_sources(text)
    assert src['fusion.3'][0].endswith('repro/models/cnn.py')
    assert src['custom-call.1'][0].endswith('repro/kernels/ops.py')
    assert 'copy.2' not in src


class _FakeTrace:
    """Two devices' op intervals and host spans, in seconds."""

    def __init__(self):
        self.spans = [('bench/segment', 0.0, 6.0), ('bench/flush', 6.0, 10.0)]
        self.devices = [[('fusion.3', 1.0, 4.0), ('custom-call.1', 4.0, 5.0)],
                        [('fusion.3', 1.0, 5.0)]]
        self.t0, self.t1 = 0.0, 10.0

    window_s = T.Trace.window_s
    _clipped = T.Trace._clipped
    busy_s = T.Trace.busy_s
    op_self_times = T.Trace.op_self_times
    idle_gaps = T.Trace.idle_gaps


def _ctx(rounds=4):
    class Cell:
        pass
    return T.Context(None, Cell(), None, rounds, [object(), object()],
                     hlo_text=(
                         '%fusion.3 = f32[] fusion(), metadata={op_name="a" '
                         'source_file="/c/src/repro/models/cnn.py"}\n'
                         '%custom-call.1 = u32[] custom-call(), metadata={'
                         'op_name="b" source_file="/c/src/repro/wire/'
                         'pack_kernel.py"}\n'),
                     trace=_FakeTrace())


def test_busy_idle_and_module_time_on_a_known_trace():
    ctx = _ctx()
    assert ctx.busy_s() == pytest.approx(4.0)        # (4 + 4) / 2 devices
    assert ctx.idle_share() == pytest.approx(0.6)
    assert ctx.module_seconds(['repro/models/']) == pytest.approx(3.5)
    assert ctx.module_seconds(['repro/wire/']) == pytest.approx(0.5)
    assert ctx.module_seconds(['repro/core/']) is None
    assert ctx.per_round_ms(0.5) == pytest.approx(125.0)
    gaps = ctx.trace.idle_gaps()
    assert gaps[0] == ('bench/flush', pytest.approx(5.0))
    assert gaps[1] == ('bench/segment', pytest.approx(1.0))
    out = ctx.breakdown()
    assert out['device_ops'][0][0].startswith('fusion.3 (models/cnn.py)')


def test_recorded_chip_trace_reduces_to_sane_numbers():
    """The first 20,000 device ops of a traced window of the paper CNN's
    packed segment, recorded on one TPU v5e (seed 61), with the benchmark's
    host spans, and the compiled segment's HLO text (same program, seed
    11): its stack-frame tables name the file and function behind every
    op."""
    import json
    ex = json.loads(gzip.decompress(
        (DATA / 'trace_extract.json.gz').read_bytes()))
    tr = T.Trace.from_events(ex['ops'], ex['spans'], ex['t0'], ex['t1'])
    hlo = gzip.decompress((DATA / 'segment.hlo.txt.gz').read_bytes())
    sources = T.hlo_sources(hlo.decode())
    assert 0 < tr.busy_s() <= tr.window_s()
    times = tr.op_self_times()
    assert sum(times.values()) == pytest.approx(tr.busy_s(), rel=1e-6)

    def share(fragments):
        return sum(t for op, t in times.items()
                   if any(f in sources.get(op, ('', ''))[0]
                          for f in fragments)) / sum(times.values())
    assert share(['repro/']) > 0.95
    grad = json.loads((DATA.parents[1] / 'metrics/grad_ms.json').read_text())
    alloc = json.loads((DATA.parents[1] / 'metrics/alloc_ms.json').read_text())
    assert 0.5 < share(grad['modules']) < 0.95
    assert 0.05 < share(alloc['modules']) < 0.5
