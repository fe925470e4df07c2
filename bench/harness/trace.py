"""Reduction of one traced window to device busy time, idle gaps and
per-module device time.

Device time comes from the profiler's device planes (one per chip): the
events of the line that carries the XLA ops, each named by its HLO
instruction.  An instruction is attributed to a repository module by its
metadata in the compiled program's HLO text: a `source_file`, or a
`stack_frame_id` into the module's frame tables (a fusion carries the
metadata of its root), so per-layer time needs no scope inside the
program.  Host spans written by the benchmark (`bench/segment`,
`bench/flush`) bound the window and name what the host did in each idle
gap.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

HOST_SPANS = ('bench/segment', 'bench/flush')
OPS_LINE = 'XLA Ops'

_INSTR = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$')
_SOURCE = re.compile(r'source_file="([^"]*)"')
_OPNAME = re.compile(r'op_name="([^"]*)"')
_FRAME = re.compile(r'stack_frame_id=(\d+)')
_TABLE_ROW = re.compile(r'^(\d+) (.*)$')
_FIELD = re.compile(r'(\w+)=(\d+)')


def _tables(hlo_text: str) -> dict:
    """The module's FileNames, FileLocations and StackFrames tables (the
    form in which recent XLA prints source locations: instructions name a
    `stack_frame_id`)."""
    tables, current = {}, None
    for line in hlo_text.splitlines():
        if line in ('FileNames', 'FunctionNames', 'FileLocations',
                    'StackFrames'):
            current = tables.setdefault(line, {})
            continue
        m = _TABLE_ROW.match(line) if current is not None else None
        if not m:
            current = None
            continue
        body = m.group(2)
        current[int(m.group(1))] = (body.strip('"') if body.startswith('"')
                                    else dict((k, int(v)) for k, v in
                                              _FIELD.findall(body)))
    return tables


def _frame_source(tables: dict, frame: int) -> str:
    """'<file>:<function>' of the innermost frame of a stack that lies in
    the repository package (`repro/`), else of the innermost frame."""
    files = tables.get('FileNames', {})
    names = tables.get('FunctionNames', {})
    locs = tables.get('FileLocations', {})
    frames = tables.get('StackFrames', {})
    first, seen = '', set()
    while frame in frames and frame not in seen:
        seen.add(frame)
        loc = locs.get(frames[frame].get('file_location_id'), {})
        where = (f"{files.get(loc.get('file_name_id'), '')}:"
                 f"{names.get(loc.get('function_name_id'), '')}")
        if 'repro/' in where:
            return where
        first = first or where
        frame = frames[frame].get('parent_frame_id')
    return first


def hlo_sources(hlo_text: str) -> dict:
    """HLO instruction name -> (source, op_name) from its metadata: the
    `source_file`, or '<file>:<function>' of its `stack_frame_id`."""
    tables = _tables(hlo_text)
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if not m or 'metadata={' not in line:
            continue
        src = _SOURCE.search(line)
        frame = _FRAME.search(line)
        op = _OPNAME.search(line)
        path = (src.group(1) if src else
                _frame_source(tables, int(frame.group(1))) if frame else '')
        out[m.group(1)] = (path, op.group(1) if op else '')
    return out


def op_name(event_name: str) -> str:
    """A device op event's HLO instruction name: TPU traces name the
    event by the instruction's text, '%fusion.3 = f32[...] fusion(...)'."""
    head = event_name.split(' = ', 1)[0].strip()
    return head[1:] if head.startswith('%') else head


def union_length(intervals) -> float:
    """Measure of the union of (start, end) intervals."""
    total, end = 0.0, None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def merged(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def self_times(events):
    """(name, start, end) events of one line -> [(name, self seconds)]:
    an event's time minus that of events nested inside it."""
    out = []
    stack = []  # [name, start, end, child time]
    for name, s, e in sorted(events, key=lambda x: (x[1], -x[2])):
        while stack and s >= stack[-1][2]:
            n0, s0, e0, c0 = stack.pop()
            out.append((n0, (e0 - s0) - c0))
        if stack:
            stack[-1][3] += e - s
        stack.append([name, s, e, 0.0])
    while stack:
        n0, s0, e0, c0 = stack.pop()
        out.append((n0, (e0 - s0) - c0))
    return out


class Trace:
    """The parsed xplane of one traced window (times in seconds)."""

    def __init__(self, path: str, n_devices: int = 1):
        from jax.profiler import ProfileData
        data = ProfileData.from_file(path)
        self.spans = []      # (name, start, end) host spans of the bench
        self.devices = []    # per device: [(hlo op, start, end)]
        dev_planes = []
        for plane in data.planes:
            if plane.name.startswith('/host:'):
                for line in plane.lines:
                    for ev in line.events:
                        if ev.name in HOST_SPANS:
                            self.spans.append(
                                (ev.name, ev.start_ns * 1e-9,
                                 (ev.start_ns + ev.duration_ns) * 1e-9))
            elif re.match(r'/device:TPU:\d+$', plane.name):
                dev_planes.append(plane)
        dev_planes.sort(key=lambda p: int(p.name.rsplit(':', 1)[1]))
        for plane in dev_planes[:n_devices]:
            ops = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    ops.append((op_name(ev.name), ev.start_ns * 1e-9,
                                (ev.start_ns + ev.duration_ns) * 1e-9))
            self.devices.append(ops)
        if self.spans:
            self.t0 = min(s for _, s, _ in self.spans)
            self.t1 = max(e for _, _, e in self.spans)
        else:
            every = [t for ops in self.devices for _, s, e in ops
                     for t in (s, e)]
            self.t0, self.t1 = (min(every), max(every)) if every else (0, 0)

    @classmethod
    def from_events(cls, ops, spans, t0, t1):
        """A one-device trace from already extracted events (tests)."""
        tr = cls.__new__(cls)
        tr.devices = [[tuple(o) for o in ops]]
        tr.spans = [tuple(s) for s in spans]
        tr.t0, tr.t1 = t0, t1
        return tr

    def window_s(self) -> float:
        return self.t1 - self.t0

    def _clipped(self, ops):
        for name, s, e in ops:
            s, e = max(s, self.t0), min(e, self.t1)
            if e > s:
                yield name, s, e

    def busy_s(self) -> float:
        """Union of device op intervals in the window, averaged over the
        devices."""
        if not self.devices:
            return 0.0
        return sum(union_length([(s, e) for _, s, e in self._clipped(ops)])
                   for ops in self.devices) / len(self.devices)

    def op_self_times(self) -> dict:
        """HLO op name -> device self seconds in the window, averaged over
        the devices."""
        total = defaultdict(float)
        for ops in self.devices:
            for name, t in self_times(list(self._clipped(ops))):
                total[name] += t / len(self.devices)
        return dict(total)

    def idle_gaps(self):
        """[(what the host was doing, seconds)] for every gap between
        merged busy intervals of the first device, longest first."""
        if not self.devices:
            return []
        busy = merged([(s, e) for _, s, e in self._clipped(self.devices[0])])
        edges = [self.t0] + [t for iv in busy for t in iv] + [self.t1]
        gaps = []
        for a, b in zip(edges[0::2], edges[1::2]):
            if b <= a:
                continue
            mid = 0.5 * (a + b)
            what = 'other'
            for name, s, e in self.spans:
                if s <= mid <= e:
                    what = name
            gaps.append((what, b - a))
        gaps.sort(key=lambda g: -g[1])
        return gaps


def find_xplane(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, '**', '*.xplane.pb'),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f'no .xplane.pb under {trace_dir}')
    return max(files, key=os.path.getmtime)


class Context:
    """What a per-layer metric reader may read from one traced run."""

    def __init__(self, spec, cell, trace_dir, rounds, devices,
                 telemetry=None, hlo_text=None, trace=None):
        self.spec, self.cell = spec, cell
        self.rounds = rounds
        self.devices = devices
        self.telemetry = telemetry or []
        self.trace = trace or Trace(find_xplane(trace_dir), len(devices))
        self.sources = hlo_sources(hlo_text if hlo_text is not None
                                   else cell.hlo_text())
        self._self = self.trace.op_self_times()

    # -- device time --------------------------------------------------------
    def traced_window_s(self) -> float:
        return self.trace.window_s()

    def busy_s(self) -> float:
        return self.trace.busy_s()

    def idle_share(self) -> float | None:
        w = self.traced_window_s()
        return None if w <= 0 else 1.0 - self.busy_s() / w

    def module_of(self, op: str) -> str:
        return self.sources.get(op, ('', ''))[0]

    def module_seconds(self, fragments) -> float | None:
        """Device self seconds of ops whose source file matches one of
        ``fragments``; None when no op in the window matches."""
        total, hit = 0.0, False
        for op, t in self._self.items():
            src = self.module_of(op)
            if any(f in src for f in fragments):
                total += t
                hit = True
        return total if hit else None

    def per_round_ms(self, seconds) -> float | None:
        if seconds is None or self.rounds <= 0:
            return None
        return 1e3 * seconds / self.rounds

    # -- the breakdown the ledger keeps ---------------------------------------
    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self._self.items(), key=lambda kv: -kv[1])[:top]
        return {
            'device_ops': [[f'{name} ({self._short(name)})', t]
                           for name, t in ops],
            'idle_gaps': [[n, t] for n, t in self.trace.idle_gaps()[:top]],
        }

    def _short(self, op: str) -> str:
        src = self.module_of(op).split(':', 1)[0]
        if not src:
            return 'other'
        i = src.find('repro/')
        return src[i + len('repro/'):] if i >= 0 else os.path.basename(src)
