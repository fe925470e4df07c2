"""Stage scopes, host spans and compile counters of the SP-FL round.

Three records, one per clock:

* **Traced scopes** (:func:`stage_scope`) — ``jax.named_scope``
  wrappers inside jitted code.  Every op of a stage carries
  ``obs/<stage>`` in its HLO ``op_name`` metadata, through nested jits,
  ``while_loop`` and ``scan`` bodies, so a profiler's device ops join to
  the stage by name.  Metadata only: no op is added.  ``STAGES`` lists
  exactly the scopes the round bodies emit.

* **Host spans** (:class:`StageTrace`) — named intervals on the host.
  Each span keeps its name, its parent span and its start and end in
  ``time.time_ns()``, the realtime clock behind the profiler's host
  events, and is written into the profiler as a
  ``jax.profiler.TraceAnnotation('obs/<name>')``.  Spans are kept in a
  buffer of the last ``SPAN_CAPACITY``; ``SPANS`` is the process-wide
  instance that :func:`span`, the telemetry flush and
  ``FLSimulator.trace`` record into.  A span around a dispatch times the
  dispatch, not the device.

* **Compile counters** (:func:`compile_counters`) — counts and seconds
  of jax's tracing, lowering and backend-compile events, summed from
  the moment ``repro.obs`` is imported by one ``jax.monitoring``
  listener.  An event nested in another of its kind (a jit traced
  inside another's trace) is part of the outer one and not counted
  again.
"""
from __future__ import annotations

import collections
import threading
import time
from contextlib import contextmanager
from typing import Any, Dict, List, NamedTuple, Optional

import jax

# the scopes the round bodies emit, in round order: gradient pass ->
# fading/cohort step -> eq. (28) solve -> transport stages -> update ->
# telemetry record and ring push
STAGES = ('grad', 'fading', 'alloc_solve', 'quantize_pack', 'corrupt_fold',
          'screen', 'decode_aggregate', 'psum', 'update', 'telemetry')


@contextmanager
def stage_scope(name: str):
    """Name a pipeline stage inside traced code: ``jax.named_scope`` so
    the ops carry ``obs/<name>`` in jaxprs, HLO metadata and profiler
    timelines.  No runtime cost; safe outside tracing too."""
    with jax.named_scope(f'obs/{name}'):
        yield


class Span(NamedTuple):
    """One finished host span; times are ``time.time_ns()``."""
    id: int
    name: str
    parent: Optional[int]     # id of the enclosing span, None at top
    start_ns: int
    end_ns: int

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9


SPAN_CAPACITY = 4096     # spans a StageTrace keeps (the newest)


class StageTrace:
    """Bounded record of host spans (the last ``SPAN_CAPACITY``).

    >>> tracer = StageTrace()
    >>> with tracer.span('flush'):
    ...     with tracer.span('flush.get'):
    ...         pass
    >>> [s.name for s in tracer.spans()]
    ['flush.get', 'flush']
    """

    def __init__(self) -> None:
        self._spans: collections.deque = collections.deque(
            maxlen=SPAN_CAPACITY)
        self._open = threading.local()
        self._next_id = 0
        self._lock = threading.Lock()

    @contextmanager
    def span(self, name: str):
        stack = self._open.__dict__.setdefault('stack', [])
        with self._lock:
            sid = self._next_id
            self._next_id += 1
        parent = stack[-1] if stack else None
        stack.append(sid)
        start = time.time_ns()
        try:
            with jax.profiler.TraceAnnotation(f'obs/{name}'):
                yield
        finally:
            end = time.time_ns()
            stack.pop()
            self._spans.append(Span(sid, name, parent, start, end))

    def mark(self) -> int:
        """The id the next span will get: ``spans(since=mark())`` later
        holds only the spans opened after this call."""
        with self._lock:
            return self._next_id

    def spans(self, name: Optional[str] = None,
              since: int = 0) -> List[Span]:
        """Finished spans in the order they ended (a child before its
        parent), optionally only those called ``name`` or opened at or
        after the :meth:`mark` ``since``."""
        return [s for s in list(self._spans)
                if s.id >= since and (name is None or s.name == name)]

    def summary(self, since: int = 0) -> Dict[str, Dict[str, Any]]:
        """Per name: count, total, last and mean seconds of the kept
        spans opened at or after the :meth:`mark` ``since``."""
        out: Dict[str, Dict[str, Any]] = {}
        for s in self.spans(since=since):
            d = out.setdefault(s.name, {'count': 0, 'total_s': 0.0})
            d['count'] += 1
            d['total_s'] += s.seconds
            d['last_s'] = s.seconds
        for d in out.values():
            d['mean_s'] = d['total_s'] / d['count']
        return out

    def reset(self) -> None:
        self._spans.clear()


SPANS = StageTrace()


def span(name: str):
    """A span of the process-wide record ``SPANS``."""
    return SPANS.span(name)


# ---------------------------------------------------------------------------
# compile counters
# ---------------------------------------------------------------------------

COMPILE_EVENTS = {
    '/jax/core/compile/jaxpr_trace_duration': 'trace',
    '/jax/core/compile/jaxpr_to_mlir_module_duration': 'lower',
    '/jax/core/compile/backend_compile_duration': 'backend_compile',
}


class _CompileCounters:
    """Per kind: count and seconds of the outermost events.  jax records
    an event when it ends, so an event that ends inside the next one's
    interval was nested in it and is folded into it."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._outer = {k: [] for k in COMPILE_EVENTS.values()}

    def __call__(self, event: str, duration_secs: float, **_) -> None:
        kind = COMPILE_EVENTS.get(event)
        if kind is None:
            return
        end = time.perf_counter()
        with self._lock:
            outer = self._outer[kind]
            while outer and outer[-1][0] >= end - duration_secs:
                outer.pop()
            outer.append((end, duration_secs))

    def snapshot(self) -> Dict[str, Dict[str, float]]:
        with self._lock:
            return {kind: {'count': len(ev),
                           'seconds': sum(d for _, d in ev)}
                    for kind, ev in self._outer.items()}


_COMPILES = _CompileCounters()
jax.monitoring.register_event_duration_secs_listener(_COMPILES)


def compile_counters() -> Dict[str, Dict[str, float]]:
    """``{kind: {'count', 'seconds'}}`` for ``trace``, ``lower`` and
    ``backend_compile``, plus ``total_s``: the three kinds' seconds."""
    snap: Dict[str, Any] = _COMPILES.snapshot()
    snap['total_s'] = sum(v['seconds'] for v in snap.values())
    return snap
