"""On-device telemetry ring buffer — the zero-sync half of the obs layer.

The training loop pushes one :class:`~repro.obs.record.RoundTelemetry`
per round into a fixed-capacity ring of stacked device arrays.  The push
is ONE jitted ``dynamic_update_index_in_dim`` over the record's pytree —
no host transfer, no ``float()``, nothing the transfer guard can object
to — so a non-flush round costs a single async dispatch.  Only
:func:`flush` crosses to the host, with ONE ``jax.device_get`` of the
whole buffer, amortized over ``capacity`` rounds.

The ring is itself a pytree (buffer + write index), so it threads through
``jax.lax.scan`` as carry state — which is exactly what the ROADMAP's
fully-fused multi-round round needs: telemetry that accumulates on device
across scanned rounds and surfaces once at the end.

Records pushed into one ring must share a treedef (same transport /
channel / collective configuration — ``None`` fields are structural), and
the capacity must cover the flush cadence: pushing more than ``capacity``
records between flushes wraps and overwrites the oldest (``flush``
returns the surviving window, oldest first).
"""
from __future__ import annotations

from typing import Any, List, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.obs.trace import span


class TelemetryRing(NamedTuple):
    """Device-resident ring: ``buf`` holds each record leaf stacked to
    ``(capacity, *leaf.shape)``; ``idx`` counts total pushes (slot =
    ``idx % capacity``, static from the leaf shapes)."""
    buf: Any            # pytree of (capacity, ...) device arrays
    idx: jax.Array      # int32 scalar — total records pushed

    @property
    def capacity(self) -> int:
        return jax.tree.leaves(self.buf)[0].shape[0]


def ring_init(proto, capacity: int) -> TelemetryRing:
    """A fresh ring shaped after ``proto`` (a record of device arrays —
    typically round 0's).  Zeros-allocated on device; no host data."""
    assert capacity >= 1, capacity
    buf = jax.tree.map(
        lambda x: jnp.zeros((capacity,) + jnp.shape(x),
                            jnp.asarray(x).dtype), proto)
    return TelemetryRing(buf, jnp.zeros((), jnp.int32))


def ring_init_abstract(proto_sds, capacity: int) -> TelemetryRing:
    """``ring_init`` from a ``jax.eval_shape`` record prototype.

    The fused multi-round scan needs the ring in the scan carry BEFORE
    any round has produced a concrete record; the round body's record
    structure is known abstractly (``jax.eval_shape(round_core, ...)``),
    and this builds the matching zeroed ring from the ShapeDtypeStruct
    pytree without tracing or running anything.
    """
    assert capacity >= 1, capacity
    buf = jax.tree.map(
        lambda s: jnp.zeros((capacity,) + tuple(s.shape), s.dtype),
        proto_sds)
    return TelemetryRing(buf, jnp.zeros((), jnp.int32))


def ring_push(ring: TelemetryRing, rec) -> TelemetryRing:
    """Write ``rec`` into the next slot.  Pure + traceable — the round
    interior's only telemetry op."""
    cap = ring.capacity
    slot = jax.lax.rem(ring.idx, jnp.int32(cap))
    buf = jax.tree.map(
        lambda b, x: jax.lax.dynamic_update_index_in_dim(
            b, jnp.asarray(x).astype(b.dtype), slot, 0),
        ring.buf, rec)
    return TelemetryRing(buf, ring.idx + 1)


# one compiled push per (treedef, shapes); reused across rounds and runs.
# The ring argument is DONATED so XLA updates the buffer in place — without
# donation every push copies the full (capacity, ...) buffer, which is
# exactly the overhead the ring exists to avoid.  Callers must rebind
# (``ring = push(ring, rec)``) and never touch the old ring again.
push = jax.jit(ring_push, donate_argnums=0)


def flush(ring: TelemetryRing) -> Tuple[List[Any], TelemetryRing]:
    """Drain the ring: ONE device->host transfer of the stacked buffer,
    sliced into per-round host records (oldest first), plus a reset ring
    that reuses the device buffer.  The only obs call that syncs.

    Recorded as the host span ``flush`` with the children ``flush.get``
    (the transfer, which waits for the device), ``flush.unstack`` and
    ``flush.reset``."""
    with span('flush'):
        with span('flush.get'):
            buf, idx = jax.device_get((ring.buf, ring.idx))
        with span('flush.unstack'):
            n = int(idx)
            cap = ring.capacity
            if n <= cap:
                order = range(n)
            else:                 # wrapped: oldest surviving slot first
                start = n % cap
                order = list(range(start, cap)) + list(range(start))
            rows = [jax.tree.map(lambda b, i=i: b[i], buf) for i in order]
        with span('flush.reset'):
            reset = TelemetryRing(ring.buf, jnp.zeros((), jnp.int32))
    return rows, reset
