# Zero-sync round telemetry: typed per-round records (record.py), the
# on-device ring buffer (ringbuf.py), host-side metrics channels
# (metrics.py), stage scopes, host spans and compile counters
# (trace.py), and the JSONL sink + run manifest (sink.py).  See
# obs/README.md for the schema and the zero-sync contract.  Importing
# the package registers the compile-counter listener.
from repro.obs.record import (  # noqa: F401
    SCALAR_KEYS, VECTOR_KEYS, RoundTelemetry, round_scalars,
    sign_agreement, to_row,
)
from repro.obs.ringbuf import (  # noqa: F401
    TelemetryRing, flush, push, ring_init, ring_push,
)
from repro.obs.metrics import (  # noqa: F401
    Counter, Gauge, MetricsRegistry, ReservoirHistogram,
)
from repro.obs.trace import (  # noqa: F401
    SPANS, STAGES, Span, StageTrace, compile_counters, span, stage_scope,
)
from repro.obs.sink import (  # noqa: F401
    JsonlSink, config_hash, git_sha, read_jsonl, run_manifest,
)
