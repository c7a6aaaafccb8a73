"""Unified observability layer (DESIGN.md §Observability).

One measurement substrate for the whole stack — the paper's headline claims
are *efficiency* claims, so every subsystem reports through the same three
primitives instead of private dicts of ``perf_counter()`` bookkeeping:

* :mod:`repro.obs.metrics` — process-local registry of counters, gauges, and
  fixed-bucket histograms.  Thread-safe (the serving engine's submit path),
  ``snapshot()`` returns plain dicts, near-zero cost when no registry is
  installed.
* :mod:`repro.obs.events` — structured JSONL event sink: schema-versioned
  records with monotonic timestamps, run id, git sha, and device/mesh info.
  The single durable record of a run (train loop + serving engine both emit
  through it).
* :mod:`repro.obs.trace` — ``jax.profiler`` ``TraceAnnotation`` /
  ``named_scope`` wrappers gated by ``REPRO_TRACE``; compile-time no-ops
  when off.  Wrapped around the kernel dispatch boundary, the cp carry
  exchange / ring-flash rotation, every host phase of the engine's tick
  and the packer, so an xprof trace attributes device time and idle gaps
  to named phases; garbage collections and JAX's trace / compile phases
  get spans of their own while tracing is on.
* :mod:`repro.obs.export` — Prometheus-style text exposition + JSON
  snapshot, served from ``launch/serve.py`` and dumped at loop exit from
  ``train/loop.py``.
"""

from repro.obs.events import (
    EventLog,
    read_events,
    run_metadata,
    use_events,
    validate_event,
    validate_events,
)
from repro.obs.export import (
    prometheus_text,
    serve_metrics,
    snapshot_document,
    write_snapshot,
)
from repro.obs.metrics import MetricsRegistry, use_metrics
from repro.obs.trace import span, trace_enabled

__all__ = [
    "EventLog",
    "MetricsRegistry",
    "prometheus_text",
    "read_events",
    "run_metadata",
    "serve_metrics",
    "snapshot_document",
    "span",
    "trace_enabled",
    "use_events",
    "use_metrics",
    "validate_event",
    "validate_events",
    "write_snapshot",
]
