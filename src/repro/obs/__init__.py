"""Telemetry plane: span tracing, idle attribution, metrics registry.

Three pieces, no third-party deps:

* :mod:`repro.obs.trace` — span/instant tracing on the sanitizer's
  detached-seam pattern (one module-flag read per site when off), with
  Chrome trace-event JSON export (Perfetto / chrome://tracing).
* :mod:`repro.obs.idle` — per-lane gap classification into the paper's
  two idle classes (task-dependency vs straggler) plus pipeline-fill
  warmup, from a captured trace.
* :mod:`repro.obs.metrics` — counters / gauges / fixed-bucket histograms
  behind one :class:`MetricsRegistry`, replacing scattered ad-hoc
  accounting; snapshots ride ``BENCH_*.json`` records.
* :mod:`repro.obs.clock` — the blessed wall-clock (``now()``) for
  instrumented hot paths (lint rule RP002 requires it there).
* :mod:`repro.obs.scopes` — the named scopes of the pod round program
  and the table placing a compiled round's instructions in them.
"""
from .clock import now  # noqa: F401
from .metrics import Counter, Gauge, Histogram, MetricsRegistry  # noqa: F401
from .trace import (Tracer, attach, detach, emit_instant,  # noqa: F401
                    emit_span, span, traced, validate_chrome_trace)
from .idle import attribute_idle  # noqa: F401
