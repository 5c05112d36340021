"""repro.obs — observability for the scheduler/serving stack (PR 8-9).

Six layers:

* :mod:`repro.obs.trace`   — :class:`ScheduleTrace`, the per-kernel
  admission/completion recorder every simulator feeds via ``trace=``;
  exports Chrome-trace-event JSON (Perfetto) and terminal Gantt.
* :mod:`repro.obs.metrics` — :class:`MetricsRegistry` with counters /
  gauges / histograms (seeded reservoir p50/p95/p99); the single sink
  behind ``ScheduleCache.stats()``, the composer counters, and the
  refiners' budget accounting.  Every timer is also a
  ``jax.profiler.TraceAnnotation`` span of its series' name, so the
  timed phases appear in a profiler trace on the device's clock; a
  timer's metadata (request id, position) rides on the span and is
  never a label.
* :mod:`repro.obs.profile` — phase-timing conventions
  (:data:`PHASES`: compose/guard/refine, execute and, inside it, each
  request's prefill, decode and argmax sync, audit) and
  :func:`phase_breakdown` for the per-step wall-clock view.
* :mod:`repro.obs.audit`   — :class:`QualityAuditor`, the online
  Fig.-1 sampler: served compositions scored against K seeded random
  orders under the step's own currency, with the paper's 90th
  percentile as a live SLO floor.
* :mod:`repro.obs.latency` — :class:`LatencyTracker` (per-request
  arrival→completion spans: queue wait to the start of prefill, each
  request's own execute seconds, shared compose; p50/p95/p99 and
  goodput) and :class:`DriftMonitor` (EWMA modelled-vs-revalidated
  replay drift per cache namespace).
* :mod:`repro.obs.export`  — :func:`prometheus_text` exposition for
  any registry and :class:`FlightRecorder`, the JSONL event log with
  a postmortem timeline loader.

Design contract: a ``None`` recorder is zero-cost (every hook is
``if trace is not None`` / ``if recorder is not None``) and an
attached recorder never changes modelled times or served tokens — it
only reads simulator state.  ``tests/test_obs.py`` and
``tests/test_audit.py`` property-test both.
"""

from .audit import QualityAuditor
from .export import FlightRecorder, parse_prometheus_text, prometheus_text
from .latency import DriftMonitor, LatencyTracker
from .metrics import Counter, Gauge, Histogram, MetricsRegistry
from .profile import PHASES, phase_breakdown
from .trace import ScheduleTrace

__all__ = ["Counter", "DriftMonitor", "FlightRecorder", "Gauge",
           "Histogram", "LatencyTracker", "MetricsRegistry", "PHASES",
           "QualityAuditor", "ScheduleTrace", "parse_prometheus_text",
           "phase_breakdown", "prometheus_text"]
