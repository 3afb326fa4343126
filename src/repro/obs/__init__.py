"""Observability subsystem: metrics registry, span tracing, flight recorder,
host pauses.

    from repro.obs import MetricsRegistry, Tracer, FlightRecorder

The layers share one design rule — observation must never change what it
observes:

* **Metrics** (always on): every counter the stack exposes through
  ``PipelineServer.stats()`` / ``pipeline.explain()`` lives in a
  :class:`MetricsRegistry`; an increment costs a dict lookup + float add.
* **Tracing** (opt-in): :class:`Tracer` records nested spans with
  explicit parent ids, exportable as Chrome trace-event JSON
  (Perfetto-loadable).  Disabled, with no profiler session, every call
  returns a shared no-op.
* **Profiler bridge**: a live span is also a ``jax.profiler``
  annotation of the same name while a profiler session runs, tracer on
  or off, so program spans share the device trace's clock;
  :func:`span` is the helper for code that holds no tracer.
* **Host pauses** (``repro.obs.hostpause``): a process-wide watch of the
  garbage collector's collections, installed by
  ``PipelineServer.start()``: a lock-free bounded ring, from which trace
  exports (``host.gc`` spans) and a pulled pause counter are derived.
* **Flight recorder** (opt-in): :class:`FlightRecorder` rings the last N
  scheduler/engine decisions for overload post-mortems.

Serving opts in via ``ServeConfig.with_observability(...)``; offline
compile/plan instrumentation via ``BackendDescriptor.with_observability()``
(which routes through the process-global tracer, see ``set_tracer``).
"""
from repro.obs.metrics import (LATENCY_BUCKETS_MS, Counter,  # noqa: F401
                               CounterMap, Gauge, Histogram,
                               MetricsRegistry, get_registry)
from repro.obs.recorder import FlightRecorder  # noqa: F401
from repro.obs.tracing import (NOOP_SPAN, NOOP_TRACER, Span,  # noqa: F401
                               Tracer, get_tracer, set_tracer, span)
