"""repro.telemetry — convergence diagnostics, named-phase profiler tracing,
and a unified runtime metrics/event layer.

The paper's claim is *efficiency*; this package is how the repo sees it:

* **Convergence diagnostics** — every Krylov solve carries a
  ``SolveInfo(iters, residual, converged)``; the ``return_info=True`` paths
  on :func:`repro.core.sparse_solve` / :func:`repro.core.matfree_solve` /
  the transient integrators expose it as a non-differentiated auxiliary
  output (stop-gradient leaves — gradients match the plain path to machine
  precision), and :func:`check_convergence` turns a silent ``maxiter`` exit
  into a warning or error.
* **Named-phase tracing** — :class:`annotate` stamps the Map / Reduce /
  gather / scatter / Pallas stages with names visible in a profile;
  :func:`capture` records a TensorBoard/Perfetto trace of any block.
* **Metrics & events** — a process-global registry (jit-trace and
  cache counters unifying ``n_core_traces``/``n_matfree_traces``, memory
  gauges, iteration/wall-time histograms) plus a structured event stream
  with JSON-lines export in the ``BENCH_JSON`` row format; rendered by
  ``python -m repro.telemetry.report``.
* **Request tracing** — :func:`span_root` / :func:`span` build host-side
  span trees with one propagated trace id per request (the serve tier
  opens one per ``submit()``; closed spans fold into ``span_us``
  histograms and stream as ``span/<name>`` rows); a bounded **flight
  recorder** (:func:`configure_flight` / :func:`flight_dump`) keeps the
  last K completed request traces and auto-dumps them on
  nonconverged/expired/shed; :func:`define_slo` tracks latency SLO
  attainment and burn rate against any histogram, surfaced in
  :func:`snapshot` and ``report --slo``.
* **Span log** — every finished span also lands in a bounded in-memory log
  (:func:`span_log`); a span opened with ``with span(...)`` holds a
  profiler ``TraceAnnotation`` of its name, so a profile shows the
  program's own phases (``tg.solve``, ``tg.assemble``, ``serve.batch``,
  ...) on the profiler's clock, and while enabled every garbage-collector
  pass is logged as a ``host.gc`` span.

Disabled by default and zero-cost when off: recording entry points return
after one boolean check, annotations are trace-time-only, nothing telemetry
does is ever staged into a jaxpr (so toggling cannot retrace), and tracers
are never captured into host state.  Enable with :func:`enable` (or
``REPRO_TELEMETRY=1`` in the environment).

This package deliberately imports nothing from :mod:`repro.core` — the core
imports *it*.
"""

from .events import (  # noqa: F401
    ConvergenceWarning,
    NonConvergedError,
    check_convergence,
    clear_events,
    event_log,
    record_assembly,
    record_event,
    record_solve,
)
from .metrics import (  # noqa: F401
    count_cache,
    count_trace,
    counter_inc,
    disable,
    enable,
    enabled,
    export_jsonl,
    gauge_set,
    histogram_observe,
    is_enabled,
    jit_trace_total,
    jsonl_path,
    metric_rows,
    nonconverged_policy,
    reset,
    snapshot,
)
from .slo import (  # noqa: F401
    SLO,
    clear_slos,
    define_slo,
    defined_slos,
    slo_status,
)
from .spans import (  # noqa: F401
    NULL_SPAN,
    Span,
    clear_flight,
    configure_flight,
    current_span,
    flight_autodump,
    flight_dump,
    flight_record,
    flight_records,
    span,
    span_log,
    span_root,
)
from .trace import annotate, capture  # noqa: F401

__all__ = [
    # switchboard
    "enable", "disable", "enabled", "is_enabled", "reset", "jsonl_path",
    "nonconverged_policy",
    # tracing
    "annotate", "capture",
    # metrics
    "counter_inc", "gauge_set", "histogram_observe", "count_trace",
    "count_cache", "jit_trace_total", "snapshot", "export_jsonl",
    "metric_rows",
    # spans / flight recorder
    "Span", "NULL_SPAN", "span", "span_root", "current_span", "span_log",
    "configure_flight", "flight_record", "flight_records", "flight_dump",
    "flight_autodump", "clear_flight",
    # SLOs
    "SLO", "define_slo", "defined_slos", "clear_slos", "slo_status",
    # events / convergence
    "record_event", "record_solve", "record_assembly", "check_convergence",
    "event_log", "clear_events", "ConvergenceWarning", "NonConvergedError",
]
