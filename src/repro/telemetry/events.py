"""Structured runtime events: solves, assemblies, captures — one stream.

An *event* is a host-side record emitted at an eager boundary (a problem
``.solve`` returning, an assembly producing a CSR, a profile capture
finishing).  Events are:

* appended to a bounded in-memory log (:func:`event_log`),
* folded into the metrics registry (solve-iteration and wall-time
  histograms, solve/assembly counters),
* streamed to the configured JSON-lines file in the ``BENCH_JSON`` row
  format (``{"name", "us_per_call", "derived", ...extras}``) when
  :func:`repro.telemetry.enable` was given a ``jsonl`` path.

Tracer discipline: every field runs through
:func:`~repro.telemetry.metrics.concrete_or_none`; a recording call made
from inside a traced context (a ``vmap``-ed solve, a ``lax.scan`` body)
silently records nothing — abstract values never leak into host state, and
toggling telemetry never changes a jaxpr.

Convergence policy lives here too: :func:`check_convergence` is the
host-side guard that turns a silently-garbage ``maxiter`` exit into a
:class:`ConvergenceWarning` (default) or :class:`NonConvergedError` — it
works with telemetry disabled, because a wrong answer should never need a
flag to be reported.
"""

from __future__ import annotations

import threading
import time
import warnings

import numpy as np

from . import metrics, spans

__all__ = [
    "ConvergenceWarning",
    "NonConvergedError",
    "record_event",
    "record_solve",
    "record_assembly",
    "check_convergence",
    "event_log",
    "clear_events",
]

_EVENTS: list[dict] = []
_EVENT_LIMIT = 65536
# guards _EVENTS across recorder threads (the serve dispatch worker records
# concurrently with driver-thread exports)
_EVENTS_LOCK = threading.Lock()


class ConvergenceWarning(UserWarning):
    """A Krylov solve exited at ``maxiter`` without reaching tolerance."""


class NonConvergedError(RuntimeError):
    """Raised (under the ``on_nonconverged="raise"`` policy) when a solve
    reports ``converged=False``."""


def event_log() -> list[dict]:
    """The in-memory event list (bounded; newest last)."""
    with _EVENTS_LOCK:
        return list(_EVENTS)


def clear_events() -> None:
    with _EVENTS_LOCK:
        _EVENTS.clear()


def _derived(fields: dict) -> str:
    return ";".join(f"{k}={v}" for k, v in fields.items() if v is not None)


def record_event(kind: str, name: str, *, wall_us: float | None = None,
                 **fields):
    """Record one structured event.  Returns the event dict, or ``None``
    when telemetry is disabled or any field is abstract (tracer-safe)."""
    if not metrics.is_enabled():
        return None
    clean: dict = {}
    for k, v in fields.items():
        c = metrics.concrete_or_none(v)
        if c is None and v is not None:
            return None  # a tracer snuck in: skip the whole event
        if isinstance(c, np.ndarray):
            c = c.tolist()
        if isinstance(c, np.generic):
            c = c.item()
        clean[k] = c
    wall = metrics.concrete_or_none(wall_us)
    ev = {"kind": kind, "name": name, "t": time.time(), **clean}
    if wall is not None:
        ev["wall_us"] = round(float(wall), 1)
    # span-awareness: an event recorded under an open span inherits its
    # trace identity, so per-request timelines include their solve events
    sp = spans.current_span()
    if sp is not None and sp is not spans.NULL_SPAN:
        ev["trace_id"] = sp.trace_id
        ev["span_id"] = sp.span_id
    with _EVENTS_LOCK:
        if len(_EVENTS) < _EVENT_LIMIT:
            _EVENTS.append(ev)
    metrics.counter_inc("events", 1, kind=kind)
    if metrics.jsonl_path():
        row = {
            "name": f"{kind}/{name}",
            "us_per_call": ev.get("wall_us", 0.0),
            "derived": _derived(clean),
            "kind": kind,
            **clean,
        }
        if "trace_id" in ev:
            row["trace_id"] = ev["trace_id"]
            row["span_id"] = ev["span_id"]
        metrics.append_jsonl_row(row)
    return ev


def _summarize_info(info):
    """Host scalars from a ``SolveInfo`` (possibly with batched / per-step
    leaves): total + max iterations, worst residual, all-converged.  Returns
    ``None`` if any leaf is abstract."""
    it = metrics.concrete_or_none(info.iters)
    res = metrics.concrete_or_none(info.residual)
    conv = metrics.concrete_or_none(getattr(info, "converged", True))
    if it is None or res is None or conv is None:
        return None
    it = np.asarray(it)
    res = np.asarray(res)
    conv = np.asarray(conv)
    return {
        "iterations": int(it.sum()),
        "iterations_max": int(it.max()),
        "n_solves": int(it.size),
        "final_residual": float(res.max()),
        "converged": bool(conv.all()),
    }


def check_convergence(info, where: str = "solve", on_fail: str | None = None):
    """Host-side non-convergence guard.  ``info`` is a ``SolveInfo`` (scalar
    or batched/stacked leaves).  If every leaf is concrete and any solve has
    ``converged=False``, apply the policy: ``"warn"`` (default, a
    :class:`ConvergenceWarning`), ``"raise"`` (:class:`NonConvergedError`),
    or ``"ignore"``.  Abstract leaves (called under trace) are a no-op.
    Returns the summary dict (or ``None`` when abstract).

    Works with telemetry disabled — silent garbage from a ``maxiter`` exit
    is a correctness bug, not an observability feature.
    """
    s = _summarize_info(info)
    if s is None or s["converged"]:
        return s
    policy = on_fail or metrics.nonconverged_policy()
    msg = (
        f"{where}: solver did NOT converge after {s['iterations_max']} "
        f"iterations (final residual {s['final_residual']:.3e}"
        + (f", {s['n_solves']} solves" if s["n_solves"] > 1 else "")
        + ") — the returned solution does not meet tolerance"
    )
    if policy == "raise":
        raise NonConvergedError(msg)
    if policy == "warn":
        warnings.warn(msg, ConvergenceWarning, stacklevel=3)
    return s


def record_solve(name: str, info, *, method: str | None = None,
                 backend: str | None = None, precond: str | None = None,
                 phase: str = "forward",
                 wall_us: float | None = None, **extra):
    """Record one solve event from a ``SolveInfo`` and fold it into the
    metrics (iteration histogram, optional wall-time histogram, solve
    counter).  ``precond`` labels the iteration histogram per
    preconditioner, so convergence regressions show up per backend.
    Tracer-safe no-op when disabled or under trace."""
    if not metrics.is_enabled():
        return None
    s = _summarize_info(info)
    if s is None:
        return None
    labels = {"solver": method or "?", "phase": phase}
    if backend:
        labels["backend"] = backend
    if precond:
        labels["precond"] = precond
    metrics.counter_inc("solves", s["n_solves"], **labels)
    metrics.histogram_observe("solve_iterations", s["iterations"], **labels)
    if wall_us is not None:
        w = metrics.concrete_or_none(wall_us)
        if w is not None:
            metrics.histogram_observe("solve_wall_us", float(w), **labels)
    return record_event(
        "solve", name, wall_us=wall_us, method=method, backend=backend,
        precond=precond, phase=phase, **s, **extra,
    )


def record_assembly(name: str, *, num_dofs: int | None = None,
                    nnz: int | None = None, num_cells: int | None = None,
                    form: str | None = None, **extra):
    """Record one assembly event (an eager ``assemble``/``assemble_rhs``
    producing a global operator or load vector).  It carries no wall time:
    the dispatch is asynchronous, so the host's wall is the ``tg.assemble``
    span's and the device's is in a profile."""
    if not metrics.is_enabled():
        return None
    metrics.counter_inc("assemblies", 1, form=form or "?")
    return record_event(
        "assembly", name, num_dofs=num_dofs, nnz=nnz,
        num_cells=num_cells, form=form, **extra,
    )
