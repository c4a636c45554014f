"""Device idle time put down to the program's own host spans.

While telemetry is on, as it is in a traced run, the program logs each
span it finishes (``repro.telemetry.span_log()``, on ``time.monotonic_ns``):
``tg.*`` around its solves and assemblies, the solve service's
``serve.batch`` with its phases and each request's segments, and
``host.gc`` for each pass of Python's garbage collector.  The trace's
``clock_offset_ns`` puts them on the trace's clock.

Device busy time is rebuilt from the trace's whole program runs
(``TraceSummary.program_runs``, one chip), over the stretch from the first
of them to the last: a run cut by the traced window's edge is not among
them.  Each idle stretch of the device there is put down to the innermost
span open on the host at the time (the shortest), or to ``host`` where the
program had none open.  A program that keeps no span log gives nothing.
"""

from __future__ import annotations

import sys

from .trace import gaps, merged

HOST = "host"


def span_log() -> list | None:
    """The program's span log, or ``None`` where it keeps none."""
    from repro import telemetry

    log = getattr(telemetry, "span_log", None)
    return None if log is None else log()


def program_spans(run) -> list | None:
    """``(name, start, end)`` of the logged spans on the trace's clock."""
    log = span_log()
    if log is None or run.trace is None:
        return None
    off = run.trace.clock_offset_ns
    return [(s["name"], s["start_ns"] + off, s["end_ns"] + off) for s in log]


def idle_by_span(spans, runs) -> dict:
    """Idle ns of the device between the first and the last of ``runs``
    (``(start, end)`` of whole program runs), by the innermost of ``spans``
    (``(name, start, end)``) open at the time, ``HOST`` where none is."""
    busy = merged(runs)
    if not busy:
        return {}
    spans = [sp for sp in spans if sp[2] > sp[1]]
    edges = sorted([(s, 1, i) for i, (_, s, _e) in enumerate(spans)]
                   + [(e, -1, i) for i, (_, _s, e) in enumerate(spans)])
    out: dict = {}
    live: set = set()
    k = 0
    for lo, hi in gaps(busy, busy[0][0], busy[-1][1]):
        t = lo
        while t < hi:
            while k < len(edges) and edges[k][0] <= t:
                _, kind, i = edges[k]
                (live.add if kind > 0 else live.discard)(i)
                k += 1
            nxt = min(edges[k][0], hi) if k < len(edges) else hi
            inner = min((spans[i] for i in live),
                        key=lambda sp: sp[2] - sp[1], default=(HOST,))
            out[inner[0]] = out.get(inner[0], 0) + (nxt - t)
            t = nxt
    return out


def pct(run) -> float | None:
    """Share of the traced window in which the device is idle while the
    host is inside a program span, in %.  The seconds by span go to
    standard error, longest first."""
    spans = program_spans(run)
    if spans is None or run.trace.window_s <= 0:
        return None
    runs = [r for rs in run.trace.program_runs.values() for r in rs]
    if not runs:
        return None
    idle = idle_by_span(spans, runs)
    print("device idle by program span: " + ", ".join(
        f"{name} {ns / 1e9:.6f} s" for name, ns in
        sorted(idle.items(), key=lambda kv: -kv[1])), file=sys.stderr)
    inside = sum(ns for name, ns in idle.items() if name != HOST)
    return 100.0 * inside / 1e9 / run.trace.window_s
