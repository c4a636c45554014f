"""The Krylov program of each solve in a traced window, found without
regard to how much device time it holds.

The program's ``tg.solve.krylov`` span builds the matvec and the
preconditioner and dispatches the Krylov loop; the loop then runs on the
device as one program run, after the assembly and condensation that the
host dispatched before it.  So the Krylov run of a step is the longest
program run of the step that starts after the step's ``tg.solve.krylov``
span opened (the matrix assembly starts before it; condensation and the
load, which may start after it, are short).  A step counts where all of
its program runs lie in the traced window.
"""

from __future__ import annotations

from . import program_idle


def split(run) -> list | None:
    """Per step traced whole: ``(iterations, Krylov seconds, other device
    seconds)``, or ``None`` where the program keeps no span log or the
    trace holds no such step."""
    log = program_idle.span_log()
    if log is None or run.trace is None:
        return None
    opened = sorted(s["start_ns"] for s in log
                    if s["name"] == "tg.solve.krylov")
    off = run.trace.clock_offset_ns
    runs = [(s, e) for rs in run.trace.program_runs.values() for s, e in rs]
    out = []
    for step in run.driver.steps:
        lo, hi = step["t0_ns"], step["t1_ns"]
        mine = [(s, e) for s, e in runs if lo + off <= s and e <= hi + off]
        start = next((t for t in opened if lo <= t <= hi), None)
        after = [(s, e) for s, e in mine
                 if start is not None and s >= start + off]
        if not after:
            continue
        s, e = max(after, key=lambda r: r[1] - r[0])
        n_dev = run.device["count"]
        total = sum(b - a for a, b in mine)
        out.append((step["iters"], (e - s) / 1e9 / n_dev,
                    (total - (e - s)) / 1e9 / n_dev))
    return out or None
