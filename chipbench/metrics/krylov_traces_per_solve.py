"""Traces of the Krylov loop per solve in the window: the program's
``krylov_traces`` counter, bumped each time a loop body is traced (never by
a run of a compiled loop), over the solves completed.  1.0 while every
eager solve traces and compiles its loop anew."""


def read(run):
    from repro import telemetry
    from repro.core import solvers

    name = getattr(solvers, "KRYLOV_TRACES", None)
    if name is None or not run.driver.steps:
        return None
    counters = telemetry.snapshot()["counters"]
    traces = sum(v for k, v in counters.items()
                 if k == name or k.startswith(name + "{"))
    return traces / len(run.driver.steps)
