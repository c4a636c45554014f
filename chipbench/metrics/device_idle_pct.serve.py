"""Share of the window in which no operation ran on the device, in %
(1 − busy union / window, from the profiler trace)."""


def read(run):
    return run.idle_pct()
