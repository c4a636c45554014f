"""Share of the traced window in which the device is idle while the host
is inside one of the program's own spans, in % (``harness/program_idle``:
the program's span log on the trace's clock against the device's whole
program runs).  At most ``device_idle_pct`` of the same run."""

from harness import program_idle


def read(run):
    return program_idle.pct(run)
