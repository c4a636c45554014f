"""Entries of the CSR matvec's row layout, K × rows, from the program's
``matvec_gathers`` gauge (set each time a layout is looked up): K vertex
blocks of 3-vectors per vertex row on the vertex-block layout, K entries
per DoF row on a column table or on diagonals.  The layout's kind goes to
standard error."""

import sys


def read(run):
    from repro import telemetry

    gauges = {k: v for k, v in telemetry.snapshot()["gauges"].items()
              if k == "matvec_gathers" or k.startswith("matvec_gathers{")}
    if not gauges:
        return None
    print("matvec layouts: " + ", ".join(f"{k} {v}" for k, v in
                                         sorted(gauges.items())),
          file=sys.stderr)
    return float(max(gauges.values()))
