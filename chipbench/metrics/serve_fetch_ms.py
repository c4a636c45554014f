"""Median wall of the ``fetch`` phase of the solve service's batches (the
device-to-host copy of a batch's answers and their info, a child of the
dispatch thread's ``serve.batch`` span), in ms."""

import numpy as np

from harness import program_idle


def read(run):
    log = program_idle.span_log() or ()
    batches = {s["span_id"] for s in log if s["name"] == "serve.batch"}
    walls = [s["end_ns"] - s["start_ns"] for s in log
             if s["name"] == "fetch" and s["parent_id"] in batches]
    return float(np.median(walls)) / 1e6 if walls else None
