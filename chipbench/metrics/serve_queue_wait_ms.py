"""Median of the ``queue_wait`` segment of the span trees that the solve
service returns on its responses (submit to dispatch), in ms."""

import numpy as np


def read(run):
    waits = [r.span_segments_us["queue_wait"] for r in run.driver.responses
             if r is not None and "queue_wait" in r.span_segments_us]
    return float(np.median(waits)) / 1e3 if waits else None
