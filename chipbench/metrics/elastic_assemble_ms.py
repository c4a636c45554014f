"""Mean wall of the program's ``tg.assemble`` span per solve in the window,
in ms: the host's part of the elasticity assembly and condensation
(dispatch, executable lookup, the condensation's eager operations), not
the device's."""

import numpy as np

from harness import program_idle


def read(run):
    walls = [s["end_ns"] - s["start_ns"] for s in program_idle.span_log() or ()
             if s["name"] == "tg.assemble"]
    return float(np.mean(walls)) / 1e6 if walls else None
