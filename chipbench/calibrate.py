"""Readings from which a cell's limits are set, in one process on the chip.

    python3 chipbench/calibrate.py --workload <cell> --seeds <a,b,...> \
        --control-seeds <c,d,e> --seconds <s>

For every seed of ``--seeds`` the cell's own timed path runs a window of
``--seconds`` (at the cell's sizes and load; set-up is built once and only
the traffic is drawn again), and the numbers that ``correct`` compares are
printed.  For every seed of ``--control-seeds`` the same window's answers
are replaced by the precision control, the plain reference computed in
float32, and compared in the same way.  Set-up is ``harness.cell.prepare``,
the same as a benchmark run's.  One JSON line per reading;
benchmark runs never run this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH_DIR), str(BENCH_DIR.parent / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def readings(workload, seeds, control_seeds, seconds, *, require_chip=True,
             overrides=None):
    """Yield ``{"seed", "kind", "checks": {name: [value, limit]}}`` for
    each seed and control seed."""
    from harness.cell import prepare

    driver = prepare(workload, seeds[0], seconds, require_chip=require_chip,
                     overrides=overrides).driver
    for kind, seed in ([("program", s) for s in seeds]
                       + [("control", s) for s in control_seeds]):
        driver.draw(seed)
        driver.window()
        driver.collect()
        answers = driver.answers()
        if kind == "control":
            answers = [(k, driver.control_answer(k)) for k, _ in answers]
        checks = driver.compare(answers)
        yield {"seed": seed, "kind": kind, "answers": len(answers),
               "failed": driver.failed,
               "checks": {k: list(v) for k, v in checks.items()}}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--control-seeds", default="")
    p.add_argument("--seconds", type=float, required=True)
    args = p.parse_args(argv)
    ints = lambda s: [int(x) for x in s.split(",") if x]  # noqa: E731
    t0 = time.perf_counter()
    for row in readings(args.workload, ints(args.seeds),
                        ints(args.control_seeds), args.seconds):
        row["t_s"] = time.perf_counter() - t0
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
