"""What every driver of a program entry point shares.

A driver lives in ``drivers/<entry>.py`` and exposes ``Driver``; a traffic
mix names it by its ``entry``.  It builds its problem from the
configuration (set-up), runs the measured window, and afterwards compares
what the window produced with the plain reference.  The reference
(``reference/<config's reference>.py``) gets the same coefficient fields,
drawn by the benchmark from the seed, and nothing the program made.
"""

from __future__ import annotations

import importlib
import math
import time

import numpy as np

from . import traffic as gen
from .spans import Spans


class Problem:
    """The mesh from the program's constructor that the configuration names
    (``program_mesh``), checked against the reference's, the field sampler
    and the solver spec."""

    def __init__(self, config, traffic, seed, seconds, spans: Spans):
        self.config, self.traffic = config, traffic
        self.seed, self.seconds, self.spans = seed, seconds, spans
        self.ref = importlib.import_module(f"reference.{config['reference']}")
        self.n = int(config["cells_per_side"])
        self.points, self.cells = self.ref.mesh(self.n)
        self.sampler = gen.FieldSampler(self.points, self.cells,
                                        config["coefficient"])

    def program_mesh(self):
        """The program's own mesh, built by the constructor that the
        configuration names."""
        import repro.core

        return getattr(repro.core, self.config["program_mesh"])(self.n)

    def check_mesh(self, mesh) -> None:
        if not (np.array_equal(mesh.cells, self.cells)
                and np.allclose(mesh.points, self.points, rtol=0, atol=1e-15)):
            raise RuntimeError("the program's mesh is not the reference's")

    def spec(self):
        from repro.core import SolverSpec

        s = self.config["solver"]
        return SolverSpec(method=s["method"], precond=s["precond"],
                          tol=float(s["tol"]), atol=float(s["atol"]),
                          maxiter=int(s["maxiter"]))

    def reference_system(self, k, dtype=np.float64):
        """The reference's condensed ``(K, F)`` for field ``k``."""
        return self.ref.assemble(self.points, self.cells, self.fields[k],
                                 float(self.config["load"]), dtype=dtype)

    def setup(self):
        """Build the problem, draw the seed's traffic, warm up."""
        self.build()
        with self.spans.timed("fields"):
            self.draw(self.seed)
        with self.spans.timed("warmup"):
            self.warmup()

    def draw(self, seed):
        """Draw the traffic of ``seed``; forget any earlier window."""
        self.seed = seed
        self.steps, self.failed, self._last = [], 0, {}

    def attempted(self) -> int:
        return len(self.steps)

    def control_answer(self, k):
        """The precision control for field ``k``: the reference's system and
        Jacobi-CG, both in float32, at the configuration's tolerance."""
        kr, fr = self.reference_system(k, np.float32)
        return self.ref.solve(kr, fr, tol=float(self.config["solver"]["tol"]),
                              dtype=np.float32)

    def worst_relres(self, answers) -> float:
        """The worst reference residual of ``(field, u)`` answers."""
        worst = 0.0
        for k, u in answers:
            kr, fr = self.reference_system(k)
            worst = max(worst, self.ref.relres(kr, fr, u))
        return worst

    def program_spans(self) -> list:
        """``(name, start, end)`` spans that the program itself recorded in
        the window, on the ``time.monotonic_ns`` clock."""
        return []

    def limit(self, name):
        return float(self.config["limits"][self.traffic["entry"]][name])

    def collect(self) -> None:
        """Bring the window's answers to the host."""

    def release(self) -> None:
        """Drop the program's state before the reference runs."""


class PoolDriver(Problem):
    """A closed loop over a pool of fields placed on the device in set-up,
    on one ``PoissonProblem``."""

    #: called with the seconds elapsed after each step (the profiler of a
    #: traced part of the window starts and stops here, between steps)
    on_step = None

    def build(self):
        from repro.fem.tensormesh import PoissonProblem

        with self.spans.timed("mesh_plan"):
            self.prob = PoissonProblem(self.program_mesh())
        self.check_mesh(self.prob.mesh)

    def draw(self, seed):
        import jax.numpy as jnp

        super().draw(seed)
        self.fields = self.sampler.pool(seed, int(self.traffic["pool"]))
        self.device_fields = [jnp.asarray(f) for f in self.fields]

    def run_steps(self) -> float:
        """Whole steps, one after another, for about ``seconds``: a step
        starts while the steps so far, at their mean length, leave room
        for it to end within the window.  Returns the seconds taken."""
        t0 = time.perf_counter()
        i = 0
        while True:
            self.step(i)
            i += 1
            if self.on_step is not None:
                self.on_step(time.perf_counter() - t0)
            elapsed = time.perf_counter() - t0
            if elapsed * (i + 1) / i > self.seconds:
                return elapsed

    def release(self):
        del self.prob, self.device_fields


def nearest_rank(values, q: float) -> float:
    """The nearest-rank ``q``-quantile: the smallest value with at least a
    share ``q`` of the values at or below it."""
    v = np.sort(np.asarray(values, dtype=float))
    return float(v[max(0, math.ceil(q * len(v)) - 1)])
