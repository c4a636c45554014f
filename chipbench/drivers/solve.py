"""Closed loop over ``PoissonProblem.solve``: each step takes the next field
of a pool drawn from the seed and placed on the device in set-up, solves,
and blocks until ``u`` is ready.  Every answer is compared."""

import time

import numpy as np

from harness.problem import PoolDriver


class Driver(PoolDriver):

    def warmup(self):
        import jax

        # a zero load stops CG before its first step: every program of the
        # solve compiles, and none of the iterations runs
        res, _ = self.call(self.device_fields[0], 0.0)
        jax.block_until_ready(res.u)

    def call(self, field, load):
        return self.prob.solve(rho=field, f=load, spec=self.spec(),
                               backend=self.config["backend"],
                               return_info=True)

    def step(self, i):
        k = i % len(self.fields)
        t0 = time.monotonic_ns()
        with self.spans.span("bench.call"):
            res, info = self.call(self.device_fields[k],
                                  float(self.config["load"]))
        with self.spans.span("bench.block"):
            u = res.u.block_until_ready()
        self.steps.append({"field": k, "u": u, "iters": int(info.iters),
                           "converged": bool(info.converged),
                           "t0_ns": t0, "t1_ns": time.monotonic_ns()})

    def window(self):
        return {"solve_s": self.run_steps() / len(self.steps)}

    def collect(self):
        for s in self.steps:
            s["u"] = np.asarray(s["u"])
        self.failed = sum(not s["converged"] for s in self.steps)

    def answers(self):
        return [(s["field"], s["u"]) for s in self.steps]

    def compare(self, answers):
        return {"relres": (self.worst_relres(answers), self.limit("relres"))}
