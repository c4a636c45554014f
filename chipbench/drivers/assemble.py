"""Closed loop over ``PoissonProblem.assemble``: each step re-assembles the
condensed operator and load for the next field of a pool placed on the
device in set-up, and blocks on the values of K and F.  The answers of the
first step and of a sample of the first ``sample_from`` steps, drawn from
the seed, are compared."""

import numpy as np

from harness import traffic as gen
from harness.problem import PoolDriver


class Driver(PoolDriver):

    def warmup(self):
        import jax

        k, f = self.prob.assemble(self.device_fields[0], 0.0)
        jax.block_until_ready((k.vals, f))

    def draw(self, seed):
        super().draw(seed)
        self.sampled = {0} | set(gen.sample(
            seed, int(self.traffic["sample_from"]),
            int(self.traffic["sample"])).tolist())

    def step(self, i):
        import jax

        k = i % len(self.fields)
        with self.spans.span("bench.call"):
            mat, load = self.prob.assemble(self.device_fields[k],
                                           float(self.config["load"]))
        with self.spans.span("bench.block"):
            jax.block_until_ready((mat.vals, load))
        if i in self.sampled:
            self._last[i] = (k, mat, load)
        self.steps.append(k)

    def window(self):
        return {"assemble_ms": 1e3 * self.run_steps() / len(self.steps)}

    def collect(self):
        import scipy.sparse as sp

        self._kept = []
        for i in sorted(self._last):
            k, mat, load = self._last[i]
            csr = sp.csr_matrix((np.asarray(mat.vals), mat.indices,
                                 mat.indptr), shape=mat.shape)
            self._kept.append((k, (csr, np.asarray(load))))
        self._last = {}

    def answers(self):
        return self._kept

    def control_answer(self, k):
        return self.reference_system(k, np.float32)

    def compare(self, answers):
        worst_k = worst_f = 0.0
        for k, (mat, load) in answers:
            kr, fr = self.reference_system(k)
            worst_k = max(worst_k, self.ref.max_relerr(mat, kr))
            worst_f = max(worst_f, self.ref.max_relerr(load, fr))
        return {"k_relerr": (worst_k, self.limit("k_relerr")),
                "f_relerr": (worst_f, self.limit("f_relerr"))}
