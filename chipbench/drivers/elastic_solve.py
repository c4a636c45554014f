"""Closed loop over ``ElasticityProblem.solve``: each step takes the next load
case of a pool, constant body forces of unit length drawn from the seed and
placed on the device in set-up, solves, and blocks until ``u`` is ready.
Every answer is compared with the reference, whose stiffness is built once
per run."""

import importlib
import time

import numpy as np

from harness import traffic as gen
from harness.problem import PoolDriver

_LOAD_STREAM = 4


class Driver(PoolDriver):

    def __init__(self, config, traffic, seed, seconds, spans):
        # the material is constant: no coefficient field to sample
        self.config, self.traffic = config, traffic
        self.seed, self.seconds, self.spans = seed, seconds, spans
        self.ref = importlib.import_module(f"reference.{config['reference']}")
        self.n = int(config["cells_per_side"])
        self.points, self.cells = self.ref.mesh(self.n)
        self._stiffness = {}

    def build(self):
        from repro.fem.tensormesh import ElasticityProblem

        self.require_vertex_routing()
        mat = self.config["coefficient"]
        with self.spans.timed("mesh_plan"):
            self.prob = ElasticityProblem(self.program_mesh(),
                                          e_mod=float(mat["e_mod"]),
                                          nu=float(mat["nu"]))
        self.check_mesh(self.prob.mesh)

    def require_vertex_routing(self):
        """Fail at once where the program routes each scalar entry of a
        vector-valued space's local matrices (``E·k²`` slots, 282M at 72
        cells a side): its assembly then compiles 4.35 GB of routing tables
        in as constants, and the compile outgrows a one-chip host's 40 GiB
        (found on such a host) instead of failing."""
        import repro.core
        from repro.core.mesh import element_for_mesh

        mesh = getattr(repro.core, self.config["program_mesh"])(4)
        plan = repro.core.build_plan(repro.core.FunctionSpace(
            mesh, element_for_mesh(mesh), value_size=3))
        if getattr(plan.static.mat_routing, "block", 1) == 1:
            raise RuntimeError(
                "this program routes a vector-valued space's Reduce through "
                "its DoF pattern; at this size that assembly does not fit "
                "the host")

    def draw(self, seed):
        import jax.numpy as jnp

        super(PoolDriver, self).draw(seed)
        b = gen.rng(seed, _LOAD_STREAM).standard_normal(
            (int(self.traffic["pool"]), 3))
        self.forces = b / np.linalg.norm(b, axis=1, keepdims=True)
        self.device_forces = [jnp.asarray(f) for f in self.forces]
        self._loads, self._controls = {}, {}

    def warmup(self):
        import jax.numpy as jnp

        # a zero load stops CG before its first step: every program of the
        # solve compiles, and none of the iterations runs
        res, _ = self.call(jnp.zeros(3))
        res.u.block_until_ready()

    def call(self, force):
        return self.prob.solve(body_force=force, spec=self.spec(),
                               backend=self.config["backend"],
                               return_info=True)

    def step(self, i):
        k = i % len(self.forces)
        t0 = time.monotonic_ns()
        with self.spans.span("bench.call"):
            res, info = self.call(self.device_forces[k])
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

    def release(self):
        del self.prob, self.device_forces

    def answers(self):
        return [(s["field"], s["u"]) for s in self.steps]

    def reference_system(self, k, dtype=np.float64):
        """The reference's condensed ``(K, F)`` for load case ``k``: K is
        built once per run and dtype, F once per load case."""
        key = np.dtype(dtype).name
        if key not in self._stiffness:
            mat = self.config["coefficient"]
            self._stiffness[key] = self.ref.stiffness(
                self.n, float(mat["e_mod"]), float(mat["nu"]), dtype)
        if (k, key) not in self._loads:
            self._loads[k, key] = self.ref.load(self.n, self.forces[k], dtype)
        return self._stiffness[key], self._loads[k, key]

    def control_answer(self, k):
        """The precision control for load case ``k``: the reference's system
        and Jacobi-CG, both in float32, at the configuration's tolerance
        (solved once per load case)."""
        if k not in self._controls:
            kr, fr = self.reference_system(k, np.float32)
            s = self.config["solver"]
            self._controls[k] = self.ref.solve(
                kr, fr, tol=float(s["tol"]), maxiter=int(s["maxiter"]),
                dtype=np.float32)
        return self._controls[k]

    def compare(self, answers):
        return {"relres": (self.worst_relres(answers), self.limit("relres"))}
