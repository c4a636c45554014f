"""Open loop over ``SolveService.submit``: requests fall due at the times
that the mix's ``arrivals`` give (``harness/traffic.arrivals``), each with
its own field on one shared plan, and each is timed from when it was due.
A failed, refused or unanswered request counts as infinitely late.  Every
answer is compared."""

import math
import time

import numpy as np

from harness import traffic as gen
from harness.problem import Problem, nearest_rank


class Driver(Problem):

    def build(self):
        from repro import serve
        from repro.core import (DirichletCondenser, FunctionSpace,
                                assemble_rhs, build_plan)
        from repro.core import weakform as wf
        from repro.core.mesh import element_for_mesh

        with self.spans.timed("mesh_plan"):
            mesh = self.program_mesh()
            space = FunctionSpace(mesh, element_for_mesh(mesh, 1))
            self.plan = build_plan(space)
        self.check_mesh(mesh)
        self.bc = DirichletCondenser(self.plan.static.mat_routing,
                                     space.boundary_dofs())
        self.rhs = assemble_rhs(self.plan,
                                wf.source(float(self.config["load"])))
        svc = self.config["service"]
        self.service = serve.SolveService(window=float(svc["window_s"]),
                                          max_batch=int(svc["max_batch"]))

    def request(self, field, rhs):
        from repro import serve
        from repro.core import weakform as wf

        return serve.SolveRequest(plan=self.plan, form=wf.diffusion(field),
                                  rhs=rhs, bc=self.bc,
                                  backend=self.config["backend"],
                                  spec=self.spec())

    def draw(self, seed):
        super().draw(seed)
        self.due = gen.arrivals(seed, self.traffic["arrivals"], self.seconds)
        self.fields = self.sampler.pool(seed, len(self.due))
        self.requests = [self.request(f, self.rhs) for f in self.fields]

    def warmup(self):
        import jax.numpy as jnp

        # a zero load answers at once: every bucket's executable compiles
        # and no iteration runs
        zeros = jnp.zeros_like(self.rhs)
        template = self.request(self.fields[0], zeros)
        self.service.warmup(template,
                            batch_sizes=self.config["service"]["buckets"])
        # stacking b requests into a bucket compiles per batch size b: one
        # batch of each size, dispatched in this thread
        for b in range(1, int(self.config["service"]["max_batch"]) + 1):
            pending = [self.service.submit(self.request(self.fields[0], zeros))
                       for _ in range(b)]
            self.service.drain()
            for p in pending:
                p.result(timeout=600)

    def window(self):
        pendings = []
        late = []
        with self.service:
            t0 = time.monotonic()
            for req, due in zip(self.requests, self.due):
                wait = t0 + due - time.monotonic()
                if wait > 0:
                    time.sleep(wait)
                pendings.append(self.service.submit(req))
                late.append(time.monotonic() - (t0 + due))
            close = t0 + self.seconds
            self.responses = []
            for p in pendings:
                left = close + 60.0 - time.monotonic()
                try:
                    self.responses.append(p.response(timeout=max(left, 0.0)))
                except TimeoutError:
                    self.responses.append(None)
        self.late = np.asarray(late)
        lat = np.full(len(self.due), math.inf)
        for i, r in enumerate(self.responses):
            if r is not None and r.ok:
                lat[i] = r.t_done - (t0 + self.due[i])
        self.failed = int(np.sum(~np.isfinite(lat)))
        return {"serve_p95_ms": 1e3 * nearest_rank(lat, 0.95)}

    def generator_note(self) -> str:
        return (f"generator lateness: max {1e3 * self.late.max():.3f} ms, "
                f"p95 {1e3 * nearest_rank(self.late, 0.95):.3f} ms over "
                f"{len(self.late)} arrivals")

    def attempted(self):
        return len(self.due)

    def collect(self):
        self._answers = [
            (i, np.asarray(r.u)) for i, r in enumerate(self.responses)
            if r is not None and r.ok]
        self.unanswered = sum(r is None for r in self.responses)

    def release(self):
        del self.service, self.requests

    def answers(self):
        return self._answers

    def compare(self, answers):
        return {"relres": (self.worst_relres(answers), self.limit("relres")),
                "unanswered": (float(self.unanswered), 0.0)}

    def program_spans(self):
        """The dispatch, solve and slice segments of the service's span
        trees (present when telemetry is on), one per batch."""
        seen = set()
        for r in self.responses:
            for c in (r.trace or {}).get("children", ()) if r else ():
                if c["name"] != "queue_wait" and c["end_ns"] is not None:
                    seen.add((f"serve.{c['name']}", c["start_ns"],
                              c["end_ns"]))
        return sorted(seen, key=lambda s: s[1])
