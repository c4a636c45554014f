"""Closed loop over ``SolveService.submit`` in full batches: the client
submits as many requests as the service batches at most, each with its own
field of a pool drawn from the seed in set-up, waits for all of them, and
submits the next batch.  Each request is timed from its submission; a
failed or unanswered request counts as infinitely late.  Every answer is
compared with the reference system of its field."""

import math
import time

import numpy as np

from drivers.submit import Driver as SubmitDriver
from harness.problem import nearest_rank


class Driver(SubmitDriver):

    #: called with the seconds elapsed after each batch (the profiler of a
    #: traced part of the window starts and stops here, between batches)
    on_step = None

    def draw(self, seed):
        super(SubmitDriver, self).draw(seed)
        self.fields = self.sampler.pool(seed, int(self.traffic["pool"]))
        self.requests = [self.request(f, self.rhs) for f in self.fields]
        self.batch = int(self.config["service"]["max_batch"])
        self.submitted, self._systems = [], {}

    def window(self):
        self.submitted, self.responses, lat = [], [], []
        with self.service:
            t0 = time.perf_counter()
            i = 0
            while True:
                ks = [(i * self.batch + j) % len(self.requests)
                      for j in range(self.batch)]
                sent = []
                for k in ks:
                    sent.append((time.monotonic(),
                                 self.service.submit(self.requests[k])))
                for k, (t_sub, p) in zip(ks, sent):
                    try:
                        r = p.response(timeout=60.0)
                    except TimeoutError:
                        r = None
                    self.submitted.append(k)
                    self.responses.append(r)
                    lat.append(r.t_done - t_sub if r is not None and r.ok
                               else math.inf)
                i += 1
                elapsed = time.perf_counter() - t0
                if self.on_step is not None:
                    self.on_step(elapsed)
                    elapsed = time.perf_counter() - t0
                if elapsed * (i + 1) / i > self.seconds:
                    break
        self.failed = int(np.sum(~np.isfinite(lat)))
        return {"serve_p95_ms": 1e3 * nearest_rank(lat, 0.95)}

    def generator_note(self) -> str:
        return (f"closed loop: {len(self.submitted)} requests in batches of "
                f"{self.batch}")

    def attempted(self):
        return len(self.submitted)

    def collect(self):
        self._answers = [
            (k, np.asarray(r.u)) for k, r in zip(self.submitted,
                                                 self.responses)
            if r is not None and r.ok]
        self.unanswered = sum(r is None for r in self.responses)

    def reference_system(self, k, dtype=np.float64):
        """One reference system per pool field, built on first use."""
        key = (k, np.dtype(dtype).name)
        if key not in self._systems:
            self._systems[key] = super().reference_system(k, dtype)
        return self._systems[key]

    def limit(self, name):
        # the same service, solver and configuration as the open loop
        return float(self.config["limits"]["submit"][name])
