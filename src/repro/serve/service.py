"""`SolveService` — the multi-tenant batched PDE solve front-end.

Request lifecycle::

    submit() ──▶ admission queue ──▶ [window] ──▶ group by admission key
       │              │                               │
       │ queue full   │ deadline passed               ▼
       ▼              ▼                      pad to bucket, fetch/compile
    "overloaded"   "expired"                 executable, ONE vmapped solve
                                                      │
                                                      ▼
                                        per-request slice → PendingSolve

The admission window is open-ended batching: the dispatch worker wakes on
the first queued request, sleeps ``window`` seconds while compatible
requests accumulate, then drains the queue grouped by
:func:`~repro.serve.batching.admission_key` — each group becomes one
:class:`~repro.core.sparse.BatchedCSR` assembly+solve or one
:class:`~repro.core.operator.MatFreeFamily` solve, padded to a power-of-two
bucket so wave-to-wave size jitter never recompiles.

All accounting goes through :mod:`repro.telemetry` — no timing machinery of
its own:

* ``serve_queue_wait_us`` / ``serve_e2e_us`` histograms (p50/p90/p99 via
  ``telemetry.snapshot()``; the SLO gate reads these),
* ``serve_batch_size`` histogram,
* ``serve_requests{outcome=...}`` counters (ok / shed / expired /
  nonconverged),
* ``cache_lookups{kind=serve_exec}`` + ``jit_traces{kind=serve}`` — the
  executable-cache hit rate and the zero-retrace-after-warmup proof,
* ``record_solve("serve.dispatch", ...)`` — Krylov iteration stats and
  solve wall time per dispatched batch,
* ``serve_queue_depth`` gauge + histogram — admission depth sampled at
  every drain (separates overload from a slow executable),
* **span trees** — every request gets a root span at :meth:`submit`
  (trace-ID minted there) with ``queue_wait`` / ``dispatch`` / ``solve`` /
  ``fetch`` / ``slice`` children summing exactly to its end-to-end
  latency; the tree rides back on ``SolveResponse.trace`` and every
  completed request is recorded in the :mod:`~repro.telemetry.spans`
  flight recorder, which auto-dumps on shed / expiry / non-convergence /
  failure.  On the dispatch thread the same phases are live spans of a
  ``serve.batch`` span (``cache_lookup``, ``pad``, ``solve`` — to
  ``block_until_ready`` —, ``fetch`` — the device→host copy — and
  ``slice`` — per-request slicing, re-upload and responses), so a profile
  shows them on its own clock.

Non-converged solves follow the PR-5 policy
(``telemetry.nonconverged_policy()``): ``"warn"`` answers ``"ok"`` with a
:class:`~repro.telemetry.ConvergenceWarning`; ``"raise"`` answers
``"nonconverged"`` with a typed :class:`~repro.serve.batching.NonConverged`
error on exactly the requests whose instance hit ``maxiter``; ``"ignore"``
stays silent.
"""

from __future__ import annotations

import threading
import time
import warnings
from collections import OrderedDict

import jax
import jax.numpy as jnp
import numpy as np

from .. import telemetry
from ..telemetry.events import ConvergenceWarning
from .batching import (
    DeadlineExpired,
    NonConverged,
    Overloaded,
    PendingSolve,
    SolveRequest,
    SolveResponse,
    admission_key,
    pad_bucket,
)
from .cache import ExecutableCache

__all__ = ["SolveService"]


class SolveService:
    """Admission-batched solve service over one or more assembly plans.

    ``window``: seconds the dispatcher waits after the first queued request
    before draining (the batching window — higher amortizes better, costs
    p50 latency).  ``max_batch`` bounds one dispatched family;
    ``queue_limit`` bounds the admission queue (submissions beyond it are
    shed with an ``"overloaded"`` response).  ``cache_capacity`` sizes the
    unpinned part of the executable cache.

    Use as a context manager (starts/stops the dispatch thread), or leave
    it unstarted and call :meth:`drain` for synchronous, deterministic
    dispatch (tests, batch jobs).
    """

    def __init__(self, *, window: float = 0.002, max_batch: int = 64,
                 queue_limit: int = 1024, cache_capacity: int = 32):
        if max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {max_batch}")
        if queue_limit < 1:
            raise ValueError(f"queue_limit must be >= 1, got {queue_limit}")
        self.window = float(window)
        self.max_batch = int(max_batch)
        self.queue_limit = int(queue_limit)
        self.cache = ExecutableCache(cache_capacity)
        self._queue: list[tuple[PendingSolve, float, float | None]] = []
        self._lock = threading.Lock()
        self._wake = threading.Condition(self._lock)
        self._worker: threading.Thread | None = None
        self._stopping = False

    # -- lifecycle ---------------------------------------------------------
    def start(self) -> "SolveService":
        """Start the dispatch thread (idempotent).  Requests submitted
        before ``start()`` sit in the queue and dispatch on the first
        window after it."""
        with self._lock:
            if self._worker is not None:
                return self
            self._stopping = False
            self._worker = threading.Thread(
                target=self._worker_loop, name="repro-serve-dispatch",
                daemon=True)
        self._worker.start()
        return self

    def stop(self) -> None:
        """Drain the queue, then stop the dispatch thread."""
        with self._lock:
            worker, self._worker = self._worker, None
            self._stopping = True
            self._wake.notify_all()
        if worker is not None:
            worker.join()
        self.drain()

    def __enter__(self) -> "SolveService":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    # -- admission ---------------------------------------------------------
    def submit(self, request: SolveRequest) -> PendingSolve:
        """Admit one request.  Returns immediately with a
        :class:`PendingSolve`; if the admission queue is full the future is
        already resolved with an ``"overloaded"`` response (typed
        :class:`Overloaded` error from ``result()``) — overload is shed, not
        queued."""
        now_ns = time.monotonic_ns()
        now = now_ns / 1e9
        pending = PendingSolve(request)
        # root of the request's span tree: trace_id minted here, carried to
        # the response via the dispatch path (NULL_SPAN when telemetry off)
        pending.span = telemetry.span_root(
            "serve.request", start_ns=now_ns,
            request_id=request.request_id, backend=request.backend,
            method=request.spec.method)
        deadline = None if request.timeout is None else now + request.timeout
        with self._lock:
            if len(self._queue) >= self.queue_limit:
                telemetry.counter_inc("serve_requests", outcome="shed")
                root = pending.span.finish(end_ns=now_ns, outcome="shed")
                telemetry.flight_record(
                    root, outcome="shed", request_id=request.request_id,
                    backend=request.backend, queue_limit=self.queue_limit)
                telemetry.flight_autodump("shed")
                pending._resolve(SolveResponse(
                    status="overloaded",
                    error=Overloaded(
                        f"admission queue full ({self.queue_limit} pending)"),
                    t_submit=now, t_dispatch=now, t_done=now,
                    trace=root.to_dict(),
                ))
                return pending
            self._queue.append((pending, now, deadline))
            self._wake.notify_all()
        return pending

    def solve(self, request: SolveRequest, timeout: float | None = None):
        """Convenience synchronous path: submit and wait.  With no worker
        running the queue is drained inline."""
        pending = self.submit(request)
        if self._worker is None and not pending.done():
            self.drain()
        return pending.result(timeout)

    # -- dispatch ----------------------------------------------------------
    def drain(self) -> int:
        """Synchronously dispatch everything queued right now (no window
        wait).  Returns the number of requests answered — the deterministic
        path used by tests and by :meth:`stop`."""
        with self._lock:
            batch, self._queue = self._queue, []
        self._sample_queue_depth(len(batch))
        return self._dispatch(batch)

    def _sample_queue_depth(self, depth: int) -> None:
        """Admission queue depth at drain time — the gauge that separates
        'the service is loaded' (depth grows) from 'one executable is slow'
        (depth normal, queue-wait p99 grows)."""
        telemetry.gauge_set("serve_queue_depth", depth)
        telemetry.histogram_observe("serve_queue_depth", depth)

    def _worker_loop(self) -> None:
        while True:
            with self._lock:
                while not self._queue and not self._stopping:
                    self._wake.wait()
                if self._stopping:
                    return
            # open the admission window: compatible requests accumulate
            if self.window > 0:
                time.sleep(self.window)
            with self._lock:
                batch, self._queue = self._queue, []
            self._sample_queue_depth(len(batch))
            self._dispatch(batch)

    def _dispatch(self, entries) -> int:
        """Group → pad → run → slice → resolve.  ``entries`` are
        ``(pending, t_submit, deadline)`` triples."""
        if not entries:
            return 0
        now_ns = time.monotonic_ns()
        now = now_ns / 1e9
        groups: OrderedDict = OrderedDict()
        n_done = 0
        for pending, t_submit, deadline in entries:
            if deadline is not None and now > deadline:
                telemetry.counter_inc("serve_requests", outcome="expired")
                root = pending.span
                root.child("queue_wait",
                           start_ns=root.start_ns).finish(end_ns=now_ns)
                root.finish(end_ns=now_ns, outcome="expired")
                telemetry.flight_record(
                    root, outcome="expired",
                    request_id=pending.request.request_id,
                    backend=pending.request.backend,
                    waited_s=round(now - t_submit, 4))
                telemetry.flight_autodump("expired")
                pending._resolve(SolveResponse(
                    status="expired",
                    error=DeadlineExpired(
                        f"request {pending.request.request_id} expired after "
                        f"{now - t_submit:.3f}s in the admission queue"),
                    t_submit=t_submit, t_dispatch=now, t_done=now,
                    trace=root.to_dict(),
                ))
                n_done += 1
                continue
            key = admission_key(pending.request)
            groups.setdefault(key, []).append((pending, t_submit))
        for key, members in groups.items():
            for start in range(0, len(members), self.max_batch):
                chunk = members[start:start + self.max_batch]
                self._run_group(key, chunk)
                n_done += len(chunk)
        return n_done

    def _run_group(self, key, members) -> None:
        pendings = [p for p, _ in members]
        submits = [t for _, t in members]
        template = pendings[0].request
        b = len(pendings)
        padded = min(pad_bucket(b), self.max_batch)
        roots = [p.span for p in pendings]
        # the batch's phases are live spans of this thread; every member's
        # tree repeats their boundaries as contiguous segments queue_wait →
        # dispatch → solve → fetch → slice, summing exactly to its
        # end-to-end latency (t_done - t_submit)
        with telemetry.span("serve.batch", batch=b, padded=padded,
                            backend=template.backend):
            t_dispatch_ns = time.monotonic_ns()
            t_dispatch = t_dispatch_ns / 1e9
            for t, root in zip(submits, roots):
                telemetry.histogram_observe(
                    "serve_queue_wait_us", 1e6 * (t_dispatch - t),
                    backend=template.backend)
                root.child("queue_wait",
                           start_ns=root.start_ns).finish(end_ns=t_dispatch_ns)
            telemetry.histogram_observe("serve_batch_size", b,
                                        backend=template.backend)
            try:
                with telemetry.span("cache_lookup"):
                    fn, cache_hit = self.cache.get(key, padded, template)
                t_lookup_ns = time.monotonic_ns()
                with telemetry.span("pad"):
                    leaves = tuple(
                        _stack_padded([p.request.leaves[j] for p in pendings],
                                      padded)
                        for j in range(len(template.leaves))
                    )
                    rhs = _stack_padded([p.request.rhs for p in pendings],
                                        padded)
                t_padded_ns = time.monotonic_ns()
                # the vmapped device solve (compiled on a cache miss)
                with telemetry.span("solve", compiled=not cache_hit):
                    x_pad, info_pad = fn(template.plan, leaves, rhs)
                    jax.block_until_ready(x_pad)
                t_solved_ns = time.monotonic_ns()
                # the device→host copy of the answers and their info
                with telemetry.span("fetch"):
                    x = np.asarray(x_pad)[:b]
                    info_b = jax.tree_util.tree_map(
                        lambda leaf: np.asarray(leaf)[:b], info_pad)
                t_fetched_ns = time.monotonic_ns()
            except Exception as err:  # compile/solve failure → fail the batch
                self._fail_group(key, members, err, padded, t_dispatch)
                return
            # per-request slicing, re-upload and responses
            with telemetry.span("slice"):
                responses = self._responses(pendings, submits, x, info_b,
                                            cache_hit, t_dispatch)
            t_done_ns = time.monotonic_ns()
            t_done = t_done_ns / 1e9
            telemetry.record_solve(
                "serve.dispatch", info_b, method=template.spec.method,
                precond=template.spec.precond_name,
                backend=template.backend,
                wall_us=1e-3 * (t_done_ns - t_dispatch_ns),
                batch=b, padded=padded, cache_hit=cache_hit)
            for (p, t), root, resp in zip(members, roots, responses):
                # the batch-level walls are duplicated into every member's
                # tree: each response carries its complete timeline
                d = root.child("dispatch", start_ns=t_dispatch_ns,
                               batch=b, padded=padded, cache_hit=cache_hit)
                d.child("cache_lookup",
                        start_ns=t_dispatch_ns).finish(end_ns=t_lookup_ns)
                d.child("pad",
                        start_ns=t_lookup_ns).finish(end_ns=t_padded_ns)
                d.finish(end_ns=t_padded_ns)
                root.child("solve", start_ns=t_padded_ns,
                           compiled=not cache_hit).finish(end_ns=t_solved_ns)
                root.child("fetch",
                           start_ns=t_solved_ns).finish(end_ns=t_fetched_ns)
                root.child("slice",
                           start_ns=t_fetched_ns).finish(end_ns=t_done_ns)
                resp.t_done = t_done
                telemetry.histogram_observe(
                    "serve_e2e_us", 1e6 * (t_done - t),
                    backend=template.backend)
                converged = bool(resp.info.converged)
                iters = int(resp.info.iters)
                root.finish(end_ns=t_done_ns, outcome=resp.status,
                            converged=converged, iters=iters)
                resp.trace = root.to_dict()
                telemetry.flight_record(
                    root, outcome=resp.status,
                    request_id=p.request.request_id, admission=_key_tag(key),
                    bucket=padded, batch=b, backend=template.backend,
                    cache_hit=cache_hit, iterations=iters,
                    final_residual=float(resp.info.residual),
                    converged=converged)
                p._resolve(resp)
        if any(r.status == "nonconverged" for r in responses):
            telemetry.flight_autodump("nonconverged")

    def _responses(self, pendings, submits, x, info_b, cache_hit,
                   t_dispatch) -> list:
        """One response per request of a solved batch, its status set by
        the non-convergence policy (``t_done`` and ``trace`` are left to
        the caller)."""
        policy = telemetry.nonconverged_policy()
        out = []
        for i, (p, t) in enumerate(zip(pendings, submits)):
            info = jax.tree_util.tree_map(lambda leaf: leaf[i], info_b)
            resp = SolveResponse(
                status="ok", u=jnp.asarray(x[i]), info=info,
                batch_size=len(pendings), cache_hit=cache_hit,
                t_submit=t, t_dispatch=t_dispatch,
            )
            outcome = "ok"
            if not info.converged:
                msg = (f"request {p.request.request_id}: solve not converged "
                       f"after {int(info.iters)} iterations "
                       f"(residual {float(info.residual):.3e})")
                if policy == "raise":
                    resp.status = outcome = "nonconverged"
                    resp.error = NonConverged(msg)
                    resp.u = None
                elif policy == "warn":
                    warnings.warn(msg, ConvergenceWarning, stacklevel=3)
            telemetry.counter_inc("serve_requests", outcome=outcome)
            out.append(resp)
        return out

    def _fail_group(self, key, members, err, padded, t_dispatch) -> None:
        t_done_ns = time.monotonic_ns()
        t_done = t_done_ns / 1e9
        b = len(members)
        telemetry.counter_inc("serve_requests", value=b, outcome="failed")
        for p, t in members:
            root = p.span
            root.finish(end_ns=t_done_ns, outcome="failed",
                        error=type(err).__name__)
            telemetry.flight_record(
                root, outcome="failed", request_id=p.request.request_id,
                admission=_key_tag(key), bucket=padded, batch=b,
                error=repr(err))
            p._resolve(SolveResponse(
                status="failed", error=err, batch_size=b,
                t_submit=t, t_dispatch=t_dispatch, t_done=t_done,
                trace=root.to_dict()))
        telemetry.flight_autodump("failed")

    # -- warmup ------------------------------------------------------------
    def warmup(self, request: SolveRequest, batch_sizes=(1,),
               pin: bool = True) -> None:
        """Pre-compile (and optionally pin) the executables a production
        signature needs: one padded-bucket executable per entry of
        ``batch_sizes``.  The request's coefficient values are only a
        template — warmup runs real (cold) solves on copies of it so the
        first tenant wave is a pure cache hit."""
        key = admission_key(request)
        with telemetry.span("serve.warmup", backend=request.backend,
                            buckets=len(tuple(batch_sizes))):
            for bs in batch_sizes:
                padded = min(pad_bucket(int(bs)), self.max_batch)
                if pin:
                    self.cache.pin(key, padded)
                fn, hit = self.cache.get(key, padded, request)
                if not hit:
                    leaves = tuple(
                        _stack_padded([request.leaves[j]], padded)
                        for j in range(len(request.leaves))
                    )
                    rhs = _stack_padded([request.rhs], padded)
                    x, _ = fn(request.plan, leaves, rhs)
                    jax.block_until_ready(x)


def _key_tag(key) -> str:
    """Short printable admission-key tag for flight-recorder context (the
    raw key holds object ids and a lowered form signature — not JSON)."""
    plan_id, _form, _bc, backend, spec = key
    return (f"plan={plan_id & 0xFFFFFFFF:08x};backend={backend};"
            f"method={spec.method}")


def _stack_padded(arrays, padded: int) -> jnp.ndarray:
    """Stack per-request arrays to ``(padded, ...)``, repeating the last
    entry into the padding rows (padding solves then converge like real
    ones instead of iterating on garbage)."""
    out = jnp.stack([jnp.asarray(a) for a in arrays])
    if out.shape[0] < padded:
        reps = jnp.broadcast_to(
            out[-1], (padded - out.shape[0],) + out.shape[1:])
        out = jnp.concatenate([out, reps], axis=0)
    return out
