"""Admission-batching data model: requests, responses, compatibility keys.

A :class:`SolveRequest` is one tenant's PDE solve: a plan reference, a
:class:`~repro.core.weakform.WeakForm` (whose traced leaves carry the
tenant's coefficients), an assembled RHS vector, an optional Dirichlet
condenser, and solve/QoS knobs.  Two requests are *compatible* — batchable
into one vmapped executable — exactly when they share the admission key

    (plan.static identity, lowered form signature, bc identity,
     backend, SolverSpec)

i.e. the same jit signature the core assembly/operator caches key on: only
the coefficient leaf *values* and the RHS differ across a batch, so B
compatible requests run as ONE :class:`~repro.core.sparse.BatchedCSR`
assembly+solve or one :class:`~repro.core.operator.MatFreeFamily` solve.

The response side is deliberately boring: a :class:`PendingSolve` is a
minimal future (threading.Event + slot) resolved by the service worker with
a :class:`SolveResponse` whose ``status`` is one of ``"ok"``,
``"overloaded"`` (shed at admission), ``"expired"`` (deadline passed before
dispatch) or ``"nonconverged"`` (Krylov maxiter exit under the
``on_nonconverged="raise"`` policy).  ``result()`` raises the typed error;
``response()`` never raises.
"""

from __future__ import annotations

import dataclasses
import itertools
import threading
from typing import Any

import jax.numpy as jnp

from ..core import weakform
from ..core.solvers import SolverSpec, resolve_solver_spec
from ..telemetry.spans import NULL_SPAN

__all__ = [
    "SolveRequest",
    "SolveResponse",
    "PendingSolve",
    "Overloaded",
    "DeadlineExpired",
    "NonConverged",
    "admission_key",
    "pad_bucket",
]

_REQUEST_IDS = itertools.count()


class Overloaded(RuntimeError):
    """Request shed at admission: the bounded queue was full."""


class DeadlineExpired(TimeoutError):
    """Request expired in the admission queue before dispatch."""


class NonConverged(RuntimeError):
    """The request's Krylov solve exited at ``maxiter`` and the service
    runs under the ``on_nonconverged="raise"`` policy."""


@dataclasses.dataclass(frozen=True)
class SolveRequest:
    """One tenant solve: ``A(form) u = rhs`` on ``plan``, condensed by ``bc``.

    ``form``'s traced leaves are the tenant's coefficient values; ``rhs`` is
    the *assembled* load vector ``(n,)`` (use ``assemble_rhs(plan,
    wf.source(f))``).  Dirichlet conditions are homogeneous (condensation
    masks the RHS); ``timeout`` is the seconds the request may wait in the
    admission queue before it is answered ``"expired"`` instead of solved.
    """

    plan: Any                      # AssemblyPlan (shared across a batch)
    form: Any                      # WeakForm — per-tenant coefficient leaves
    rhs: jnp.ndarray               # assembled (n,) load vector
    bc: Any = None                 # DirichletCondenser | None (homogeneous)
    backend: str = "csr"           # "csr" | "matfree"
    spec: SolverSpec | None = None  # Krylov config; part of the admission key
    method: str | None = None      # deprecated → spec.method
    tol: float | None = None       # deprecated → spec.tol (and atol)
    maxiter: int | None = None     # deprecated → spec.maxiter
    timeout: float | None = None   # admission-queue deadline [s]
    request_id: int = dataclasses.field(
        default_factory=lambda: next(_REQUEST_IDS))

    def __post_init__(self):
        if self.backend not in ("csr", "matfree"):
            raise ValueError(
                f"unknown backend {self.backend!r}: expected 'csr' or 'matfree'"
            )
        # fold legacy per-field knobs into one hashable SolverSpec (the
        # admission key carries the spec object, so every solver knob —
        # including precond — separates compatibility classes)
        spec = resolve_solver_spec(
            self.spec, method=self.method, tol=self.tol, atol=self.tol,
            maxiter=self.maxiter,
            default=SolverSpec(method="cg", tol=1e-10, atol=1e-10,
                               maxiter=10000),
            where="SolveRequest")
        object.__setattr__(self, "spec", spec)
        object.__setattr__(self, "method", spec.method)
        object.__setattr__(self, "tol", spec.tol)
        object.__setattr__(self, "maxiter", spec.maxiter)
        form_sig, leaves = weakform.lower(self.form, weakform.MATRIX)
        object.__setattr__(self, "_form_sig", form_sig)
        object.__setattr__(
            self, "_leaves", tuple(jnp.asarray(lf) for lf in leaves))

    @property
    def form_sig(self):
        """The lowered (hashable) form signature — the batching key part."""
        return self._form_sig

    @property
    def leaves(self) -> tuple:
        """The traced coefficient leaves, in lowering slot order."""
        return self._leaves


@dataclasses.dataclass
class SolveResponse:
    """What a :class:`PendingSolve` resolves to.  ``u``/``info`` are set for
    ``status == "ok"`` (and ``"nonconverged"``); ``error`` carries the typed
    exception otherwise.  Timestamps are ``time.monotonic()`` seconds (the
    service's clock) so clients can cross-check the telemetry histograms."""

    status: str                    # "ok" | "overloaded" | "expired" | "nonconverged"
    u: jnp.ndarray | None = None
    info: Any = None               # per-request SolveInfo slice
    error: Exception | None = None
    batch_size: int = 0            # admission batch the request rode in
    cache_hit: bool | None = None  # executable-cache outcome of that batch
    t_submit: float = 0.0
    t_dispatch: float = 0.0
    t_done: float = 0.0
    trace: dict | None = None      # span tree (telemetry on) — see spans.py

    @property
    def ok(self) -> bool:
        return self.status == "ok"

    @property
    def span_segments_us(self) -> dict:
        """Top-level segment walls (µs) of the carried span tree — e.g.
        ``{"queue_wait": ..., "dispatch": ..., "solve": ..., "fetch": ...,
        "slice": ...}`` summing to the end-to-end latency.  Empty without
        telemetry."""
        if not self.trace:
            return {}
        return {
            c["name"]: c["wall_us"]
            for c in self.trace.get("children", ())
            if c.get("wall_us") is not None
        }

    @property
    def queue_wait_s(self) -> float:
        return max(0.0, self.t_dispatch - self.t_submit)

    @property
    def e2e_s(self) -> float:
        return max(0.0, self.t_done - self.t_submit)


class PendingSolve:
    """A minimal future for one submitted request."""

    def __init__(self, request: SolveRequest):
        self.request = request
        # the request's root span, set by SolveService.submit() when
        # telemetry is on (NULL_SPAN otherwise: every span call is a no-op)
        self.span = NULL_SPAN
        self._event = threading.Event()
        self._response: SolveResponse | None = None

    def _resolve(self, response: SolveResponse) -> None:
        self._response = response
        self._event.set()

    def done(self) -> bool:
        return self._event.is_set()

    def response(self, timeout: float | None = None) -> SolveResponse:
        """Block until the service answers; never raises on error statuses."""
        if not self._event.wait(timeout):
            raise TimeoutError(
                f"request {self.request.request_id} not answered within "
                f"{timeout}s"
            )
        return self._response

    def result(self, timeout: float | None = None) -> jnp.ndarray:
        """The solution vector; raises the typed error on non-``ok`` statuses
        (:class:`Overloaded` / :class:`DeadlineExpired` /
        :class:`NonConverged`)."""
        resp = self.response(timeout)
        if resp.error is not None:
            raise resp.error
        return resp.u


def admission_key(req: SolveRequest) -> tuple:
    """The compatibility key: requests with equal keys batch into one
    executable.  Plan and condenser enter by *identity* (same convention as
    the core jit caches — ``PlanStatic`` is identity-hashed); the frozen
    :class:`~repro.core.SolverSpec` enters by value, so every solver knob
    (method, tolerances, preconditioner) separates compatibility classes."""
    return (
        id(req.plan.static),
        req.form_sig,
        id(req.bc) if req.bc is not None else None,
        req.backend,
        req.spec,
    )


def pad_bucket(b: int) -> int:
    """Round a batch size up to the next power of two.  Padding admission
    batches to bucket sizes keeps the executable cache small and stable:
    waves of 9, 13 and 16 requests all reuse the B=16 executable instead of
    compiling three."""
    if b < 1:
        raise ValueError(f"batch size must be >= 1, got {b}")
    return 1 << (b - 1).bit_length()
