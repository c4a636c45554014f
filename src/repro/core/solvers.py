"""Iterative sparse solvers + the differentiable solve (paper Eq. 11).

* :func:`cg`, :func:`bicgstab` — preconditioned Krylov solvers as
  ``lax.while_loop`` (O(1) trace size; matches the paper's solver setup:
  BiCGSTAB + Jacobi, tol 1e-10, maxiter 10k — SM Table B.1).  Both return
  ``(x, SolveInfo)`` where :class:`SolveInfo` carries the iteration count,
  the final residual norm and a ``converged`` flag set from the exit
  condition — an exit at ``maxiter`` is *visible*, not silent garbage.
* :class:`SolverSpec` — one frozen, hashable value object for the solver
  knobs ``(method, tol, atol, maxiter, precond)``.  Every solve entry point
  (:func:`sparse_solve`, :func:`matfree_solve`, both ``_batched`` variants,
  problem ``.solve()``, the transient integrators) accepts ``spec=``; the
  old per-kwarg form still works but emits a :class:`DeprecationWarning`.
  Because a spec is hashable it is also the jit/custom-vjp static argument
  and the ``repro.serve`` admission-key component.
* preconditioner registry — :func:`register_preconditioner` maps a name to
  a ``factory(op) -> m(x)`` (mirroring :mod:`repro.core.matvec`'s backend
  registry).  Built-ins: ``identity``/``none``, ``jacobi``; the element
  tensor-algebra layer (:mod:`repro.core.elemalg`) registers ``ebe`` and
  ``chebyshev`` on import (resolved lazily here, so ``SolverSpec(precond=
  "chebyshev")`` works without importing elemalg first).
* :func:`sparse_solve` — ``jax.custom_vjp``: the backward pass solves the
  adjoint system ``Kᵀλ = ḡ`` with the *same* solver and emits the **sparse**
  cotangent ``∂/∂vals = −λ[rows]·U[cols]`` (only at stored nnz positions) and
  ``∂/∂F = λ``.  This is the TORCH-SLA trick: O(1) extra graph nodes per
  optimization iteration instead of O(iters × DoFs) from unrolling.
* :func:`matfree_solve` — the same adjoint structure for ANY pytree linear
  operator (notably :class:`repro.core.operator.MatFreeOperator`): the
  backward pass solves ``Aᵀλ = ḡ`` via ``rmatvec`` and obtains the operator
  cotangent as the vjp of ``θ ↦ A(θ)·x`` at ``−λ`` — so ``grad`` through a
  matrix-free solve matches the assembled adjoint path without ever
  materializing values.

Convergence diagnostics (``repro.telemetry``): every solve entry point
accepts ``return_info=True`` and then returns ``(x, SolveInfo)``.  The info
is a **non-differentiated auxiliary output** — its leaves are stop-gradient,
so the ``custom_vjp`` adjoint structure is untouched and ``jax.grad``
through the info-returning path matches the plain path to machine
precision.  Solve events are labelled with method *and* preconditioner, so
the telemetry iteration histograms split per preconditioner.

``cg`` / ``bicgstab`` accept either a matvec callable or any object with a
``.matvec`` method (CSR, MatFreeOperator); :func:`jacobi_preconditioner`
needs only ``.diagonal()`` — for matrix-free operators that is a cheap
diagonal-only assembly, memoized per operator identity through
:func:`repro.core.sparse.cached_diagonal`.
"""

from __future__ import annotations

import dataclasses
import warnings
from functools import partial
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from ..telemetry import annotate, events, metrics, spans
from .sparse import CSR, BatchedCSR, _dev, cached_diagonal

__all__ = [
    "cg",
    "bicgstab",
    "SolverSpec",
    "resolve_solver_spec",
    "register_preconditioner",
    "make_preconditioner",
    "jacobi_preconditioner",
    "sparse_solve",
    "sparse_solve_batched",
    "matfree_solve",
    "matfree_solve_batched",
    "SolveInfo",
]


class SolveInfo(NamedTuple):
    """Per-solve diagnostics: iteration count, final residual norm, and the
    exit condition (``converged = ‖r‖ ≤ max(tol·‖b‖, atol)``).  Leaves are
    jnp arrays — a batched / per-step solve stacks them (``(B,)`` /
    ``(n_steps,)``)."""

    iters: jnp.ndarray
    residual: jnp.ndarray
    converged: jnp.ndarray


def _info_aux(info: SolveInfo) -> SolveInfo:
    """The info as a non-differentiated auxiliary output: stop-gradient on
    every leaf, so returning it cannot perturb the adjoint structure."""
    return SolveInfo(*(jax.lax.stop_gradient(leaf) for leaf in info))


# ---------------------------------------------------------------------------
# SolverSpec: the solver knobs as one frozen, hashable value object
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SolverSpec:
    """Solver configuration ``(method, tol, atol, maxiter, precond)`` as a
    frozen, hashable value object.

    One spec flows from the public entry points through the ``custom_vjp``
    static arguments into the Krylov loop, and doubles as the solver part of
    the ``repro.serve`` admission key — requests with different specs never
    co-batch.  ``precond`` names a registered preconditioner (see
    :func:`register_preconditioner`) or is a ``factory(op) -> m`` callable.
    """

    method: str = "bicgstab"
    tol: float = 1e-10
    atol: float = 1e-10
    maxiter: int = 10000
    precond: str | Callable = "jacobi"

    def replace(self, **kw) -> "SolverSpec":
        return dataclasses.replace(self, **kw)

    @property
    def precond_name(self) -> str:
        return self.precond if isinstance(self.precond, str) else getattr(
            self.precond, "__name__", "custom")


_LEGACY_POS = ("tol", "atol", "maxiter", "precond")


def resolve_solver_spec(spec, legacy_pos=(), *, method=None, tol=None,
                        atol=None, maxiter=None, precond=None,
                        default: SolverSpec | None = None,
                        where: str = "solve") -> SolverSpec:
    """Fold a ``spec=`` argument and/or legacy per-kwarg arguments into one
    :class:`SolverSpec`.

    The pre-redesign signatures took ``method, tol, atol, maxiter, precond``
    positionally after the right-hand side; those forms still work — a bare
    string in the spec slot is the legacy ``method``, ``legacy_pos`` maps to
    ``(tol, atol, maxiter, precond)`` — but any legacy use emits a
    ``DeprecationWarning`` naming the entry point.
    """
    base_default = SolverSpec() if default is None else default
    if isinstance(spec, str):
        if method is not None:
            raise TypeError(f"{where}: got both a positional method string "
                            f"({spec!r}) and method={method!r}")
        method, spec = spec, None
    if spec is not None and not isinstance(spec, SolverSpec):
        raise TypeError(
            f"{where}: spec must be a SolverSpec (got {type(spec).__name__});"
            " build one with repro.core.SolverSpec(method=..., tol=...)"
        )
    if len(legacy_pos) > len(_LEGACY_POS):
        raise TypeError(f"{where}: too many positional arguments")
    legacy = dict(zip(_LEGACY_POS, legacy_pos))
    for name, val in (("method", method), ("tol", tol), ("atol", atol),
                      ("maxiter", maxiter), ("precond", precond)):
        if val is not None:
            if name in legacy:
                raise TypeError(f"{where}: {name} given positionally and as "
                                "a keyword")
            legacy[name] = val
    if not legacy:
        return spec if spec is not None else base_default
    warnings.warn(
        f"{where}: passing method/tol/atol/maxiter/precond individually is "
        f"deprecated — pass spec=SolverSpec({', '.join(f'{k}={v!r}' for k, v in legacy.items())})",
        DeprecationWarning, stacklevel=3,
    )
    base = spec if spec is not None else base_default
    return dataclasses.replace(base, **legacy)


# defaults per entry point: the paper's BiCGSTAB+Jacobi for assembled CSR
# systems, CG+Jacobi for the (symmetric-by-construction) matrix-free path
_SPARSE_DEFAULT = SolverSpec(method="bicgstab")
_MATFREE_DEFAULT = SolverSpec(method="cg")


# ---------------------------------------------------------------------------
# Preconditioner registry (mirrors repro.core.matvec's backend registry)
# ---------------------------------------------------------------------------

def jacobi_preconditioner(a) -> Callable:
    """Diagonal (Jacobi) preconditioner from anything with ``.diagonal()`` —
    an assembled :class:`CSR` or a matrix-free operator (diagonal-only
    assembly, no nnz vector).  The diagonal is memoized per (operator
    identity, dtype) via :func:`repro.core.sparse.cached_diagonal`, so
    repeated solves against the same operator skip the re-densification."""
    d = cached_diagonal(a)
    inv = jnp.where(jnp.abs(d) > 0, 1.0 / d, 1.0)
    return lambda x: inv * x


def _identity(x):
    return x


_PRECONDITIONERS: dict[str, Callable] = {}


def register_preconditioner(name: str, factory: Callable, *,
                            overwrite: bool = False):
    """Register ``factory(op) -> m`` under ``name`` so any
    :class:`SolverSpec` (and the legacy ``precond=`` kwarg) can select it.

    ``op`` is whatever reaches the solve (CSR, MatFreeOperator, ...);
    ``m(x)`` must be trace-compatible (it runs inside the Krylov
    ``while_loop``).  Mirrors :func:`repro.core.matvec.register_matvec_backend`.
    """
    if name in _PRECONDITIONERS and not overwrite:
        raise ValueError(
            f"preconditioner {name!r} already registered; pass overwrite=True"
        )
    _PRECONDITIONERS[name] = factory


register_preconditioner("identity", lambda op: _identity)
register_preconditioner("none", lambda op: _identity)
register_preconditioner("jacobi", jacobi_preconditioner)


def make_preconditioner(op, precond="jacobi") -> Callable:
    """Resolve a preconditioner name (or ``factory`` callable, or ``None``
    for identity) against ``op`` via the registry.  Unknown names raise a
    ``KeyError`` listing what is registered."""
    if precond is None:
        return _identity
    if callable(precond):
        return precond(op)
    factory = _PRECONDITIONERS.get(precond)
    if factory is None and precond in ("ebe", "chebyshev"):
        from . import elemalg  # noqa: F401  (registers ebe/chebyshev)
        factory = _PRECONDITIONERS.get(precond)
    if factory is None:
        raise KeyError(
            f"unknown preconditioner {precond!r}; registered: "
            f"{sorted(_PRECONDITIONERS)} — add one with "
            "repro.core.register_preconditioner(name, factory)"
        )
    return factory(op)


def _as_matvec(a) -> Callable:
    """Normalize an operator argument: a callable is used as-is, anything
    else must expose ``.matvec`` (CSR, MatFreeOperator, ELL)."""
    return a if callable(a) else a.matvec


def _dot(a, b):
    """``jnp.vdot`` as a product and a sum reduce.  XLA:TPU emulates a
    float64 ``dot_general`` by splitting its operands into slices inside
    while loops, which at 177k DOFs took most of a CG iteration on a v5e;
    a float64 reduce needs no such loops."""
    return jnp.sum(jnp.conj(a) * b)


# ---------------------------------------------------------------------------
# Conjugate gradients (SPD systems: Poisson, elasticity)
# ---------------------------------------------------------------------------

# the counter of Krylov loop traces, labelled by ``method``: each loop body
# bumps it while it is traced, so it counts traces and compiles of the loop,
# never runs of a compiled executable
KRYLOV_TRACES = "krylov_traces"

# CG folds its correction into the iterate and recomputes the residual each
# time the residual has fallen by _CG_REPLACE; it restarts from the
# recomputed residual when that differs from the recursive one by more than
# _CG_AGREE of its norm
_CG_REPLACE = 1e-3
_CG_AGREE = 0.1


def cg(matvec, b, x0=None, *, tol=1e-10, atol=1e-10, maxiter=10000, m=_identity):
    """Preconditioned CG with residual replacement and group update (van der
    Vorst & Ye 2000): steps accumulate in a correction ``dx`` (rounded
    relative to its own small size, not to ``x``); each time the residual
    has fallen by ``_CG_REPLACE`` the correction is folded into ``x`` and
    the residual recomputed as ``b − A x``.  Updating ``x`` in place leaves
    a rounding error in every entry each iteration, which a stiffness
    operator (‖A‖‖x‖/‖b‖ ≈ 4e5 at 1M DOFs) turns into a true residual that
    stalls far above the recursive one: 3e-9 against 1e-10 on a TPU v5e."""
    matvec = _as_matvec(matvec)
    x0 = jnp.zeros_like(b) if x0 is None else x0
    bnorm = jnp.linalg.norm(b)
    target = jnp.maximum(tol * bnorm, atol)

    r0 = b - matvec(x0)
    z0 = m(r0)
    rnorm0 = jnp.linalg.norm(r0)
    fold0 = jnp.array(False)
    state = (x0, jnp.zeros_like(b), r0, z0, _dot(r0, z0), rnorm0,
             jnp.maximum(target, _CG_REPLACE * rnorm0), fold0, jnp.array(0))

    def cond(s):
        *_, rnorm, _, _, it = s
        return (rnorm > target) & (it < maxiter)

    # one matvec per pass: a CG step applies A to p; a fold pass (after the
    # residual fell below the segment's stop) applies it to x + dx instead
    def body(s):
        metrics.counter_inc(KRYLOV_TRACES, method="cg")
        x, dx, r, p, rz, rnorm, stop, fold, it = s
        xf = x + dx
        av = matvec(jnp.where(fold, xf, p))
        # fold: restart from the true residual, keeping the search direction
        # only while it agrees with the recursive one (else CG diverges)
        r_true = b - av
        keep = jnp.linalg.norm(r_true - r) <= _CG_AGREE * rnorm
        # step
        alpha = rz / _dot(p, av)
        r_new = jnp.where(fold, r_true, r - alpha * av)
        z = m(r_new)
        rz_new = _dot(r_new, z)
        p_new = jnp.where(fold, jnp.where(keep, p, z), z + (rz_new / rz) * p)
        rnorm_new = jnp.linalg.norm(r_new)
        stop = jnp.where(fold, jnp.maximum(target, _CG_REPLACE * rnorm_new),
                         stop)
        return (jnp.where(fold, xf, x),
                jnp.where(fold, 0.0, dx + alpha * p),
                r_new, p_new, rz_new, rnorm_new, stop,
                ~fold & (rnorm_new <= stop) & (rnorm_new > target),
                it + jnp.where(fold, 0, 1))

    with annotate("tg.solve.cg"):
        x, dx, _, _, _, rnorm, *_, it = jax.lax.while_loop(cond, body, state)
    return x + dx, SolveInfo(it, rnorm, rnorm <= target)


# ---------------------------------------------------------------------------
# BiCGSTAB (general systems; the paper's default — van der Vorst 1992)
# ---------------------------------------------------------------------------

def bicgstab(matvec, b, x0=None, *, tol=1e-10, atol=1e-10, maxiter=10000, m=_identity):
    matvec = _as_matvec(matvec)
    x0 = jnp.zeros_like(b) if x0 is None else x0
    bnorm = jnp.linalg.norm(b)
    target = jnp.maximum(tol * bnorm, atol)

    r0 = b - matvec(x0)
    rhat = r0
    state = (
        x0, r0,
        jnp.ones((), b.dtype), jnp.ones((), b.dtype), jnp.ones((), b.dtype),
        jnp.zeros_like(b), jnp.zeros_like(b),
        jnp.array(0),
    )

    def cond(s):
        _, r, *_, it = s
        return (jnp.linalg.norm(r) > target) & (it < maxiter)

    def body(s):
        metrics.counter_inc(KRYLOV_TRACES, method="bicgstab")
        x, r, rho, alpha, omega, v, p, it = s
        rho_new = _dot(rhat, r)
        beta = (rho_new / jnp.where(rho == 0, 1e-30, rho)) * (
            alpha / jnp.where(omega == 0, 1e-30, omega)
        )
        p = r + beta * (p - omega * v)
        phat = m(p)
        v = matvec(phat)
        denom = _dot(rhat, v)
        alpha = rho_new / jnp.where(denom == 0, 1e-30, denom)
        s_vec = r - alpha * v
        shat = m(s_vec)
        t = matvec(shat)
        tt = _dot(t, t)
        omega = _dot(t, s_vec) / jnp.where(tt == 0, 1e-30, tt)
        x = x + alpha * phat + omega * shat
        r = s_vec - omega * t
        return (x, r, rho_new, alpha, omega, v, p, it + 1)

    with annotate("tg.solve.bicgstab"):
        x, r, *_, it = jax.lax.while_loop(cond, body, state)
    rnorm = jnp.linalg.norm(r)
    return x, SolveInfo(it, rnorm, rnorm <= target)


_METHODS = {"cg": cg, "bicgstab": bicgstab}


def _method(name):
    try:
        return _METHODS[name]
    except KeyError:
        raise KeyError(
            f"unknown solver method {name!r}; use one of {sorted(_METHODS)}"
        ) from None


# ---------------------------------------------------------------------------
# Differentiable sparse solve (TORCH-SLA analogue)
# ---------------------------------------------------------------------------

def _solve_impl(a: CSR, b, spec: SolverSpec, transpose=False):
    matvec = a.rmatvec if transpose else a.matvec
    m = make_preconditioner(a, spec.precond)
    return _method(spec.method)(matvec, b, tol=spec.tol, atol=spec.atol,
                                maxiter=spec.maxiter, m=m)


@partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _sparse_solve(a: CSR, b, spec: SolverSpec, return_info):
    x, info = _solve_impl(a, b, spec)
    return (x, _info_aux(info)) if return_info else x


def _solve_fwd(a, b, spec, return_info):
    x, info = _solve_impl(a, b, spec)
    out = (x, _info_aux(info)) if return_info else x
    return out, (a, x)


def _solve_bwd(spec, return_info, res, g):
    a, x = res
    gx = g[0] if return_info else g
    # adjoint: Kᵀ λ = ḡ   (Eq. 11; sign handled by the chain rule caller)
    lam, adj_info = _solve_impl(a, gx, spec, transpose=True)
    # adjoint-solve diagnostics: recorded when the backward pass runs with
    # concrete cotangents (eager grad); a no-op under further tracing
    events.record_solve("sparse_solve.adjoint", adj_info, method=spec.method,
                        precond=spec.precond_name, phase="adjoint")
    # ∂L/∂vals = −λ_r · x_c at each stored (r, c) — never densified
    dvals = -lam[_dev(a.row_of_nnz)] * x[_dev(a.indices)]
    da = dataclasses.replace(a, vals=dvals)
    return (da, lam)


_sparse_solve.defvjp(_solve_fwd, _solve_bwd)


def sparse_solve(a: CSR, b, spec: SolverSpec | None = None, *legacy,
                 method=None, tol=None, atol=None, maxiter=None, precond=None,
                 return_info=False):
    """x = A⁻¹ b, differentiable w.r.t. ``a.vals`` and ``b`` via the adjoint.

    Solver knobs come in as one :class:`SolverSpec` (``spec=``; default
    BiCGSTAB + Jacobi at 1e-10).  The legacy per-kwarg form
    (``method=, tol=, ...``) still works but emits a ``DeprecationWarning``.

    ``return_info=True`` additionally returns the :class:`SolveInfo`
    (iterations / final residual / ``converged``) as a stop-gradient
    auxiliary output — gradients are bit-identical to the plain path.
    """
    spec = resolve_solver_spec(spec, legacy, method=method, tol=tol,
                               atol=atol, maxiter=maxiter, precond=precond,
                               default=_SPARSE_DEFAULT, where="sparse_solve")
    # span-aware eager boundary: the solve (host dispatch wall) becomes a
    # span — child of any open request/driver span — and the record_solve
    # event inherits its trace identity
    with spans.span("sparse_solve", method=spec.method, backend="csr"):
        out = _sparse_solve(a, b, spec, bool(return_info))
        if return_info:
            x, info = out
            events.record_solve("sparse_solve", info, method=spec.method,
                                backend="csr", precond=spec.precond_name)
            return x, info
        return out


# ---------------------------------------------------------------------------
# Differentiable matrix-free solve: the adjoint trick for pytree operators
# ---------------------------------------------------------------------------

def _op_solve_impl(op, b, spec: SolverSpec, transpose=False):
    matvec = op.rmatvec if transpose else op.matvec
    m = make_preconditioner(op, spec.precond)
    return _method(spec.method)(matvec, b, tol=spec.tol, atol=spec.atol,
                                maxiter=spec.maxiter, m=m)


@partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _matfree_solve(op, b, spec: SolverSpec, return_info):
    x, info = _op_solve_impl(op, b, spec)
    return (x, _info_aux(info)) if return_info else x


def _matfree_fwd(op, b, spec, return_info):
    x, info = _op_solve_impl(op, b, spec)
    out = (x, _info_aux(info)) if return_info else x
    return out, (op, x)


def _matfree_bwd(spec, return_info, res, g):
    op, x = res
    gx = g[0] if return_info else g
    lam, adj_info = _op_solve_impl(op, gx, spec, transpose=True)
    events.record_solve("matfree_solve.adjoint", adj_info, method=spec.method,
                        precond=spec.precond_name, phase="adjoint")
    # ∂L/∂θ = −λᵀ (∂A/∂θ) x — the vjp of the apply w.r.t. the operator pytree
    _, pullback = jax.vjp(lambda o: o.matvec(x), op)
    (d_op,) = pullback(-lam)
    return (d_op, lam)


_matfree_solve.defvjp(_matfree_fwd, _matfree_bwd)


def matfree_solve(op, b, spec: SolverSpec | None = None, *legacy,
                  method=None, tol=None, atol=None, maxiter=None, precond=None,
                  return_info=False):
    """``x = A⁻¹ b`` for any pytree linear operator with ``matvec`` /
    ``rmatvec`` / ``diagonal`` — differentiable w.r.t. the operator's traced
    leaves (coefficients, geometry) *and* ``b`` via the adjoint solve.

    The backward pass solves ``Aᵀλ = ḡ`` with the same Krylov method, then
    recovers the operator cotangent as ``vjp(θ ↦ A(θ)·x)(−λ)`` — for a
    :class:`~repro.core.operator.MatFreeOperator` that is one extra
    matrix-free apply-transpose, never an assembled matrix.  (A :class:`CSR`
    works too and reproduces :func:`sparse_solve`'s sparse cotangent.)

    Solver knobs come in as one :class:`SolverSpec` (default CG + Jacobi);
    legacy per-kwarg use emits a ``DeprecationWarning``.  ``return_info=True``
    additionally returns the :class:`SolveInfo` as a stop-gradient auxiliary
    output (gradients match the plain path).
    """
    spec = resolve_solver_spec(spec, legacy, method=method, tol=tol,
                               atol=atol, maxiter=maxiter, precond=precond,
                               default=_MATFREE_DEFAULT, where="matfree_solve")
    with spans.span("matfree_solve", method=spec.method, backend="matfree"):
        out = _matfree_solve(op, b, spec, bool(return_info))
        if return_info:
            x, info = out
            events.record_solve("matfree_solve", info, method=spec.method,
                                backend="matfree", precond=spec.precond_name)
            return x, info
        return out


def matfree_solve_batched(family, b, spec: SolverSpec | None = None, *legacy,
                          method=None, tol=None, atol=None, maxiter=None,
                          precond=None, return_info=False):
    """``X_b = A_b⁻¹ b_b`` over a matrix-free
    :class:`~repro.core.operator.MatFreeFamily` — one ``vmap`` of the
    differentiable :func:`matfree_solve` with the family's leaf axes, so the
    B Krylov solves (and their adjoint solves under ``grad``) share a single
    executable on one plan/signature, with zero matrix materialization.

    ``b`` is ``(B, n)`` per-instance or ``(n,)`` shared; returns ``(B, n)``
    (plus a ``SolveInfo`` with ``(B,)`` leaves under ``return_info=True``).
    Gradients w.r.t. the batched coefficient leaves match B per-instance
    adjoint :func:`matfree_solve` calls.
    """
    spec = resolve_solver_spec(spec, legacy, method=method, tol=tol,
                               atol=atol, maxiter=maxiter, precond=precond,
                               default=_MATFREE_DEFAULT,
                               where="matfree_solve_batched")
    b = jnp.asarray(b)
    in_b = None if b.ndim == 1 else 0
    with spans.span("matfree_solve_batched", method=spec.method,
                    backend="matfree"):
        out = jax.vmap(
            lambda op, bi: _matfree_solve(op, bi, spec, bool(return_info)),
            in_axes=(family.in_axes(), in_b),
        )(family.op, b)
        if return_info:
            x, info = out
            events.record_solve("matfree_solve_batched", info,
                                method=spec.method, backend="matfree",
                                precond=spec.precond_name)
            return x, info
        return out


def sparse_solve_batched(a: BatchedCSR, b, spec: SolverSpec | None = None,
                         *legacy, method=None, tol=None, atol=None,
                         maxiter=None, precond=None, return_info=False):
    """X_b = A_b⁻¹ b_b over a :class:`BatchedCSR` family — one ``vmap`` of the
    differentiable :func:`sparse_solve`, so the B Krylov solves share a
    single XLA executable (and a single adjoint executable under ``grad``).

    ``b`` is ``(B, n)`` per-instance or ``(n,)`` shared; returns ``(B, n)``
    (plus a ``SolveInfo`` with ``(B,)`` leaves under ``return_info=True``).
    """
    spec = resolve_solver_spec(spec, legacy, method=method, tol=tol,
                               atol=atol, maxiter=maxiter, precond=precond,
                               default=_SPARSE_DEFAULT,
                               where="sparse_solve_batched")
    b = jnp.asarray(b)
    in_b = None if b.ndim == 1 else 0
    with spans.span("sparse_solve_batched", method=spec.method,
                    backend="csr"):
        out = jax.vmap(
            lambda ab, bi: _sparse_solve(ab.as_csr(), bi, spec,
                                         bool(return_info)),
            in_axes=(0, in_b),
        )(a, b)
        if return_info:
            x, info = out
            events.record_solve("sparse_solve_batched", info,
                                method=spec.method, backend="csr",
                                precond=spec.precond_name)
            return x, info
        return out
