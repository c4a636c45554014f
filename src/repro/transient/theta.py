"""θ-method time integration for parabolic problems (heat, diffusion).

Semidiscrete system:  M u̇ + K u = F(t),  u(0) = u₀, with the one-parameter
family

    (M + θ Δt K) uⁿ⁺¹ = (M − (1−θ) Δt K) uⁿ + Δt Fⁿ⁺ᶿ

θ = 1 is backward Euler (first order, L-stable), θ = ½ is Crank–Nicolson
(second order, A-stable).  Both effective operators share the sparsity
pattern of M and K, so they are formed **once** outside the time loop
(:func:`repro.transient.stepping.axpy_csr`) and the rollout is a
``lax.scan`` whose trace holds exactly one solve — the O(1)-graph property
extended to time stepping.

Differentiability: the per-step solve goes through
:func:`repro.core.sparse_solve` (adjoint sparse solve), so whole
trajectories differentiate w.r.t. the operator values (coefficients, mesh
coordinates via assembly) and the initial condition, with optional
``jax.checkpoint`` segmentation for long rollouts.  Dirichlet data may vary
per step: the condensed matrix is hoisted out of the loop and only the
cheap RHS lift (:meth:`DirichletCondenser.lift`) runs inside the scan.
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp

from ..core.boundary import DirichletCondenser
from ..core.solvers import (
    SolverSpec,
    matfree_solve,
    resolve_solver_spec,
    sparse_solve,
)
from ..core.sparse import CSR
from ..telemetry import events
from .stepping import axpy_csr, segmented_scan

__all__ = ["ThetaIntegrator", "BACKWARD_EULER", "CRANK_NICOLSON"]

BACKWARD_EULER = 1.0
CRANK_NICOLSON = 0.5

_BACKENDS = ("csr", "matfree", "matfree_sharded")


@dataclasses.dataclass
class ThetaIntegrator:
    """One-step θ-method over pre-assembled CSR mass/stiffness operators.

    Construct *inside* a traced function to differentiate through the
    operator values — preferably via :meth:`from_form`, which builds both
    effective operators with fused weak-form assemblies
    (``assemble(mass(c) + θΔt·form)``) sharing one jit signature; the
    static sparsity pattern is reused across traces.

    ``backend`` picks the operators' kind: ``"csr"`` (default) steps on
    the assembled operators through the differentiable ``sparse_solve``;
    ``"matfree"`` (build via :meth:`from_form` with ``backend="matfree"``)
    steps on matrix-free operators through the differentiable
    :func:`~repro.core.solvers.matfree_solve` — no CSR values are ever
    materialized for the rollout; ``"matfree_sharded"`` additionally
    partitions every apply over the local device mesh
    (:meth:`~repro.core.operator.MatFreeOperator.sharded`), so each step's
    solve — and its adjoint — spans all devices.
    """

    mass: CSR | None
    stiff: CSR | None
    dt: float
    theta: float = BACKWARD_EULER
    bc: DirichletCondenser | None = None
    spec: SolverSpec | None = None  # Krylov config (method/tol/precond/...)
    solver: str | None = None       # deprecated → spec.method
    tol: float | None = None        # deprecated → spec.tol (and atol)
    maxiter: int | None = None      # deprecated → spec.maxiter
    backend: str = "csr"
    # effective operators; pass directly (see from_form) or leave None to
    # have them formed from mass/stiff (same pattern as M / K)
    lhs_full: CSR | None = None
    rhs_op: CSR | None = None

    def __post_init__(self):
        if self.backend not in _BACKENDS:
            raise ValueError(f"unknown backend {self.backend!r}: expected "
                             f"one of {_BACKENDS}")
        # M + θΔtK is SPD for θ ≥ 0 → CG default; legacy solver/tol/maxiter
        # fields fold into the spec (DeprecationWarning) and stay readable
        # as mirrors afterwards
        self.spec = resolve_solver_spec(
            self.spec, method=self.solver, tol=self.tol, atol=self.tol,
            maxiter=self.maxiter, default=SolverSpec(method="cg"),
            where="ThetaIntegrator")
        self.solver = self.spec.method
        self.tol = self.spec.tol
        self.maxiter = self.spec.maxiter
        if self.lhs_full is None:
            self.lhs_full = axpy_csr(1.0, self.mass, self.theta * self.dt, self.stiff)
        if self.rhs_op is None:
            self.rhs_op = axpy_csr(
                1.0, self.mass, -(1.0 - self.theta) * self.dt, self.stiff
            )
        if self.backend == "matfree_sharded":
            from ..core.operator import MatFreeOperator

            # partition both effective applies over the device mesh; every
            # step's solve (and its adjoint) then spans all local devices
            if isinstance(self.lhs_full, MatFreeOperator):
                self.lhs_full = self.lhs_full.sharded()
            if isinstance(self.rhs_op, MatFreeOperator):
                self.rhs_op = self.rhs_op.sharded()
        if self.bc is None:
            self.lhs = self.lhs_full
        elif isinstance(self.lhs_full, CSR):
            self.lhs = self.bc.apply_matrix_only(self.lhs_full)
        else:  # matrix-free operator: condensation as an apply wrapper
            self.lhs = self.lhs_full.condensed(self.bc)

    @classmethod
    def from_form(cls, asm, form, dt, *, theta: float = BACKWARD_EULER,
                  mass_coeff=None, bc=None, **kw) -> "ThetaIntegrator":
        """Build the θ-step operators with two *fused* assemblies over the
        weak-form API: ``lhs = assemble(mass(c) + θΔt·form)`` and
        ``rhs_op = assemble(mass(c) − (1−θ)Δt·form)``.

        ``form`` is the spatial bilinear form (e.g.
        ``weakform.diffusion(kappa)`` — or a multi-term
        ``diffusion(kappa) + advection(beta)``).  Both operators share one
        static signature, so a single XLA executable serves both calls and
        all subsequent ``dt``/coefficient updates.  Forms containing an
        advection term make the lhs nonsymmetric, so the solver defaults to
        BiCGStab for them (CG otherwise — pass ``solver=`` to override).

        ``backend="matfree"`` builds both effective operators matrix-free
        (:func:`repro.core.matfree_operator`) — no CSR values for either
        operator, steps stay differentiable via
        :func:`~repro.core.solvers.matfree_solve`.
        """
        from ..core import weakform as wf

        terms = wf._as_form(form).terms
        if kw.get("spec") is None and kw.get("solver") is None:
            # advection makes the lhs nonsymmetric → BiCGStab; CG otherwise
            kw["spec"] = SolverSpec(
                method="bicgstab"
                if any(t.kind == "advection" for t in terms) else "cg"
            )
        lhs_form = wf.mass(mass_coeff) + (theta * dt) * form
        rhs_form = wf.mass(mass_coeff) + (-(1.0 - theta) * dt) * form
        if kw.get("backend") in ("matfree", "matfree_sharded"):
            from ..core.operator import matfree_operator

            # matfree_sharded: __post_init__ wraps both in the sharded apply
            lhs = matfree_operator(asm.plan, lhs_form)
            rhs = matfree_operator(asm.plan, rhs_form)
        else:
            lhs = asm.assemble(lhs_form)
            rhs = asm.assemble(rhs_form)
        return cls(None, None, dt, theta=theta, bc=bc,
                   lhs_full=lhs, rhs_op=rhs, **kw)

    # -- one step --------------------------------------------------------------
    def step(self, u, load=None, bc_values=None, return_info=False):
        """Advance uⁿ → uⁿ⁺¹.  ``load`` is the assembled Fⁿ⁺ᶿ (already the
        θ-weighted quadrature of F if time-varying); ``bc_values`` the
        Dirichlet data at tⁿ⁺¹ (scalar, (n_bc,), or full field).

        ``return_info=True`` additionally returns the step's
        :class:`~repro.core.solvers.SolveInfo` as a non-differentiated
        auxiliary output (stop-gradient leaves)."""
        b = self.rhs_op.matvec(u)
        if load is not None:
            b = b + self.dt * load
        if self.bc is None:
            if bc_values is not None:
                raise ValueError("bc_values given but no DirichletCondenser (bc=)")
        elif bc_values is None:
            # homogeneous Dirichlet: u_D = 0, so the full lift reduces to
            # masking — skips a dead K·u_D matvec on every scan step
            b = self.bc.project_residual(b)
        else:
            b = self.bc.lift(self.lhs_full, b, bc_values)
        if self.backend == "csr":
            return sparse_solve(self.lhs, b, self.spec,
                                return_info=return_info)
        # differentiable adjoint solve on the matrix-free operator
        # (sharded: the same solve with every apply spanning the mesh)
        return matfree_solve(self.lhs, b, self.spec, return_info=return_info)

    # -- rollout ---------------------------------------------------------------
    def rollout(self, u0, n_steps: int, *, loads=None, bc_values=None,
                checkpoint_every: int | None = None,
                return_info: bool = False) -> jnp.ndarray:
        """Scan ``n_steps`` steps from ``u0``; returns ``(n_steps, N)``
        (u0 excluded, matching the reference-integrator convention).

        ``loads``: None | (N,) static | (n_steps, N) per-step.
        ``bc_values``: None | scalar | (n_bc,) static | (n_steps, n_bc)
        per-step (time-varying Dirichlet data, evaluated at tⁿ⁺¹).

        ``return_info=True`` returns ``(traj, info)`` where ``info`` is a
        :class:`~repro.core.solvers.SolveInfo` with per-step ``(n_steps,)``
        leaves stacked out of the scan — the iteration-count trajectory of
        the rollout.  The leaves carry stop-gradients, so gradients through
        ``traj`` are unchanged.
        """
        loads = None if loads is None else jnp.asarray(loads)
        bcv = None if bc_values is None else jnp.asarray(bc_values)
        scan_loads = loads is not None and loads.ndim == 2
        scan_bcv = bcv is not None and bcv.ndim == 2
        if bcv is not None and self.bc is None:
            raise ValueError("bc_values given but no DirichletCondenser (bc=)")
        if bcv is not None:
            n_bc, n = self.bc.bc_dofs.shape[0], self.bc.num_dofs
            ok = (
                bcv.ndim == 0
                or (bcv.ndim == 1 and bcv.shape[0] in (n_bc, n))
                or (bcv.ndim == 2 and bcv.shape == (n_steps, n_bc))
            )
            if not ok:
                raise ValueError(
                    f"bc_values shape {bcv.shape} not understood: expected a "
                    f"scalar, ({n_bc},) / ({n},) static data, or "
                    f"({n_steps}, {n_bc}) per-step data"
                )

        xs = {}
        if scan_loads:
            xs["f"] = loads
        if scan_bcv:
            xs["g"] = bcv

        def body(u, x):
            f = x["f"] if scan_loads else loads
            g = x["g"] if scan_bcv else bcv
            if return_info:
                u_new, info = self.step(u, load=f, bc_values=g,
                                        return_info=True)
                return u_new, (u_new, info)
            u_new = self.step(u, load=f, bc_values=g)
            return u_new, u_new

        # u0 is taken as-is: with Dirichlet data it must satisfy u0[bc] = g(t0)
        _, out = segmented_scan(body, u0, xs or None, n_steps, checkpoint_every)
        if return_info:
            traj, info = out
            events.check_convergence(info, where="theta.rollout")
            events.record_solve("theta.rollout", info, method=self.spec.method,
                                backend=self.backend,
                                precond=self.spec.precond_name)
            return traj, info
        return out
