"""Newmark-β time integration for second-order hyperbolic problems (wave,
elastodynamics).

Semidiscrete system:  M ü + K u = F(t)  (``K`` already carries any material
scaling, e.g. c² for the scalar wave equation).  The predictor–corrector
form solves for the acceleration each step:

    u*  = uⁿ + Δt vⁿ + ½Δt²(1−2β) aⁿ
    v*  = vⁿ + Δt(1−γ) aⁿ
    (M + βΔt²K) aⁿ⁺¹ = Fⁿ⁺¹ − K u*
    uⁿ⁺¹ = u* + βΔt² aⁿ⁺¹,   vⁿ⁺¹ = v* + γΔt aⁿ⁺¹

β = ¼, γ = ½ (average acceleration / trapezoidal) is unconditionally stable
and conserves the discrete energy ½(vᵀMv + uᵀKu) exactly for F = 0 — the
property the wave benchmarks check.  The effective operator is formed once;
the rollout is a ``lax.scan`` with one ``sparse_solve`` per step, hence
differentiable end-to-end (adjoint solves in the backward pass) with
optional ``jax.checkpoint`` segmentation.

Dirichlet: homogeneous (or fixed-in-time) constraints via a
:class:`DirichletCondenser` — accelerations and velocities vanish on
constrained DoFs, displacements keep their initial boundary values.
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp

from ..core.boundary import DirichletCondenser
from ..core.solvers import SolverSpec, resolve_solver_spec, sparse_solve
from ..core.sparse import CSR
from ..telemetry import events
from .stepping import axpy_csr, segmented_scan

__all__ = ["NewmarkIntegrator"]


@dataclasses.dataclass
class NewmarkIntegrator:
    mass: CSR
    stiff: CSR
    dt: float
    beta: float = 0.25
    gamma: float = 0.5
    bc: DirichletCondenser | None = None
    spec: SolverSpec | None = None  # Krylov config (method/tol/precond/...)
    solver: str | None = None       # deprecated → spec.method
    tol: float | None = None        # deprecated → spec.tol (and atol)
    maxiter: int | None = None      # deprecated → spec.maxiter

    def __post_init__(self):
        # M + βΔt²K is SPD → CG default
        self.spec = resolve_solver_spec(
            self.spec, method=self.solver, tol=self.tol, atol=self.tol,
            maxiter=self.maxiter, default=SolverSpec(method="cg"),
            where="NewmarkIntegrator")
        self.solver = self.spec.method
        self.tol = self.spec.tol
        self.maxiter = self.spec.maxiter
        self.lhs_full = axpy_csr(
            1.0, self.mass, self.beta * self.dt**2, self.stiff
        )
        if self.bc is not None:
            self.lhs = self.bc.apply_matrix_only(self.lhs_full)
            self.mass_c = self.bc.apply_matrix_only(self.mass)
        else:
            self.lhs = self.lhs_full
            self.mass_c = self.mass

    def _mask(self, r):
        return r if self.bc is None else self.bc.project_residual(r)

    def initial_acceleration(self, u0, load0=None):
        """Consistent a₀ from M a₀ = F(0) − K u₀ (condensed)."""
        r = -self.stiff.matvec(u0)
        if load0 is not None:
            r = r + load0
        return sparse_solve(self.mass_c, self._mask(r), self.spec)

    def step(self, u, v, a, load=None, return_info=False):
        dt, beta, gamma = self.dt, self.beta, self.gamma
        u_star = u + dt * v + 0.5 * dt**2 * (1 - 2 * beta) * a
        v_star = v + dt * (1 - gamma) * a
        rhs = -self.stiff.matvec(u_star)
        if load is not None:
            rhs = rhs + load
        out = sparse_solve(self.lhs, self._mask(rhs), self.spec,
                           return_info=return_info)
        a_new, info = out if return_info else (out, None)
        u_new = u_star + beta * dt**2 * a_new
        if self.bc is not None:
            # constrained DoFs stay at their (initial) boundary values
            u_new = u_new * self.bc.free_mask + u * (1.0 - self.bc.free_mask)
        v_new = v_star + gamma * dt * a_new
        if return_info:
            return u_new, v_new, a_new, info
        return u_new, v_new, a_new

    def rollout(self, u0, n_steps: int, *, v0=None, loads=None, load0=None,
                checkpoint_every: int | None = None,
                return_velocity: bool = False,
                return_info: bool = False):
        """Scan ``n_steps`` Newmark steps; returns ``(n_steps, N)``
        displacements (u0 excluded), or ``(u_traj, v_traj)`` when
        ``return_velocity``.  ``loads``: None | (N,) | (n_steps, N), where
        per-step row ``n`` is Fⁿ⁺¹.  ``load0`` is F(0) for the consistent
        initial acceleration; defaults to ``loads`` when static and to
        ``loads[0]`` when per-step (one Δt off — pass ``load0`` explicitly
        for rapidly varying forcing).

        ``return_info=True`` appends a per-step
        :class:`~repro.core.solvers.SolveInfo` with ``(n_steps,)`` leaves
        (stop-gradient — gradients through the trajectory are unchanged)."""
        v0 = jnp.zeros_like(u0) if v0 is None else v0
        loads = None if loads is None else jnp.asarray(loads)
        scan_loads = loads is not None and loads.ndim == 2
        if load0 is None and loads is not None:
            load0 = loads[0] if scan_loads else loads
        a0 = self.initial_acceleration(u0, load0)

        def body(carry, x):
            u, v, a = carry
            f = x if scan_loads else loads
            if return_info:
                u, v, a, info = self.step(u, v, a, load=f, return_info=True)
                return (u, v, a), (u, v, info)
            u, v, a = self.step(u, v, a, load=f)
            return (u, v, a), (u, v)

        _, ys = segmented_scan(
            body, (u0, v0, a0), loads if scan_loads else None,
            n_steps, checkpoint_every,
        )
        if return_info:
            u_traj, v_traj, info = ys
            events.check_convergence(info, where="newmark.rollout")
            events.record_solve("newmark.rollout", info,
                                method=self.spec.method, backend="csr",
                                precond=self.spec.precond_name)
            out = (u_traj, v_traj) if return_velocity else u_traj
            return out, info
        u_traj, v_traj = ys
        return (u_traj, v_traj) if return_velocity else u_traj
