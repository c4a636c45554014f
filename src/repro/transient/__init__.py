"""repro.transient — differentiable time integration over TensorGalerkin
operators.

Module map
----------
* :mod:`~repro.transient.stepping` — shared rollout machinery:
  checkpoint-segmented ``lax.scan``, same-pattern CSR combination.
* :mod:`~repro.transient.theta` — :class:`ThetaIntegrator`: the θ-method
  for parabolic problems (θ=1 backward Euler, θ=½ Crank–Nicolson), with
  per-step time-varying loads and Dirichlet data inside the scan.
* :mod:`~repro.transient.newmark` — :class:`NewmarkIntegrator`: Newmark-β
  for second-order hyperbolic problems (β=¼, γ=½ conserves discrete
  energy — the wave benchmark's integrator).
* :mod:`~repro.transient.newton` — :class:`NewtonKrylovIntegrator`:
  backward Euler + Newton–Krylov for semilinear problems, with the
  reaction term and its exact mass-weighted Jacobian assembled through the
  Batch-Map + Sparse-Reduce pipeline (Allen–Cahn).

Every rollout is a ``lax.scan`` with O(1) trace size over pre-assembled
CSR operators; per-step solves go through ``sparse_solve`` (adjoint
backward pass), so trajectories differentiate w.r.t. coefficients, initial
conditions, and mesh coordinates.  :func:`batched_rollout` vmaps a rollout
over a batch of initial conditions; to batch over coefficient fields,
assemble the per-instance effective operators in ONE call
(``repro.core.assemble_batched`` → :class:`~repro.core.sparse.BatchedCSR`)
and roll the family out with :func:`batched_theta_rollout`::

    lhs = assemble_batched(plan, wf.mass(1.0) + (theta * dt) * wf.diffusion(k0),
                           leaves_batch=(None, None, kappa_batch, None))
    rhs = assemble_batched(plan, wf.mass(1.0) - ((1 - theta) * dt) * wf.diffusion(k0),
                           leaves_batch=(None, None, kappa_batch, None))
    trajs = batched_theta_rollout(lhs, rhs, u0_batch, n_steps, dt=dt,
                                  theta=theta, bc=bc)       # (B, T, N)
"""

from __future__ import annotations

import jax

from .newmark import NewmarkIntegrator
from .newton import NewtonKrylovIntegrator
from .stepping import axpy_csr, segmented_scan
from ..core.solvers import SolverSpec
from .theta import BACKWARD_EULER, CRANK_NICOLSON, ThetaIntegrator

__all__ = [
    "ThetaIntegrator",
    "NewmarkIntegrator",
    "NewtonKrylovIntegrator",
    "BACKWARD_EULER",
    "CRANK_NICOLSON",
    "batched_rollout",
    "batched_theta_rollout",
    "segmented_scan",
    "axpy_csr",
]


def batched_rollout(integrator, u0_batch, n_steps: int, **rollout_kwargs):
    """vmap ``integrator.rollout`` over a leading batch of initial
    conditions: ``(B, N) → (B, n_steps, N)``.  Keyword args (loads,
    bc_values, checkpoint_every, ...) are shared across the batch."""
    return jax.vmap(
        lambda u0: integrator.rollout(u0, n_steps, **rollout_kwargs)
    )(u0_batch)


def batched_theta_rollout(lhs_full, rhs_op, u0_batch, n_steps: int, *, dt,
                          theta: float = BACKWARD_EULER, bc=None, loads=None,
                          bc_values=None, checkpoint_every: int | None = None,
                          **integrator_kwargs):
    """θ-rollouts for a *family* of problem instances over
    :class:`~repro.core.sparse.BatchedCSR` effective operators.

    ``lhs_full`` / ``rhs_op`` hold the B per-instance operators
    ``M + θΔtK_b`` / ``M − (1−θ)ΔtK_b`` on one shared static pattern (from
    ``assemble_batched``); the whole family rolls out in one vmapped
    ``lax.scan`` — a single XLA executable, no per-instance re-vmapping of
    raw value vectors.  ``u0_batch: (B, N) → (B, n_steps, N)``; ``loads`` /
    ``bc_values`` are shared across the batch.

    Both operators may instead be
    :class:`~repro.core.operator.MatFreeFamily` (from
    :func:`repro.core.matfree_family` on the two effective forms): the
    family rolls out matrix-free — per-step solves through
    ``matfree_solve``, zero CSR values materialized for the whole batch.
    """
    if hasattr(lhs_full, "in_axes"):  # MatFreeFamily pair
        integrator_kwargs.setdefault("backend", "matfree")
        if integrator_kwargs.get("solver") is None:
            integrator_kwargs.setdefault("spec", SolverSpec(method="cg"))

        def one_mf(lhs_op, rhs_op_b, u0):
            integ = ThetaIntegrator(
                None, None, dt, theta=theta, bc=bc,
                lhs_full=lhs_op, rhs_op=rhs_op_b, **integrator_kwargs,
            )
            return integ.rollout(u0, n_steps, loads=loads, bc_values=bc_values,
                                 checkpoint_every=checkpoint_every)

        return jax.vmap(
            one_mf, in_axes=(lhs_full.in_axes(), rhs_op.in_axes(), 0)
        )(lhs_full.op, rhs_op.op, u0_batch)

    def one(lhs_b, rhs_b, u0):
        integ = ThetaIntegrator(
            None, None, dt, theta=theta, bc=bc,
            lhs_full=lhs_b.as_csr(), rhs_op=rhs_b.as_csr(), **integrator_kwargs,
        )
        return integ.rollout(u0, n_steps, loads=loads, bc_values=bc_values,
                             checkpoint_every=checkpoint_every)

    return jax.vmap(one)(lhs_full, rhs_op, u0_batch)
