"""repro.transient tests: MMS convergence orders (BE vs CN), exactness with
time-varying Dirichlet data, Newmark energy conservation, Newton–Krylov on
Allen–Cahn, adjoint grad-check through a scanned rollout, batched vmap+jit
rollouts, and backend/checkpoint equivalences."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import repro.core  # noqa: F401  (x64 on)
from repro.core import (
    DirichletCondenser,
    FunctionSpace,
    GalerkinAssembler,
    unit_square_tri,
)
from repro.core.mesh import element_for_mesh
from repro.transient import (
    CRANK_NICOLSON,
    NewmarkIntegrator,
    NewtonKrylovIntegrator,
    ThetaIntegrator,
    batched_rollout,
    segmented_scan,
)


@pytest.fixture(scope="module")
def heat_setup():
    m = unit_square_tri(8)
    sp = FunctionSpace(m, element_for_mesh(m))
    asm = GalerkinAssembler(sp)
    bc = DirichletCondenser(asm, sp.boundary_dofs())
    return m, sp, asm, bc, asm.assemble_mass(), asm.assemble_stiffness()


def _interior_dense(mat, free):
    return np.asarray(mat.to_dense())[np.ix_(free, free)]


# ---------------------------------------------------------------------------
# θ-method
# ---------------------------------------------------------------------------

def test_theta_mms_convergence_orders(heat_setup):
    """Heat MMS: observed temporal order ≈1 for backward Euler, ≈2 for
    Crank–Nicolson, against the exact decay of a discrete eigenmode."""
    import scipy.linalg as sla

    m, sp, asm, bc, mass, stiff = heat_setup
    free = np.asarray(bc.free_mask, dtype=bool)
    md = _interior_dense(mass, free)
    kd = _interior_dense(stiff, free)
    lam, vecs = sla.eigh(kd, md)
    u0f = vecs[:, 0] / np.linalg.norm(vecs[:, 0])
    u0 = np.zeros(sp.num_dofs)
    u0[free] = u0f
    u0 = jnp.asarray(u0)
    t_final = 0.05
    u_exact = np.exp(-lam[0] * t_final) * u0f

    orders = {}
    for theta in (1.0, CRANK_NICOLSON):
        errs = []
        for nsteps in (4, 8, 16):
            integ = ThetaIntegrator(
                mass, stiff, dt=t_final / nsteps, theta=theta, bc=bc, tol=1e-13
            )
            traj = integ.rollout(u0, nsteps)
            errs.append(float(np.linalg.norm(np.asarray(traj[-1])[free] - u_exact)))
        orders[theta] = [np.log2(errs[i] / errs[i + 1]) for i in range(2)]

    for p in orders[1.0]:
        assert 0.8 < p < 1.25, f"backward Euler order {p} not ≈1"
    for p in orders[CRANK_NICOLSON]:
        assert 1.8 < p < 2.3, f"Crank–Nicolson order {p} not ≈2"


def test_theta_exact_on_linear_in_time_with_moving_dirichlet(heat_setup):
    """u(x,t) = t(1+x+y): u_t = 1+x+y, Δu = 0 — backward Euler reproduces
    the semidiscrete solution to solver tolerance, exercising per-step
    time-varying Dirichlet data inside the lax.scan (no condenser rebuild)."""
    m, sp, asm, bc, mass, stiff = heat_setup
    w = jnp.asarray(1.0 + sp.dof_points[:, 0] + sp.dof_points[:, 1])
    load = mass.matvec(w)                                    # ∫(1+x+y)φ = M w
    n_steps, dt = 10, 0.01
    integ = ThetaIntegrator(mass, stiff, dt=dt, theta=1.0, bc=bc, tol=1e-13)
    bcd = jnp.asarray(bc.bc_dofs)
    g = jnp.stack([(n + 1) * dt * w[bcd] for n in range(n_steps)])  # (T, n_bc)
    traj = integ.rollout(jnp.zeros(sp.num_dofs), n_steps, loads=load, bc_values=g)
    exact = n_steps * dt * w
    np.testing.assert_allclose(np.asarray(traj[-1]), np.asarray(exact), atol=1e-10)


def test_grad_through_rollout_matches_finite_differences(heat_setup):
    """∂(trajectory loss)/∂κ through the scanned rollout (adjoint sparse
    solves) vs central finite differences — ≤1e-4 relative error."""
    m = unit_square_tri(5)
    sp = FunctionSpace(m, element_for_mesh(m))
    asm = GalerkinAssembler(sp)
    bc = DirichletCondenser(asm, sp.boundary_dofs())
    mass = asm.assemble_mass()
    pts = sp.dof_points
    u0 = (
        jnp.sin(np.pi * jnp.asarray(pts[:, 0]))
        * jnp.sin(np.pi * jnp.asarray(pts[:, 1]))
    ) * bc.free_mask

    def loss(kappa):
        stiff = asm.assemble_stiffness(kappa)
        integ = ThetaIntegrator(mass, stiff, dt=0.01, theta=CRANK_NICOLSON,
                                bc=bc, tol=1e-13)
        return jnp.sum(integ.rollout(u0, 5) ** 2)

    kappa = jnp.ones(m.num_cells)
    grad = jax.grad(loss)(kappa)
    v = jnp.asarray(np.random.default_rng(0).normal(size=m.num_cells))
    eps = 1e-5
    fd = (loss(kappa + eps * v) - loss(kappa - eps * v)) / (2 * eps)
    ad = jnp.vdot(grad, v)
    assert abs(float(fd - ad)) / abs(float(fd)) < 1e-4


def test_checkpoint_segmentation_preserves_values_and_grads(heat_setup):
    m, sp, asm, bc, mass, stiff = heat_setup
    pts = sp.dof_points
    u0 = (
        jnp.sin(np.pi * jnp.asarray(pts[:, 0]))
        * jnp.sin(np.pi * jnp.asarray(pts[:, 1]))
    ) * bc.free_mask

    def loss(u0, ck):
        integ = ThetaIntegrator(mass, stiff, dt=0.01, theta=1.0, bc=bc, tol=1e-13)
        return jnp.sum(integ.rollout(u0, 8, checkpoint_every=ck) ** 2)

    np.testing.assert_allclose(
        float(loss(u0, None)), float(loss(u0, 4)), rtol=1e-14
    )
    ga = jax.grad(lambda u: loss(u, None))(u0)
    gb = jax.grad(lambda u: loss(u, 4))(u0)
    np.testing.assert_allclose(np.asarray(ga), np.asarray(gb), atol=1e-12)
    with pytest.raises(ValueError):
        segmented_scan(lambda c, _: (c, c), u0, None, 7, checkpoint_every=3)


def test_batched_rollout_vmap_under_jit(heat_setup):
    """A vmapped batch of 8 trajectories runs under jit and each row
    matches the unbatched rollout."""
    m, sp, asm, bc, mass, stiff = heat_setup
    integ = ThetaIntegrator(mass, stiff, dt=0.01, theta=1.0, bc=bc, tol=1e-12)
    keys = jax.random.split(jax.random.PRNGKey(0), 8)
    u0s = jax.vmap(
        lambda k: jax.random.normal(k, (sp.num_dofs,)) * bc.free_mask
    )(keys)
    batched = jax.jit(lambda b: batched_rollout(integ, b, 4))(u0s)
    assert batched.shape == (8, 4, sp.num_dofs)
    single = integ.rollout(u0s[3], 4)
    np.testing.assert_allclose(np.asarray(batched[3]), np.asarray(single), atol=1e-10)


# ---------------------------------------------------------------------------
# Newmark-β
# ---------------------------------------------------------------------------

def test_newmark_energy_conservation(heat_setup):
    """β=¼, γ=½ with F=0 conserves E = ½(vᵀMv + uᵀKu) to solver tolerance
    over 200 steps of the wave equation."""
    m, sp, asm, bc, mass, stiff = heat_setup
    pts = sp.dof_points
    u0 = (
        jnp.sin(np.pi * jnp.asarray(pts[:, 0]))
        * jnp.sin(np.pi * jnp.asarray(pts[:, 1]))
    ) * bc.free_mask
    nm = NewmarkIntegrator(mass, stiff, dt=0.01, bc=bc, tol=1e-12)
    u_traj, v_traj = nm.rollout(u0, 200, return_velocity=True)
    assert not bool(jnp.any(jnp.isnan(u_traj)))

    def energy(u, v):
        return 0.5 * (jnp.vdot(v, mass.matvec(v)) + jnp.vdot(u, stiff.matvec(u)))

    e0 = energy(u0, jnp.zeros_like(u0))
    es = jax.vmap(energy)(u_traj, v_traj)
    drift = float(jnp.abs(es - e0).max() / e0)
    assert drift < 1e-6, f"Newmark energy drift {drift}"


# ---------------------------------------------------------------------------
# Newton–Krylov (semilinear)
# ---------------------------------------------------------------------------

def test_newton_krylov_allen_cahn_residual_small(heat_setup):
    """BE+Newton on Allen–Cahn: the produced steps nearly zero the discrete
    residual, and the jvp-derived r′ matches the analytic Jacobian path."""
    m, sp, asm, bc, mass, stiff = heat_setup
    eps2 = 1.0
    reaction = lambda u: -eps2 * u * (u**2 - 1.0)
    pts = sp.dof_points
    u0 = (
        jnp.sin(np.pi * jnp.asarray(pts[:, 0]))
        * jnp.sin(np.pi * jnp.asarray(pts[:, 1]))
    ) * bc.free_mask

    nk = NewtonKrylovIntegrator(
        asm, mass, stiff, dt=1e-3, reaction=reaction,
        diffusion_scale=1e-2, bc=bc, newton_iters=4, tol=1e-12,
    )
    traj = nk.rollout(u0, 5)
    assert not bool(jnp.any(jnp.isnan(traj)))
    res = nk.residual(traj[-2], traj[-1])
    assert float(jnp.linalg.norm(res)) < 1e-8

    # jvp-derived derivative equals the closed form −ε²(3u²−1)
    u = jnp.linspace(-1.5, 1.5, 7)
    np.testing.assert_allclose(
        np.asarray(nk.reaction_prime(u)),
        np.asarray(-eps2 * (3 * u**2 - 1.0)),
        atol=1e-12,
    )


# ---------------------------------------------------------------------------
# DirichletCondenser lift (time-varying values API)
# ---------------------------------------------------------------------------

def test_condenser_lift_matches_apply(heat_setup):
    m, sp, asm, bc, mass, stiff = heat_setup
    f = jnp.asarray(np.random.default_rng(1).normal(size=sp.num_dofs))
    g = jnp.asarray(np.random.default_rng(2).normal(size=bc.bc_dofs.shape[0]))
    k_cond, f_cond = bc.apply(stiff, f, g)
    np.testing.assert_allclose(
        np.asarray(bc.lift(stiff, f, g)), np.asarray(f_cond), atol=1e-14
    )
    np.testing.assert_allclose(
        np.asarray(bc.apply_matrix_only(stiff).vals), np.asarray(k_cond.vals),
        atol=1e-14,
    )
    # full-field and scalar encodings agree with the (n_bc,) encoding
    full = jnp.zeros(sp.num_dofs).at[jnp.asarray(bc.bc_dofs)].set(g)
    np.testing.assert_allclose(
        np.asarray(bc.boundary_field(g)), np.asarray(bc.boundary_field(full)),
        atol=1e-14,
    )
    np.testing.assert_allclose(
        np.asarray(bc.boundary_field(2.0)),
        np.asarray(bc.boundary_field(jnp.full(bc.bc_dofs.shape[0], 2.0))),
        atol=1e-14,
    )
