"""Integration tests for the three downstream products:
TensorMesh (solver), TensorPILS (learning), TensorOpt (optimization)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import (
    CSR,
    DirichletCondenser,
    FunctionSpace,
    GalerkinAssembler,
    disk_tri,
    unit_square_tri,
)
from repro.core.mesh import element_for_mesh
from repro.fem import (
    AdvectionDiffusionProblem,
    ElasticityProblem,
    MixedBCPoisson,
    PoissonProblem,
)
from repro.core import hollow_cube_tet, unit_cube_tet
from repro.pils import (
    GalerkinResidualLoss,
    deep_ritz_loss,
    pinn_poisson_loss,
    siren_apply,
    siren_init,
    train_adam,
    lbfgs_minimize,
    vpinn_loss,
)
from repro.pils.operator import TimeDependentProblem, random_initial_condition
from repro.opt import CantileverProblem, MMAState, mma_update, oc_update


# ---------------------------------------------------------------------------
# TensorMesh
# ---------------------------------------------------------------------------

def test_poisson3d_residual_below_paper_tol():
    res = PoissonProblem(unit_cube_tet(5)).solve()
    assert res.residual < 1e-10


def test_elasticity3d_hollow_cube():
    res = ElasticityProblem(hollow_cube_tet(6)).solve()
    assert res.residual < 1e-8
    assert float(jnp.abs(res.u).max()) > 0


PROBLEMS = {
    "poisson": lambda: (PoissonProblem(unit_square_tri(16)), {}),
    "advection_diffusion": lambda: (
        AdvectionDiffusionProblem(unit_square_tri(8)), {}),
    "elasticity": lambda: (ElasticityProblem(unit_square_tri(6)), {}),
    "mixed_bc": lambda: (MixedBCPoisson(
        unit_square_tri(8),
        dirichlet_pred=lambda c: c[:, 0] < 0.5,
        neumann_pred=lambda c: c[:, 1] < 0.5,
        robin_pred=lambda c: c[:, 1] >= 0.5), {"f": 1.0}),
}


@pytest.mark.parametrize("which", sorted(PROBLEMS))
def test_default_solve_is_the_csr_loop_apply(monkeypatch, which):
    """``.solve()`` with no backend applies the assembled operator through
    ``CSR.loop_matvec``, once a solve, and returns what ``backend="csr"``
    returns, bit for bit."""
    calls = []
    loop_matvec = CSR.loop_matvec

    def spy(self):
        calls.append(self.shape)
        return loop_matvec(self)

    monkeypatch.setattr(CSR, "loop_matvec", spy)
    prob, kw = PROBLEMS[which]()
    default = prob.solve(**kw)
    assert len(calls) == 1
    csr = prob.solve(backend="csr", **kw)
    assert len(calls) == 2
    assert default.converged and default.residual < 1e-9
    np.testing.assert_array_equal(np.asarray(default.u), np.asarray(csr.u))


def test_batched_rhs_matches_individual():
    p = PoissonProblem(unit_square_tri(8))
    rng = np.random.default_rng(0)
    fb = jnp.asarray(rng.normal(size=(3, p.space.num_dofs)))
    us, _ = p.solve_batch(fb)
    for b in range(3):
        res = p.solve(f=fb[b])
        np.testing.assert_allclose(np.asarray(us[b]), np.asarray(res.u), atol=1e-8)


def test_mixed_bc_disk_analytic():
    """Paper SM B.1.5 analogue: u = x with Dirichlet+Neumann+Robin parts."""
    m = disk_tri(10, center=(0.0, 0.0), radius=1.0)
    prob = MixedBCPoisson(
        m,
        dirichlet_pred=lambda c: c[:, 1] > 0,
        neumann_pred=lambda c: (c[:, 1] <= 0) & (c[:, 0] > 0),
        robin_pred=lambda c: (c[:, 1] <= 0) & (c[:, 0] <= 0),
    )
    res = prob.solve(
        f=0.0,
        g_neumann=lambda x: x[..., 0],
        robin_alpha=1.0,
        g_robin=lambda x: 2 * x[..., 0],
        dirichlet_values=lambda p: p[:, 0],
    )
    exact = prob.space.dof_points[:, 0]
    err = np.linalg.norm(np.asarray(res.u) - exact) / np.linalg.norm(exact)
    assert err < 1e-3, err  # paper reports <1e-4 vs FEniCS at finer meshes


def test_mixed_bc_nonconvex_boomerang():
    from repro.core import annulus_sector_tri

    m = annulus_sector_tri(6, 24)
    prob = MixedBCPoisson(m, dirichlet_pred=lambda c: np.ones(len(c), bool))
    res = prob.solve(f=1.0)
    assert res.residual < 1e-9


# ---------------------------------------------------------------------------
# TensorPILS — neural solvers (reduced-budget versions of Table 1)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def checkerboard_setup():
    m = unit_square_tri(10)
    space = FunctionSpace(m, element_for_mesh(m))
    asm = GalerkinAssembler(space)
    bc = DirichletCondenser(asm, space.boundary_dofs())
    f = lambda x: jnp.sign(
        jnp.sin(2 * np.pi * x[..., 0] + 1e-9) * jnp.sin(2 * np.pi * x[..., 1] + 1e-9)
    )
    return m, space, asm, bc, f


def test_galerkin_loss_trains_to_fem_solution(checkerboard_setup):
    m, space, asm, bc, f = checkerboard_setup
    gl = GalerkinResidualLoss(asm, bc, f=f)
    params = siren_init(jax.random.PRNGKey(0), 2, 32, 1, depth=3)
    loss_fn = lambda p: gl.loss_from_net(siren_apply, p)
    params, hist, _ = train_adam(loss_fn, params, 400, lr=2e-3, log_every=100)
    # the discrete residual must drop by orders of magnitude
    assert hist[-1] < 1e-4 * hist[0]
    # and the recovered field must approach the FEM solution
    from repro.core import cg, jacobi_preconditioner

    u_fem, _ = cg(gl.k.matvec, gl.f, m=jacobi_preconditioner(gl.k), tol=1e-12)
    u_net = siren_apply(params, gl.dof_points)[:, 0]
    u_net = u_net * bc.free_mask
    rel = np.linalg.norm(np.asarray(u_net - u_fem)) / np.linalg.norm(np.asarray(u_fem))
    assert rel < 0.05, rel


def test_pinn_and_ritz_losses_decrease(checkerboard_setup):
    m, space, asm, bc, f = checkerboard_setup
    pts = jnp.asarray(space.dof_points)
    interior = pts[np.asarray(bc.free_mask, bool)]
    boundary = pts[~np.asarray(bc.free_mask, bool)]
    f_int = f(interior[None])[0]
    params = siren_init(jax.random.PRNGKey(1), 2, 16, 1, depth=2)

    pinn = lambda p: pinn_poisson_loss(siren_apply, p, interior, f_int, boundary)
    p1, h1, _ = train_adam(pinn, params, 60, lr=1e-3, log_every=59)
    assert h1[-1] < h1[0]

    ctx = asm.context()
    fq = f(ctx.xq)
    ritz = lambda p: deep_ritz_loss(siren_apply, p, ctx.xq, ctx.wdet, fq, boundary)
    p2, h2, _ = train_adam(ritz, params, 60, lr=1e-3, log_every=59)
    assert h2[-1] < h2[0]


def test_vpinn_loss_runs(checkerboard_setup):
    m, space, asm, bc, f = checkerboard_setup
    f_load = asm.assemble_load(f)
    boundary = jnp.asarray(space.dof_points[~np.asarray(bc.free_mask, bool)])
    params = siren_init(jax.random.PRNGKey(2), 2, 16, 1, depth=2)
    loss = lambda p: vpinn_loss(
        siren_apply, p, asm, f_load, bc.free_mask, boundary
    )
    val = loss(params)
    assert np.isfinite(float(val))
    g = jax.grad(loss)(params)
    assert all(np.all(np.isfinite(x)) for x in jax.tree.leaves(g))


def test_lbfgs_refines_after_adam(checkerboard_setup):
    m, space, asm, bc, f = checkerboard_setup
    gl = GalerkinResidualLoss(asm, bc, f=f)
    params = siren_init(jax.random.PRNGKey(3), 2, 16, 1, depth=2)
    loss_fn = lambda p: gl.loss_from_net(siren_apply, p)
    params, hist, _ = train_adam(loss_fn, params, 100, lr=2e-3, log_every=99)
    params, losses, _ = lbfgs_minimize(loss_fn, params, steps=20)
    assert losses[-1] < losses[0]


# ---------------------------------------------------------------------------
# operator learning substrate
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def wave_problem():
    return TimeDependentProblem(disk_tri(6), dt=5e-4)


def test_wave_reference_stable_and_consistent(wave_problem):
    tp = wave_problem
    u0 = random_initial_condition(jax.random.PRNGKey(0), tp.space.dof_points)
    traj = tp.wave_reference(u0, 40)
    assert not bool(jnp.any(jnp.isnan(traj)))
    # energy boundedness (Newmark β=¼ is unconditionally stable)
    assert float(jnp.abs(traj).max()) < 10 * float(jnp.abs(u0).max())
    # the reference trajectory nearly zeroes the discrete residual
    full = jnp.concatenate([(u0 * tp.bc.free_mask)[None], traj], axis=0)
    r = tp.wave_trajectory_loss(full)
    u_scale = float(jnp.sum(full[0] ** 2))
    assert float(r) < 1e-2 * max(u_scale, 1e-12) * (tp.c / tp.dt) ** 0


def test_ac_reference_decays(wave_problem):
    tp = TimeDependentProblem(disk_tri(5), dt=1e-4, a2=1e-2, eps2=1.0)
    u0 = random_initial_condition(jax.random.PRNGKey(1), tp.space.dof_points)
    traj = tp.ac_reference(u0, 30)
    assert not bool(jnp.any(jnp.isnan(traj)))
    full = jnp.concatenate([(u0 * tp.bc.free_mask)[None], traj], axis=0)
    assert float(tp.ac_trajectory_loss(full)) < 1e-6


def test_agn_shapes_and_rollout():
    from repro.pils.gnn import agn_init, agn_apply, agn_rollout, element_graph_edges

    m = disk_tri(4)
    edges = element_graph_edges(m.cells)
    deg = np.zeros(m.num_vertices)
    np.add.at(deg, edges[:, 1], 1)
    deg = jnp.asarray(np.maximum(deg, 1.0))
    coords = jnp.asarray(m.points)
    w = 4
    params = agn_init(jax.random.PRNGKey(0), w, w, hidden=16, n_layers=2)
    u_win = jnp.asarray(np.random.default_rng(0).normal(size=(m.num_vertices, w)))
    out = agn_apply(params, u_win, coords, edges, deg)
    assert out.shape == (m.num_vertices, w)
    interior = jnp.asarray(np.ones(m.num_vertices, bool))
    traj = agn_rollout(params, u_win, coords, edges, deg, 3, interior)
    assert traj.shape == (m.num_vertices, 3 * w)
    assert np.all(np.isfinite(np.asarray(traj)))


# ---------------------------------------------------------------------------
# TensorOpt
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def cantilever():
    return CantileverProblem(nx=16, ny=8, lx=16.0, ly=8.0)


def test_ad_sensitivity_matches_analytic_eq_b28(cantilever):
    """The paper's consistency claim: autodiff through assembly+solve equals
    the closed-form SIMP sensitivity (Eq. B.28)."""
    rho = jnp.full((cantilever.n_elem,), 0.5)
    _, g_ad = cantilever.compliance_and_sensitivity(rho)
    g_an = cantilever.analytic_sensitivity(rho)
    np.testing.assert_allclose(np.asarray(g_ad), np.asarray(g_an), rtol=1e-5)


def test_oc_optimization_reduces_compliance(cantilever):
    rho = jnp.full((cantilever.n_elem,), 0.5)
    c0, _ = cantilever.compliance_and_sensitivity(rho)
    for _ in range(8):
        c, g = cantilever.compliance_and_sensitivity(rho)
        gf = cantilever.filter(g * rho) / jnp.maximum(rho, 1e-3)
        rho = oc_update(rho, gf, cantilever.volfrac)
    c_end, _ = cantilever.compliance_and_sensitivity(rho)
    assert float(c_end) < 0.7 * float(c0)
    assert abs(float(rho.mean()) - cantilever.volfrac) < 1e-3


def test_mma_optimization_reduces_compliance(cantilever):
    rho = jnp.full((cantilever.n_elem,), 0.5)
    c0, _ = cantilever.compliance_and_sensitivity(rho)
    state = MMAState(low=rho - 0.5, upp=rho + 0.5)
    n = cantilever.n_elem
    for _ in range(8):
        c, g = cantilever.compliance_and_sensitivity(rho)
        gf = cantilever.filter(g * rho) / jnp.maximum(rho, 1e-3)
        vol_g = float(rho.mean()) - cantilever.volfrac
        rho, state = mma_update(
            rho, gf, jnp.asarray(vol_g), jnp.full((n,), 1.0 / n), state
        )
    c_end, _ = cantilever.compliance_and_sensitivity(rho)
    assert float(c_end) < 0.8 * float(c0)
    assert float(rho.mean()) <= cantilever.volfrac + 1e-2
