"""Matrix-free operator subsystem, and the assembled and matrix-free
applies its consumers choose between.

Covers apply parity vs the assembled CSR matvec (≤1e-12) across ALL
element types, grad-vs-FD and grad-vs-adjoint through matrix-free solves,
the zero-retrace property on coefficient value updates, the condensed
(Dirichlet) apply, and the ``backend`` choice of the problems, integrators
and residual losses.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    CSR,
    DirichletCondenser,
    FunctionSpace,
    GalerkinAssembler,
    assemble,
    assemble_rhs,
    build_plan,
    matfree_operator,
    SolverSpec,
    matfree_solve,
    n_matfree_traces,
    sparse_solve,
    unit_cube_hex,
    unit_cube_tet,
    unit_square_tri,
    weakform as wf,
)
from repro.core.mesh import element_for_mesh, rectangle_quad
from repro.core.operator import _apply_jit  # noqa: F401 (retrace counter target)

RNG = np.random.default_rng(0)
_SPEC = SolverSpec(method="cg", tol=1e-12, atol=1e-12, maxiter=10000)


def _space(mesh, degree=1, value_size=1):
    return FunctionSpace(mesh, element_for_mesh(mesh, degree), value_size)


CASES = {
    "P1_tri": lambda: _space(unit_square_tri(6)),
    "P2_tri": lambda: _space(unit_square_tri(4), degree=2),
    "P1_tet": lambda: _space(unit_cube_tet(3)),
    "Q1_quad": lambda: _space(rectangle_quad(5, 4, 1.0, 1.0)),
    "Q1_hex": lambda: _space(unit_cube_hex(3)),
}


# ---------------------------------------------------------------------------
# apply parity across element types and storage strategies
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("element", sorted(CASES))
@pytest.mark.parametrize("store", ["coords", "context", "local"])
def test_apply_parity_all_elements(element, store):
    space = CASES[element]()
    assert space.element.name == element
    plan = build_plan(space)
    rho = jnp.asarray(RNG.uniform(0.5, 2.0, space.mesh.num_cells))
    form = wf.diffusion(rho) + 0.3 * wf.mass()
    k = assemble(plan, form)
    op = matfree_operator(plan, form, store=store)
    x = jnp.asarray(RNG.standard_normal(space.num_dofs))
    np.testing.assert_allclose(
        np.asarray(op.matvec(x)), np.asarray(k.matvec(x)), atol=1e-12
    )
    np.testing.assert_allclose(
        np.asarray(op.diagonal()), np.asarray(k.diagonal()), atol=1e-12
    )


def test_rmatvec_parity_nonsymmetric():
    space = CASES["P1_tri"]()
    plan = build_plan(space)
    form = wf.diffusion() + wf.advection(jnp.array([1.0, 0.5]))
    k = assemble(plan, form)
    op = matfree_operator(plan, form)
    x = jnp.asarray(RNG.standard_normal(space.num_dofs))
    np.testing.assert_allclose(
        np.asarray(op.rmatvec(x)), np.asarray(k.rmatvec(x)), atol=1e-12
    )


def test_anisotropic_action_parity():
    space = CASES["P1_tri"]()
    plan = build_plan(space)
    a = jnp.array([[2.0, 0.5], [0.3, 1.0]])  # nonsymmetric tensor coeff
    form = wf.anisotropic_diffusion(a)
    k = assemble(plan, form)
    op = matfree_operator(plan, form)
    x = jnp.asarray(RNG.standard_normal(space.num_dofs))
    np.testing.assert_allclose(
        np.asarray(op.matvec(x)), np.asarray(k.matvec(x)), atol=1e-12
    )
    np.testing.assert_allclose(
        np.asarray(op.rmatvec(x)), np.asarray(k.rmatvec(x)), atol=1e-12
    )
    np.testing.assert_allclose(
        np.asarray(op.diagonal()), np.asarray(k.diagonal()), atol=1e-12
    )


def test_elasticity_vector_space_fallback():
    # no fused action registered for elasticity → the generic K_e fallback,
    # on an interleaved vector space
    mesh = unit_square_tri(4)
    space = _space(mesh, value_size=2)
    plan = build_plan(space)
    form = wf.elasticity(1.2, 0.7)
    k = assemble(plan, form)
    op = matfree_operator(plan, form)
    x = jnp.asarray(RNG.standard_normal(space.num_dofs))
    np.testing.assert_allclose(
        np.asarray(op.matvec(x)), np.asarray(k.matvec(x)), atol=1e-12
    )
    np.testing.assert_allclose(
        np.asarray(op.diagonal()), np.asarray(k.diagonal()), atol=1e-12
    )


def test_condensed_matches_condensed_csr():
    space = CASES["P1_tri"]()
    plan = build_plan(space)
    bc = DirichletCondenser(plan.static.mat_routing, space.boundary_dofs())
    form = wf.diffusion(2.0)
    kc = bc.apply_matrix_only(assemble(plan, form))
    opc = matfree_operator(plan, form).condensed(bc)
    x = jnp.asarray(RNG.standard_normal(space.num_dofs))
    np.testing.assert_allclose(
        np.asarray(opc.matvec(x)), np.asarray(kc.matvec(x)), atol=1e-12
    )
    np.testing.assert_allclose(
        np.asarray(opc.diagonal()), np.asarray(kc.diagonal()), atol=1e-12
    )


# ---------------------------------------------------------------------------
# zero-retrace on coefficient value updates
# ---------------------------------------------------------------------------

def test_zero_retrace_on_coefficient_update():
    space = CASES["P1_tri"]()
    plan = build_plan(space)
    rho = jnp.asarray(RNG.uniform(0.5, 2.0, space.mesh.num_cells))
    x = jnp.asarray(RNG.standard_normal(space.num_dofs))
    op = matfree_operator(plan, wf.diffusion(rho))
    jax.block_until_ready(op.matvec(x))  # compile once
    before = n_matfree_traces()
    for scale in (2.0, 3.0, 4.0):
        op2 = matfree_operator(plan, wf.diffusion(scale * rho))
        jax.block_until_ready(op2.matvec(2.0 * x))
    assert n_matfree_traces() == before, "coefficient value update retraced"


# ---------------------------------------------------------------------------
# differentiable matrix-free solve (the PR acceptance criterion)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def cube_problem():
    mesh = unit_cube_tet(3)
    space = _space(mesh)
    plan = build_plan(space)
    bc = DirichletCondenser(plan.static.mat_routing, space.boundary_dofs())
    f = bc.project_residual(assemble_rhs(plan, wf.source(1.0)))
    rho0 = jnp.asarray(RNG.uniform(0.5, 2.0, mesh.num_cells))
    return plan, bc, f, rho0


def _solve_mf(plan, bc, f, rho):
    op = matfree_operator(plan, wf.diffusion(rho)).condensed(bc)
    return matfree_solve(op, f, _SPEC)


def _solve_csr(plan, bc, f, rho):
    k = bc.apply_matrix_only(assemble(plan, wf.diffusion(rho)))
    return sparse_solve(k, f, _SPEC)


def test_matfree_solve_matches_assembled_3d(cube_problem):
    plan, bc, f, rho0 = cube_problem
    u_mf = _solve_mf(plan, bc, f, rho0)
    u_csr = _solve_csr(plan, bc, f, rho0)
    assert float(jnp.max(jnp.abs(u_mf - u_csr))) < 1e-8


def test_grad_matches_adjoint_sparse_solve(cube_problem):
    plan, bc, f, rho0 = cube_problem
    g_mf = jax.grad(lambda r: jnp.sum(_solve_mf(plan, bc, f, r) ** 2))(rho0)
    g_csr = jax.grad(lambda r: jnp.sum(_solve_csr(plan, bc, f, r) ** 2))(rho0)
    np.testing.assert_allclose(np.asarray(g_mf), np.asarray(g_csr), atol=1e-6)


def test_grad_vs_finite_differences(cube_problem):
    plan, bc, f, rho0 = cube_problem
    loss = lambda r: jnp.sum(_solve_mf(plan, bc, f, r) ** 2)  # noqa: E731
    g = jax.grad(loss)(rho0)
    eps = 1e-5
    for i in (0, 11, 47):
        e = jnp.zeros_like(rho0).at[i].set(1.0)
        fd = (loss(rho0 + eps * e) - loss(rho0 - eps * e)) / (2 * eps)
        assert abs(float(g[i]) - float(fd)) < 1e-6


def test_grad_wrt_rhs_is_adjoint_solution(cube_problem):
    plan, bc, f, rho0 = cube_problem
    gb = jax.grad(
        lambda b: jnp.sum(_solve_mf(plan, bc, b, rho0) ** 2)
    )(f)
    gb_csr = jax.grad(
        lambda b: jnp.sum(_solve_csr(plan, bc, b, rho0) ** 2)
    )(f)
    np.testing.assert_allclose(np.asarray(gb), np.asarray(gb_csr), atol=1e-8)


def test_poisson_problem_matfree_backend():
    from repro.fem.tensormesh import PoissonProblem

    prob = PoissonProblem(unit_cube_tet(3))
    res_csr = prob.solve()
    res_mf = prob.solve(backend="matfree")
    assert float(jnp.max(jnp.abs(res_csr.u - res_mf.u))) < 1e-8
    assert res_mf.residual < 1e-9


# ---------------------------------------------------------------------------
# the assembled and the matrix-free apply
# ---------------------------------------------------------------------------

def _small_system():
    space = CASES["P1_tri"]()
    plan = build_plan(space)
    k = assemble(plan, wf.diffusion(1.5))
    x = jnp.asarray(RNG.standard_normal(space.num_dofs))
    return plan, k, x


def test_registry_backends_agree():
    """The CSR's apply, its loop apply and the matrix-free apply agree."""
    plan, k, x = _small_system()
    y_ref = np.asarray(k.to_dense()) @ np.asarray(x)
    op = matfree_operator(plan, wf.diffusion(1.5))
    for mv in (k.matvec, k.loop_matvec(), op.matvec):
        np.testing.assert_allclose(np.asarray(mv(x)), y_ref, atol=1e-12)


def test_registry_residuals_agree():
    """The residual loss's ``K u − f`` on the assembled and on the
    matrix-free operator, against the dense condensed system."""
    from repro.pils.losses import GalerkinResidualLoss

    space = CASES["P1_tri"]()
    asm = GalerkinAssembler(space)
    bc = DirichletCondenser(asm, space.boundary_dofs())
    u = jnp.asarray(RNG.standard_normal(space.num_dofs))
    kc, fc = bc.apply(asm.assemble(wf.diffusion()),
                      asm.assemble_rhs(wf.source(1.0)))
    r_ref = np.asarray(kc.to_dense()) @ np.asarray(u) - np.asarray(fc)
    for backend in ("csr", "matfree"):
        r = GalerkinResidualLoss(asm, bc, backend=backend).residual(u)
        np.testing.assert_allclose(np.asarray(r), r_ref, atol=1e-12)


@pytest.mark.parametrize("backend", ["nope", "ell", "ell_pallas",
                                     "ell_stream"])
def test_registry_errors(backend):
    """A backend name outside the accepted ones is refused, naming them,
    by the problems, the θ-integrator and the residual loss."""
    from repro.fem.tensormesh import PoissonProblem
    from repro.pils.losses import GalerkinResidualLoss
    from repro.transient import ThetaIntegrator

    space = CASES["P1_tri"]()
    asm = GalerkinAssembler(space)
    bc = DirichletCondenser(asm, space.boundary_dofs())
    mass = asm.assemble(wf.mass())
    stiff = asm.assemble(wf.diffusion())
    named = f"unknown backend '{backend}'.*'csr'.*'matfree'"
    with pytest.raises(ValueError, match=named):
        PoissonProblem(unit_square_tri(4)).solve(backend=backend)
    with pytest.raises(ValueError, match=named):
        ThetaIntegrator(mass, stiff, 0.01, bc=bc, backend=backend)
    with pytest.raises(ValueError, match=named):
        GalerkinResidualLoss(asm, bc, backend=backend)


# ---------------------------------------------------------------------------
# consumers: losses, transient
# ---------------------------------------------------------------------------

def test_galerkin_residual_loss_backends():
    from repro.pils.losses import GalerkinResidualLoss

    space = CASES["P1_tri"]()
    asm = GalerkinAssembler(space)
    bc = DirichletCondenser(asm, space.boundary_dofs())
    u = jnp.asarray(RNG.standard_normal(space.num_dofs))
    ref = float(GalerkinResidualLoss(asm, bc, backend="csr")(u))
    val = float(GalerkinResidualLoss(asm, bc, backend="matfree")(u))
    assert abs(val - ref) < 1e-9 * max(1.0, abs(ref))


def test_theta_matfree_rollout_matches_csr():
    from repro.transient import ThetaIntegrator

    space = CASES["P1_tri"]()
    asm = GalerkinAssembler(space)
    bc = DirichletCondenser(asm, space.boundary_dofs())
    u0 = jnp.asarray(RNG.standard_normal(space.num_dofs)) * jnp.asarray(bc.free_mask)
    mk = lambda be: ThetaIntegrator.from_form(  # noqa: E731
        asm, wf.diffusion(1.0), 0.01, theta=0.5, bc=bc, backend=be
    )
    traj_csr = mk("csr").rollout(u0, 4)
    traj_mf = mk("matfree").rollout(u0, 4)
    np.testing.assert_allclose(
        np.asarray(traj_mf), np.asarray(traj_csr), atol=1e-10
    )
    # grad through the matrix-free rollout matches the adjoint CSR path
    def loss(kappa, backend):
        integ = ThetaIntegrator.from_form(
            asm, wf.diffusion(kappa), 0.01, theta=0.5, bc=bc, backend=backend
        )
        return jnp.sum(integ.rollout(u0, 3) ** 2)

    g_csr = jax.grad(lambda c: loss(c, "csr"))(1.3)
    g_mf = jax.grad(lambda c: loss(c, "matfree"))(1.3)
    assert abs(float(g_csr) - float(g_mf)) < 1e-8 * max(1.0, abs(float(g_csr)))


def test_newmark_backend_dispatch():
    """The Newmark rollout, whose predictor applies the stiffness CSR,
    against Newmark steps on the dense free-DoF system in NumPy."""
    from repro.transient import NewmarkIntegrator

    space = CASES["P1_tri"]()
    asm = GalerkinAssembler(space)
    bc = DirichletCondenser(asm, space.boundary_dofs())
    mass = asm.assemble(wf.mass())
    stiff = asm.assemble(wf.diffusion())
    u0 = jnp.asarray(RNG.standard_normal(space.num_dofs)) * jnp.asarray(bc.free_mask)
    dt, beta, gamma, n_steps = 0.01, 0.25, 0.5, 3
    traj = np.asarray(NewmarkIntegrator(mass, stiff, dt, bc=bc).rollout(u0, n_steps))

    free = np.asarray(bc.free_mask).astype(bool)
    m_ff = np.asarray(mass.to_dense())[np.ix_(free, free)]
    k_ff = np.asarray(stiff.to_dense())[np.ix_(free, free)]
    u = np.asarray(u0)[free]
    v = np.zeros_like(u)
    a = np.linalg.solve(m_ff, -k_ff @ u)
    want = []
    for _ in range(n_steps):
        u_star = u + dt * v + 0.5 * dt**2 * (1 - 2 * beta) * a
        v_star = v + dt * (1 - gamma) * a
        a = np.linalg.solve(m_ff + beta * dt**2 * k_ff, -k_ff @ u_star)
        u = u_star + beta * dt**2 * a
        v = v_star + gamma * dt * a
        want.append(u)
    scale = np.abs(traj).max()
    np.testing.assert_allclose(traj[:, free], np.stack(want), rtol=0,
                               atol=1e-8 * scale)
    np.testing.assert_array_equal(traj[:, ~free], 0.0)


def test_matfree_rejects_facet_terms_and_vector_arity():
    from repro.core.boundary import FacetAssembler

    space = CASES["P1_tri"]()
    plan = build_plan(space)
    fa = FacetAssembler(space, space.mesh.boundary_facets(),
                        volume_routing=plan.static.mat_routing)
    with pytest.raises(NotImplementedError, match="volume terms only"):
        matfree_operator(plan, wf.diffusion() + wf.robin(1.0, on=fa))
    with pytest.raises(TypeError):
        matfree_operator(plan, wf.source(1.0))


def test_matfree_solve_on_csr_matches_sparse_solve():
    # the generic adjoint solve also accepts an assembled CSR pytree
    space = CASES["P1_tri"]()
    plan = build_plan(space)
    bc = DirichletCondenser(plan.static.mat_routing, space.boundary_dofs())
    f = bc.project_residual(assemble_rhs(plan, wf.source(1.0)))
    rho = jnp.asarray(RNG.uniform(0.5, 2.0, space.mesh.num_cells))

    def solve_generic(r):
        k = bc.apply_matrix_only(assemble(plan, wf.diffusion(r)))
        return matfree_solve(k, f, _SPEC)

    def solve_sparse(r):
        k = bc.apply_matrix_only(assemble(plan, wf.diffusion(r)))
        return sparse_solve(k, f, _SPEC)

    np.testing.assert_allclose(
        np.asarray(solve_generic(rho)), np.asarray(solve_sparse(rho)), atol=1e-10
    )
    g1 = jax.grad(lambda r: jnp.sum(solve_generic(r) ** 2))(rho)
    g2 = jax.grad(lambda r: jnp.sum(solve_sparse(r) ** 2))(rho)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), atol=1e-6)


# ---------------------------------------------------------------------------
# the new hex mesh (satellite: Q1_hex end-to-end)
# ---------------------------------------------------------------------------

def test_hex_mesh_poisson_sanity():
    mesh = unit_cube_hex(4)
    assert mesh.cell_type == "hex"
    # structured box: volumes sum to 1, boundary facet count = 6 n²
    np.testing.assert_allclose(mesh.cell_volumes().sum(), 1.0, atol=1e-12)
    assert mesh.boundary_facets().shape == (6 * 16, 4)
    space = _space(mesh)
    plan = build_plan(space)
    bc = DirichletCondenser(plan.static.mat_routing, space.boundary_dofs())
    k = bc.apply_matrix_only(assemble(plan, wf.diffusion()))
    f = bc.project_residual(assemble_rhs(plan, wf.source(1.0)))
    u = sparse_solve(k, f, _SPEC)
    # interior solution of -Δu = 1 on the unit cube is positive, max ≈ 0.056
    assert float(jnp.min(u)) >= 0.0
    assert 0.03 < float(jnp.max(u)) < 0.09


def test_matfree_state_is_small():
    # the memory story: a coords-store operator carries only the coefficient
    # leaves beyond the plan — far below the 3 nnz-sized CSR arrays
    space = _space(unit_cube_tet(4))
    plan = build_plan(space)
    rho = jnp.asarray(RNG.uniform(0.5, 2.0, space.mesh.num_cells))
    k = assemble(plan, wf.diffusion(rho))
    op = matfree_operator(plan, wf.diffusion(rho), store="coords")
    csr_bytes = k.vals.nbytes + k.indices.nbytes + k.row_of_nnz.nbytes
    assert op.state_bytes() < csr_bytes / 2
    assert isinstance(k, CSR)


# ---------------------------------------------------------------------------
# batched matrix-free families (PR 7): (B, ...) coefficient leaves on one
# shared plan — vmap-able diagonal()/condensed(), family solves + gradients
# ---------------------------------------------------------------------------

from repro.core import (  # noqa: E402
    MatFreeFamily,
    assemble_batched,
    matfree_family,
    matfree_solve_batched,
)


def _family_fixture(batch=4, n=6, seed=3):
    space = _space(unit_square_tri(n))
    plan = build_plan(space)
    rng = np.random.default_rng(seed)
    rho_b = jnp.asarray(
        rng.uniform(0.5, 2.0, (batch, space.mesh.num_cells)))
    bc = DirichletCondenser(plan.static.mat_routing, space.boundary_dofs())
    return plan, rho_b, bc


@pytest.mark.parametrize("store", ["context", "coords", "local"])
def test_family_matvec_diagonal_parity(store):
    plan, rho_b, _ = _family_fixture()
    fam = matfree_family(plan, wf.diffusion(rho_b[0]),
                         leaves_batch=(rho_b, None), store=store)
    assert isinstance(fam, MatFreeFamily) and fam.batch == rho_b.shape[0]
    x = jnp.asarray(RNG.normal(size=fam.shape[0]))
    y = fam.matvec(x)
    d = fam.diagonal()
    for b in range(fam.batch):
        op_b = matfree_operator(plan, wf.diffusion(rho_b[b]))
        np.testing.assert_allclose(np.asarray(y[b]),
                                   np.asarray(op_b.matvec(x)), atol=1e-12)
        np.testing.assert_allclose(np.asarray(d[b]),
                                   np.asarray(op_b.diagonal()), atol=1e-12)


def test_family_condensed_diagonal_under_vmap():
    # satellite regression: diagonal() and condensed(bc) must work when
    # vmapped over coefficient leaves (family Jacobi preconditioning)
    plan, rho_b, bc = _family_fixture()
    fam = matfree_family(plan, wf.diffusion(rho_b[0]),
                         leaves_batch=(rho_b, None)).condensed(bc)
    d = fam.diagonal()
    x = jnp.asarray(RNG.normal(size=fam.shape[0]))
    y = fam.matvec(x)
    for b in range(fam.batch):
        opc = matfree_operator(plan, wf.diffusion(rho_b[b])).condensed(bc)
        np.testing.assert_allclose(np.asarray(d[b]),
                                   np.asarray(opc.diagonal()), atol=1e-12)
        np.testing.assert_allclose(np.asarray(y[b]),
                                   np.asarray(opc.matvec(x)), atol=1e-12)


def test_family_getitem_and_validation():
    plan, rho_b, _ = _family_fixture()
    fam = matfree_family(plan, wf.diffusion(rho_b[0]),
                         leaves_batch=(rho_b, None))
    x = jnp.asarray(RNG.normal(size=fam.shape[0]))
    np.testing.assert_allclose(np.asarray(fam[2].matvec(x)),
                               np.asarray(fam.matvec(x)[2]), atol=1e-12)
    with pytest.raises(TypeError):
        fam[0:2]
    with pytest.raises(ValueError, match="nothing is batched"):
        matfree_family(plan, wf.diffusion(rho_b[0]))
    with pytest.raises(ValueError, match="leaves_batch has"):
        matfree_family(plan, wf.diffusion(rho_b[0]), leaves_batch=(rho_b,))
    with pytest.raises(ValueError, match="inconsistent"):
        matfree_family(plan, wf.mass(1.0) + wf.diffusion(rho_b[0]),
                       leaves_batch=(jnp.ones((3, 1)), None, rho_b, None))


def test_family_solve_matches_sequential_and_batched_csr():
    plan, rho_b, bc = _family_fixture()
    f = jnp.asarray(RNG.normal(size=(rho_b.shape[0], plan.static.num_dofs)))
    f = f * bc.free_mask
    fam = matfree_family(plan, wf.diffusion(rho_b[0]),
                         leaves_batch=(rho_b, None)).condensed(bc)
    x = matfree_solve_batched(fam, f, _SPEC)
    kb = bc.apply_matrix_only(assemble_batched(
        plan, wf.diffusion(rho_b[0]), leaves_batch=(rho_b, None)))
    from repro.core import sparse_solve_batched
    x_csr = sparse_solve_batched(kb, f, _SPEC)
    np.testing.assert_allclose(np.asarray(x), np.asarray(x_csr), atol=1e-9)
    for b in range(fam.batch):
        opc = matfree_operator(plan, wf.diffusion(rho_b[b])).condensed(bc)
        xb = matfree_solve(opc, f[b], _SPEC)
        np.testing.assert_allclose(np.asarray(x[b]), np.asarray(xb),
                                   atol=1e-9)


def test_family_solve_info_and_record():
    plan, rho_b, bc = _family_fixture()
    f = bc.project_residual(
        jnp.asarray(RNG.normal(size=plan.static.num_dofs)))
    fam = matfree_family(plan, wf.diffusion(rho_b[0]),
                         leaves_batch=(rho_b, None)).condensed(bc)
    x, info = matfree_solve_batched(fam, f, return_info=True)
    assert x.shape == (fam.batch, plan.static.num_dofs)
    assert info.iters.shape == (fam.batch,)
    assert bool(jnp.all(info.converged))


def test_family_grad_matches_per_instance_adjoints():
    # acceptance: gradients through the vmapped family solve match B
    # per-instance adjoint matfree_solve gradients to <= 1e-12 (relative)
    plan, rho_b, bc = _family_fixture(batch=3)
    f = bc.project_residual(
        jnp.asarray(RNG.normal(size=plan.static.num_dofs)))

    def loss_family(rb):
        fam = matfree_family(plan, wf.diffusion(rb[0]),
                             leaves_batch=(rb, None)).condensed(bc)
        return jnp.sum(matfree_solve_batched(fam, f, _SPEC) ** 2)

    def loss_sequential(rb):
        tot = 0.0
        for b in range(rb.shape[0]):
            opc = matfree_operator(plan, wf.diffusion(rb[b])).condensed(bc)
            tot = tot + jnp.sum(
                matfree_solve(opc, f, _SPEC) ** 2)
        return tot

    g1 = jax.grad(loss_family)(rho_b)
    g2 = jax.grad(loss_sequential)(rho_b)
    scale = float(jnp.max(jnp.abs(g2)))
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                               atol=1e-12 * scale)


def test_family_batched_coords():
    # batched geometry: perturb each instance's mesh, store forced to coords
    plan, rho_b, _ = _family_fixture()
    batch = rho_b.shape[0]
    rng = np.random.default_rng(11)
    coords_b = jnp.asarray(
        np.asarray(plan.coords)[None]
        + 1e-3 * rng.normal(size=(batch,) + plan.coords.shape))
    fam = matfree_family(plan, wf.diffusion(rho_b[0]),
                         leaves_batch=(rho_b, None), coords_batch=coords_b)
    assert fam.op.store == "coords" and fam.coords_ax == 0
    x = jnp.asarray(RNG.normal(size=fam.shape[0]))
    y = fam.matvec(x)
    for b in range(batch):
        op_b = matfree_operator(plan, wf.diffusion(rho_b[b]), store="coords",
                                coords=coords_b[b])
        np.testing.assert_allclose(np.asarray(y[b]),
                                   np.asarray(op_b.matvec(x)), atol=1e-12)


def test_family_theta_rollout_matches_batched_csr():
    from repro.transient import batched_theta_rollout

    plan, kap_b, bc = _family_fixture()
    batch, dt, theta, n_steps = kap_b.shape[0], 0.01, 1.0, 4
    u0 = jnp.asarray(RNG.normal(size=(batch, plan.static.num_dofs)))
    u0 = u0 * bc.free_mask
    lhs_form = wf.mass(1.0) + (theta * dt) * wf.diffusion(kap_b[0])
    rhs_form = wf.mass(1.0) + (-(1 - theta) * dt) * wf.diffusion(kap_b[0])
    lb = (None, None, kap_b, None)
    traj_csr = batched_theta_rollout(
        assemble_batched(plan, lhs_form, leaves_batch=lb),
        assemble_batched(plan, rhs_form, leaves_batch=lb),
        u0, n_steps, dt=dt, theta=theta, bc=bc)
    traj_mf = batched_theta_rollout(
        matfree_family(plan, lhs_form, leaves_batch=lb),
        matfree_family(plan, rhs_form, leaves_batch=lb),
        u0, n_steps, dt=dt, theta=theta, bc=bc)
    np.testing.assert_allclose(np.asarray(traj_mf), np.asarray(traj_csr),
                               atol=1e-10)


def test_family_pils_loss_backend_parity():
    from repro.pils.losses import BatchedGalerkinResidualLoss

    space = _space(unit_square_tri(6))
    asm = GalerkinAssembler(space)
    plan = build_plan(space)
    bc = DirichletCondenser(plan.static.mat_routing, space.boundary_dofs())
    rng = np.random.default_rng(5)
    rho_b = jnp.asarray(rng.uniform(0.5, 2.0, (3, space.mesh.num_cells)))
    l_csr = BatchedGalerkinResidualLoss(asm, bc, rho_b)
    l_mf = BatchedGalerkinResidualLoss(asm, bc, rho_b, backend="matfree")
    u = jnp.asarray(rng.normal(size=(3, space.num_dofs)))
    np.testing.assert_allclose(float(l_mf(u)), float(l_csr(u)), rtol=1e-12)
    np.testing.assert_allclose(np.asarray(l_mf.solve()),
                               np.asarray(l_csr.solve()), atol=1e-9)
    with pytest.raises(ValueError, match="unknown backend"):
        BatchedGalerkinResidualLoss(asm, bc, rho_b, backend="ell")
