"""Functional assembly core: AssemblyPlan, batched multi-instance assembly,
BatchedCSR / batched sparse_solve, dtype + deprecation regressions."""

import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import (
    AssemblyPlan,
    BatchedCSR,
    DirichletCondenser,
    FacetAssembler,
    FunctionSpace,
    GalerkinAssembler,
    assemble,
    assemble_batched,
    assemble_rhs,
    assemble_rhs_batched,
    disk_tri,
    sparse_solve,
    sparse_solve_batched,
    unit_square_tri,
    weakform as wf,
)
from repro.core import assembly as asm_mod
from repro.core.mesh import element_for_mesh


def _setup(n=6, mesh_fn=unit_square_tri, **kw):
    m = mesh_fn(n)
    space = FunctionSpace(m, element_for_mesh(m), **kw)
    return m, space, GalerkinAssembler(space)


# ---------------------------------------------------------------------------
# the plan: pytree structure + pure functions == facade
# ---------------------------------------------------------------------------

def test_plan_is_pytree_with_single_coords_leaf():
    m, space, asm = _setup(4)
    plan = asm.plan
    leaves, treedef = jax.tree_util.tree_flatten(plan)
    assert len(leaves) == 1 and leaves[0] is plan.coords
    plan2 = jax.tree_util.tree_unflatten(treedef, leaves)
    assert isinstance(plan2, AssemblyPlan)
    assert plan2.static is plan.static  # aux shared by identity
    # a plan crosses jit as an argument (coords traced, static hashed)
    vals = jax.jit(lambda p: assemble(p, wf.diffusion()).vals)(plan)
    np.testing.assert_allclose(
        np.asarray(vals), np.asarray(asm.assemble(wf.diffusion()).vals), atol=1e-15
    )


def test_pure_assemble_matches_facade():
    m, space, asm = _setup(6)
    rng = np.random.default_rng(0)
    rho = jnp.asarray(rng.uniform(0.5, 2.0, m.num_cells))
    form = wf.diffusion(rho) + wf.mass(0.3)
    np.testing.assert_array_equal(
        np.asarray(assemble(asm.plan, form).vals),
        np.asarray(asm.assemble(form).vals),
    )
    rhs = wf.source(lambda x: x[..., 0])
    np.testing.assert_array_equal(
        np.asarray(assemble_rhs(asm.plan, rhs)),
        np.asarray(asm.assemble_rhs(rhs)),
    )


def test_plan_coords_differentiable():
    m, space, asm = _setup(4)

    def vol(coords):  # ∫ 1 dx via the mass matrix row sums
        k = assemble(asm.plan.with_coords(coords), wf.mass())
        return jnp.sum(k.vals)

    g = jax.grad(vol)(asm.plan.coords)
    assert np.all(np.isfinite(np.asarray(g)))
    eps = 1e-6
    c = asm.plan.coords
    fd = (vol(c.at[3, 0, 0].add(eps)) - vol(c.at[3, 0, 0].add(-eps))) / (2 * eps)
    np.testing.assert_allclose(float(g[3, 0, 0]), float(fd), rtol=1e-6, atol=1e-9)


# ---------------------------------------------------------------------------
# batched assembly: exact parity with stacked single assembles
# ---------------------------------------------------------------------------

def test_batched_coefficients_match_stacked_singles():
    m, space, asm = _setup(8)
    rng = np.random.default_rng(1)
    b = 5
    rho_b = jnp.asarray(rng.uniform(0.5, 2.0, (b, m.num_cells)))
    kb = assemble_batched(asm.plan, wf.diffusion(rho_b[0]),
                          leaves_batch=(rho_b, None))
    assert isinstance(kb, BatchedCSR) and kb.vals.shape == (b, kb.nnz)
    stacked = jnp.stack(
        [assemble(asm.plan, wf.diffusion(rho_b[i])).vals for i in range(b)]
    )
    np.testing.assert_allclose(np.asarray(kb.vals), np.asarray(stacked), atol=1e-12)


def test_batched_geometries_match_stacked_singles():
    m, space, asm = _setup(6)
    b = 4
    coords_b = jnp.stack([asm.plan.coords * (1.0 + 0.05 * i) for i in range(b)])
    kb = assemble_batched(asm.plan, wf.diffusion() + wf.mass(0.5),
                          coords_batch=coords_b)
    stacked = jnp.stack(
        [assemble(asm.plan, wf.diffusion() + wf.mass(0.5), coords=coords_b[i]).vals
         for i in range(b)]
    )
    np.testing.assert_allclose(np.asarray(kb.vals), np.asarray(stacked), atol=1e-12)


def test_batched_rhs_and_mixed_batching():
    m, space, asm = _setup(6)
    rng = np.random.default_rng(2)
    b = 3
    f_b = jnp.asarray(rng.uniform(-1.0, 1.0, (b, m.num_cells)))
    fb = assemble_rhs_batched(asm.plan, wf.source(f_b[0]), leaves_batch=(f_b, None))
    assert fb.shape == (b, space.num_dofs)
    stacked = jnp.stack([assemble_rhs(asm.plan, wf.source(f_b[i])) for i in range(b)])
    np.testing.assert_allclose(np.asarray(fb), np.asarray(stacked), atol=1e-13)
    # bare-array convenience batches the first traced slot
    fb2 = assemble_rhs_batched(asm.plan, wf.source(f_b[0]), leaves_batch=f_b)
    np.testing.assert_array_equal(np.asarray(fb), np.asarray(fb2))


def test_batched_assembly_validates_inputs():
    m, space, asm = _setup(4)
    with pytest.raises(ValueError, match="nothing is batched"):
        assemble_batched(asm.plan, wf.diffusion(1.0))
    with pytest.raises(ValueError, match="slots"):
        assemble_batched(asm.plan, wf.diffusion(1.0) + wf.mass(1.0),
                         leaves_batch=(jnp.ones((2, m.num_cells)),))
    with pytest.raises(ValueError, match="batch sizes"):
        assemble_batched(asm.plan, wf.diffusion(jnp.ones(m.num_cells)),
                         coords_batch=jnp.stack([asm.plan.coords] * 2),
                         leaves_batch=(jnp.ones((3, m.num_cells)), None))
    fa = FacetAssembler(space, m.boundary_facets(), volume_routing=asm.mat_routing)
    with pytest.raises(NotImplementedError, match="volume terms only"):
        assemble_batched(asm.plan, wf.diffusion() + wf.robin(1.0, on=fa),
                         coords_batch=jnp.stack([asm.plan.coords] * 2))


def test_batched_assembly_zero_retraces_across_values():
    """One trace serves the whole batch loop: new coefficient *values* (and
    new batched coords values) must not retrace the functional core."""
    m, space, asm = _setup(7)
    b = 3
    rho_b = jnp.ones((b, m.num_cells))
    form = wf.mass(1.0) + 0.1 * wf.diffusion(rho_b[0])
    lb = (None, None, rho_b, None)
    assemble_batched(asm.plan, form, leaves_batch=lb)      # trace once
    n0 = asm_mod.n_core_traces()
    for i in range(4):
        assemble_batched(asm.plan, form, leaves_batch=(None, None, rho_b * (i + 2), None))
    assert asm_mod.n_core_traces() == n0, "batched assembly retraced on new values"


# ---------------------------------------------------------------------------
# BatchedCSR ops + vmapped differentiable solve
# ---------------------------------------------------------------------------

def _family(n=6, b=4, seed=3):
    m, space, asm = _setup(n)
    bc = DirichletCondenser(asm, space.boundary_dofs())
    rng = np.random.default_rng(seed)
    rho_b = jnp.asarray(rng.uniform(0.5, 2.0, (b, m.num_cells)))
    kb = assemble_batched(asm.plan, wf.diffusion(rho_b[0]),
                          leaves_batch=(rho_b, None))
    f = bc.project_residual(assemble_rhs(asm.plan, wf.source(1.0)))
    return asm, bc, rho_b, bc.apply_matrix_only(kb), f


def test_batched_csr_ops_match_per_instance():
    asm, bc, rho_b, kc, f = _family()
    assert isinstance(kc, BatchedCSR)  # condensation preserves the container
    x = jnp.asarray(np.random.default_rng(4).uniform(-1, 1, (kc.batch, kc.shape[0])))
    y = kc.matvec(x)
    for i in range(kc.batch):
        np.testing.assert_allclose(
            np.asarray(y[i]), np.asarray(kc[i].matvec(x[i])), atol=1e-14
        )
    np.testing.assert_allclose(
        np.asarray(kc.diagonal()[1]), np.asarray(kc[1].diagonal()), atol=1e-14
    )
    np.testing.assert_allclose(
        np.asarray(kc.to_dense()[2]), np.asarray(kc[2].to_dense()), atol=1e-14
    )
    restacked = BatchedCSR.stack([kc[i] for i in range(kc.batch)])
    np.testing.assert_array_equal(np.asarray(restacked.vals), np.asarray(kc.vals))
    # slicing returns a sub-family, not a malformed CSR
    sub = kc[1:3]
    assert isinstance(sub, BatchedCSR) and sub.batch == 2
    np.testing.assert_array_equal(np.asarray(sub.matvec(x[1:3])), np.asarray(y[1:3]))
    with pytest.raises(TypeError, match="int or slice"):
        kc[[0, 1]]


def test_batched_csr_stack_rejects_mismatched_patterns():
    _, _, _, k_a, _ = _family(n=5)
    _, _, _, k_b, _ = _family(n=6)
    with pytest.raises(ValueError, match="patterns differ"):
        BatchedCSR.stack([k_a[0], k_b[0]])


def test_plan_identity_eq_and_hash():
    m, space, asm = _setup(4)
    p = asm.plan
    assert p == p and hash(p) == hash(p)
    assert p != p.with_coords(p.coords * 2.0)  # identity semantics, no raise


def test_form_executable_cache_is_fifo_bounded():
    """Per-call lambda coefficients mint fresh signatures; the executable
    cache must evict instead of growing without limit."""
    m, space, asm = _setup(4)
    limit = asm_mod._FORM_FNS_LIMIT
    asm_mod._FORM_FNS_LIMIT = 4
    try:
        for i in range(10):
            assemble_rhs(asm.plan, wf.source(lambda x, i=i: x[..., 0] + i))
        assert len(asm_mod._FORM_FNS) <= 4
    finally:
        asm_mod._FORM_FNS_LIMIT = limit


def test_facade_and_pure_api_share_one_executable():
    """Mixing asm.assemble(form) and assemble(plan, form) on one signature
    must not compile twice (the facade delegates to the module jit cache)."""
    m, space, asm = _setup(7)
    rho = jnp.asarray(np.random.default_rng(10).uniform(0.5, 2.0, m.num_cells))
    form = wf.diffusion(rho) + wf.advection(jnp.array([0.3, 0.9]))
    assemble(asm.plan, form)                      # traces the core once
    n0, t0 = asm_mod.n_core_traces(), asm.n_traces
    k = asm.assemble(form)                        # facade: cache hit, no trace
    assert asm_mod.n_core_traces() == n0
    assert asm.n_traces == t0
    np.testing.assert_array_equal(
        np.asarray(k.vals), np.asarray(assemble(asm.plan, form).vals)
    )


def test_sparse_solve_batched_matches_per_instance():
    asm, bc, rho_b, kc, f = _family()
    u_b = sparse_solve_batched(kc, f, "cg", 1e-12, 1e-12, 2000)
    for i in range(kc.batch):
        u_i = sparse_solve(kc[i], f, "cg", 1e-12, 1e-12, 2000)
        np.testing.assert_allclose(np.asarray(u_b[i]), np.asarray(u_i), atol=1e-10)


def test_vmap_grad_through_sparse_solve_on_batched_csr():
    """vmap(grad(...)) through the adjoint solve over a BatchedCSR family:
    per-instance coefficient gradients in one executable, checked vs FD."""
    asm, bc, rho_b, _, f = _family(n=5, b=3)

    def loss_one(rho):
        k = bc.apply_matrix_only(assemble(asm.plan, wf.diffusion(rho)))
        u = sparse_solve(k, f, "cg", 1e-12, 1e-12, 2000)
        return jnp.sum(u**2)

    def loss_batched(rho_b):
        kb = bc.apply_matrix_only(
            assemble_batched(asm.plan, wf.diffusion(rho_b[0]),
                             leaves_batch=(rho_b, None))
        )
        u = sparse_solve_batched(kb, f, "cg", 1e-12, 1e-12, 2000)
        return jnp.sum(u**2, axis=-1)

    g_b = jax.vmap(jax.grad(loss_one))(rho_b)
    assert np.all(np.isfinite(np.asarray(g_b)))
    # per-instance gradient of the batched pipeline (vjp rows) agrees
    _, vjp = jax.vjp(loss_batched, rho_b)
    (g_rows,) = vjp(jnp.ones(rho_b.shape[0]))
    np.testing.assert_allclose(np.asarray(g_b), np.asarray(g_rows), rtol=1e-8,
                               atol=1e-10)
    i = int(np.argmax(np.abs(np.asarray(g_b[0]))))
    eps = 1e-6
    fd = (loss_one(rho_b[0].at[i].add(eps)) - loss_one(rho_b[0].at[i].add(-eps))) / (2 * eps)
    np.testing.assert_allclose(float(g_b[0, i]), float(fd), rtol=1e-4)


# ---------------------------------------------------------------------------
# downstream batched consumers
# ---------------------------------------------------------------------------

def test_batched_theta_rollout_matches_per_instance():
    from repro.transient import CRANK_NICOLSON, ThetaIntegrator, batched_theta_rollout

    m, space, asm = _setup(5)
    bc = DirichletCondenser(asm, space.boundary_dofs())
    rng = np.random.default_rng(5)
    b, dt, theta, steps = 3, 1e-2, CRANK_NICOLSON, 4
    kappa_b = jnp.asarray(rng.uniform(0.5, 2.0, (b, m.num_cells)))
    lb = (None, None, kappa_b, None)
    lhs = assemble_batched(asm.plan, wf.mass(1.0) + (theta * dt) * wf.diffusion(kappa_b[0]),
                           leaves_batch=lb)
    rhs = assemble_batched(
        asm.plan, wf.mass(1.0) + (-(1.0 - theta) * dt) * wf.diffusion(kappa_b[0]),
        leaves_batch=lb,
    )
    u0_b = jnp.asarray(rng.uniform(-1, 1, (b, space.num_dofs))) * jnp.asarray(bc.free_mask)
    trajs = batched_theta_rollout(lhs, rhs, u0_b, steps, dt=dt, theta=theta, bc=bc)
    assert trajs.shape == (b, steps, space.num_dofs)
    for i in range(b):
        integ = ThetaIntegrator.from_form(asm, wf.diffusion(kappa_b[i]), dt=dt,
                                          theta=theta, mass_coeff=1.0, bc=bc)
        ref = integ.rollout(u0_b[i], steps)
        np.testing.assert_allclose(np.asarray(trajs[i]), np.asarray(ref), atol=1e-12)


def test_poisson_solve_coeff_batch_matches_single_solves():
    from repro.fem import PoissonProblem

    prob = PoissonProblem(unit_square_tri(8))
    rng = np.random.default_rng(6)
    rho_b = jnp.asarray(rng.uniform(0.5, 2.0, (3, prob.mesh.num_cells)))
    u_b = prob.solve_coeff_batch(rho_b)
    for i in range(3):
        res = prob.solve(rho=rho_b[i])
        np.testing.assert_allclose(np.asarray(u_b[i]), np.asarray(res.u), atol=1e-8)


def test_batched_galerkin_residual_loss_matches_single():
    from repro.pils import BatchedGalerkinResidualLoss, GalerkinResidualLoss

    m, space, asm = _setup(6)
    bc = DirichletCondenser(asm, space.boundary_dofs())
    rng = np.random.default_rng(7)
    rho_b = jnp.asarray(rng.uniform(0.5, 2.0, (3, m.num_cells)))
    loss_b = BatchedGalerkinResidualLoss(asm, bc, rho_b)
    u_b = jnp.asarray(rng.uniform(-1, 1, (3, space.num_dofs)))
    singles = [GalerkinResidualLoss(asm, bc, rho=rho_b[i]) for i in range(3)]
    want = np.mean([float(s(u_b[i])) for i, s in enumerate(singles)])
    np.testing.assert_allclose(float(loss_b(u_b)), want, rtol=1e-12)
    # direct family solve zeroes the family residual
    u_star = loss_b.solve()
    assert float(loss_b(u_star)) < 1e-16


def test_fit_family_trains_toward_direct_solves():
    from repro.pils import fit_family

    m, space, asm = _setup(5)
    bc = DirichletCondenser(asm, space.boundary_dofs())
    rng = np.random.default_rng(11)
    rho_b = jnp.asarray(rng.uniform(0.5, 2.0, (3, m.num_cells)))
    u_fit, hist, its, loss = fit_family(asm, bc, rho_b, steps=800, lr=5e-2)
    assert u_fit.shape == (3, space.num_dofs)
    assert float(loss(u_fit)) < 1e-4  # family residual driven toward zero
    u_star = loss.solve()
    rel = float(jnp.linalg.norm(u_fit - u_star) / jnp.linalg.norm(u_star))
    assert rel < 0.05, rel
    # hard-constrained net loss: zero net + zero Dirichlet data has residual
    # equal to the plain ||F||² family loss
    zero_net = lambda p, x: jnp.zeros((x.shape[0], 1))
    val = loss.loss_from_net(zero_net, jnp.zeros((3, 1)))
    want = loss(jnp.zeros((3, space.num_dofs)))
    np.testing.assert_allclose(float(val), float(want), rtol=1e-12)


def test_simp_compliance_batch_matches_single():
    from repro.opt import CantileverProblem

    prob = CantileverProblem(nx=10, ny=5, lx=10.0, ly=5.0)
    rng = np.random.default_rng(8)
    rho_b = jnp.asarray(rng.uniform(0.3, 0.9, (2, prob.n_elem)))
    c_b = prob.compliance_batch(rho_b)
    c_sens, g_b = prob.compliance_and_sensitivity_batch(rho_b)
    np.testing.assert_allclose(np.asarray(c_b), np.asarray(c_sens), rtol=1e-12)
    for i in range(2):
        c_i, g_i = prob.compliance_and_sensitivity(rho_b[i])
        np.testing.assert_allclose(float(c_b[i]), float(c_i), rtol=1e-9)
        np.testing.assert_allclose(np.asarray(g_b[i]), np.asarray(g_i), rtol=1e-6,
                                   atol=1e-8)


# ---------------------------------------------------------------------------
# satellite regressions
# ---------------------------------------------------------------------------

def test_facet_only_form_preserves_input_dtype():
    """The all-facet zero fallback must derive its dtype from the traced
    inputs — a float32 plan/facet geometry must not upcast to float64."""
    m = disk_tri(6, center=(0.0, 0.0), radius=1.0)
    space = FunctionSpace(m, element_for_mesh(m))
    asm = GalerkinAssembler(space)
    fa = FacetAssembler(space, m.boundary_facets(), volume_routing=asm.mat_routing)
    fa32 = FacetAssembler(space, m.boundary_facets(), volume_routing=asm.mat_routing)
    for name in ("coords", "w", "phi", "gradhat"):
        setattr(fa32, name, getattr(fa32, name).astype(jnp.float32))
    plan32 = asm.plan.with_coords(asm.plan.coords.astype(jnp.float32))

    k32 = assemble(plan32, wf.robin(jnp.float32(1.0), on=fa32))
    assert k32.vals.dtype == jnp.float32, k32.vals.dtype
    f32 = assemble_rhs(plan32, wf.neumann(jnp.float32(1.0), on=fa32))
    assert f32.dtype == jnp.float32, f32.dtype

    # float64 facet values stay float64 and exact
    k64 = assemble(asm.plan, wf.robin(1.0, on=fa))
    assert k64.vals.dtype == jnp.float64
    np.testing.assert_allclose(
        np.asarray(k64.vals), np.asarray(k32.vals), atol=1e-6
    )


def test_deprecated_shims_warn_and_match_form_api():
    m, space, asm = _setup(5)
    rho = jnp.asarray(np.random.default_rng(9).uniform(0.5, 2.0, m.num_cells))
    with pytest.warns(DeprecationWarning, match="assemble_stiffness"):
        k_shim = asm.assemble_stiffness(rho)
    np.testing.assert_array_equal(
        np.asarray(k_shim.vals), np.asarray(asm.assemble(wf.diffusion(rho)).vals)
    )
    with pytest.warns(DeprecationWarning, match="assemble_mass"):
        asm.assemble_mass()
    with pytest.warns(DeprecationWarning, match="assemble_load"):
        f_shim = asm.assemble_load(2.0)
    np.testing.assert_array_equal(
        np.asarray(f_shim), np.asarray(asm.assemble_rhs(wf.source(2.0)))
    )
    with pytest.warns(DeprecationWarning, match="assemble_reaction_load"):
        asm.assemble_reaction_load(jnp.ones(space.num_dofs), jnp.tanh)
    m2, space2, asm2 = _setup(4, value_size=2)
    with pytest.warns(DeprecationWarning, match="assemble_elasticity"):
        k_el = asm2.assemble_elasticity(1.0, 1.0)
    np.testing.assert_array_equal(
        np.asarray(k_el.vals),
        np.asarray(asm2.assemble(wf.elasticity(1.0, 1.0)).vals),
    )


def test_routing_device_mirrors_are_prestaged():
    m, space, asm = _setup(4)
    r = asm.mat_routing
    for name in ("perm_dev", "seg_ids_dev", "seg_ids_unsorted_dev",
                 "table_dev"):
        arr = getattr(r, name)
        assert isinstance(arr, jnp.ndarray)
    np.testing.assert_array_equal(np.asarray(r.perm_dev), r.perm)
    v = asm.vec_routing
    assert isinstance(v.touched_dev, jnp.ndarray)
    # frozen dataclass round-trip (replace) recomputes the mirrors
    r2 = dataclasses.replace(r)
    assert isinstance(r2.perm_dev, jnp.ndarray)
