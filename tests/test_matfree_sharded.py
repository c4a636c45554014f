"""Multi-device sharded matrix-free Krylov solve paths.

``ShardedMatFreeOperator`` partitions the gather → per-element action →
scatter apply over the named FEM mesh axis (per-device partial touched-DoF
scatter + one psum); every test asserts ≤1e-12 parity against the
single-device operator — applies, solves, and custom-vjp gradients.

Runs on however many devices the host exposes (1 locally); CI exercises the
real multi-device path with
``XLA_FLAGS=--xla_force_host_platform_device_count=8``.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import (
    DirichletCondenser,
    FunctionSpace,
    GalerkinAssembler,
    ShardedMatFreeOperator,
    assemble,
    build_plan,
    matfree_operator,
    matfree_solve,
    sparse_solve,
    unit_cube_tet,
    unit_square_tri,
    weakform as wf,
)
from repro.core.mesh import element_for_mesh
from repro.fem.tensormesh import PoissonProblem
from repro.sharding.partitioning import FEM_MESH_AXIS, fem_mesh
from repro.transient.theta import CRANK_NICOLSON, ThetaIntegrator

RNG = np.random.default_rng(0)


def _setup(n=8, cube=False, **kw):
    m = unit_cube_tet(n) if cube else unit_square_tri(n)
    space = FunctionSpace(m, element_for_mesh(m), **kw)
    return m, space, build_plan(space)


# ---------------------------------------------------------------------------
# apply parity: matvec / rmatvec / diagonal across storage strategies
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("store", ["coords", "context", "local"])
def test_sharded_apply_parity(store):
    m, space, plan = _setup(7)
    rho = jnp.asarray(RNG.uniform(0.5, 2.0, m.num_cells))
    form = wf.diffusion(rho) + 0.3 * wf.mass()
    op = matfree_operator(plan, form, store=store)
    sop = op.sharded()
    assert isinstance(sop, ShardedMatFreeOperator)
    assert sop.shape == op.shape
    x = jnp.asarray(RNG.standard_normal(op.shape[0]))
    np.testing.assert_allclose(
        np.asarray(sop.matvec(x)), np.asarray(op.matvec(x)), atol=1e-12)
    np.testing.assert_allclose(
        np.asarray(sop.rmatvec(x)), np.asarray(op.rmatvec(x)), atol=1e-12)
    np.testing.assert_allclose(
        np.asarray(sop.diagonal()), np.asarray(op.diagonal()), atol=1e-12)


def test_sharded_transpose_on_nonsymmetric_form():
    """advection makes A ≠ Aᵀ — the sharded rmatvec must take the true
    per-element transpose path, not the symmetric shortcut."""
    m, space, plan = _setup(8)
    form = wf.diffusion(1.0) + wf.advection(jnp.asarray([1.0, 0.5]))
    k = assemble(plan, form)
    sop = matfree_operator(plan, form).sharded()
    x = jnp.asarray(RNG.standard_normal(k.shape[0]))
    np.testing.assert_allclose(
        np.asarray(sop.rmatvec(x)), np.asarray(k.rmatvec(x)), atol=1e-12)
    with np.testing.assert_raises(AssertionError):  # sanity: truly nonsym
        np.testing.assert_allclose(
            np.asarray(sop.matvec(x)), np.asarray(sop.rmatvec(x)), atol=1e-8)


def test_sharded_handles_nondivisible_element_count():
    # E = 2·9² = 162: not divisible by 2/4/8 devices → element padding path
    m, space, plan = _setup(9)
    assert m.num_cells % 4 != 0
    op = matfree_operator(plan, wf.diffusion())
    sop = op.sharded(mesh=fem_mesh(), axis_name=FEM_MESH_AXIS)
    x = jnp.asarray(RNG.standard_normal(op.shape[0]))
    np.testing.assert_allclose(
        np.asarray(sop.matvec(x)), np.asarray(op.matvec(x)), atol=1e-12)


@pytest.mark.parametrize("store", ["coords", "context", "local"])
def test_sharded_element_arrays_span_the_mesh(store):
    """The compiled sharded apply receives every element-aligned array it
    reads split along the element axis over all devices of the mesh, one
    block per device (128 cells divide evenly over 1, 2, 4 or 8 devices)."""
    m, space, plan = _setup(8)
    rho = jnp.asarray(RNG.uniform(0.5, 2.0, m.num_cells))
    sop = matfree_operator(plan, wf.diffusion(rho), store=store).sharded()
    devices = set(sop.mesh.devices.flat)
    x = jax.ShapeDtypeStruct((sop.shape[0],), jnp.float64)
    compiled = jax.jit(lambda s, v: s.matvec(v)).lower(sop, x).compile()
    pairs = jax.tree.map(lambda a, s: (np.shape(a), s), sop,
                         compiled.input_shardings[0][0])
    elem = [(shape, s) for shape, s in jax.tree.leaves(
                pairs, is_leaf=lambda t: isinstance(t, tuple) and len(t) == 2
                and isinstance(t[0], tuple))
            if s is not None and shape[:1] == (m.num_cells,)]
    assert elem
    for shape, s in elem:
        assert s.device_set == devices, shape
        assert s.shard_shape(shape)[0] == m.num_cells // len(devices), shape


def test_sharded_vector_valued_space():
    m, space, plan = _setup(6, value_size=2)
    form = wf.elasticity(1.2, 0.6)
    op = matfree_operator(plan, form)
    sop = op.sharded()
    x = jnp.asarray(RNG.standard_normal(op.shape[0]))
    np.testing.assert_allclose(
        np.asarray(sop.matvec(x)), np.asarray(op.matvec(x)), atol=1e-12)
    np.testing.assert_allclose(
        np.asarray(sop.diagonal()), np.asarray(op.diagonal()), atol=1e-12)


# ---------------------------------------------------------------------------
# sharded Krylov solve: one CG spans all devices, ≤1e-12 vs single-device
# ---------------------------------------------------------------------------

def _poisson_setup(n=4):
    m, space, plan = _setup(n, cube=True)
    bc = DirichletCondenser(plan.static.mat_routing, space.boundary_dofs())
    rho = jnp.asarray(RNG.uniform(0.5, 2.0, m.num_cells))
    b = bc.project_residual(jnp.asarray(RNG.standard_normal(plan.static.num_dofs)))
    return plan, bc, rho, b


def test_sharded_solve_matches_single_device():
    plan, bc, rho, b = _poisson_setup()
    form = wf.diffusion(rho) + 0.3 * wf.mass()
    u0 = matfree_solve(matfree_operator(plan, form).condensed(bc), b, tol=1e-12)
    u1 = matfree_solve(
        matfree_operator(plan, form).sharded().condensed(bc), b, tol=1e-12)
    np.testing.assert_allclose(np.asarray(u1), np.asarray(u0), atol=1e-12)


def test_sharded_grads_match_assembled_adjoint():
    """d(loss)/d(rho) through the sharded matfree_solve (custom-vjp adjoint
    solve + operator-cotangent pullback, all sharded) vs the assembled
    sparse_solve adjoint — ≤1e-12."""
    plan, bc, rho, b = _poisson_setup(3)

    def loss_sharded(r):
        op = matfree_operator(plan, wf.diffusion(r)).sharded().condensed(bc)
        return jnp.sum(matfree_solve(op, b, tol=1e-13) ** 2)

    def loss_assembled(r):
        k = bc.apply_matrix_only(assemble(plan, wf.diffusion(r)))
        return jnp.sum(sparse_solve(k, b, "cg", 1e-13, 1e-13, 10000) ** 2)

    g0 = jax.grad(loss_assembled)(rho)
    g1 = jax.grad(loss_sharded)(rho)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g0), atol=1e-12)


def test_sharded_reapply_hits_compiled_executable():
    """New coefficient values on the same signature must NOT retrace."""
    from repro.core import n_matfree_traces

    plan, bc, rho, b = _poisson_setup(3)
    sop = matfree_operator(plan, wf.diffusion(rho)).sharded()
    x = jnp.asarray(RNG.standard_normal(sop.shape[0]))
    sop.matvec(x)
    before = n_matfree_traces()
    sop2 = matfree_operator(plan, wf.diffusion(rho * 2.0)).sharded()
    y2 = sop2.matvec(x)
    assert n_matfree_traces() == before
    np.testing.assert_allclose(
        np.asarray(y2), 2.0 * np.asarray(sop.matvec(x)), atol=1e-12)


# ---------------------------------------------------------------------------
# consumer dispatch
# ---------------------------------------------------------------------------

def test_registry_dispatch_parity():
    """The sharded matrix-free apply, its residual, transpose apply and
    diagonal against the CSR's."""
    m, space, plan = _setup(8)
    form = wf.diffusion(1.0) + 0.2 * wf.mass()
    k = assemble(plan, form)
    op = matfree_operator(plan, form)
    x = jnp.asarray(RNG.standard_normal(k.shape[0]))
    f = jnp.asarray(RNG.standard_normal(k.shape[0]))
    ref = np.asarray(k.matvec(x))
    sop = op.sharded()
    np.testing.assert_allclose(np.asarray(sop.matvec(x)), ref, atol=1e-12)
    np.testing.assert_allclose(
        np.asarray(sop.matvec(x) - f), ref - np.asarray(f), atol=1e-12)
    np.testing.assert_allclose(np.asarray(sop.rmatvec(x)),
                               np.asarray(k.rmatvec(x)), atol=1e-12)
    np.testing.assert_allclose(np.asarray(sop.diagonal()),
                               np.asarray(k.diagonal()), atol=1e-12)


def test_poisson_problem_sharded_backend():
    p = PoissonProblem(unit_cube_tet(4))
    r0 = p.solve(backend="matfree", tol=1e-12)
    r1 = p.solve(backend="matfree_sharded", tol=1e-12)
    assert r1.converged
    np.testing.assert_allclose(np.asarray(r1.u), np.asarray(r0.u), atol=1e-12)


def test_theta_integrator_sharded_backend():
    m, space, plan = _setup(8)
    asm = GalerkinAssembler(space)
    bc = DirichletCondenser(asm, space.boundary_dofs())
    u0 = bc.project_residual(jnp.asarray(RNG.standard_normal(space.num_dofs)))
    kw = dict(dt=0.01, theta=CRANK_NICOLSON, bc=bc, tol=1e-12)
    t0 = ThetaIntegrator.from_form(asm, wf.diffusion(1.0),
                                   backend="matfree", **kw)
    t1 = ThetaIntegrator.from_form(asm, wf.diffusion(1.0),
                                   backend="matfree_sharded", **kw)
    assert isinstance(t1.lhs_full, ShardedMatFreeOperator)
    np.testing.assert_allclose(
        np.asarray(t1.rollout(u0, 5)), np.asarray(t0.rollout(u0, 5)),
        atol=1e-12)
