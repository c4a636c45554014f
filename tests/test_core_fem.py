"""Unit + integration tests for the TensorGalerkin core (assembly, solvers, BCs)."""

import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest
import scipy.sparse as sp
import scipy.sparse.linalg as spla

from repro.core import (
    CSR,
    DirichletCondenser,
    FacetAssembler,
    FunctionSpace,
    GalerkinAssembler,
    cg,
    bicgstab,
    csr_to_ell,
    disk_tri,
    hollow_cube_tet,
    jacobi_preconditioner,
    l_shape_tri,
    rectangle_tri,
    sparse_solve,
    unit_cube_tet,
    unit_square_tri,
)
from repro.core import weakform as wf
from repro.core.elements import get_element
from repro.core.mesh import element_for_mesh
from repro.core.quadrature import triangle_rule, tetrahedron_rule, quad_rule


# ---------------------------------------------------------------------------
# quadrature + elements
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("order", [1, 2, 3, 4])
def test_triangle_rule_exactness(order):
    pts, w = triangle_rule(order)
    # integrate x^p y^q over unit triangle: p!q!/(p+q+2)!
    import math

    for p in range(order + 1):
        for q in range(order + 1 - p):
            exact = math.factorial(p) * math.factorial(q) / math.factorial(p + q + 2)
            approx = np.sum(w * pts[:, 0] ** p * pts[:, 1] ** q)
            np.testing.assert_allclose(approx, exact, rtol=1e-12, err_msg=f"{p},{q}")


@pytest.mark.parametrize("order", [1, 2, 3])
def test_tet_rule_exactness(order):
    import math

    pts, w = tetrahedron_rule(order)
    for p in range(order + 1):
        for q in range(order + 1 - p):
            for r in range(order + 1 - p - q):
                exact = (
                    math.factorial(p) * math.factorial(q) * math.factorial(r)
                    / math.factorial(p + q + r + 3)
                )
                approx = np.sum(w * pts[:, 0] ** p * pts[:, 1] ** q * pts[:, 2] ** r)
                np.testing.assert_allclose(approx, exact, rtol=1e-11)


@pytest.mark.parametrize(
    "name", ["P1_tri", "P2_tri", "P1_tet", "Q1_quad", "Q1_hex", "P1_line"]
)
def test_partition_of_unity(name):
    el = get_element(name)
    pts, _ = el.default_rule()
    vals = el.tabulate(pts)
    np.testing.assert_allclose(vals.sum(axis=1), 1.0, atol=1e-12)
    grads = el.tabulate_grad(pts)
    np.testing.assert_allclose(grads.sum(axis=1), 0.0, atol=1e-12)


def test_element_nodal_property():
    # φ_a(x̂_b) = δ_ab at the element's nodes
    el = get_element("P1_tri")
    nodes = np.array([[0.0, 0.0], [1.0, 0.0], [0.0, 1.0]])
    np.testing.assert_allclose(el.tabulate(nodes), np.eye(3), atol=1e-14)


# ---------------------------------------------------------------------------
# assembly correctness
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mesh_fn", [unit_square_tri, l_shape_tri])
def test_assembly_matches_loop_baseline(mesh_fn):
    m = mesh_fn(5)
    space = FunctionSpace(m, element_for_mesh(m))
    asm = GalerkinAssembler(space)
    k_mr = np.asarray(asm.assemble_stiffness().to_dense())
    k_loop = asm.assemble_stiffness_loop()
    np.testing.assert_allclose(k_mr, k_loop, atol=1e-12)


def test_assembly_scatter_baseline_agrees():
    m = unit_square_tri(6)
    space = FunctionSpace(m, element_for_mesh(m))
    asm = GalerkinAssembler(space)
    k1 = np.asarray(asm.assemble_stiffness().to_dense())
    k2 = np.asarray(asm.assemble_stiffness_scatter())
    np.testing.assert_allclose(k1, k2, atol=1e-12)


def test_reduce_modes_agree():
    m = unit_square_tri(7)
    space = FunctionSpace(m, element_for_mesh(m))
    a_sorted = GalerkinAssembler(space, reduce_mode="sorted")
    for mode in ("direct", "table"):
        a = GalerkinAssembler(space, reduce_mode=mode)
        np.testing.assert_allclose(
            np.asarray(a_sorted.assemble_stiffness().vals),
            np.asarray(a.assemble_stiffness().vals),
            atol=1e-13,
        )
        np.testing.assert_allclose(
            np.asarray(a_sorted.assemble_rhs(wf.source(1.0))),
            np.asarray(a.assemble_rhs(wf.source(1.0))),
            atol=1e-13,
        )


@pytest.mark.parametrize("backend,mode", [("cpu", "direct"),
                                          ("tpu", "table")])
def test_default_reduce_mode_follows_the_platform(monkeypatch, backend, mode):
    """Plans built without a reduce_mode scatter-add on the CPU and gather
    through the static tables on a TPU, which has no float64 scatter-add."""
    from repro.core import assembly

    monkeypatch.setattr(assembly.jax, "default_backend", lambda: backend)
    m = unit_square_tri(3)
    space = FunctionSpace(m, element_for_mesh(m))
    assert assembly.build_plan(space).static.reduce_mode == mode
    assert GalerkinAssembler(space).reduce_mode == mode


@pytest.mark.parametrize("batch", [(), (3,)])
def test_table_segment_sum_matches_segment_sum(batch):
    """The scatter-free Reduce: gathering each segment's entries through a
    padded static table sums what segment_sum sums, empty segments
    included, over any leading batch axes."""
    from repro.core.routing import segment_table, table_segment_sum

    rng = np.random.default_rng(3)
    n_seg, size = 40, 300
    ids = rng.integers(0, n_seg - 5, size)           # last segments empty
    perm = np.argsort(ids, kind="stable")
    table = segment_table(perm, ids[perm], n_seg)
    assert table.shape == (np.bincount(ids).max(), n_seg)
    v = jnp.asarray(rng.standard_normal(batch + (size,)))
    want = np.stack([np.asarray(jax.ops.segment_sum(row, ids,
                                                    num_segments=n_seg))
                     for row in np.asarray(v).reshape(-1, size)])
    got = np.asarray(table_segment_sum(v, jnp.asarray(table)))
    np.testing.assert_allclose(got.reshape(-1, n_seg), want, atol=1e-13)


@pytest.mark.parametrize("mesh,diagonals", [
    (lambda: unit_square_tri(9), True),    # banded: 9 column offsets
    (lambda: disk_tri(4), False),          # unstructured numbering
])
def test_csr_matvec_row_layouts_match_dense(monkeypatch, mesh, diagonals):
    """On a TPU CSR.matvec reads rows as shifted diagonals on a banded
    pattern and through a column table otherwise; both equal the dense
    product, for one vector and for a batch of operators."""
    from repro.core.sparse import _row_layout

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    m = mesh()
    space = FunctionSpace(m, element_for_mesh(m))
    k = GalerkinAssembler(space).assemble(wf.diffusion() + 0.5 * wf.mass())
    assert (_row_layout(k).offsets is not None) == diagonals
    rng = np.random.default_rng(5)
    x = rng.standard_normal(k.shape[0])
    np.testing.assert_allclose(np.asarray(k.matvec(jnp.asarray(x))),
                               np.asarray(k.to_dense()) @ x, atol=1e-12)
    vals = jnp.stack([k.vals, 2.0 * k.vals])
    xb = jnp.asarray(rng.standard_normal((2, k.shape[0])))
    got = jax.vmap(lambda v, xi: dataclasses.replace(k, vals=v).matvec(xi))(
        vals, xb)
    want = np.stack([np.asarray(k.to_dense()) @ np.asarray(xb[0]),
                     2.0 * np.asarray(k.to_dense()) @ np.asarray(xb[1])])
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-12)


def test_assembly_deterministic():
    m = unit_square_tri(9)
    space = FunctionSpace(m, element_for_mesh(m))
    asm = GalerkinAssembler(space)
    v1 = np.asarray(asm.assemble_stiffness().vals)
    v2 = np.asarray(asm.assemble_stiffness().vals)
    assert np.array_equal(v1, v2)  # bit-identical (paper's determinism claim)


def test_stiffness_symmetric_psd():
    m = unit_cube_tet(3)
    space = FunctionSpace(m, element_for_mesh(m))
    asm = GalerkinAssembler(space)
    k = np.asarray(asm.assemble_stiffness().to_dense())
    np.testing.assert_allclose(k, k.T, atol=1e-13)
    w = np.linalg.eigvalsh(k)
    assert w.min() > -1e-10  # PSD (singular until BCs applied)


def test_mass_matrix_total_volume():
    m = unit_square_tri(6)
    space = FunctionSpace(m, element_for_mesh(m))
    asm = GalerkinAssembler(space)
    mass = np.asarray(asm.assemble_mass().to_dense())
    np.testing.assert_allclose(mass.sum(), 1.0, rtol=1e-12)  # ∫∫ 1 = |Ω|


def test_load_vector_total_integral():
    m = unit_cube_tet(4)
    space = FunctionSpace(m, element_for_mesh(m))
    asm = GalerkinAssembler(space)
    f = asm.assemble_load(2.5)
    np.testing.assert_allclose(float(jnp.sum(f)), 2.5, rtol=1e-12)


def test_nodal_coefficient_interpolation():
    # ρ(x) = x+y nodal field must give same K as the callable version
    m = unit_square_tri(5)
    space = FunctionSpace(m, element_for_mesh(m))
    asm = GalerkinAssembler(space)
    k_callable = asm.assemble_stiffness(lambda x: x[..., 0] + x[..., 1])
    nodal = jnp.asarray(space.dof_points[:, 0] + space.dof_points[:, 1])
    k_nodal = asm.assemble_stiffness(nodal)
    np.testing.assert_allclose(
        np.asarray(k_callable.vals), np.asarray(k_nodal.vals), atol=1e-12
    )


def test_assembly_trace_is_o1_in_elements():
    """The paper's O(1)-graph property: jaxpr size independent of E."""
    sizes = []
    for n in (4, 16):
        m = unit_square_tri(n)
        space = FunctionSpace(m, element_for_mesh(m))
        asm = GalerkinAssembler(space)

        def assemble(coords, rho):
            ctx = asm.context(coords)
            from repro.core import forms
            from repro.core.assembly import reduce_matrix

            return reduce_matrix(forms.diffusion(ctx, rho), asm.mat_routing)

        jaxpr = jax.make_jaxpr(assemble)(asm.coords, jnp.ones(m.num_cells))
        sizes.append(len(jaxpr.jaxpr.eqns))
    assert sizes[0] == sizes[1], f"graph grew with E: {sizes}"


# ---------------------------------------------------------------------------
# solvers
# ---------------------------------------------------------------------------

def _poisson_system(n=8, dim=2):
    m = unit_square_tri(n) if dim == 2 else unit_cube_tet(n)
    space = FunctionSpace(m, element_for_mesh(m))
    asm = GalerkinAssembler(space)
    k = asm.assemble_stiffness()
    f = asm.assemble_load(1.0)
    bc = DirichletCondenser(asm, space.boundary_dofs())
    return bc.apply(k, f) + (space,)


@pytest.mark.parametrize("method", [cg, bicgstab])
def test_krylov_matches_scipy(method):
    k, f, _ = _poisson_system()
    x, info = method(k.matvec, f, m=jacobi_preconditioner(k), tol=1e-12)
    x_ref = spla.spsolve(k.to_scipy().tocsc(), np.asarray(f))
    np.testing.assert_allclose(np.asarray(x), x_ref, atol=1e-9)
    assert float(info.residual) < 1e-9


def test_solver_residual_meets_paper_tolerance():
    # Paper SM B.1.2: relative residual < 1e-10
    k, f, _ = _poisson_system(10)
    x, _ = bicgstab(k.matvec, f, m=jacobi_preconditioner(k), tol=1e-10)
    rel = float(jnp.linalg.norm(k.matvec(x) - f) / jnp.linalg.norm(f))
    assert rel < 1e-10


@pytest.mark.parametrize("method", [cg, bicgstab])
def test_krylov_program_holds_no_dot_general(method):
    """The inner products are products and sum reduces: XLA:TPU emulates a
    float64 ``dot_general`` with while loops over operand slices, which
    held most of a CG iteration on a v5e."""
    k, f, _ = _poisson_system()
    m = jacobi_preconditioner(k)
    text = jax.jit(lambda b: method(k.matvec, b, m=m)).lower(f).as_text()
    assert f.dtype == jnp.float64
    assert "dot_general" not in text
    assert "while" in text


def test_cg_matches_scipy_iteration_count():
    """Jacobi-CG on a 16² P1 Poisson matrix reaches a relative 1e-10 with
    a true residual within 2e-10, in as many iterations as SciPy's CG."""
    k, f, _ = _poisson_system(16)
    x, info = cg(k.matvec, f, m=jacobi_preconditioner(k), tol=1e-10,
                 atol=0.0)
    ks, fs = k.to_scipy(), np.asarray(f)
    true = np.linalg.norm(ks @ np.asarray(x) - fs) / np.linalg.norm(fs)
    steps = []
    _, status = spla.cg(ks, fs, rtol=1e-10, atol=0.0,
                        M=sp.diags(1.0 / ks.diagonal()),
                        callback=steps.append)
    assert bool(info.converged) and status == 0
    assert true <= 2e-10
    assert abs(int(info.iters) - len(steps)) <= 2


@pytest.mark.parametrize("n", [64, 80])
def test_cg_true_residual_meets_tolerance_in_float32(n):
    """CG folds its steps into the iterate and recomputes the residual, so
    a converged solve meets its tolerance in the true residual too.  In
    float32 on a 1-D Laplacian (‖A‖‖x‖/‖b‖ grows like n²) updating x in
    place lets the true residual stall above the recursive one — the
    effect emulated float64 has at 1M DOFs on a TPU."""
    main, off = np.full(n, 2.0, np.float32), np.full(n - 1, -1.0, np.float32)
    a = np.diag(main) + np.diag(off, 1) + np.diag(off, -1)
    b = np.sin(np.linspace(0.0, 3.0, n)).astype(np.float32) / n**2
    aj = jnp.asarray(a, jnp.float32)
    x, info = cg(lambda v: aj @ v, jnp.asarray(b), tol=1e-4, atol=0.0)
    true = np.linalg.norm(a.astype(np.float64) @ np.asarray(x, np.float64)
                          - b) / np.linalg.norm(b)
    assert bool(info.converged)
    assert true <= 1e-4


def test_ell_spmv_matches_csr():
    k, f, _ = _poisson_system(7)
    ell = csr_to_ell(k)
    x = jnp.asarray(np.random.default_rng(0).normal(size=f.shape))
    np.testing.assert_allclose(
        np.asarray(ell.matvec(x)), np.asarray(k.matvec(x)), atol=1e-12
    )


def test_csr_matmat_batched_rhs():
    k, f, _ = _poisson_system(6)
    rng = np.random.default_rng(1)
    xs = jnp.asarray(rng.normal(size=(f.shape[0], 5)))
    got = np.asarray(k.matmat(xs))
    want = k.to_scipy() @ np.asarray(xs)
    np.testing.assert_allclose(got, want, atol=1e-12)


def test_sparse_solve_adjoint_gradient():
    k, f, _ = _poisson_system(6)

    def loss_vals(vals):
        kv = CSR(vals, k.indptr, k.indices, k.row_of_nnz, k.shape, k.diag_pos)
        return jnp.sum(sparse_solve(kv, f, "cg", 1e-12, 1e-12) ** 2)

    def loss_rhs(b):
        return jnp.sum(sparse_solve(k, b, "cg", 1e-12, 1e-12) ** 2)

    g_vals = jax.grad(loss_vals)(k.vals)
    g_rhs = jax.grad(loss_rhs)(f)
    rng = np.random.default_rng(2)
    nz = np.nonzero(np.abs(np.asarray(g_vals)) > 1e-6)[0]
    for i in rng.choice(nz, 3, replace=False):
        eps = 1e-6
        fd = (loss_vals(k.vals.at[i].add(eps)) - loss_vals(k.vals.at[i].add(-eps))) / (2 * eps)
        np.testing.assert_allclose(float(g_vals[i]), float(fd), rtol=5e-3)
    i = int(np.argmax(np.abs(np.asarray(g_rhs))))
    eps = 1e-6
    fd = (loss_rhs(f.at[i].add(eps)) - loss_rhs(f.at[i].add(-eps))) / (2 * eps)
    np.testing.assert_allclose(float(g_rhs[i]), float(fd), rtol=1e-5)


# ---------------------------------------------------------------------------
# boundary conditions
# ---------------------------------------------------------------------------

def test_inhomogeneous_dirichlet_exact_linear():
    # u = x solves Laplace; impose u=x on boundary, solution must be exact.
    m = unit_square_tri(6)
    space = FunctionSpace(m, element_for_mesh(m))
    asm = GalerkinAssembler(space)
    k = asm.assemble_stiffness()
    f = jnp.zeros(space.num_dofs)
    bdofs = space.boundary_dofs()
    bvals = jnp.asarray(space.dof_points[bdofs, 0])
    bc = DirichletCondenser(asm, bdofs)
    kc, fc = bc.apply(k, f, bvals)
    u, _ = cg(kc.matvec, fc, m=jacobi_preconditioner(kc), tol=1e-13)
    np.testing.assert_allclose(np.asarray(u), space.dof_points[:, 0], atol=1e-10)


def test_mixed_bc_analytic_disk():
    """Robin BC du/dn + u = g chosen so u = x² + y² − r²/2·… — simpler:
    verify pure-Neumann compatibility instead: −Δu = 0, du/dn = cos θ on the
    unit-ish disk has u = x (up to constant); pin one DoF."""
    m = disk_tri(10, center=(0.0, 0.0), radius=1.0)
    space = FunctionSpace(m, element_for_mesh(m))
    asm = GalerkinAssembler(space)
    k = asm.assemble_stiffness()
    facets = m.boundary_facets()
    fa = FacetAssembler(space, facets, volume_routing=asm.mat_routing)
    # du/dn on r=1 for u=x is x/r = x
    g = fa.neumann_load(lambda x: x[..., 0])
    # Robin with α=1: du/dn + u = 2x on the boundary → same solution u = x
    k_r = fa.add_robin(k, 1.0)
    g2 = fa.neumann_load(lambda x: 2.0 * x[..., 0])
    u, info = bicgstab(k_r.matvec, g2, m=jacobi_preconditioner(k_r), tol=1e-12)
    exact = space.dof_points[:, 0]
    err = np.linalg.norm(np.asarray(u) - exact) / np.linalg.norm(exact)
    assert err < 5e-3, err  # O(h²) discretization error on the polygonal disk
    assert float(info.residual) < 1e-9


# ---------------------------------------------------------------------------
# convergence (validates paper's accuracy claims)
# ---------------------------------------------------------------------------

def _poisson_error(n, degree):
    m = unit_square_tri(n)
    el = get_element("P1_tri" if degree == 1 else "P2_tri")
    space = FunctionSpace(m, el)
    asm = GalerkinAssembler(space)
    f = lambda x: 2 * np.pi**2 * jnp.sin(np.pi * x[..., 0]) * jnp.sin(np.pi * x[..., 1])
    k = asm.assemble_stiffness()
    load = asm.assemble_load(f)
    bc = DirichletCondenser(asm, space.boundary_dofs())
    kc, fc = bc.apply(k, load)
    u, _ = cg(kc.matvec, fc, m=jacobi_preconditioner(kc), tol=1e-13)
    exact = np.sin(np.pi * space.dof_points[:, 0]) * np.sin(np.pi * space.dof_points[:, 1])
    # L2 norm via mass matrix
    mass = asm.assemble_mass() if degree == 1 else GalerkinAssembler(space).assemble_mass()
    e = jnp.asarray(np.asarray(u) - exact)
    return float(jnp.sqrt(e @ mass.matvec(e)))


def test_p1_h_convergence_rate():
    e1, e2 = _poisson_error(8, 1), _poisson_error(16, 1)
    rate = np.log2(e1 / e2)
    assert 1.8 < rate < 2.2, rate


def test_p2_more_accurate_than_p1():
    assert _poisson_error(8, 2) < 0.05 * _poisson_error(8, 1)


def test_3d_poisson_vs_scipy():
    m = unit_cube_tet(5)
    space = FunctionSpace(m, element_for_mesh(m))
    asm = GalerkinAssembler(space)
    k = asm.assemble_stiffness()
    f = asm.assemble_load(1.0)
    bc = DirichletCondenser(asm, space.boundary_dofs())
    kc, fc = bc.apply(k, f)
    u, _ = cg(kc.matvec, fc, m=jacobi_preconditioner(kc), tol=1e-12)
    u_ref = spla.spsolve(kc.to_scipy().tocsc(), np.asarray(fc))
    np.testing.assert_allclose(np.asarray(u), u_ref, atol=1e-9)


def test_elasticity_3d_hollow_cube_solves():
    m = hollow_cube_tet(6)
    space = FunctionSpace(m, element_for_mesh(m), value_size=3)
    asm = GalerkinAssembler(space)
    e_mod, nu = 1.0, 0.3
    lam = e_mod * nu / ((1 + nu) * (1 - 2 * nu))
    mu = e_mod / (2 * (1 + nu))
    k = asm.assemble_elasticity(lam, mu)
    f = asm.assemble_load(jnp.array([1.0, 1.0, 1.0]))
    bc = DirichletCondenser(asm, space.boundary_dofs())
    kc, fc = bc.apply(k, f)
    u, info = bicgstab(kc.matvec, fc, m=jacobi_preconditioner(kc), tol=1e-10)
    assert float(info.residual) < 1e-8
    assert float(jnp.abs(u).max()) > 0  # nontrivial interior displacement
    rel = float(jnp.linalg.norm(kc.matvec(u) - fc) / jnp.linalg.norm(fc))
    assert rel < 1e-8


def test_elasticity_rigid_body_nullspace():
    # translations are in the kernel of the unconstrained elasticity operator
    m = unit_square_tri(4)
    space = FunctionSpace(m, element_for_mesh(m), value_size=2)
    asm = GalerkinAssembler(space)
    k = asm.assemble_elasticity(1.0, 1.0)
    tx = jnp.zeros(space.num_dofs).at[0::2].set(1.0)
    np.testing.assert_allclose(np.asarray(k.matvec(tx)), 0.0, atol=1e-11)
