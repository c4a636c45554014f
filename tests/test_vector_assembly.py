"""Vector-valued spaces through the vertex pattern: the block Reduce
(``routing.BlockRouting``), the vertex-block matvec (``sparse.BlockLayout``)
and 3D elasticity against the benchmark's plain reference.

Acceptance-critical properties:

* the block Reduce gives the DoF pattern of a routing of each scalar entry,
  with values equal to a relative 1e-14, in every Reduce mode;
* the vertex-block matvec, banded (shifted slices of ``x`` and a gathered
  remainder) where the vertex pattern is and through its column table
  where it is not, equals the column-table matvec of the same values;
* ``ElasticityProblem`` assembles the reference's K and F and solves them
  on the hollow cube; a 2D space (value_size 2) matches a NumPy assembly;
* scalar spaces keep their routing and row layouts.
"""

import dataclasses
import gc
import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import scipy.sparse as sp

from repro import telemetry
from repro.core import (
    FunctionSpace,
    SolverSpec,
    assemble,
    assemble_batched,
    assemble_rhs,
    build_plan,
    hollow_cube_tet,
    rectangle_tri,
    sparse_solve,
    unit_cube_tet,
    unit_square_tri,
    weakform as wf,
)
from repro.core import forms, sparse
from repro.core.assembly import reduce_matrix
from repro.core.mesh import Mesh, element_for_mesh
from repro.core.routing import BlockRouting, MatrixRouting, build_matrix_routing
from repro.fem.tensormesh import ElasticityProblem

_REF = Path(__file__).resolve().parents[1] / "chipbench" / "reference" / \
    "elasticity3d_p1.py"
LAM, MU = 0.7, 1.3
CG = SolverSpec(method="cg", precond="jacobi", tol=1e-10, atol=0.0,
                maxiter=5000)


def _reference():
    spec = importlib.util.spec_from_file_location("elasticity3d_p1", _REF)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _space(mesh, value_size):
    return FunctionSpace(mesh, element_for_mesh(mesh), value_size=value_size)


def _renumbered(mesh, seed=0):
    """The mesh with its vertices numbered at random: a pattern with many
    column offsets, so a scalar operator takes the column table."""
    perm = np.random.default_rng(seed).permutation(mesh.num_vertices)
    inv = np.empty_like(perm)
    inv[perm] = np.arange(perm.size)
    return Mesh(mesh.points[perm], inv[mesh.cells], mesh.cell_type)


SPACES = {"tet3": (hollow_cube_tet(4), 3), "tri2": (rectangle_tri(5, 3), 2)}


@pytest.mark.parametrize("mode", ["direct", "sorted", "table"])
@pytest.mark.parametrize("which", sorted(SPACES))
def test_block_reduce_matches_dof_routing(which, mode):
    mesh, vs = SPACES[which]
    space = _space(mesh, vs)
    plan = build_plan(space, reduce_mode=mode)
    r = plan.static.mat_routing
    assert isinstance(r, BlockRouting) and r.block == vs
    dof = build_matrix_routing(space.cell_dofs, None, space.num_dofs)
    for name in ("indptr", "indices", "row_of_nnz", "diag_pos",
                 "seg_ids_unsorted"):
        np.testing.assert_array_equal(getattr(r, name), getattr(dof, name))
    assert (r.num_dofs, r.nnz) == (dof.num_dofs, dof.nnz)
    scale = jnp.asarray(np.random.default_rng(1).uniform(0.5, 2.0,
                                                         mesh.num_cells))
    k = assemble(plan, wf.elasticity(LAM, MU, scale=scale))
    k_local = forms.elasticity(plan.context(), LAM, MU, scale)
    want = np.asarray(reduce_matrix(k_local, dof, "direct"))
    np.testing.assert_allclose(np.asarray(k.vals), want, rtol=0,
                               atol=1e-14 * np.abs(want).max())
    assert k.block == vs


def test_elasticity_3d_matches_reference():
    ref = _reference()
    n = 6
    prob = ElasticityProblem(hollow_cube_tet(n), e_mod=1.0, nu=0.3)
    points, cells = ref.mesh(n)
    np.testing.assert_array_equal(prob.mesh.cells, cells)
    np.testing.assert_allclose(prob.mesh.points, points, rtol=0, atol=1e-15)
    b = np.array([0.3, -0.8, 0.5])
    kc, fc = prob.assemble(b)
    k_ref, f_ref = ref.stiffness(n, 1.0, 0.3), ref.load(n, b)
    diff = (kc.to_scipy() - k_ref.tocsr()).tocsr()
    assert abs(diff).max() <= 1e-14 * abs(k_ref).max()
    np.testing.assert_allclose(np.asarray(fc), f_ref, rtol=0,
                               atol=1e-14 * np.abs(f_ref).max())
    res, info = prob.solve(body_force=b, spec=CG, backend="csr",
                           return_info=True)
    assert bool(info.converged)
    assert ref.relres(k_ref, f_ref, np.asarray(res.u)) < 2e-10


def _numpy_elasticity_2d(mesh, lam, mu, f):
    """Plane elasticity with P1 triangles by NumPy and SciPy: element blocks
    ``A (λ g_a g_bᵀ + μ g_b g_aᵀ + μ (g_a·g_b) I)`` and load ``A/3 · f``."""
    x = mesh.points[mesh.cells]
    jac = np.stack([x[:, 1] - x[:, 0], x[:, 2] - x[:, 0]], axis=1)
    g1 = np.swapaxes(np.linalg.inv(jac), 1, 2)
    grad = np.concatenate([-g1.sum(axis=1, keepdims=True), g1], axis=1)
    area = np.abs(np.linalg.det(jac)) / 2
    outer = np.einsum("eai,ebj->eabij", grad, grad)
    dot = np.einsum("eai,ebi->eab", grad, grad)
    blocks = area[:, None, None, None, None] * (
        lam * outer + mu * np.swapaxes(outer, -1, -2)
        + mu * dot[..., None, None] * np.eye(2))
    dof = 2 * mesh.cells[:, :, None] + np.arange(2)            # (E, 3, 2)
    rows = np.broadcast_to(dof[:, :, None, :, None], blocks.shape)
    cols = np.broadcast_to(dof[:, None, :, None, :], blocks.shape)
    n = 2 * mesh.num_vertices
    k = sp.coo_matrix((blocks.ravel(), (rows.ravel(), cols.ravel())),
                      shape=(n, n)).tocsr()
    load = np.zeros(n)
    np.add.at(load, dof.reshape(-1),
              np.repeat(area / 3, 6) * np.tile(f, 3 * mesh.num_cells))
    return k, load


def test_elasticity_2d_matches_numpy_assembly():
    mesh = rectangle_tri(6, 4)
    space = _space(mesh, 2)
    plan = build_plan(space)
    f = np.array([0.4, -1.1])
    k = assemble(plan, wf.elasticity(LAM, MU)).to_scipy()
    load = np.asarray(assemble_rhs(plan, wf.source(jnp.asarray(f))))
    k_ref, load_ref = _numpy_elasticity_2d(mesh, LAM, MU, f)
    assert abs(k - k_ref).max() <= 1e-14 * abs(k_ref).max()
    np.testing.assert_allclose(load, load_ref, rtol=0,
                               atol=1e-14 * np.abs(load_ref).max())


# vertex patterns by the layout they take: banded with pairs off the band
# (the hollow cube's numbering skips the cavity), banded with none, and not
# banded (the column table); the space has one component per dimension, as
# elasticity's has
BLOCK_PATTERNS = {
    "hollow12": (hollow_cube_tet(12), "remainder"),
    "cube6": (unit_cube_tet(6), "band"),
    "cube3": (unit_cube_tet(3), "band"),
    "square6_2d": (unit_square_tri(6), "band"),
    "renumbered": (_renumbered(hollow_cube_tet(6)), "columns"),
}


@pytest.mark.parametrize("which", sorted(BLOCK_PATTERNS))
def test_vertex_block_matvec_matches_column_table(monkeypatch, which):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh, form = BLOCK_PATTERNS[which]
    space = _space(mesh, mesh.dim)
    plan = build_plan(space)
    vals = jnp.asarray(np.random.default_rng(2).standard_normal(plan.nnz))
    k = plan.csr(vals)
    x = jnp.asarray(np.random.default_rng(3).standard_normal(k.shape[0]))
    layout = sparse._row_layout(k)
    if form == "columns":
        assert isinstance(layout, sparse.BlockLayout)
    else:
        assert isinstance(layout, sparse.BandLayout)
        assert (layout.rows is not None) == (form == "remainder")
    # the vertex-block column table of the same pattern, and the scalar
    # operator's own row layout
    with monkeypatch.context() as m:
        m.setattr(sparse, "_vertex_bands", lambda *_: None)
        columns = sparse._block_layout(k)
    assert isinstance(columns, sparse.BlockLayout)
    want = np.asarray(sparse._block_matvec(vals, x, columns))
    scalar = dataclasses.replace(k, block=1,
                                 row_of_nnz=np.array(k.row_of_nnz))
    scalar_layout = sparse._row_layout(scalar)
    if form == "columns":
        assert scalar_layout.cols is not None
        assert scalar_layout.offsets is None
    scale = np.abs(want).max()
    for got in (k.matvec(x), k.loop_matvec()(x),
                jax.jit(lambda v, y: dataclasses.replace(k, vals=v)
                        .matvec(y))(vals, x),
                sparse.row_layout_matvec(vals, x, scalar_layout)):
        np.testing.assert_allclose(np.asarray(got), want, rtol=0,
                                   atol=1e-13 * scale)
    np.testing.assert_allclose(want, k.to_scipy() @ np.asarray(x), rtol=0,
                               atol=1e-13 * scale)


def test_vertex_bands_hold_every_block_once():
    """Each vertex pair of the hollow cube's pattern sits in the band slot
    of its offset or in the remainder, not both; the band's offsets are
    those that at least 1% of the vertex rows hold."""
    plan = build_plan(_space(hollow_cube_tet(12), 3))
    k = plan.csr(jnp.zeros(plan.nnz))
    layout = sparse._row_layout(k)
    n_v = k.shape[0] // 3
    dof_rows, dof_cols = np.asarray(k.row_of_nnz), np.asarray(k.indices)
    first = (dof_rows % 3 == 0) & (dof_cols % 3 == 0)
    rows, cols = dof_rows[first] // 3, dof_cols[first] // 3
    held, count = np.unique(cols - rows, return_counts=True)
    assert layout.offsets == tuple(held[count >= 0.01 * n_v])
    band = {(i, c) for i, c in zip(rows.tolist(), cols.tolist())
            if c - i in layout.offsets}
    rem = [(i, c) for i, col in zip(np.asarray(layout.rows).tolist(),
                                    np.asarray(layout.rem_cols).T.tolist())
           for c in col if c != i]
    assert len(rem) == len(set(rem)) and band.isdisjoint(rem)
    assert band.union(rem) == set(zip(rows.tolist(), cols.tolist()))
    assert int(np.sum(np.asarray(layout.slots) >= 0)) == len(band)


@pytest.mark.parametrize("mesh,kind,width", [
    (unit_square_tri(6), "diagonals", 9),
    (_renumbered(unit_square_tri(6)), "columns", None),
])
def test_scalar_plan_keeps_its_routing_and_layout(monkeypatch, mesh, kind,
                                                  width):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    space = _space(mesh, 1)
    plan = build_plan(space)
    r = plan.static.mat_routing
    assert type(r) is MatrixRouting
    dof = build_matrix_routing(space.cell_dofs, None, space.num_dofs)
    for f in dataclasses.fields(MatrixRouting):
        np.testing.assert_array_equal(getattr(r, f.name), getattr(dof, f.name))
    k = assemble(plan, wf.diffusion())
    assert k.block == 1
    telemetry.enable()
    try:
        layout = sparse._row_layout(k)
        gauges = telemetry.snapshot()["gauges"]
    finally:
        telemetry.disable()
        telemetry.reset()
    assert isinstance(layout, sparse.RowLayout)
    assert (layout.offsets is not None) == (kind == "diagonals")
    rows = layout.table.shape
    assert gauges == {**gauges, f"matvec_gathers{{kind={kind}}}":
                      rows[0] * rows[1]}
    if width is not None:
        assert len(layout.offsets) == width


@pytest.mark.parametrize("which", ["hollow12", "renumbered"])
def test_vertex_blocks_gauge_counts_vertex_rows(which):
    mesh, form = BLOCK_PATTERNS[which]
    prob = ElasticityProblem(mesh)
    k, _ = prob.assemble()
    telemetry.enable()
    try:
        k.matvec(jnp.ones(k.shape[0]))
        gauges = telemetry.snapshot()["gauges"]
    finally:
        telemetry.disable()
        telemetry.reset()
    if form == "columns":
        # a vertex of the Kuhn cube mesh shares cells with at most 14 others
        assert gauges["matvec_gathers{kind=vertex_blocks}"] == \
            15 * prob.mesh.num_vertices
    else:
        # 448 pairs off the 31 band slots, on 144 vertex rows, at most 4 a
        # row: the remainder's (4, 144) table
        assert gauges["matvec_gathers{kind=vertex_bands}"] == 4 * 144
        assert "matvec_gathers{kind=vertex_blocks}" not in gauges


def test_elasticity_problem_solves_with_cg_and_spans():
    prob = ElasticityProblem(hollow_cube_tet(6))
    assert prob._spec(None, None, None, "solve").method == "cg"
    telemetry.enable()
    try:
        prob.solve(body_force=jnp.asarray([0.0, 0.0, 1.0]), backend="csr")
        log = [s for s in telemetry.span_log() if s["name"] != "host.gc"]
    finally:
        telemetry.disable()
        telemetry.reset()
    root = [s for s in log if s["name"] == "tg.solve"]
    assert len(root) == 1 and root[0]["parent_id"] is None

    def children(name):
        parent = [s for s in log if s["name"] == name][0]
        return [s["name"] for s in log if s["parent_id"] == parent["span_id"]]

    assert children("tg.solve") == ["tg.assemble", "tg.solve.krylov",
                                    "tg.solve.wait"]
    assert children("tg.assemble") == ["tg.assemble.matrix",
                                       "tg.assemble.vector", "tg.condense"]


def test_block_family_and_adjoint():
    mesh = rectangle_tri(4, 4)
    space = _space(mesh, 2)
    plan = build_plan(space)
    scales = jnp.asarray(np.random.default_rng(4).uniform(0.5, 2.0,
                                                          (3, mesh.num_cells)))
    kb = assemble_batched(plan, wf.elasticity(LAM, MU, scale=scales[0]),
                          leaves_batch=(None, None, scales, None))
    x = jnp.asarray(np.random.default_rng(5).standard_normal(
        (3, space.num_dofs)))
    y = kb.matvec(x)
    for i in range(3):
        ki = assemble(plan, wf.elasticity(LAM, MU, scale=scales[i]))
        assert ki.block == kb[i].block == 2
        np.testing.assert_allclose(np.asarray(y[i]),
                                   ki.to_scipy() @ np.asarray(x[i]),
                                   rtol=1e-12, atol=1e-12)
    # the adjoint's cotangent keeps the operator's block structure
    from repro.core import DirichletCondenser

    bc = DirichletCondenser(plan.static.mat_routing, space.boundary_dofs())
    f = bc.project_residual(jnp.ones(space.num_dofs))

    def loss(vals, block):
        k = dataclasses.replace(kb[0], vals=vals, block=block)
        return jnp.sum(sparse_solve(bc.apply_matrix_only(k), f, CG) ** 2)

    vals = kb[0].vals
    g_block = jax.grad(loss)(vals, 2)
    g_scalar = jax.grad(loss)(vals, 1)
    np.testing.assert_allclose(np.asarray(g_block), np.asarray(g_scalar),
                               rtol=1e-8, atol=1e-12)


def test_cached_diagonal_lives_as_long_as_its_values():
    k = assemble(build_plan(_space(unit_square_tri(4), 1)), wf.diffusion())
    vals = k.vals + 0.0
    op = dataclasses.replace(k, vals=vals)
    before = len(sparse._DIAGONALS)
    np.testing.assert_array_equal(np.asarray(sparse.cached_diagonal(op)),
                                  np.asarray(op.diagonal()))
    assert len(sparse._DIAGONALS) == before + 1
    del op, vals
    gc.collect()
    assert len(sparse._DIAGONALS) == before
