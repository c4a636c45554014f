"""Compile the main path for a described TPU v5e, without a chip.

The TPU compiler ships with jaxlib's TPU library and compiles for a
topology that is described, not attached: what it refuses here (a layout
that does not fit 16 GB of HBM, an op Mosaic cannot lower) would be refused
on the chip.  Nothing runs, so these tests say nothing about results or
speed.  The topology is described inside a module-scoped fixture only —
never at import — and every test is skipped where it cannot be described.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import (
    FunctionSpace,
    assemble,
    build_plan,
    hollow_cube_tet,
    matfree_operator,
    unit_square_tri,
    weakform as wf,
)
from repro.core.mesh import element_for_mesh
from repro.core.solvers import cg
from repro.core.sparse import (
    BandLayout,
    RowLayout,
    block_layout_matvec,
    row_layout_matvec,
)

HBM_BYTES = 16 * 2**30          # one v5e chip
MILLION_DOFS = 1_002_001        # unit_square_tri(1000)
MILLION_NNZ = 7_006_001
# column offsets of its stiffness pattern (lexicographic vertex numbering)
MILLION_OFFSETS = (-1002, -1001, -1000, -1, 0, 1, 1000, 1001, 1002)
MILLION_CELLS = 2_000_000
ELASTIC_CELLS = 1_959_552        # hollow_cube_tet(72): 1,038,426 DOFs
ELASTIC_VERTICES = 346_142
# its vertex pattern's band slots (the vertex offsets that at least 1% of
# the vertex rows hold) and the pairs off them: 5,244 rows, at most 4 a row
ELASTIC_OFFSETS = (-5403, -5402, -5330, -5329, -4178, -4177, -4143, -4142,
                   -4105, -4104, -74, -73, -39, -38, -1, 0, 1, 38, 39, 73,
                   74, 4104, 4105, 4142, 4143, 4177, 4178, 5329, 5330, 5402,
                   5403)
ELASTIC_REMAINDER = (4, 5_244)
# Map + Reduce and the matrix-free apply compile their routing tables in as
# constants, so compile time grows with the mesh: resolution 64 (8,192
# triangles) compiles in a few seconds, and the memory check below scales
# its temporaries up to the 2,000,000 triangles of the 1M-DOF mesh
SMALL_RESOLUTION = 64
SERVE_RESOLUTION = 256
SERVE_BATCH = 8


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described device writes a cache entry that cannot be
    # read back without the chip; keep the persistent cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    with pytest.MonkeyPatch.context() as mp:
        # plans and operators built here take the paths they take on the
        # chip (scatter-free Reduce and CSR matvec)
        mp.setattr(jax, "default_backend", lambda: "tpu")
        yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _shapes(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), jnp.asarray(a).dtype,
                                       sharding=sharding), tree)


def _device_bytes(compiled, scale: float = 1.0) -> float:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + scale * m.temp_size_in_bytes)


def _small_plan():
    mesh = unit_square_tri(SMALL_RESOLUTION)
    space = FunctionSpace(mesh, element_for_mesh(mesh, 1))
    plan = build_plan(space)
    assert plan.static.reduce_mode == "table"
    return mesh, space, plan


def _cg_solve(offsets):
    """CG + Jacobi, float64, on ``CSR.matvec``'s row-layout arithmetic: on
    the diagonals ``offsets`` of a banded pattern, or (``None``) on a
    column table.  Values and layout tables are arguments, as they are
    operands of the eager solve."""

    def solve(vals, table, cols, diag, b):
        rl = (RowLayout(table, cols=cols) if offsets is None
              else RowLayout(table, offsets=offsets))
        return cg(lambda x: row_layout_matvec(vals, x, rl), b,
                  m=lambda r: r / diag, tol=1e-10, atol=0.0, maxiter=10000)

    return solve


@pytest.mark.parametrize("layout", ["diagonals", "columns"])
def test_csr_cg_solve_compiles_at_a_million_dofs(one_chip, layout):
    """The solve of chip_smoke.py: CG + Jacobi with the ``csr`` matvec
    (``CSR.matvec``'s row-layout arithmetic, on the diagonals that the
    structured mesh has and on the column table of a general pattern),
    float64, 1,002,001 DOFs."""
    n, nnz, width = MILLION_DOFS, MILLION_NNZ, len(MILLION_OFFSETS)
    solve = _cg_solve(MILLION_OFFSETS if layout == "diagonals" else None)
    args = [((nnz,), jnp.float64), ((width, n), jnp.int32),
            ((width, n), jnp.int32), ((n,), jnp.float64), ((n,), jnp.float64)]
    compiled = jax.jit(solve).lower(
        *(jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in args)
    ).compile()
    assert compiled.memory_analysis().argument_size_in_bytes >= nnz * 8
    assert _device_bytes(compiled) < HBM_BYTES


@pytest.mark.parametrize("batch", [None, SERVE_BATCH])
def test_cg_program_holds_one_while_loop(one_chip, batch):
    """CG with the 9-offset ``csr`` matvec, float64, at SMALL_RESOLUTION,
    alone and vmapped over a batch of operators as the service solves: the
    compiled program holds its Krylov loop and no other.  A float64
    ``dot_general`` would add five while loops over operand slices."""
    r = SMALL_RESOLUTION
    n, width = (r + 1) ** 2, len(MILLION_OFFSETS)
    solve = _cg_solve((-(r + 2), -(r + 1), -r, -1, 0, 1, r, r + 1, r + 2))
    lead = () if batch is None else (batch,)
    args = [(lead + (width * n,), jnp.float64), ((width, n), jnp.int32),
            ((width, n), jnp.int32), (lead + (n,), jnp.float64),
            (lead + (n,), jnp.float64)]
    if batch is not None:
        solve = jax.vmap(solve, in_axes=(0, None, None, 0, 0))
    compiled = jax.jit(solve).lower(
        *(jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in args)
    ).compile()
    assert compiled.as_text().count(" while(") == 1


def test_diffusion_assembly_compiles_and_fits(one_chip):
    """Map (geometry + diffusion contraction) + Reduce, float64, at
    SMALL_RESOLUTION; the temporaries scaled to 2M triangles must fit one
    chip (a float64 einsum here once needed 32 GB at that size)."""
    mesh, _, plan = _small_plan()
    rho = jnp.ones(mesh.num_cells)
    compiled = jax.jit(
        lambda p, r: assemble(p, wf.diffusion(r)).vals
    ).lower(_shapes(plan, one_chip), _shapes(rho, one_chip)).compile()
    scale = MILLION_CELLS / mesh.num_cells
    assert _device_bytes(compiled, scale) < HBM_BYTES


def _elastic_plan():
    mesh = hollow_cube_tet(8)
    plan = build_plan(FunctionSpace(mesh, element_for_mesh(mesh),
                                    value_size=3))
    assert plan.static.reduce_mode == "table"
    return plan


def test_elasticity_assembly_holds_no_dot_general(one_chip):
    """Map (geometry + elasticity blocks) + the vertex-pattern Reduce of a
    vector-valued space, float64, on the hollow cube at 8 cells a side: no
    float64 ``dot_general``, which XLA:TPU emulates with while loops over
    operand slices."""
    lowered = jax.jit(
        lambda p: assemble(p, wf.elasticity(0.6, 0.4)).vals
    ).lower(_shapes(_elastic_plan(), one_chip))
    assert "dot_general" not in lowered.as_text()
    text = lowered.compile().as_text()
    assert " dot(" not in text and "convolution(" not in text


def test_elasticity_map_reduce_fits_at_a_million_dofs(one_chip):
    """The same Map + Reduce at the sizes of 72 cells a side (1,959,552
    tetrahedra, 5,035,286 vertex pairs, 45,317,574 stored entries), its
    tables as operands as the assembly passes them: it fits one chip (a
    reshape that merged the cells with a tiny axis once needed 36 GB)."""
    import types

    from repro.core import forms
    from repro.core.assembly import _reduce_blocks, geometry_context

    st = _elastic_plan().static
    cells, verts, pairs, nnz = ELASTIC_CELLS, 346_142, 5_035_286, 45_317_574
    routing = types.SimpleNamespace(block=3, cell_vertices=4,
                                    vertex=types.SimpleNamespace(nnz=pairs))

    def map_reduce(coords, diag, off, order):
        ctx = geometry_context(coords, st.geo_phi, st.geo_grad, st.phi,
                               st.gradhat, st.w)
        return _reduce_blocks(forms.elasticity(ctx, 0.6, 0.4), routing,
                              "table", (diag, off, order))

    args = [((cells, 4, 3), jnp.float64), ((24, verts), jnp.int32),
            ((6, pairs - verts), jnp.int32), ((nnz,), jnp.int32)]
    compiled = jax.jit(map_reduce).lower(
        *(jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in args)
    ).compile()
    assert _device_bytes(compiled) < HBM_BYTES


def _gather_sizes(hlo: str) -> list[int]:
    """Elements of the output of each gather in a compiled program."""
    return [int(np.prod([int(d) for d in dims.split(",") if d]))
            for dims in re.findall(r"= [a-z0-9]+\[([0-9,]*)\]\S* gather\(",
                                   hlo)]


def test_elasticity_cg_compiles_on_the_vertex_bands(one_chip):
    """The elasticity solve's Krylov loop at 72 cells a side: CG + Jacobi
    on the vertex-band matvec (``CSR.loop_matvec``), float64, 1,038,426
    DOFs, the laid-out blocks and the remainder's tables as arguments.  It
    fits one chip, holds one ``while``, and reads ``x`` through no gather
    as large as the (15, n_v) column table's."""
    n_v, (k_r, n_r) = ELASTIC_VERTICES, ELASTIC_REMAINDER
    n, slots = 3 * n_v, len(ELASTIC_OFFSETS)

    def solve(band, sums, rem, rem_cols, rows, diag, b):
        layout = BandLayout(None, None, None, rem_cols, rows, ELASTIC_OFFSETS)
        return cg(lambda x: block_layout_matvec((band, sums, rem), x, layout),
                  b, m=lambda r: r / diag, tol=1e-10, atol=0.0, maxiter=5000)

    args = [((3, 3, slots, n_v), jnp.float64), ((3, 3, n_v), jnp.float64),
            ((3, 3, k_r, n_r), jnp.float64), ((k_r, n_r), jnp.int32),
            ((n_r,), jnp.int32), ((n,), jnp.float64), ((n,), jnp.float64)]
    compiled = jax.jit(solve).lower(
        *(jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in args)
    ).compile()
    assert _device_bytes(compiled) < HBM_BYTES
    text = compiled.as_text()
    assert text.count(" while(") == 1
    gathers = _gather_sizes(text)
    assert gathers and max(gathers) < 15 * n_v


def test_matfree_apply_compiles_and_fits(one_chip):
    """The matrix-free diffusion apply (gather → per-element action →
    scatter-Reduce), float64, at SMALL_RESOLUTION, scaled as above."""
    mesh, space, plan = _small_plan()
    op = matfree_operator(plan, wf.diffusion(jnp.ones(mesh.num_cells)))
    x = jnp.ones(space.num_dofs)
    compiled = jax.jit(lambda o, v: o.matvec(v)).lower(
        _shapes(op, one_chip), _shapes(x, one_chip)).compile()
    scale = MILLION_CELLS / mesh.num_cells
    assert _device_bytes(compiled, scale) < HBM_BYTES


def test_serve_batched_solve_compiles(one_chip):
    """The serve tier's executable for one admission class: batched
    assembly + condensation + vmapped CG over 8 resolution-256 requests."""
    from repro import serve
    from repro.serve.batching import admission_key
    from repro.serve.cache import _build_executable

    req = serve.poisson_requests(n_requests=1,
                                 resolution=SERVE_RESOLUTION)[0]
    assert req.plan.static.reduce_mode == "table"
    fn = _build_executable(req, admission_key(req))
    leaves = tuple(jnp.broadcast_to(lf, (SERVE_BATCH,) + lf.shape)
                   for lf in req.leaves)
    rhs = jnp.broadcast_to(req.rhs, (SERVE_BATCH,) + req.rhs.shape)
    compiled = fn.lower(_shapes(req.plan, one_chip),
                        _shapes(leaves, one_chip),
                        _shapes(rhs, one_chip)).compile()
    assert _device_bytes(compiled) < HBM_BYTES
