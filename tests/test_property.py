"""Hypothesis property tests on system invariants (routing, assembly,
sparse ops, MoE dispatch)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

pytest.importorskip("hypothesis", reason="property tests need hypothesis")
from hypothesis import given, settings, strategies as st

import repro.core  # noqa: F401  (x64)
from repro.core.routing import build_matrix_routing, build_vector_routing


@settings(max_examples=30, deadline=None)
@given(
    e=st.integers(1, 50),
    k=st.integers(1, 6),
    n=st.integers(6, 40),
    seed=st.integers(0, 2**16),
)
def test_matrix_routing_equals_scipy_coo(e, k, n, seed):
    """Sorted segment-sum reduce == scipy COO duplicate summation — the
    S_mat·vec(K_local) identity (paper Eq. 8)."""
    import scipy.sparse as sp

    rng = np.random.default_rng(seed)
    cell_dofs = rng.integers(0, n, size=(e, k))
    routing = build_matrix_routing(cell_dofs, None, n)
    vals = rng.normal(size=(e, k, k))

    from repro.core.assembly import reduce_matrix

    got = np.zeros((n, n))
    reduced = np.asarray(reduce_matrix(jnp.asarray(vals), routing))
    got[routing.row_of_nnz, routing.indices] = reduced

    rows = np.broadcast_to(cell_dofs[:, :, None], (e, k, k)).ravel()
    cols = np.broadcast_to(cell_dofs[:, None, :], (e, k, k)).ravel()
    want = sp.coo_matrix((vals.ravel(), (rows, cols)), shape=(n, n)).toarray()
    np.testing.assert_allclose(got, want, atol=1e-12)


@settings(max_examples=30, deadline=None)
@given(
    e=st.integers(1, 60),
    k=st.integers(1, 5),
    n=st.integers(5, 30),
    seed=st.integers(0, 2**16),
)
def test_vector_routing_equals_bincount(e, k, n, seed):
    rng = np.random.default_rng(seed)
    cell_dofs = rng.integers(0, n, size=(e, k))
    routing = build_vector_routing(cell_dofs, n)
    vals = rng.normal(size=(e, k))

    from repro.core.assembly import reduce_vector

    got = np.asarray(reduce_vector(jnp.asarray(vals), routing))
    want = np.bincount(cell_dofs.ravel(), weights=vals.ravel(), minlength=n)
    np.testing.assert_allclose(got, want, atol=1e-12)


@settings(max_examples=20, deadline=None)
@given(
    e=st.integers(1, 40),
    k=st.integers(1, 5),
    n=st.integers(5, 30),
    seed=st.integers(0, 2**16),
)
def test_reduce_linearity(e, k, n, seed):
    """Reduce is linear: R(a·x + b·y) == a·R(x) + b·R(y) (assembly
    linearity that justifies precomputed routing, paper §2)."""
    rng = np.random.default_rng(seed)
    cell_dofs = rng.integers(0, n, size=(e, k))
    routing = build_matrix_routing(cell_dofs, None, n)
    from repro.core.assembly import reduce_matrix

    x = jnp.asarray(rng.normal(size=(e, k, k)))
    y = jnp.asarray(rng.normal(size=(e, k, k)))
    lhs = reduce_matrix(2.5 * x - 1.5 * y, routing)
    rhs = 2.5 * reduce_matrix(x, routing) - 1.5 * reduce_matrix(y, routing)
    np.testing.assert_allclose(np.asarray(lhs), np.asarray(rhs), atol=1e-11)


@settings(max_examples=15, deadline=None)
@given(n=st.integers(4, 12), seed=st.integers(0, 2**16))
def test_csr_matvec_equals_dense(n, seed):
    rng = np.random.default_rng(seed)
    cell_dofs = rng.integers(0, n, size=(max(n // 2, 2), 3))
    routing = build_matrix_routing(cell_dofs, None, n)
    from repro.core.assembly import reduce_matrix
    from repro.core.sparse import CSR

    vals = reduce_matrix(
        jnp.asarray(rng.normal(size=(cell_dofs.shape[0], 3, 3))), routing
    )
    a = CSR(vals, routing.indptr, routing.indices, routing.row_of_nnz,
            (n, n), routing.diag_pos)
    x = jnp.asarray(rng.normal(size=n))
    dense = np.asarray(a.to_dense())
    np.testing.assert_allclose(np.asarray(a.matvec(x)), dense @ np.asarray(x), atol=1e-11)
    np.testing.assert_allclose(np.asarray(a.rmatvec(x)), dense.T @ np.asarray(x), atol=1e-11)
    np.testing.assert_allclose(np.asarray(a.loop_matvec()(x)), dense @ np.asarray(x), atol=1e-11)


_WF_CACHE = {}


def _wf_assembler(n):
    """One assembler per mesh size — keeps the jit/form caches warm across
    hypothesis examples (the property is about values, not compilation)."""
    if n not in _WF_CACHE:
        from repro.core import FunctionSpace, GalerkinAssembler, unit_square_tri
        from repro.core.mesh import element_for_mesh

        m = unit_square_tri(n)
        space = FunctionSpace(m, element_for_mesh(m))
        _WF_CACHE[n] = (m, GalerkinAssembler(space))
    return _WF_CACHE[n]


@settings(max_examples=20, deadline=None)
@given(
    n=st.integers(3, 6),
    scale=st.floats(0.1, 10.0),
    seed=st.integers(0, 2**16),
)
def test_weakform_assembly_additive_and_homogeneous(n, scale, seed):
    """assemble(a + s·b).vals == assemble(a).vals + s·assemble(b).vals on the
    shared CSR pattern (linearity of the fused Map + single Reduce)."""
    from repro.core import weakform as wf

    m, asm = _wf_assembler(n)
    rng = np.random.default_rng(seed)
    c1 = jnp.asarray(rng.uniform(0.5, 2.0, m.num_cells))
    c2 = jnp.asarray(rng.uniform(0.5, 2.0, m.num_cells))
    fused = asm.assemble(wf.diffusion(c1) + scale * wf.mass(c2)).vals
    separate = (
        np.asarray(asm.assemble(wf.diffusion(c1)).vals)
        + scale * np.asarray(asm.assemble(wf.mass(c2)).vals)
    )
    np.testing.assert_allclose(np.asarray(fused), separate, atol=1e-10, rtol=1e-12)


@settings(max_examples=10, deadline=None)
@given(
    tokens=st.integers(8, 64),
    seed=st.integers(0, 2**16),
)
def test_moe_combine_weights_sum_to_one_when_kept(tokens, seed):
    """Routing invariant: for every token, combine weights over (E, C) sum
    to ≤ 1 (== 1 when no capacity drop), and dispatch is 0/1."""
    import dataclasses

    from repro.configs import ARCHS, smoke_variant
    from repro.models import moe as moe_mod
    from repro.models.layers import init_params

    cfg = dataclasses.replace(
        smoke_variant(ARCHS["qwen3-moe-30b-a3b"]), moe_capacity_factor=8.0
    )
    params = init_params(moe_mod.moe_specs(cfg), jax.random.PRNGKey(seed))
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (1, tokens, cfg.d_model))
    out, aux = moe_mod.moe_apply(cfg, params, x)
    assert np.all(np.isfinite(np.asarray(out)))
    # generous capacity → no drops → output magnitude comparable to expert out
    assert float(jnp.abs(out).max()) > 0
