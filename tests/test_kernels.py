"""Pallas kernel tests: shape/dtype sweeps + allclose vs the ref.py oracles
(interpret=True executes kernel bodies in Python on CPU)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import repro.core  # noqa: F401  (x64 on)
from repro.core import FunctionSpace, GalerkinAssembler, csr_to_ell, unit_square_tri, unit_cube_tet
from repro.core.mesh import element_for_mesh
from repro.kernels import batch_map_stiffness, ell_matvec, ell_residual
from repro.kernels.local_assembly import local_stiffness_p1
from repro.kernels.ref import (
    galerkin_residual_ell_ref,
    local_stiffness_p1_ref,
    spmv_ell_ref,
)
from repro.kernels.spmv_ell import (
    autotune_stream,
    galerkin_residual_ell_stream,
    spmv_ell,
    spmv_ell_stream,
    stream_vmem_bytes,
)


def _random_simplices(rng, e, d, dtype):
    ident = np.concatenate([np.zeros((1, d)), np.eye(d)], axis=0)
    base = rng.normal(size=(e, 1, d))
    jitter = 0.15 * rng.normal(size=(e, d + 1, d))
    return jnp.asarray((base + ident[None] + jitter).astype(dtype))


@pytest.mark.parametrize("d", [2, 3])
@pytest.mark.parametrize("e", [1, 7, 129, 2048, 5000])
@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_local_assembly_sweep(d, e, dtype):
    rng = np.random.default_rng(e * d)
    coords = _random_simplices(rng, e, d, dtype)
    rho = jnp.asarray(rng.uniform(0.5, 2.0, size=e).astype(dtype))
    got = batch_map_stiffness(coords, rho, interpret=True)
    want = local_stiffness_p1_ref(coords, rho)
    tol = 2e-4 if dtype == np.float32 else 1e-11
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=tol, rtol=tol)


@pytest.mark.parametrize("block_e", [128, 512])
def test_local_assembly_block_size_invariance(block_e):
    rng = np.random.default_rng(3)
    coords = _random_simplices(rng, 700, 2, np.float64)
    rho = jnp.ones(700)
    a = local_stiffness_p1(coords, rho, interpret=True, block_e=block_e)
    b = local_stiffness_p1_ref(coords, rho)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-12)


def test_local_assembly_matches_full_assembler():
    """Kernel output → Sparse-Reduce must equal the einsum assembler's K."""
    from repro.core.assembly import reduce_matrix

    m = unit_cube_tet(4)
    space = FunctionSpace(m, element_for_mesh(m))
    asm = GalerkinAssembler(space)
    rho = jnp.asarray(np.random.default_rng(0).uniform(0.5, 2, m.num_cells))
    k_ref = asm.assemble_stiffness(rho)
    k_local = batch_map_stiffness(asm.coords, rho, interpret=True)
    vals = reduce_matrix(k_local, asm.mat_routing)
    np.testing.assert_allclose(np.asarray(vals), np.asarray(k_ref.vals), atol=1e-12)


@pytest.mark.parametrize("n,l", [(5, 1), (100, 7), (4096, 16), (6000, 9)])
@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_spmv_ell_sweep(n, l, dtype):
    rng = np.random.default_rng(n + l)
    vals = jnp.asarray(rng.normal(size=(n, l)).astype(dtype))
    cols = jnp.asarray(rng.integers(0, n, size=(n, l)))
    x = jnp.asarray(rng.normal(size=n).astype(dtype))
    got = spmv_ell(vals, cols, x, interpret=True)
    want = spmv_ell_ref(vals, cols, x)
    tol = 1e-4 if dtype == np.float32 else 1e-12
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=tol, rtol=tol)


def test_spmv_matches_csr_on_fem_matrix():
    m = unit_square_tri(15)
    space = FunctionSpace(m, element_for_mesh(m))
    k = GalerkinAssembler(space).assemble_stiffness()
    ell = csr_to_ell(k)
    x = jnp.asarray(np.random.default_rng(1).normal(size=k.shape[0]))
    np.testing.assert_allclose(
        np.asarray(ell_matvec(ell, x, interpret=True)),
        np.asarray(k.matvec(x)),
        atol=1e-12,
    )


def test_fused_residual():
    rng = np.random.default_rng(9)
    n, l = 513, 5
    vals = jnp.asarray(rng.normal(size=(n, l)))
    cols = jnp.asarray(rng.integers(0, n, size=(n, l)))
    u = jnp.asarray(rng.normal(size=n))
    f = jnp.asarray(rng.normal(size=n))
    got = ell_residual(
        type("E", (), {"vals": vals, "cols": np.asarray(cols)})(), u, f,
        interpret=True,
    )
    want = galerkin_residual_ell_ref(vals, cols, u, f)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-12)


# ---------------------------------------------------------------------------
# streaming SpMV (HBM-resident x, double-buffered row blocks)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,l,block_n", [
    (1000, 7, 256),   # N not divisible by block_n
    (300, 1, 128),    # L = 1
    (100, 5, 4096),   # N < block_n
    (4096, 9, 1024),  # exact multiple
    (129, 3, 128),    # one full block + remainder of 1
])
@pytest.mark.parametrize("nbuf", [2, 3])
def test_spmv_stream_sweep(n, l, block_n, nbuf):
    rng = np.random.default_rng(n + l + nbuf)
    vals = jnp.asarray(rng.normal(size=(n, l)))
    cols = np.sort(rng.integers(0, n, size=(n, l)))  # FEM-like locality
    x = jnp.asarray(rng.normal(size=n))
    got = spmv_ell_stream(vals, cols, x, interpret=True,
                          block_n=block_n, nbuf=nbuf)
    want = spmv_ell_ref(vals, jnp.asarray(cols), x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-12)


def test_stream_matches_broadcast_on_fem_matrix():
    m = unit_square_tri(15)
    space = FunctionSpace(m, element_for_mesh(m))
    k = GalerkinAssembler(space).assemble_stiffness()
    ell = csr_to_ell(k)
    x = jnp.asarray(np.random.default_rng(1).normal(size=k.shape[0]))
    from repro.kernels import ell_matvec_stream

    np.testing.assert_allclose(
        np.asarray(ell_matvec_stream(ell, x, interpret=True, block_n=64)),
        np.asarray(k.matvec(x)),
        atol=1e-12,
    )


def test_fused_residual_stream():
    rng = np.random.default_rng(11)
    n, l = 513, 4
    vals = jnp.asarray(rng.normal(size=(n, l)))
    cols = np.sort(rng.integers(0, n, size=(n, l)))
    u = jnp.asarray(rng.normal(size=n))
    f = jnp.asarray(rng.normal(size=n))
    got = galerkin_residual_ell_stream(vals, cols, u, f, interpret=True,
                                       block_n=128)
    want = galerkin_residual_ell_ref(vals, jnp.asarray(cols), u, f)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-12)


def test_stream_rejects_traced_cols():
    vals = jnp.ones((8, 2))
    x = jnp.ones(8)

    def f(cols):
        return spmv_ell_stream(vals, cols, x, interpret=True)

    with pytest.raises(TypeError, match="static column table"):
        jax.jit(f)(jnp.zeros((8, 2), dtype=jnp.int32))


def test_stream_vmem_independent_of_n():
    """The whole point: streaming VMEM footprint must not scale with N."""
    small = stream_vmem_bytes(10_000, 7, block_n=1024, nbuf=2, window=2048)
    large = stream_vmem_bytes(10_000_000, 7, block_n=1024, nbuf=2, window=2048)
    assert small == large


def test_autotune_stream_returns_valid_config():
    rng = np.random.default_rng(5)
    n, l = 600, 4
    vals = jnp.asarray(rng.normal(size=(n, l)))
    cols = np.sort(rng.integers(0, n, size=(n, l)))
    x = jnp.asarray(rng.normal(size=n))
    bn, nb = autotune_stream(vals, cols, x, block_candidates=(128, 256),
                             nbuf_candidates=(2,), interpret=True, iters=1)
    assert bn in (128, 256) and nb == 2
    # cached: same layout returns without re-measuring
    assert autotune_stream(vals, cols, x, interpret=True) == (bn, nb)


def test_interpret_default_resolution(monkeypatch):
    """interpret resolves from the active backend (off-TPU → interpret);
    an explicit ``interpret=`` wins."""
    from repro.kernels.interpret import resolve_interpret

    assert resolve_interpret() == (jax.default_backend() != "tpu")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert resolve_interpret() is False
    assert resolve_interpret(True) is True


@pytest.mark.parametrize("backend", ["ell_pallas", "ell_stream"])
def test_pallas_backends_refuse_tpu(monkeypatch, backend):
    """Neither Pallas SpMV compiles for a TPU (Mosaic rejects the 1-D
    gather), so selecting one on a TPU backend raises up front instead of
    building a kernel that cannot run."""
    from repro.core import make_matvec, make_residual, weakform as wf

    m = unit_square_tri(4)
    space = FunctionSpace(m, element_for_mesh(m))
    k = GalerkinAssembler(space).assemble(wf.diffusion())
    make_matvec(k, backend)  # off-TPU: builds (runs interpreted)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    for factory in (make_matvec, make_residual):
        with pytest.raises(NotImplementedError, match="Only 2D gather"):
            factory(k, backend)


# ---------------------------------------------------------------------------
# property-based: kernel invariances (hypothesis)
# ---------------------------------------------------------------------------

try:
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # keep the non-property tests above runnable
    HAVE_HYPOTHESIS = False


if HAVE_HYPOTHESIS:

    @settings(max_examples=20, deadline=None)
    @given(
        e=st.integers(min_value=1, max_value=300),
        seed=st.integers(min_value=0, max_value=2**16),
        scale=st.floats(min_value=0.1, max_value=10.0),
    )
    def test_local_stiffness_properties(e, seed, scale):
        """Invariances of the P1 stiffness map: symmetry, zero row-sum
        (constants in kernel), translation invariance, ρ-linearity."""
        rng = np.random.default_rng(seed)
        coords = _random_simplices(rng, e, 2, np.float64)
        rho = jnp.asarray(rng.uniform(0.5, 2.0, size=e))
        k = batch_map_stiffness(coords, rho, interpret=True)
        k_np = np.asarray(k)
        # symmetry
        np.testing.assert_allclose(k_np, np.swapaxes(k_np, 1, 2), atol=1e-11)
        # row sums vanish (gradient of constant)
        np.testing.assert_allclose(k_np.sum(axis=2), 0.0, atol=1e-10)
        # translation invariance
        shifted = coords + jnp.asarray(rng.normal(size=(1, 1, 2)))
        k2 = batch_map_stiffness(shifted, rho, interpret=True)
        np.testing.assert_allclose(k_np, np.asarray(k2), atol=1e-9)
        # linearity in rho
        k3 = batch_map_stiffness(coords, rho * scale, interpret=True)
        np.testing.assert_allclose(np.asarray(k3), k_np * scale, rtol=1e-10, atol=1e-12)

else:

    @pytest.mark.skip(reason="property tests need hypothesis")
    def test_local_stiffness_properties():
        pass


# The ELL property tests run EITHER way: under hypothesis they draw shapes
# freely; without it they sweep a hand-picked edge-case grid (N < block_n,
# N % block_n ≠ 0, L = 1) so the contracts stay enforced in minimal CI
# environments too.
_ELL_EDGE_GRID = [
    (1, 1, 128, 0), (127, 1, 128, 1), (128, 1, 128, 2), (129, 4, 128, 3),
    (300, 9, 256, 4), (511, 3, 512, 5), (700, 7, 512, 6), (64, 2, 512, 7),
]


def _check_ell_edge_shapes(n, l, block_n, seed):
    """Both SpMV plans agree with the oracle for arbitrary (N, L, block_n)."""
    rng = np.random.default_rng(seed)
    vals = jnp.asarray(rng.normal(size=(n, l)))
    cols = np.sort(rng.integers(0, n, size=(n, l)))
    x = jnp.asarray(rng.normal(size=n))
    want = np.asarray(spmv_ell_ref(vals, jnp.asarray(cols), x))
    legacy = spmv_ell(vals, cols, x, interpret=True, block_n=block_n)
    stream = spmv_ell_stream(vals, cols, x, interpret=True, block_n=block_n)
    np.testing.assert_allclose(np.asarray(legacy), want, atol=1e-12)
    np.testing.assert_allclose(np.asarray(stream), want, atol=1e-12)


def _check_ell_padding_invariant(n, l, seed):
    """ELLPACK padding contract on both kernels: slots whose value is zero
    contribute nothing, whatever (valid) column they reference — so the
    layout builders' self-referencing padded columns never alias real
    entries."""
    rng = np.random.default_rng(seed)
    vals = rng.normal(size=(n, l))
    cols = np.sort(rng.integers(0, n, size=(n, l)))
    # zero out a random set of slots and retarget their columns at an
    # arbitrary row — the result must not change
    mask = rng.uniform(size=(n, l)) < 0.4
    vals_z = np.where(mask, 0.0, vals)
    cols_alias = np.where(
        mask, np.repeat(np.arange(n)[:, None], l, axis=1), cols
    )
    x = jnp.asarray(rng.normal(size=n))
    want = np.asarray(spmv_ell_ref(jnp.asarray(vals_z), jnp.asarray(cols), x))
    for kern in (spmv_ell, spmv_ell_stream):
        got = kern(jnp.asarray(vals_z), cols_alias, x, interpret=True,
                   block_n=128)
        np.testing.assert_allclose(np.asarray(got), want, atol=1e-12)


if HAVE_HYPOTHESIS:

    @settings(max_examples=15, deadline=None)
    @given(
        n=st.integers(min_value=1, max_value=700),
        l=st.integers(min_value=1, max_value=9),
        block_n=st.sampled_from([128, 256, 512]),
        seed=st.integers(min_value=0, max_value=2**16),
    )
    def test_ell_kernels_edge_shapes(n, l, block_n, seed):
        _check_ell_edge_shapes(n, l, block_n, seed)

    @settings(max_examples=10, deadline=None)
    @given(
        n=st.integers(min_value=2, max_value=400),
        l=st.integers(min_value=1, max_value=6),
        seed=st.integers(min_value=0, max_value=2**16),
    )
    def test_ell_padding_invariant(n, l, seed):
        _check_ell_padding_invariant(n, l, seed)

else:

    @pytest.mark.parametrize("n,l,block_n,seed", _ELL_EDGE_GRID)
    def test_ell_kernels_edge_shapes(n, l, block_n, seed):
        _check_ell_edge_shapes(n, l, block_n, seed)

    @pytest.mark.parametrize("n,l,seed",
                             [(2, 1, 0), (97, 3, 1), (256, 6, 2), (400, 4, 3)])
    def test_ell_padding_invariant(n, l, seed):
        _check_ell_padding_invariant(n, l, seed)
