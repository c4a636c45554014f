"""Pallas kernel tests: shape/dtype sweeps + allclose vs the ref.py oracles
(interpret=True executes kernel bodies in Python on CPU)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import repro.core  # noqa: F401  (x64 on)
from repro.core import FunctionSpace, GalerkinAssembler, unit_cube_tet
from repro.core.mesh import element_for_mesh
from repro.kernels import batch_map_stiffness
from repro.kernels.local_assembly import local_stiffness_p1
from repro.kernels.ref import local_stiffness_p1_ref


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


def test_interpret_default_resolution(monkeypatch):
    """interpret resolves from the active backend (off-TPU → interpret);
    an explicit ``interpret=`` wins."""
    from repro.kernels.interpret import resolve_interpret

    assert resolve_interpret() == (jax.default_backend() != "tpu")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert resolve_interpret() is False
    assert resolve_interpret(True) is True


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
