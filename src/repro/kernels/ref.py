"""Pure-jnp oracles for the Pallas kernels (the correctness references)."""

from __future__ import annotations

import jax.numpy as jnp

__all__ = ["local_stiffness_p1_ref"]


def local_stiffness_p1_ref(coords: jnp.ndarray, rho: jnp.ndarray) -> jnp.ndarray:
    """Batched P1 simplex stiffness: coords (E, k, d) with k = d+1,
    rho (E,) → (E, k, k).  K_e = |e| ρ_e G Gᵀ with constant gradients."""
    e, k, d = coords.shape
    assert k == d + 1
    edges = coords[:, 1:, :] - coords[:, :1, :]          # (E, d, d) rows = edges
    jac = jnp.swapaxes(edges, 1, 2)                      # J columns = edge vectors
    det = jnp.linalg.det(jac)
    jinv = jnp.linalg.inv(jac)
    gradhat = jnp.concatenate(
        [-jnp.ones((1, d), coords.dtype), jnp.eye(d, dtype=coords.dtype)], axis=0
    )                                                    # (k, d)
    g = jnp.einsum("eji,aj->eai", jinv, gradhat)         # J^{-T} ĝ
    w = 1.0 / {1: 1.0, 2: 2.0, 3: 6.0}[d]                # reference simplex volume
    scale = w * jnp.abs(det) * rho                       # (E,)
    return jnp.einsum("e,eai,ebi->eab", scale, g, g)

