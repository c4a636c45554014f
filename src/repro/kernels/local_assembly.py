"""Pallas TPU kernel: Stage-I Batch-Map for P1 simplex stiffness.

TPU adaptation of the paper's fused-einsum Map stage (DESIGN.md §2): the
GPU-natural array-of-structs ``(E, k, d)`` layout is transposed to
structure-of-arrays ``(k·d, E)`` so that the element index rides the 128-wide
*lane* dimension.  Each grid step processes a ``(k·d, BE)`` tile resident in
VMEM; the 2×2 / 3×3 Jacobian inverse (closed-form adjugate), determinant, and
the ``G Gᵀ`` contraction are all element-wise VPU ops across lanes — zero
transposes, zero MXU dependency (per-element k≤4 matrices are too small for
the systolic array; lane-parallelism is the TPU-idiomatic fusion).

Grid:      (ceil(E / BE),)
BlockSpecs: coords (k·d, BE) VMEM;  rho (1, BE) VMEM;  out (k², BE) VMEM.
BE = 2048 lanes → VMEM footprint ≈ (kd + 1 + k²)·BE·4B ≈ 210 KB (tri, f32).

``interpret`` resolves from the platform (:mod:`repro.kernels.interpret`).
The kernel does not compile for a TPU today: in f64 the x64 rewriter cannot
pass the ``tpu_custom_call``, and in f32 Mosaic fails to legalize
``func.return``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .interpret import resolve_interpret

__all__ = ["local_stiffness_p1_kernel", "local_stiffness_p1"]

BLOCK_E = 2048


def _tri_kernel(coords_ref, rho_ref, out_ref):
    """P1 triangle: coords rows are [x0,y0,x1,y1,x2,y2] (k·d = 6)."""
    c = coords_ref[...]
    x0, y0 = c[0], c[1]
    x1, y1 = c[2], c[3]
    x2, y2 = c[4], c[5]
    e1x, e1y = x1 - x0, y1 - y0
    e2x, e2y = x2 - x0, y2 - y0
    det = e1x * e2y - e2x * e1y
    inv_det = 1.0 / det
    # G_a = J^{-T} ĝ_a ;  J = [[e1x, e2x], [e1y, e2y]]
    g1x, g1y = e2y * inv_det, -e2x * inv_det
    g2x, g2y = -e1y * inv_det, e1x * inv_det
    g0x, g0y = -(g1x + g2x), -(g1y + g2y)
    scale = 0.5 * jnp.abs(det) * rho_ref[0]
    gx = (g0x, g1x, g2x)
    gy = (g0y, g1y, g2y)
    for a in range(3):
        for b in range(3):
            out_ref[a * 3 + b, :] = scale * (gx[a] * gx[b] + gy[a] * gy[b])


def _tet_kernel(coords_ref, rho_ref, out_ref):
    """P1 tetrahedron: coords rows [x0,y0,z0, ..., x3,y3,z3] (k·d = 12)."""
    c = coords_ref[...]
    p = [(c[3 * a], c[3 * a + 1], c[3 * a + 2]) for a in range(4)]
    # J columns = edge vectors p_a − p_0
    a1 = tuple(p[1][i] - p[0][i] for i in range(3))
    a2 = tuple(p[2][i] - p[0][i] for i in range(3))
    a3 = tuple(p[3][i] - p[0][i] for i in range(3))
    # J = [[a1x,a2x,a3x],[a1y,a2y,a3y],[a1z,a2z,a3z]]
    j = ((a1[0], a2[0], a3[0]), (a1[1], a2[1], a3[1]), (a1[2], a2[2], a3[2]))
    det = (
        j[0][0] * (j[1][1] * j[2][2] - j[1][2] * j[2][1])
        - j[0][1] * (j[1][0] * j[2][2] - j[1][2] * j[2][0])
        + j[0][2] * (j[1][0] * j[2][1] - j[1][1] * j[2][0])
    )
    inv_det = 1.0 / det
    # adjugate → J^{-1}; rows of J^{-T} are columns of J^{-1}
    adj = [
        [
            j[1][1] * j[2][2] - j[1][2] * j[2][1],
            j[0][2] * j[2][1] - j[0][1] * j[2][2],
            j[0][1] * j[1][2] - j[0][2] * j[1][1],
        ],
        [
            j[1][2] * j[2][0] - j[1][0] * j[2][2],
            j[0][0] * j[2][2] - j[0][2] * j[2][0],
            j[0][2] * j[1][0] - j[0][0] * j[1][2],
        ],
        [
            j[1][0] * j[2][1] - j[1][1] * j[2][0],
            j[0][1] * j[2][0] - j[0][0] * j[2][1],
            j[0][0] * j[1][1] - j[0][1] * j[1][0],
        ],
    ]
    # ĝ_a for a=1..3 are unit vectors: G_a = (J^{-T})·e_a = row a of J^{-1} scaled
    g = [None] * 4
    g[1] = tuple(adj[0][i] * inv_det for i in range(3))
    g[2] = tuple(adj[1][i] * inv_det for i in range(3))
    g[3] = tuple(adj[2][i] * inv_det for i in range(3))
    g[0] = tuple(-(g[1][i] + g[2][i] + g[3][i]) for i in range(3))
    scale = (1.0 / 6.0) * jnp.abs(det) * rho_ref[0]
    for a in range(4):
        for b in range(4):
            out_ref[a * 4 + b, :] = scale * (
                g[a][0] * g[b][0] + g[a][1] * g[b][1] + g[a][2] * g[b][2]
            )


def local_stiffness_p1(coords: jnp.ndarray, rho: jnp.ndarray, *,
                       interpret: bool | None = None, block_e: int = BLOCK_E):
    """coords (E, k, d) AoS, rho (E,) → (E, k, k); dispatches on d."""
    return _local_stiffness_p1(coords, rho,
                               interpret=resolve_interpret(interpret),
                               block_e=block_e)


@functools.partial(jax.jit, static_argnames=("interpret", "block_e"))
def _local_stiffness_p1(coords, rho, *, interpret: bool, block_e: int):
    e, k, d = coords.shape
    assert k == d + 1 and d in (2, 3)
    kernel = _tri_kernel if d == 2 else _tet_kernel

    e_pad = -(-e // block_e) * block_e
    soa = jnp.moveaxis(coords.reshape(e, k * d), 0, 1)     # (k·d, E)
    soa = jnp.pad(soa, ((0, 0), (0, e_pad - e)), constant_values=1.0)
    # padded elements: degenerate coords would give det=0 → 1/0; overwrite
    # with the identity simplex so the pad lanes stay finite.
    if e_pad != e:
        ident = jnp.moveaxis(
            jnp.concatenate(
                [jnp.zeros((1, d)), jnp.eye(d)], axis=0
            ).reshape(1, k * d).astype(coords.dtype), 0, 1,
        )
        soa = soa.at[:, e:].set(ident)
    rho_p = jnp.pad(rho, (0, e_pad - e))[None, :]           # (1, E)

    grid = (e_pad // block_e,)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((k * d, block_e), lambda i: (0, i)),
            pl.BlockSpec((1, block_e), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((k * k, block_e), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((k * k, e_pad), coords.dtype),
        interpret=interpret,
    )(soa, rho_p)
    return jnp.moveaxis(out[:, :e], 0, 1).reshape(e, k, k)


# alias used by tests / benchmarks
local_stiffness_p1_kernel = local_stiffness_p1
