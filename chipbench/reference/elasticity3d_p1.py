"""Plain reference for the 3D elasticity configuration: isotropic linear
elasticity, −∇·σ(u) = b, with P1 tetrahedra on the hollow cube
[0,1]³ \\ (1/4, 3/4)³ and u = 0 on its whole boundary (the outer faces and
the cavity).

NumPy and SciPy only, IEEE float64 unless a lower ``dtype`` is asked for
(the precision control).  Nothing here imports the program under test: the
mesh is built again from the same convention (an ``n × n × n`` grid of
cubes, vertex ``(i·(n+1) + j)·(n+1) + k`` at ``(i/n, j/n, k/n)``, the cubes
of the cavity left out, each kept cube, in lexicographic order, cut into the
same six tetrahedra around its main diagonal, unused vertices dropped), and
the benchmark checks once per run that the program's mesh is the same one.

The stiffness is assembled on the vertex pattern as 3 × 3 blocks (a SciPy
BSR matrix), the cells taken in chunks so that host memory stays within a
few GB at a million DoFs; DoF ``3·v + c`` is component c of vertex v.
"""

from __future__ import annotations

import functools

import numpy as np
import scipy.sparse as sp
import scipy.sparse.linalg as spla

# the six tetrahedra of a cube around its diagonal from corner 0 to corner
# 7, corners numbered x + 2y + 4z
_CUBE_TETS = np.array([[0, 1, 3, 7], [0, 1, 7, 5], [0, 5, 7, 4],
                       [0, 3, 2, 7], [0, 2, 6, 7], [0, 6, 4, 7]])
_CHUNK = 1 << 18


def _cavity(n: int) -> tuple[int, int]:
    return int(round(0.25 * n)), int(round(0.75 * n))


@functools.lru_cache(maxsize=2)
def mesh(n: int) -> tuple[np.ndarray, np.ndarray]:
    """``(points (V, 3), cells (E, 4))`` of the hollow cube at ``n`` cells a
    side."""
    lo, hi = _cavity(n)
    i, j, k = (g.ravel() for g in np.meshgrid(*[np.arange(n)] * 3,
                                              indexing="ij"))
    keep = ~((lo <= i) & (i < hi) & (lo <= j) & (j < hi)
             & (lo <= k) & (k < hi))
    i, j, k = i[keep], j[keep], k[keep]
    dx, dy, dz = (np.array([(c >> s) & 1 for c in range(8)])
                  for s in (0, 1, 2))
    corners = (((i[:, None] + dx) * (n + 1) + j[:, None] + dy) * (n + 1)
               + k[:, None] + dz)                       # (cubes, 8)
    cells = corners[:, _CUBE_TETS].reshape(-1, 4)
    used = np.unique(cells)
    remap = np.full((n + 1) ** 3, -1, np.int64)
    remap[used] = np.arange(used.size)
    xs = np.linspace(0.0, 1.0, n + 1)
    gx, gy, gz = np.meshgrid(xs, xs, xs, indexing="ij")
    points = np.stack([gx.ravel(), gy.ravel(), gz.ravel()], -1)[used]
    return points, remap[cells]


def boundary_mask(points: np.ndarray, n: int) -> np.ndarray:
    """Vertices on the outer faces or on the cavity's surface."""
    g = np.rint(points * n).astype(np.int64)
    lo, hi = _cavity(n)
    outer = np.any((g == 0) | (g == n), axis=1)
    cavity = np.all((lo <= g) & (g <= hi), axis=1)
    return outer | cavity


@functools.lru_cache(maxsize=2)
def _pattern(n: int) -> tuple[np.ndarray, np.ndarray]:
    """``(indptr, indices)`` of the vertex pattern: each pair of vertices
    that share a cell."""
    points, cells = mesh(n)
    nv = points.shape[0]
    rows = np.repeat(cells, 4, axis=1).ravel()
    cols = np.tile(cells, (1, 4)).ravel()
    keys = np.unique(rows * nv + cols)
    indptr = np.searchsorted(keys, np.arange(nv + 1) * nv)
    return indptr, keys % nv


def dofs(n: int) -> int:
    """Unknowns of the system, boundary rows included: three per vertex."""
    return 3 * mesh(n)[0].shape[0]


def nnz(n: int) -> int:
    """Stored entries of the DoF pattern: a 3 × 3 block per vertex pair."""
    return 9 * _pattern(n)[1].size


def _element(points, cells, lam, mu, dtype):
    """``(E, 4, 4, 3, 3)`` blocks ``V_e (λ g_a g_bᵀ + μ g_b g_aᵀ + μ (g_a·g_b)
    I)`` of each tetrahedron (``B_eᵀ D B_e`` with the isotropic D), and its
    volume ``V_e``."""
    x = points[cells].astype(dtype)
    jac = np.stack([x[:, 1] - x[:, 0], x[:, 2] - x[:, 0], x[:, 3] - x[:, 0]],
                   axis=1)                              # (E, 3 edges, 3)
    inv = np.linalg.inv(jac)                            # columns: dual basis
    g1 = np.swapaxes(inv, 1, 2)                         # rows: ∇λ_1..3
    grad = np.concatenate([-g1.sum(axis=1, keepdims=True), g1], axis=1)
    vol = np.abs(np.linalg.det(jac)) / 6
    outer = np.einsum("eai,ebj->eabij", grad, grad)
    dot = np.einsum("eai,ebi->eab", grad, grad)
    blocks = (lam * outer + mu * np.swapaxes(outer, -1, -2)
              + mu * dot[..., None, None] * np.eye(3, dtype=dtype))
    return vol[:, None, None, None, None] * blocks, vol


def stiffness(n: int, e_mod: float, nu: float, dtype=np.float64):
    """The condensed stiffness: a BSR matrix of 3 × 3 blocks on the vertex
    pattern, rows and columns of boundary vertices zeroed and their
    diagonal blocks the identity."""
    points, cells = mesh(n)
    indptr, indices = _pattern(n)
    nv = points.shape[0]
    lam = e_mod * nu / ((1 + nu) * (1 - 2 * nu))
    mu = e_mod / (2 * (1 + nu))
    keys = np.repeat(np.arange(nv), np.diff(indptr)) * nv + indices
    data = np.zeros((indices.size, 3, 3), dtype)
    for s in range(0, cells.shape[0], _CHUNK):
        c = cells[s:s + _CHUNK]
        blocks, _ = _element(points, c, lam, mu, dtype)
        slot = np.searchsorted(keys, (c[:, :, None] * nv
                                      + c[:, None, :]).ravel())
        flat = blocks.reshape(-1, 3, 3)
        for a in range(3):
            for b in range(3):
                data[:, a, b] += np.bincount(slot, weights=flat[:, a, b],
                                             minlength=indices.size)
    free = ~boundary_mask(points, n)
    row = np.repeat(np.arange(nv), np.diff(indptr))
    data *= (free[row] & free[indices])[:, None, None]
    data[(row == indices) & ~free[row]] = np.eye(3)
    return sp.bsr_matrix((data, indices, indptr), shape=(3 * nv, 3 * nv))


def load(n: int, body_force, dtype=np.float64) -> np.ndarray:
    """The condensed load: ``V_e / 4 · b`` to each vertex of each cell, zero
    on boundary vertices."""
    points, cells = mesh(n)
    x = points[cells].astype(dtype)
    vol = np.abs(np.linalg.det(np.stack(
        [x[:, 1] - x[:, 0], x[:, 2] - x[:, 0], x[:, 3] - x[:, 0]], 1))) / 6
    share = np.bincount(cells.ravel(), weights=np.repeat(vol / 4, 4),
                        minlength=points.shape[0]).astype(dtype)
    share *= ~boundary_mask(points, n)
    b = np.asarray(body_force, dtype)
    return (share[:, None] * b[None, :]).ravel()


def relres(k, f, u) -> float:
    """‖K u − F‖ / ‖F‖ in IEEE float64."""
    k64 = k.astype(np.float64)
    f64 = np.asarray(f, np.float64)
    r = k64 @ np.asarray(u, np.float64) - f64
    return float(np.linalg.norm(r) / np.linalg.norm(f64))


def solve(k, f, tol=1e-10, maxiter=20000, dtype=np.float64) -> np.ndarray:
    """Jacobi-preconditioned CG in ``dtype``: the textbook algorithm that the
    configuration states, used as the precision control at float32."""
    k = k.astype(dtype)
    f = np.asarray(f, dtype)
    m = sp.diags((1.0 / k.diagonal()).astype(dtype))
    u, _ = spla.cg(k, f, rtol=tol, atol=0.0, maxiter=maxiter, M=m)
    return u
