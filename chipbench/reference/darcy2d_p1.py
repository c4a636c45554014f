"""Plain reference for the 2D Darcy configurations: P1 triangles on the unit
square, −∇·(a∇u) = f, u = 0 on the boundary.

NumPy and SciPy only, IEEE float64 unless a lower ``dtype`` is asked for
(the precision control).  Nothing here imports the program under test: the
mesh is built again from the same published convention (a crossed
``n × n`` grid, vertex ``i·(n+1) + j`` at ``(i/n, j/n)``, the diagonal of
cell ``(i, j)`` alternating with the parity of ``i + j``), and the
benchmark checks once per run that the program's mesh is the same one.
"""

from __future__ import annotations

import numpy as np
import scipy.sparse as sp
import scipy.sparse.linalg as spla


def mesh(n: int) -> tuple[np.ndarray, np.ndarray]:
    """``(points (V, 2), cells (2n², 3))`` of the crossed unit-square grid."""
    xs = np.linspace(0.0, 1.0, n + 1)
    gx, gy = np.meshgrid(xs, xs, indexing="ij")
    points = np.stack([gx.ravel(), gy.ravel()], axis=-1)
    i, j = np.meshgrid(np.arange(n), np.arange(n), indexing="ij")
    i, j = i.ravel(), j.ravel()
    v00, v10 = i * (n + 1) + j, (i + 1) * (n + 1) + j
    v01, v11 = v00 + 1, v10 + 1
    even = ((i + j) % 2 == 0)[:, None]
    first = np.where(even, np.stack([v00, v10, v11], 1),
                     np.stack([v00, v10, v01], 1))
    second = np.where(even, np.stack([v00, v11, v01], 1),
                      np.stack([v10, v11, v01], 1))
    cells = np.stack([first, second], axis=1).reshape(-1, 3)
    return points, cells


def boundary_mask(points: np.ndarray) -> np.ndarray:
    on = (points == 0.0) | (points == 1.0)
    return on[:, 0] | on[:, 1]


def dofs(n: int) -> int:
    """Unknowns of the system, boundary rows included: one per vertex."""
    return (n + 1) ** 2


def nnz(n: int) -> int:
    """Stored entries of the P1 pattern: one per vertex and two per edge
    (``2n(n+1)`` axis edges and ``n²`` diagonals)."""
    return (n + 1) ** 2 + 2 * (2 * n * (n + 1) + n * n)


def assemble(points, cells, coeff, f=1.0, dtype=np.float64):
    """The condensed system ``(K, F)``: element stiffness ``a_e |T| ∇λ∇λᵀ``
    and load ``f |T| / 3`` summed by SciPy, then boundary rows and columns
    zeroed with a unit diagonal and ``F = 0`` on the boundary."""
    p = points[cells].astype(dtype)
    d1, d2 = p[:, 1] - p[:, 0], p[:, 2] - p[:, 0]
    det = d1[:, 0] * d2[:, 1] - d1[:, 1] * d2[:, 0]
    g1 = np.stack([d2[:, 1], -d2[:, 0]], -1) / det[:, None]
    g2 = np.stack([-d1[:, 1], d1[:, 0]], -1) / det[:, None]
    grad = np.stack([-(g1 + g2), g1, g2], axis=1)            # (E, 3, 2)
    area = np.abs(det) / 2
    ke = (np.asarray(coeff, dtype) * area)[:, None, None] * np.einsum(
        "eik,ejk->eij", grad, grad)
    n = points.shape[0]
    rows = np.broadcast_to(cells[:, :, None], ke.shape).ravel()
    cols = np.broadcast_to(cells[:, None, :], ke.shape).ravel()
    k = sp.coo_matrix((ke.ravel(), (rows, cols)), shape=(n, n)).tocsr()
    load = np.bincount(cells.ravel(), minlength=n,
                       weights=np.repeat(f * area / 3, 3)).astype(dtype)
    free = (~boundary_mask(points)).astype(dtype)
    k = (sp.diags(free) @ k @ sp.diags(free)
         + sp.diags(1.0 - free)).astype(dtype).tocsr()
    return k, load * free


def relres(k, f, u) -> float:
    """‖K u − F‖ / ‖F‖ in IEEE float64."""
    k64 = k.astype(np.float64)
    f64 = np.asarray(f, np.float64)
    r = k64 @ np.asarray(u, np.float64) - f64
    return float(np.linalg.norm(r) / np.linalg.norm(f64))


def max_relerr(a, b) -> float:
    """max |a − b| / max |b|, for sparse matrices or vectors."""
    if sp.issparse(b):
        d = (sp.csr_matrix(a, dtype=np.float64) - b.astype(np.float64)).data
        scale = np.abs(b.data).max()
    else:
        d = np.asarray(a, np.float64) - np.asarray(b, np.float64)
        scale = np.abs(b).max()
    return float(np.abs(d).max(initial=0.0) / scale)


def solve(k, f, tol=1e-10, maxiter=20000, dtype=np.float64) -> np.ndarray:
    """Jacobi-preconditioned CG in ``dtype``: the textbook algorithm that the
    configuration states, used as the precision control at float32."""
    k = k.astype(dtype)
    f = np.asarray(f, dtype)
    m = sp.diags((1.0 / k.diagonal()).astype(dtype))
    u, _ = spla.cg(k, f, rtol=tol, atol=0.0, maxiter=maxiter, M=m)
    return u
