"""Sparse containers (CSR / ELL / BatchedCSR) as jax pytrees, with SpMV/SpMM.

The CSR *pattern* (indptr/indices/row ids) is static numpy baked at setup —
only ``vals`` is traced, preserving the paper's O(1)-graph property: the
sparse operator participates in autodiff through a single dense value vector.
:class:`BatchedCSR` extends this to *families* of same-pattern operators:
one shared static pattern, ``(B, nnz)`` traced values — the container behind
``assemble_batched`` and the vmapped ``sparse_solve``.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..telemetry import annotate
from .routing import _staged, scatter_free, segment_table

__all__ = ["CSR", "ELL", "BatchedCSR", "csr_to_ell", "ell_layout"]


# device mirrors of static numpy pattern arrays, keyed by id: staged to the
# device once instead of per traced call.  The numpy key array is kept alive
# by the strong reference so ids cannot be recycled while cached; the cache
# is FIFO-bounded because some callers mint fresh pattern arrays per call
# (e.g. csr_to_ell cols in a solve loop) — eviction just costs a re-stage.
# The CSR row layouts derived from a pattern array live here too, under a
# ("row_layout", id) key.
_DEVICE_MIRRORS: dict[object, tuple[np.ndarray, object]] = {}
_DEVICE_MIRRORS_LIMIT = 512


def _remember(key, anchor, value):
    while len(_DEVICE_MIRRORS) >= _DEVICE_MIRRORS_LIMIT:
        _DEVICE_MIRRORS.pop(next(iter(_DEVICE_MIRRORS)))
    _DEVICE_MIRRORS[key] = (anchor, value)
    return value


def _dev(x) -> jnp.ndarray:
    if isinstance(x, jnp.ndarray):
        return x
    hit = _DEVICE_MIRRORS.get(id(x))
    if hit is not None:
        return hit[1]
    arr = jnp.asarray(x)
    if isinstance(arr, jax.core.Tracer):
        return arr  # converted inside a trace: constant-folded there, not cached
    return _remember(id(x), x, arr)


class RowLayout(NamedTuple):
    """How :meth:`CSR.matvec` reaches each row's entries, by gathers only.

    ``table`` ``(K, rows)`` holds positions into ``vals`` (padding past the
    end, read as zero).  With ``offsets`` (a static tuple) slot k of row i
    is column ``i + offsets[k]`` — the few diagonals of a banded pattern,
    read as shifted slices of ``x``; otherwise ``cols`` ``(K, rows)`` holds
    the columns (padding: i itself) and ``x`` is gathered.
    """

    table: jnp.ndarray
    cols: jnp.ndarray | None = None
    offsets: tuple | None = None


def _row_layout(csr) -> RowLayout:
    """The pattern's :class:`RowLayout`: diagonals when a square pattern has
    at most four times as many distinct column offsets as its widest row
    has entries, a column table otherwise."""
    key = ("row_layout", id(csr.row_of_nnz))
    hit = _DEVICE_MIRRORS.get(key)
    if hit is not None:
        return hit[1]
    rows = np.asarray(csr.row_of_nnz)
    indices = np.asarray(csr.indices)
    offsets = np.unique(indices - rows)
    table = segment_table(np.arange(rows.size), rows, csr.shape[0])
    square = csr.shape[0] == csr.shape[1]
    if square and offsets.size <= 4 * table.shape[0]:
        diag_table = np.full((offsets.size, csr.shape[0]), rows.size,
                             dtype=np.int32)
        diag_table[np.searchsorted(offsets, indices - rows), rows] = \
            np.arange(rows.size)
        layout = RowLayout(_staged(diag_table),
                           offsets=tuple(int(o) for o in offsets))
    else:
        # padding reads column i of a square operator (x_i − x_i = 0 in the
        # difference form), column 0 of any other
        pad = table >= rows.size
        pad_col = np.arange(csr.shape[0]) if square else np.zeros(
            csr.shape[0], np.int64)
        cols = np.where(pad, pad_col[None, :], indices[np.where(pad, 0, table)])
        layout = RowLayout(_staged(table), cols=_staged(cols.astype(np.int32)))
    return _remember(key, csr.row_of_nnz, layout)


def _shifted(x: jnp.ndarray, offsets: tuple) -> jnp.ndarray:
    """``(..., K, n)``: row k holds ``x[i + offsets[k]]`` (zero outside)."""
    n = x.shape[-1]
    lo, hi = max(0, -min(offsets)), max(0, max(offsets))
    xp = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(lo, hi)])
    return jnp.stack([jax.lax.slice_in_dim(xp, lo + o, lo + o + n, axis=-1)
                      for o in offsets], axis=-2)


@annotate("tg.spmv")
def row_layout_matvec(vals, x, layout: RowLayout, *, square: bool = True):
    """The :meth:`CSR.matvec` arithmetic on a :class:`RowLayout` (whose
    arrays may be traced)."""
    a = jnp.take(vals, layout.table, axis=-1, mode="fill", fill_value=0)
    if layout.offsets is not None:
        xc = _shifted(x, layout.offsets)
    else:
        xc = jnp.take(x, layout.cols, axis=-1)
    if not square:
        return jnp.sum(a * xc, axis=-2)
    return jnp.sum(a * (xc - x[..., None, :]), axis=-2) + jnp.sum(a, axis=-2) * x


def clear_device_mirrors():
    """Release every cached (host, device) pattern-array pair, ELL layout and
    operator diagonal — part of the ``repro.core.clear_assembly_caches``
    memory-release path."""
    _DEVICE_MIRRORS.clear()
    _ELL_LAYOUTS.clear()
    _DIAGONALS.clear()


# operator diagonals keyed by (operator identity, dtype): the Jacobi
# preconditioner asks for ``.diagonal()`` on every solve, but for a CSR the
# diagonal is a fixed gather of ``vals`` and for a matrix-free operator a
# diagonal-only assembly — both pure functions of the anchor array's values.
# Keyed on the *value anchor* (``vals`` for CSR, the operator object for
# matrix-free), with a strong reference so ids cannot be recycled while
# cached; same FIFO bound rationale as the device mirrors above.
_DIAGONALS: dict[tuple[int, str], tuple[object, jnp.ndarray]] = {}
_DIAGONALS_LIMIT = 256


def cached_diagonal(op) -> jnp.ndarray:
    """``op.diagonal()`` memoized per (operator identity, dtype).

    The cache key anchors on ``op.vals`` when present (a :class:`CSR` /
    :class:`ELL` rebuilt around the same value buffer shares the diagonal)
    and on the operator object otherwise.  Tracers are never cached: inside
    a trace the diagonal is part of the jaxpr and caching by ``id`` would
    leak abstract values across traces.
    """
    anchor = getattr(op, "vals", None)
    if anchor is None:
        anchor = op
    if any(
        isinstance(leaf, jax.core.Tracer)
        for leaf in jax.tree_util.tree_leaves(op)
    ):
        return op.diagonal()
    dtype = getattr(anchor, "dtype", None)
    if dtype is None:
        leaves = jax.tree_util.tree_leaves(op)
        dtype = getattr(leaves[0], "dtype", None) if leaves else None
    key = (id(anchor), str(dtype))
    hit = _DIAGONALS.get(key)
    if hit is not None:
        return hit[1]
    d = op.diagonal()
    if isinstance(d, jax.core.Tracer):
        return d
    while len(_DIAGONALS) >= _DIAGONALS_LIMIT:
        _DIAGONALS.pop(next(iter(_DIAGONALS)))
    _DIAGONALS[key] = (anchor, d)
    return d


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class CSR:
    vals: jnp.ndarray            # (nnz,) traced
    indptr: np.ndarray           # static
    indices: np.ndarray          # static
    row_of_nnz: np.ndarray       # static, (nnz,)
    shape: tuple[int, int]       # static
    diag_pos: np.ndarray | None = None  # static

    # -- pytree ----------------------------------------------------------
    def tree_flatten(self):
        aux = (self.indptr, self.indices, self.row_of_nnz, self.shape, self.diag_pos)
        return (self.vals,), aux

    @classmethod
    def tree_unflatten(cls, aux, children):
        (vals,) = children
        return cls(vals, *aux)

    # -- ops ---------------------------------------------------------------
    def matvec(self, x: jnp.ndarray) -> jnp.ndarray:
        """y = A @ x: gather + sorted segment-sum, or where
        :func:`~repro.core.routing.scatter_free` (a TPU) row by row
        through the static row layout — gathers and dense sums only.  On a
        banded pattern the rows are shifted slices of ``x``, not a gather
        of it (a 1M-DOF CG iteration on a TPU v5e: 16.7 ms against 146 ms).
        A square operator is then applied as ``y_i = Σ_j a_ij (x_j − x_i) +
        (Σ_j a_ij) x_i``: a stiffness row's O(1) terms would otherwise
        cancel down to its O(h²) result, which costs the digits that
        XLA:TPU's float64 emulation does not carry (there the error of
        ``K u`` drops from 2.0e-10 to 1.2e-11 of ``‖f‖`` at 1M DOFs)."""
        if scatter_free():
            return row_layout_matvec(self.vals, x, _row_layout(self),
                                     square=self.shape[0] == self.shape[1])
        with annotate("tg.spmv"):
            contrib = self.vals * x[_dev(self.indices)]
            return jax.ops.segment_sum(
                contrib,
                _dev(self.row_of_nnz),
                num_segments=self.shape[0],
                indices_are_sorted=True,
            )

    def rmatvec(self, x: jnp.ndarray) -> jnp.ndarray:
        """y = A.T @ x (scatter over columns)."""
        contrib = self.vals * x[_dev(self.row_of_nnz)]
        return jax.ops.segment_sum(
            contrib, _dev(self.indices), num_segments=self.shape[1]
        )

    def matmat(self, x: jnp.ndarray) -> jnp.ndarray:
        """Y = A @ X for X (n, b) — batched multi-RHS SpMM."""
        contrib = self.vals[:, None] * x[_dev(self.indices)]
        return jax.ops.segment_sum(
            contrib,
            _dev(self.row_of_nnz),
            num_segments=self.shape[0],
            indices_are_sorted=True,
        )

    def diagonal(self) -> jnp.ndarray:
        assert self.diag_pos is not None, "diagonal positions not precomputed"
        dp = _dev(self.diag_pos)
        return jnp.where(dp >= 0, self.vals[jnp.clip(dp, 0)], 0.0)

    def to_dense(self) -> jnp.ndarray:
        out = jnp.zeros(self.shape, dtype=self.vals.dtype)
        return out.at[self.row_of_nnz, self.indices].set(self.vals)

    def to_scipy(self):
        import scipy.sparse as sp

        return sp.csr_matrix(
            (np.asarray(self.vals), np.asarray(self.indices), np.asarray(self.indptr)),
            shape=self.shape,
        )

    @property
    def nnz(self) -> int:
        return int(self.indices.shape[0])


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class BatchedCSR:
    """B same-pattern sparse operators: shared static pattern, ``(B, nnz)``
    traced values — produced by ``assemble_batched`` over a family of
    coefficient sets / geometries.

    The aux layout is identical to :class:`CSR`, so condensers and other
    vals-elementwise transforms apply unchanged (masks broadcast over the
    batch axis), and ``jax.vmap(fn, in_axes=0)`` over a ``BatchedCSR`` hands
    ``fn`` a per-instance slice — :meth:`as_csr` converts that slice to a
    :class:`CSR` for single-instance code (solvers, integrators).
    """

    vals: jnp.ndarray            # (B, nnz) traced
    indptr: np.ndarray           # static (shared by all instances)
    indices: np.ndarray          # static
    row_of_nnz: np.ndarray       # static, (nnz,)
    shape: tuple[int, int]       # static, per-instance shape
    diag_pos: np.ndarray | None = None  # static

    # -- pytree ----------------------------------------------------------
    def tree_flatten(self):
        aux = (self.indptr, self.indices, self.row_of_nnz, self.shape, self.diag_pos)
        return (self.vals,), aux

    @classmethod
    def tree_unflatten(cls, aux, children):
        (vals,) = children
        return cls(vals, *aux)

    # -- constructors / views ---------------------------------------------
    @classmethod
    def stack(cls, csrs) -> "BatchedCSR":
        """Stack same-pattern :class:`CSR` instances along a new batch axis.

        Patterns must actually match (content, not just nnz count) — two
        different meshes can share an nnz by coincidence, and pairing one
        pattern with the other's values would be silently wrong.
        """
        csrs = list(csrs)
        first = csrs[0]
        for c in csrs[1:]:
            same = c.shape == first.shape and (
                c.indices is first.indices
                or (
                    np.array_equal(c.indices, first.indices)
                    and np.array_equal(c.indptr, first.indptr)
                )
            )
            if not same:
                raise ValueError(
                    "BatchedCSR.stack: CSR sparsity patterns differ — all "
                    "instances must share one (mesh topology × space) pattern"
                )
        return cls(
            vals=jnp.stack([c.vals for c in csrs]),
            indptr=first.indptr,
            indices=first.indices,
            row_of_nnz=first.row_of_nnz,
            shape=first.shape,
            diag_pos=first.diag_pos,
        )

    def as_csr(self) -> CSR:
        """Reinterpret as a single :class:`CSR` sharing this pattern — valid
        when ``vals`` is one instance's ``(nnz,)`` slice (e.g. inside a
        ``vmap`` over the batch axis)."""
        return CSR(self.vals, self.indptr, self.indices, self.row_of_nnz,
                   self.shape, self.diag_pos)

    def __getitem__(self, b):
        """Integer index → one instance as a :class:`CSR`; slice → the
        sub-family as a :class:`BatchedCSR`."""
        if isinstance(b, (int, np.integer)):
            return CSR(self.vals[b], self.indptr, self.indices,
                       self.row_of_nnz, self.shape, self.diag_pos)
        if isinstance(b, slice):
            return BatchedCSR(self.vals[b], self.indptr, self.indices,
                              self.row_of_nnz, self.shape, self.diag_pos)
        raise TypeError(
            f"BatchedCSR indices must be int or slice, got {type(b).__name__}"
        )

    @property
    def batch(self) -> int:
        return int(self.vals.shape[0])

    @property
    def nnz(self) -> int:
        return int(self.indices.shape[0])

    # -- ops ---------------------------------------------------------------
    def matvec(self, x: jnp.ndarray) -> jnp.ndarray:
        """Y_b = A_b @ x_b for ``x: (B, n)`` (``(n,)`` broadcasts across the
        batch) — one vmapped gather + segment-sum, under
        :meth:`CSR.matvec`'s ``tg.spmv`` scope."""
        in_x = None if x.ndim == 1 else 0
        return jax.vmap(lambda v, xi: self._one(v).matvec(xi),
                        in_axes=(0, in_x))(self.vals, x)

    def _one(self, vals) -> CSR:
        return CSR(vals, self.indptr, self.indices, self.row_of_nnz,
                   self.shape, self.diag_pos)

    def diagonal(self) -> jnp.ndarray:
        assert self.diag_pos is not None, "diagonal positions not precomputed"
        dp = _dev(self.diag_pos)
        return jnp.where(dp >= 0, self.vals[:, jnp.clip(dp, 0)], 0.0)

    def to_dense(self) -> jnp.ndarray:
        out = jnp.zeros((self.batch,) + self.shape, dtype=self.vals.dtype)
        return out.at[:, _dev(self.row_of_nnz), _dev(self.indices)].set(self.vals)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class ELL:
    """ELLPACK: fixed nnz-per-row padded format — the TPU-friendly layout
    consumed by the Pallas SpMV kernel (bounded valence of FEM meshes)."""

    vals: jnp.ndarray        # (n, L) traced, zero-padded
    cols: np.ndarray         # (n, L) static, padded with row index (self-loop)
    shape: tuple[int, int]

    def tree_flatten(self):
        return (self.vals,), (self.cols, self.shape)

    @classmethod
    def tree_unflatten(cls, aux, children):
        (vals,) = children
        return cls(vals, *aux)

    def matvec(self, x: jnp.ndarray) -> jnp.ndarray:
        return jnp.sum(self.vals * x[_dev(self.cols)], axis=1)


# static ELL layouts keyed by pattern identity: the padded column table and
# nnz→slot map depend only on (indptr, indices), so deriving them per call
# (as the old per-call-site conversions did) redid an O(nnz) numpy sort-free
# pass on every solve.  Strong references to the key arrays keep ids stable;
# FIFO-bounded like the device mirrors.
_ELL_LAYOUTS: dict[int, tuple] = {}
_ELL_LAYOUTS_LIMIT = 128


def ell_layout(csr: CSR) -> tuple[np.ndarray, np.ndarray, int]:
    """Static ELL layout of a CSR pattern: ``(cols, flat_pos, L)`` — cached
    per pattern identity so repeated conversions only pay the runtime value
    scatter."""
    hit = _ELL_LAYOUTS.get(id(csr.indices))
    if hit is not None:
        return hit[1]
    n = csr.shape[0]
    counts = np.diff(csr.indptr)
    L = int(counts.max()) if counts.size else 1
    # int32 at staging time: the Pallas kernels index with int32, and casting
    # here (once per pattern) removes the per-matvec convert from solve loops
    cols = np.repeat(np.arange(n, dtype=np.int32)[:, None], L, axis=1)
    slot = np.concatenate([np.arange(c) for c in counts]) if counts.size else np.array([], np.int64)
    rows_of = np.asarray(csr.row_of_nnz)
    cols[rows_of, slot] = np.asarray(csr.indices)
    flat_pos = rows_of * L + slot
    layout = (cols, flat_pos, L)
    if isinstance(csr.indices, np.ndarray):
        while len(_ELL_LAYOUTS) >= _ELL_LAYOUTS_LIMIT:
            _ELL_LAYOUTS.pop(next(iter(_ELL_LAYOUTS)))
        _ELL_LAYOUTS[id(csr.indices)] = (csr.indices, layout)
    return layout


def csr_to_ell(csr: CSR) -> ELL:
    cols, flat_pos, L = ell_layout(csr)
    n = csr.shape[0]
    # runtime scatter of vals into the padded layout (static slot map)
    vals = jnp.zeros((n * L,), dtype=csr.vals.dtype).at[_dev(flat_pos)].set(csr.vals)
    return ELL(vals.reshape(n, L), cols, csr.shape)
