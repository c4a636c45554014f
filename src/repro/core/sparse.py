"""Sparse containers (CSR / BatchedCSR) as jax pytrees, with SpMV/SpMM.

The CSR *pattern* (indptr/indices/row ids) is static numpy baked at setup —
only ``vals`` is traced, preserving the paper's O(1)-graph property: the
sparse operator participates in autodiff through a single dense value vector.
:class:`BatchedCSR` extends this to *families* of same-pattern operators:
one shared static pattern, ``(B, nnz)`` traced values — the container behind
``assemble_batched`` and the vmapped ``sparse_solve``.
"""

from __future__ import annotations

import dataclasses
import weakref
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import telemetry
from ..telemetry import annotate
from .routing import _staged, scatter_free, segment_table

__all__ = ["CSR", "BatchedCSR"]


# device mirrors of static numpy pattern arrays, keyed by id: staged to the
# device once instead of per traced call.  The numpy key array is kept alive
# by the strong reference so ids cannot be recycled while cached; the cache
# is FIFO-bounded because some callers mint fresh pattern arrays per call
# — eviction just costs a re-stage.
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


class BlockLayout(NamedTuple):
    """How :meth:`CSR.matvec` reaches a vector-valued operator's entries:
    as the ``b × b`` blocks of its vertex pairs.  ``table`` ``(b, b, K,
    n_v)`` holds the position in ``vals`` of entry (a, c) of vertex row
    i's k-th block (padding past the end, read as zero); ``cols`` ``(K,
    n_v)`` holds that block's vertex column (padding: i itself).  ``x`` is
    gathered once per vertex, not once per DoF entry."""

    table: jnp.ndarray
    cols: jnp.ndarray


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class BandLayout:
    """The banded form of :class:`BlockLayout`, for a vertex pattern whose
    pairs lie nearly all on a few vertex offsets: slot s of vertex row i is
    block ``(i, i + offsets[s])`` (a static tuple), zero where the pair is
    absent, so ``x`` is read as shifted slices.  The blocks are gathered
    through ``table`` as in :class:`BlockLayout` and put in their slots by
    ``slots`` ``(K, n_v)`` (-1: off the band).  The pairs off the band sit
    on the vertex rows ``rows`` alone: ``rem_table`` ``(b, b, K_r, n_r)``
    holds their positions in ``vals`` and ``rem_cols`` ``(K_r, n_r)`` their
    vertex columns (padding as in :class:`BlockLayout`); all three are
    ``None`` where every pair is on the band."""

    table: jnp.ndarray
    slots: jnp.ndarray
    rem_table: jnp.ndarray | None
    rem_cols: jnp.ndarray | None
    rows: jnp.ndarray | None
    offsets: tuple

    def tree_flatten(self):
        return ((self.table, self.slots, self.rem_table, self.rem_cols,
                 self.rows), self.offsets)

    @classmethod
    def tree_unflatten(cls, offsets, children):
        return cls(*children, offsets)


# a vertex offset takes a band slot where at least this share of the vertex
# rows holds it; a pattern is banded where its slots stay within this many
# times the column table's and the pairs off them within this share
_BAND_ROW_SHARE = 0.01
_BAND_MAX_SLOTS = 2.5
_BAND_MAX_REMAINDER = 0.05


def _row_layout(csr) -> RowLayout | BlockLayout | BandLayout:
    """The pattern's layout: for a vector-valued operator (``csr.block >
    1``) vertex bands where the vertex pattern is banded, vertex blocks
    otherwise; for a scalar one, diagonals when a square pattern has at
    most four times as many distinct column offsets as its widest row has
    entries, a column table otherwise.  Sets the gauge
    ``matvec_gathers{kind}``: the entries of the table that the layout
    gathers through, K × rows (vertex bands: the remainder's)."""
    key = ("row_layout", id(csr.row_of_nnz))
    hit = _DEVICE_MIRRORS.get(key)
    layout, kind, gathers = (
        hit[1] if hit is not None
        else _remember(key, csr.row_of_nnz, _build_row_layout(csr)))
    telemetry.gauge_set("matvec_gathers", gathers, kind=kind)
    return layout


def _build_row_layout(csr):
    """``(layout, kind, K × rows)`` of :func:`_row_layout`."""
    if csr.block > 1:
        layout = _block_layout(csr)
        if isinstance(layout, BlockLayout):
            return layout, "vertex_blocks", int(layout.cols.size)
        rem = 0 if layout.rows is None else int(layout.rem_cols.size)
        return layout, "vertex_bands", rem
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
        return layout, "diagonals", int(diag_table.size)
    # padding reads column i of a square operator (x_i − x_i = 0 in the
    # difference form), column 0 of any other
    pad = table >= rows.size
    pad_col = np.arange(csr.shape[0]) if square else np.zeros(
        csr.shape[0], np.int64)
    cols = np.where(pad, pad_col[None, :], indices[np.where(pad, 0, table)])
    layout = RowLayout(_staged(table), cols=_staged(cols.astype(np.int32)))
    return layout, "columns", int(table.size)


def _block_layout(csr) -> BlockLayout | BandLayout:
    """Layout of a pattern of full ``b × b`` blocks, whose DoF ``b·i + a``
    is component a of vertex i: the b rows of vertex i hold the same
    blocks, each b consecutive entries of a row.  A :class:`BandLayout`
    where :func:`_vertex_bands` finds the pattern banded."""
    b = csr.block
    n_v = csr.shape[0] // b
    indptr = np.asarray(csr.indptr)
    indices = np.asarray(csr.indices)
    start = indptr[:-1:b]                      # first entry of row b·i
    width = indptr[1::b] - start               # entries of each of its rows
    if not (csr.shape[0] == csr.shape[1] == n_v * b
            and np.array_equal(np.diff(indptr), np.repeat(width, b))
            and np.all(width % b == 0)):
        raise ValueError(f"not a pattern of {b} x {b} vertex blocks")
    k = np.arange(int(width.max()) // b)[:, None]
    valid = b * k < width                      # (K, n_v)
    first = np.where(valid, start + b * k, 0)
    cols = np.where(valid, indices[first] // b, np.arange(n_v))
    a = np.arange(b).reshape(b, 1, 1, 1)
    c = np.arange(b).reshape(1, b, 1, 1)
    table = np.where(valid, start + a * width + b * k + c, indices.size)
    bands = _vertex_bands(table, cols, valid, indices.size)
    if bands is not None:
        return bands
    return BlockLayout(_staged(table.astype(np.int32)),
                       _staged(cols.astype(np.int32)))


def _vertex_bands(table, cols, valid, nnz) -> BandLayout | None:
    """The :class:`BandLayout` of a vertex-block pattern of ``nnz`` stored
    entries, given as a :class:`BlockLayout`'s numpy ``table`` and ``cols``
    and the ``(K, n_v)`` mask of its blocks, or ``None`` where the pattern
    is not banded: its band slots would exceed ``_BAND_MAX_SLOTS`` × K, or
    the pairs off them ``_BAND_MAX_REMAINDER`` of all pairs (as on a mesh
    numbered at random)."""
    n_k, n_v = cols.shape
    off = cols - np.arange(n_v)
    held, rows_holding = np.unique(off[valid], return_counts=True)
    offsets = held[rows_holding >= _BAND_ROW_SHARE * n_v]
    band = valid & np.isin(off, offsets)
    rem = valid & ~band
    if (offsets.size > _BAND_MAX_SLOTS * n_k
            or rem.sum() > _BAND_MAX_REMAINDER * valid.sum()):
        return None
    slots = np.where(band, np.searchsorted(offsets, off), -1)
    rows = np.flatnonzero(rem.any(axis=0))
    rem_table = rem_cols = None
    if rows.size:
        # each remainder row's blocks off the band first, in column order
        k = np.argsort(~rem[:, rows], axis=0, kind="stable")[
            :int(rem.sum(axis=0).max())]
        on = rem[k, rows]
        rem_table = _staged(np.where(on, table[:, :, k, rows], nnz)
                            .astype(np.int32))
        rem_cols = _staged(np.where(on, cols[k, rows], rows)
                           .astype(np.int32))
        rows = _staged(rows.astype(np.int32))
    else:
        rows = None
    return BandLayout(_staged(table.astype(np.int32)),
                      _staged(slots.astype(np.int32)), rem_table, rem_cols,
                      rows, tuple(int(o) for o in offsets))


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


def block_values(vals, layout: BlockLayout | BandLayout) -> tuple:
    """What every vertex-block apply of ``vals`` reads, gathered once: the
    blocks in ``layout``'s order (``(..., b, b, K, n_v)``, zero where a
    row has fewer than K blocks; on a :class:`BandLayout` ``(..., b, b,
    S, n_v)`` by band slot), their row sums ``Σ_k A_ik`` ``(..., b, b,
    n_v)`` and, on a :class:`BandLayout` with pairs off the band, the
    remainder's blocks ``(..., b, b, K_r, n_r)``."""
    blocks = jnp.take(vals, layout.table, axis=-1, mode="fill", fill_value=0)
    sums = jnp.sum(blocks, axis=-2)
    if isinstance(layout, BlockLayout):
        return blocks, sums
    # each block into its slot by selects: a second gather of vals, at the
    # band's 2-3x the entries, would cost more than the first
    slot = jnp.arange(len(layout.offsets))[:, None]
    band = jnp.zeros(blocks.shape[:-2] + slot.shape[:1] + blocks.shape[-1:],
                     blocks.dtype)
    for k in range(blocks.shape[-2]):
        band = jnp.where(layout.slots[k] == slot, blocks[..., k:k + 1, :],
                         band)
    if layout.rows is None:
        return band, sums
    rem = jnp.take(vals, layout.rem_table, axis=-1, mode="fill",
                   fill_value=0)
    return band, sums, rem


def _block_rows(blocks, xc, xi):
    """``Σ_k A_ik (x_(c_k) − x_i)`` over b-vectors: ``blocks`` ``(..., b,
    b, K, m)``, ``xc`` ``(..., b, K, m)``, ``xi`` ``(..., b, m)``."""
    d = xc - xi[..., None, :]
    return jnp.sum(blocks * d[..., None, :, :, :], axis=(-3, -2))


@annotate("tg.spmv")
def block_layout_matvec(values, x, layout: BlockLayout | BandLayout):
    """``A x`` from the operator's :func:`block_values` in the difference
    form of :meth:`CSR.matvec`: ``y_i = Σ_k A_ik (x_(c_k) − x_i) + (Σ_k
    A_ik) x_i`` over 3-vectors (b-vectors) ``x_i`` and blocks ``A_ik``.
    ``x_(c_k)`` is gathered through a :class:`BlockLayout`'s columns; on a
    :class:`BandLayout` it is a shifted slice of ``x``, gathered only for
    the remainder's pairs, whose rows are then added in."""
    blocks, sums = values[:2]
    b, n_v = blocks.shape[-4], blocks.shape[-1]
    xv = jnp.swapaxes(x.reshape(x.shape[:-1] + (n_v, b)), -1, -2)
    if isinstance(layout, BlockLayout):
        xc = jnp.take(xv, layout.cols, axis=-1)         # (..., c, K, i)
    else:
        xc = _shifted(xv, layout.offsets)               # (..., c, S, i)
    y = (_block_rows(blocks, xc, xv)
         + jnp.sum(sums * xv[..., None, :, :], axis=-2))
    if isinstance(layout, BandLayout) and layout.rows is not None:
        rem = _block_rows(values[2], jnp.take(xv, layout.rem_cols, axis=-1),
                          jnp.take(xv, layout.rows, axis=-1))
        y = y.at[..., layout.rows].add(rem, indices_are_sorted=True,
                                       unique_indices=True)
    return jnp.swapaxes(y, -1, -2).reshape(x.shape)


_block_values = jax.jit(block_values)


@jax.jit
def _block_matvec(vals, x, layout: BlockLayout | BandLayout):
    return block_layout_matvec(block_values(vals, layout), x, layout)


def clear_device_mirrors():
    """Release every cached (host, device) pattern-array pair, row layout
    and operator diagonal — part of the ``repro.core.clear_assembly_caches``
    memory-release path."""
    _DEVICE_MIRRORS.clear()
    _DIAGONALS.clear()


# operator diagonals keyed by (operator identity, dtype): the Jacobi
# preconditioner asks for ``.diagonal()`` on every solve, but for a CSR the
# diagonal is a fixed gather of ``vals`` and for a matrix-free operator a
# diagonal-only assembly — both pure functions of the anchor array's values.
# Keyed on the *value anchor* (``vals`` for CSR, the operator object for
# matrix-free), held by a weak reference whose death drops the entry, so
# ids cannot be recycled while cached (a strong reference where the anchor
# takes none); same FIFO bound rationale as the device mirrors above.
_DIAGONALS: dict[tuple[int, str], tuple[object, jnp.ndarray]] = {}
_DIAGONALS_LIMIT = 256


def cached_diagonal(op) -> jnp.ndarray:
    """``op.diagonal()`` memoized per (operator identity, dtype).

    The cache key anchors on ``op.vals`` when present (a :class:`CSR`
    rebuilt around the same value buffer shares the diagonal)
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
    try:
        # the entry lives as long as its anchor: a solve loop that
        # assembles new values each step would otherwise keep every step's
        # values on the device (at a million DoFs, 362 MB a step)
        anchor = weakref.ref(anchor, lambda _, k=key: _DIAGONALS.pop(k, None))
    except TypeError:
        pass
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
    block: int = 1               # static: components per vertex (value_size)

    # -- pytree ----------------------------------------------------------
    def tree_flatten(self):
        aux = (self.indptr, self.indices, self.row_of_nnz, self.shape,
               self.diag_pos, self.block)
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
        ``K u`` drops from 2.0e-10 to 1.2e-11 of ``‖f‖`` at 1M DOFs).

        A vector-valued operator (``block > 1``) is applied by vertex
        blocks on every platform, in the same difference form: on a banded
        vertex pattern as shifted slices of ``x`` with a gather for the
        few pairs off the band (:class:`BandLayout`), else through a
        gather of ``x`` by vertex column (:class:`BlockLayout`)."""
        if self.block > 1:
            return _block_matvec(self.vals, x, _row_layout(self))
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

    def loop_matvec(self):
        """``x ↦ A x`` for a loop that applies A many times (a Krylov
        solve): a vector-valued operator's blocks and their row sums are
        laid out from ``vals`` once, here, rather than in every apply."""
        if self.block == 1:
            return self.matvec
        layout = _row_layout(self)
        values = _block_values(self.vals, layout)
        return lambda x: block_layout_matvec(values, x, layout)

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
    block: int = 1               # static, as in :class:`CSR`

    # -- pytree ----------------------------------------------------------
    def tree_flatten(self):
        aux = (self.indptr, self.indices, self.row_of_nnz, self.shape,
               self.diag_pos, self.block)
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
            block=first.block,
        )

    def as_csr(self) -> CSR:
        """Reinterpret as a single :class:`CSR` sharing this pattern — valid
        when ``vals`` is one instance's ``(nnz,)`` slice (e.g. inside a
        ``vmap`` over the batch axis)."""
        return self._one(self.vals)

    def __getitem__(self, b):
        """Integer index → one instance as a :class:`CSR`; slice → the
        sub-family as a :class:`BatchedCSR`."""
        if isinstance(b, (int, np.integer)):
            return self._one(self.vals[b])
        if isinstance(b, slice):
            return dataclasses.replace(self, vals=self.vals[b])
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
                   self.shape, self.diag_pos, self.block)

    def diagonal(self) -> jnp.ndarray:
        assert self.diag_pos is not None, "diagonal positions not precomputed"
        dp = _dev(self.diag_pos)
        return jnp.where(dp >= 0, self.vals[:, jnp.clip(dp, 0)], 0.0)

    def to_dense(self) -> jnp.ndarray:
        out = jnp.zeros((self.batch,) + self.shape, dtype=self.vals.dtype)
        return out.at[:, _dev(self.row_of_nnz), _dev(self.indices)].set(self.vals)

