"""Stage-II precompute: topology-aware routing for the Sparse-Reduce.

The paper's routing matrices ``S_mat ∈ {0,1}^{N_nnz × Ek²}`` and
``S_vec ∈ {0,1}^{N × Ek}`` have exactly one nonzero per column — i.e. they are
*functions* from local slots to global slots.  On TPU we realize them as a
sort-based deterministic reduction (see DESIGN.md §2):

* setup (numpy, once per mesh topology):  lexsort the ``Ek²`` COO coordinates,
  extract the unique CSR sparsity pattern, and store the permutation ``perm``
  plus sorted segment ids ``seg_ids``;
* runtime (jax, inside jit):  ``csr_vals = segment_sum(vec(K_local)[perm],
  seg_ids)`` — mathematically identical to ``S_mat · vec(K_local)``,
  deterministic, no atomics.

Three Reduce modes share these tables: "sorted" (above), "direct" (one
unsorted ``segment_sum``, i.e. one XLA scatter-add — the fastest on the
CPU) and "table", which gathers each segment's contributions through a
static padded table (:func:`segment_table`) and sums them densely, with no
scatter-add.  XLA:TPU has no native float64 scatter-add (it emulates one
with a serial two-word scatter), while a float64 gather is two native f32
gathers, so "table" is the default on a TPU (:func:`scatter_free`).

A vector-valued space routes its matrix Reduce through the vertex pattern
(:class:`BlockRouting`), with each vertex pair's block as a trailing axis.
"""

from __future__ import annotations

import dataclasses
from functools import cached_property

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "BlockRouting",
    "MatrixRouting",
    "VectorRouting",
    "build_block_routing",
    "build_matrix_routing",
    "build_vector_routing",
    "scatter_free",
    "segment_table",
    "table_segment_sum",
]


def scatter_free() -> bool:
    """Whether the Reduce and ``CSR.matvec`` avoid scatter-adds: on a TPU,
    where XLA emulates a float64 scatter-add serially (68–102 ms against
    6.9 ms in f32 for 700,000 updates on a v5e).  Elsewhere one scatter-add
    is the fastest lowering."""
    return jax.default_backend() == "tpu"


def segment_table(perm: np.ndarray, seg_ids: np.ndarray,
                  num_segments: int, size: int | None = None) -> np.ndarray:
    """``(L, num_segments)`` int32 positions of each segment's entries, from
    the sort permutation ``perm`` and the sorted ``seg_ids`` it produces;
    ``L`` is the largest segment and padding points past the end of the
    ``size`` gathered entries (default: as many as ``perm`` has), which
    :func:`table_segment_sum` gathers as zero.  Segments run along the minor
    axis: a TPU lays out a tiny minor axis of size L padded to 128 lanes,
    which made the summed gather take minutes to compile at 1M rows."""
    seg_ids = np.asarray(seg_ids)
    counts = np.bincount(seg_ids, minlength=num_segments)
    width = max(int(counts.max()) if counts.size else 0, 1)
    slot = np.arange(seg_ids.size) - (np.cumsum(counts) - counts)[seg_ids]
    table = np.full((width, num_segments),
                    seg_ids.size if size is None else size, dtype=np.int32)
    table[slot, seg_ids] = perm
    return table


def _staged(table: np.ndarray) -> jnp.ndarray:
    # a concrete device array even when first asked for inside a trace
    with jax.ensure_compile_time_eval():
        return jnp.asarray(table)


def table_segment_sum(v: jnp.ndarray, table: jnp.ndarray) -> jnp.ndarray:
    """Segment sums of the last axis of ``v`` over a :func:`segment_table`:
    one gather and a dense sum per segment."""
    return jnp.sum(jnp.take(v, table, axis=-1, mode="fill", fill_value=0),
                   axis=-2)


@dataclasses.dataclass(frozen=True)
class MatrixRouting:
    """Precomputed Sparse-Reduce for stiffness-matrix assembly.

    The numpy fields are the host-side precompute (consumed by further numpy
    setup: injections, condensers); the ``*_dev`` mirrors are the same arrays
    staged to device once at construction, so every assembly trace reuses one
    constant instead of re-staging an ``E·k²``-sized host array per trace.
    """

    num_dofs: int
    nnz: int
    indptr: np.ndarray       # (num_dofs + 1,) CSR row pointers
    indices: np.ndarray      # (nnz,) CSR column indices
    perm: np.ndarray         # (E*ka*kb,) sort permutation of local slots
    seg_ids: np.ndarray      # (E*ka*kb,) sorted segment ids (into nnz)
    seg_ids_unsorted: np.ndarray  # (E*ka*kb,) direct (scatter) segment ids
    row_of_nnz: np.ndarray   # (nnz,) row index of each stored entry
    diag_pos: np.ndarray     # (num_dofs,) position of (i,i) in vals, -1 if absent

    # device mirrors, staged on first use: a Reduce reads the ones of its
    # mode only
    @cached_property
    def perm_dev(self) -> jnp.ndarray:
        return _staged(self.perm)

    @cached_property
    def seg_ids_dev(self) -> jnp.ndarray:
        return _staged(self.seg_ids)

    @cached_property
    def seg_ids_unsorted_dev(self) -> jnp.ndarray:
        return _staged(self.seg_ids_unsorted)

    @cached_property
    def table_dev(self) -> jnp.ndarray:
        """(L, nnz) :func:`segment_table` of the local slots, on device."""
        return _staged(segment_table(self.perm, self.seg_ids, self.nnz))


@dataclasses.dataclass(frozen=True)
class VectorRouting:
    """Precomputed Sparse-Reduce for load-vector assembly (device mirrors as
    in :class:`MatrixRouting`)."""

    num_dofs: int
    perm: np.ndarray
    seg_ids: np.ndarray
    seg_ids_unsorted: np.ndarray
    touched: np.ndarray      # (n_touched,) global dofs receiving contributions

    def __post_init__(self):
        object.__setattr__(self, "perm_dev", jnp.asarray(self.perm))
        object.__setattr__(self, "seg_ids_dev", jnp.asarray(self.seg_ids))
        object.__setattr__(
            self, "seg_ids_unsorted_dev", jnp.asarray(self.seg_ids_unsorted)
        )
        object.__setattr__(self, "touched_dev", jnp.asarray(self.touched))

    @cached_property
    def spread_dev(self) -> jnp.ndarray:
        """(num_dofs,) position of each DoF in ``touched`` (past the end if
        untouched): spreads the packed sums by a gather, not a scatter."""
        spread = np.full(self.num_dofs, self.touched.shape[0], np.int32)
        spread[self.touched] = np.arange(self.touched.shape[0])
        return _staged(spread)

    @cached_property
    def table_dev(self) -> jnp.ndarray:
        """(L, n_touched) :func:`segment_table` of the local slots, on
        device."""
        return _staged(
            segment_table(self.perm, self.seg_ids, self.touched.shape[0]))


@dataclasses.dataclass(frozen=True, eq=False)
class BlockRouting:
    """Sparse-Reduce of a vector-valued space's local matrices through its
    vertex pattern.

    A space with ``block`` components per vertex numbers its DoFs
    ``vertex·block + component``, so each pair of vertices of a cell owns a
    dense ``block × block`` block of the local matrix.  The Reduce sums
    those blocks over the vertex pairs (``vertex``, a :class:`MatrixRouting`
    of the cells' vertices) with the block as a trailing axis — tables
    ``block²`` times smaller than a routing of each scalar entry's — and
    then places each summed entry at its position in the DoF pattern.
    ``num_dofs``, ``nnz``, ``indptr``, ``indices``, ``row_of_nnz`` and
    ``diag_pos`` are that DoF pattern, the one :func:`build_matrix_routing`
    gives for the DoFs: condensers and CSR matrices read them as they read
    a :class:`MatrixRouting`'s.
    """

    vertex: MatrixRouting
    block: int
    num_dofs: int
    nnz: int
    indptr: np.ndarray
    indices: np.ndarray
    row_of_nnz: np.ndarray
    diag_pos: np.ndarray
    position: np.ndarray     # (block, block, vertex nnz): DoF entry of each block entry
    cell_vertices: int       # vertices per cell

    def operands(self, mode: str) -> tuple:
        """The device arrays that the Reduce of ``mode`` reads (the
        assembly passes them to its program as operands)."""
        if mode == "table":
            return (*self._tables_dev, self._table_order_dev)
        if mode == "sorted":
            return (self.vertex.perm_dev, self.vertex.seg_ids_dev,
                    self._order_dev)
        return (self.vertex.seg_ids_unsorted_dev, self._order_dev)

    @cached_property
    def _tables_dev(self) -> tuple:
        """:func:`segment_table` of the diagonal blocks and of the others,
        each entry ``pair · E + cell`` of a contribution's cell and local
        vertex pair (``pair = a · nv + b``).  A diagonal block gathers the
        cells of a vertex's whole star, an off-diagonal one the few around
        an edge (on the hollow cube 24 and 6): one table would pad every
        block to the star's count."""
        v, nv = self.vertex, self.cell_vertices
        cells = v.perm.size // (nv * nv)
        cell, pair = np.divmod(v.perm, nv * nv)
        diag = v.row_of_nnz == v.indices
        tables = []
        for part in (diag, ~diag):
            keep = part[v.seg_ids]
            seg = (np.cumsum(part) - 1)[v.seg_ids[keep]]
            tables.append(_staged(segment_table(
                (pair * cells + cell)[keep], seg, int(part.sum()),
                size=v.perm.size)))
        return tuple(tables)

    @cached_property
    def _table_order_dev(self) -> jnp.ndarray:
        """The table Reduce's sums are ``(b², vertex nnz)``, the diagonal
        blocks first."""
        v, b = self.vertex, self.block
        diag = v.row_of_nnz == v.indices
        column = np.empty(v.nnz, np.int64)
        column[diag] = np.arange(int(diag.sum()))
        column[~diag] = int(diag.sum()) + np.arange(v.nnz - int(diag.sum()))
        return self._order(np.arange(b * b).reshape(b, b, 1) * v.nnz + column)

    @cached_property
    def _order_dev(self) -> jnp.ndarray:
        """The segment Reduces' sums are ``(vertex nnz, b²)``."""
        b = self.block
        return self._order(np.arange(self.vertex.nnz) * b * b
                           + np.arange(b * b).reshape(b, b, 1))

    def _order(self, entry: np.ndarray) -> jnp.ndarray:
        """(nnz,) position in the flattened block sums of each entry of the
        DoF pattern, from ``entry`` ``(b, b, vertex nnz)``: the position of
        each block entry."""
        order = np.empty(self.nnz, np.int32)
        order[self.position] = entry
        return _staged(order)

    @cached_property
    def seg_ids_unsorted(self) -> np.ndarray:
        """(E·k²,) DoF-pattern entry of each slot of the interleaved local
        matrices, as :class:`MatrixRouting` has them (the sharded
        Reduce's scatter ids)."""
        b, nv = self.block, self.cell_vertices
        p = self.vertex.seg_ids_unsorted.reshape(-1, nv, 1, nv, 1)
        a = np.arange(b)
        return self.position[a.reshape(1, 1, b, 1, 1),
                             a.reshape(1, 1, 1, 1, b), p].ravel()


def build_block_routing(vertex_dofs: np.ndarray, num_vertices: int,
                        block: int) -> BlockRouting:
    """:class:`BlockRouting` of cells with vertices ``vertex_dofs: (E, nv)``
    and ``block`` components per vertex (DoF ``vertex·block + component``)."""
    vertex = build_matrix_routing(vertex_dofs, None, num_vertices)
    b = block
    rows = vertex.row_of_nnz
    # DoF row b·i + a holds b entries per block of vertex row i, in order
    indptr = np.zeros(num_vertices * b + 1, np.int64)
    indptr[1:] = np.cumsum(np.repeat(b * np.diff(vertex.indptr), b))
    rank = np.arange(vertex.nnz) - vertex.indptr[rows]
    a = np.arange(b).reshape(b, 1, 1)
    c = np.arange(b).reshape(1, b, 1)
    position = indptr[b * rows + a] + b * rank + c      # (b, b, vertex nnz)
    nnz = b * b * vertex.nnz
    indices = np.empty(nnz, np.int64)
    indices[position] = b * vertex.indices + c
    row_of_nnz = np.empty(nnz, np.int64)
    row_of_nnz[position] = b * rows + a
    diag_v = vertex.diag_pos
    diag_pos = np.where(diag_v[:, None] >= 0,
                        position[np.arange(b), np.arange(b)][:, diag_v].T, -1)
    return BlockRouting(
        vertex=vertex, block=b, num_dofs=num_vertices * b, nnz=nnz,
        indptr=indptr, indices=indices, row_of_nnz=row_of_nnz,
        diag_pos=diag_pos.ravel(), position=position,
        cell_vertices=int(np.asarray(vertex_dofs).shape[1]))


def build_matrix_routing(
    row_dofs: np.ndarray, col_dofs: np.ndarray | None, num_dofs: int
) -> MatrixRouting:
    """Routing for local matrices with rows ``row_dofs: (E, ka)`` and columns
    ``col_dofs: (E, kb)`` (defaults to ``row_dofs`` — Galerkin)."""
    row_dofs = np.asarray(row_dofs, dtype=np.int64)
    col_dofs = row_dofs if col_dofs is None else np.asarray(col_dofs, dtype=np.int64)
    e, ka = row_dofs.shape
    kb = col_dofs.shape[1]

    rows = np.broadcast_to(row_dofs[:, :, None], (e, ka, kb)).ravel()
    cols = np.broadcast_to(col_dofs[:, None, :], (e, ka, kb)).ravel()
    key = rows * num_dofs + cols

    perm = np.argsort(key, kind="stable")
    sorted_key = key[perm]
    new_seg = np.empty(sorted_key.shape[0], dtype=bool)
    new_seg[0] = True
    new_seg[1:] = sorted_key[1:] != sorted_key[:-1]
    seg_ids = np.cumsum(new_seg) - 1
    nnz = int(seg_ids[-1]) + 1 if seg_ids.size else 0

    uniq_key = sorted_key[new_seg]
    uniq_rows = (uniq_key // num_dofs).astype(np.int64)
    uniq_cols = (uniq_key % num_dofs).astype(np.int64)
    indptr = np.zeros(num_dofs + 1, dtype=np.int64)
    np.add.at(indptr, uniq_rows + 1, 1)
    indptr = np.cumsum(indptr)

    seg_unsorted = np.empty_like(seg_ids)
    seg_unsorted[perm] = seg_ids

    diag_pos = -np.ones(num_dofs, dtype=np.int64)
    is_diag = uniq_rows == uniq_cols
    diag_pos[uniq_rows[is_diag]] = np.nonzero(is_diag)[0]

    return MatrixRouting(
        num_dofs=num_dofs,
        nnz=nnz,
        indptr=indptr,
        indices=uniq_cols,
        perm=perm,
        seg_ids=seg_ids,
        seg_ids_unsorted=seg_unsorted,
        row_of_nnz=uniq_rows,
        diag_pos=diag_pos,
    )


def build_vector_routing(row_dofs: np.ndarray, num_dofs: int) -> VectorRouting:
    """Routing for local vectors ``(E, k)`` onto a global ``(num_dofs,)``."""
    rows = np.asarray(row_dofs, dtype=np.int64).ravel()
    perm = np.argsort(rows, kind="stable")
    srt = rows[perm]
    new_seg = np.empty(srt.shape[0], dtype=bool)
    new_seg[0] = True
    new_seg[1:] = srt[1:] != srt[:-1]
    # segment ids index *touched* dofs, then scatter to the full vector once.
    seg_ids = np.cumsum(new_seg) - 1
    touched = srt[new_seg]
    seg_unsorted = np.empty_like(seg_ids)
    seg_unsorted[perm] = seg_ids
    return VectorRouting(
        num_dofs=num_dofs,
        perm=perm,
        seg_ids=seg_ids,
        seg_ids_unsorted=seg_unsorted,
        touched=touched,
    )
