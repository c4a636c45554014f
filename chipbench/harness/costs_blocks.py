"""Least bytes of the vector-valued configuration's two device phases: the
algorithm's traffic, not any one implementation's.

A CG iteration on a matrix of ``b × b`` blocks (``n`` rows, ``n / b``
vertex rows, ``nnz`` stored entries, ``nnz / b²`` blocks) reads every
stored value once, one column index per block and the ``n / b + 1``
vertex-row pointers, and makes the 14 vector reads and 5 vector writes of
textbook Jacobi-PCG (``harness/costs.py``).

An assembly reads the vertex coordinates and the cell table once and
writes the stored values and the load once.
"""

from __future__ import annotations

from .costs import VECTOR_READS, VECTOR_WRITES


def jacobi_pcg_block_iteration_bytes(n: int, nnz: int, block: int = 3,
                                     value_bytes: int = 8,
                                     index_bytes: int = 4) -> int:
    matrix = (nnz * value_bytes + nnz // (block * block) * index_bytes
              + (n // block + 1) * index_bytes)
    return matrix + (VECTOR_READS + VECTOR_WRITES) * n * value_bytes


def assembly_bytes(points: int, cells: int, cell_vertices: int, dim: int,
                   n: int, nnz: int, value_bytes: int = 8,
                   index_bytes: int = 4) -> int:
    reads = points * dim * value_bytes + cells * cell_vertices * index_bytes
    return reads + (nnz + n) * value_bytes
