"""Bytes that textbook Jacobi-preconditioned CG moves per iteration on a CSR
matrix: the algorithm's traffic, not any one implementation's.

Per iteration, with ``n`` rows and ``nnz`` stored entries:

* ``q = A p``: every stored value (``value_bytes``) and column index
  (``index_bytes``) once, the ``n + 1`` row pointers, ``p`` read, ``q``
  written;
* ``α = ρ / (p·q)``: ``p``, ``q`` read;
* ``x += α p``: ``x``, ``p`` read, ``x`` written;
* ``r −= α q``: ``r``, ``q`` read, ``r`` written;
* ``z = D⁻¹ r``: ``r``, ``D⁻¹`` read, ``z`` written;
* ``ρ' = r·z``: ``r``, ``z`` read;
* ``‖r‖`` for the stopping test: ``r`` read;
* ``p = z + β p``: ``z``, ``p`` read, ``p`` written.

That is 14 vector reads and 5 vector writes besides the matrix.  A
matrix-free or stencil path may legitimately move fewer bytes; the count is
then for the benchmark to correct, not for the program to beat.
"""

from __future__ import annotations

VECTOR_READS = 14
VECTOR_WRITES = 5


def jacobi_pcg_iteration_bytes(n: int, nnz: int, value_bytes: int = 8,
                               index_bytes: int = 4) -> int:
    matrix = nnz * (value_bytes + index_bytes) + (n + 1) * index_bytes
    vectors = (VECTOR_READS + VECTOR_WRITES) * n * value_bytes
    return matrix + vectors
