"""Public jit'd wrappers for the Pallas kernels, with backend dispatch.

``interpret`` resolves from the active JAX backend
(:mod:`repro.kernels.interpret`: Mosaic on TPU, the DMA-emulating
interpreter elsewhere); tests pass ``interpret=`` explicitly.

Two SpMV memory plans back the ELL operators (kernel module docstring):
the broadcast plan (:func:`ell_matvec`) replicates ``x`` into VMEM per row
block — fastest while N fits; the streaming plan (:func:`ell_matvec_stream`)
keeps every operand HBM-resident with double-buffered DMA — VMEM use is
independent of N, so million-DOF solves fit.
"""

from __future__ import annotations

from .local_assembly import local_stiffness_p1
from .spmv_ell import (
    autotune_stream,
    galerkin_residual_ell,
    galerkin_residual_ell_stream,
    spmv_ell,
    spmv_ell_stream,
)

__all__ = [
    "batch_map_stiffness",
    "ell_matvec",
    "ell_residual",
    "ell_matvec_stream",
    "ell_residual_stream",
    "autotune_ell_stream",
]


def batch_map_stiffness(coords, rho, *, interpret: bool | None = None):
    """Stage-I Batch-Map for P1 simplices: (E,k,d),(E,) → (E,k,k)."""
    return local_stiffness_p1(coords, rho, interpret=interpret)


def ell_matvec(ell, x, *, interpret: bool | None = None):
    """SpMV on a :class:`repro.core.sparse.ELL` operator (broadcast plan).

    The static column table is staged (int32 cast + block padding + device
    transfer) once per layout inside the kernel module's id-keyed cache."""
    return spmv_ell(ell.vals, ell.cols, x, interpret=interpret)


def ell_residual(ell, u, f, *, interpret: bool | None = None):
    return galerkin_residual_ell(ell.vals, ell.cols, u, f, interpret=interpret)


def ell_matvec_stream(ell, x, *, interpret: bool | None = None,
                      block_n: int | None = None, nbuf: int | None = None):
    """Streaming SpMV on an ELL operator: HBM-resident ``x``, double-buffered
    ``vals``/``cols`` row blocks — N bounded by HBM, not VMEM."""
    kw = {}
    if block_n is not None:
        kw["block_n"] = block_n
    if nbuf is not None:
        kw["nbuf"] = nbuf
    return spmv_ell_stream(ell.vals, ell.cols, x, interpret=interpret, **kw)


def ell_residual_stream(ell, u, f, *, interpret: bool | None = None,
                        block_n: int | None = None, nbuf: int | None = None):
    """Fused streaming residual ``r = K·u − f`` on an ELL operator."""
    kw = {}
    if block_n is not None:
        kw["block_n"] = block_n
    if nbuf is not None:
        kw["nbuf"] = nbuf
    return galerkin_residual_ell_stream(ell.vals, ell.cols, u, f,
                                        interpret=interpret, **kw)


def autotune_ell_stream(ell, x, **kw):
    """Pick the fastest ``(block_n, nbuf)`` for this layout by measurement —
    results are cached and recorded through :mod:`repro.telemetry`."""
    return autotune_stream(ell.vals, ell.cols, x, **kw)
