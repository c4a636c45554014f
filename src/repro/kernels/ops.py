"""Public wrappers for the Pallas kernels.

``interpret`` resolves from the active JAX backend
(:mod:`repro.kernels.interpret`: Mosaic on TPU, the interpreter
elsewhere); tests pass ``interpret=`` explicitly.
"""

from __future__ import annotations

from .local_assembly import local_stiffness_p1

__all__ = ["batch_map_stiffness"]


def batch_map_stiffness(coords, rho, *, interpret: bool | None = None):
    """Stage-I Batch-Map for P1 simplices: (E,k,d),(E,) → (E,k,k)."""
    return local_stiffness_p1(coords, rho, interpret=interpret)
