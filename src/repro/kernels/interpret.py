"""Interpret-mode resolution shared by every Pallas kernel in this package.

The platform decides: Mosaic on a TPU, the Pallas interpreter everywhere
else (so CPU runs execute the same kernel bodies).  Tests that need the
other mode pass ``interpret=`` explicitly; there is no process-wide switch.
"""

from __future__ import annotations

import jax

__all__ = ["resolve_interpret"]


def resolve_interpret(interpret: bool | None = None) -> bool:
    """``interpret`` if given, else ``True`` exactly when JAX's default
    backend is not a TPU."""
    if interpret is not None:
        return bool(interpret)
    return jax.default_backend() != "tpu"
