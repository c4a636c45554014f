"""Unified matvec-backend registry — ONE dispatch point for the inner loop.

Every Krylov solve, θ/Newmark rollout, residual loss and problem ``.solve``
ultimately spends its time in ``y = A @ x``.  Historically each consumer
re-derived its own dispatch (``transient.stepping.make_matvec``, the
``use_ell`` flag in ``fem.tensormesh``, ad-hoc ``csr_to_ell`` call sites);
this module is the single registry they all consume:

=============  =============================================================
backend        apply path
=============  =============================================================
``csr``        gather + sorted segment-sum on the assembled values
               (differentiable; the adjoint-solve default)
``ell``        padded ELLPACK gather, pure jnp (bounded-valence FEM layout)
``ell_pallas`` the Pallas TPU SpMV kernel over the ELL layout (broadcast
               plan: ``x`` replicated into VMEM per row block)
``ell_stream`` the streaming Pallas SpMV: ``x`` stays HBM-resident, row
               blocks double-buffered through VMEM by async DMA — VMEM use
               is independent of N, so million-DOF operators fit
``matfree``    element-local Map → per-element action → scatter-Reduce,
               no global values (:mod:`repro.core.operator`)
``matfree_sharded``  the matrix-free apply ``shard_map``-partitioned over
               the element axis of the local device mesh: per-device
               partial scatter + one psum — a single Krylov solve spans
               all devices (:class:`repro.core.operator.ShardedMatFreeOperator`)
=============  =============================================================

The two Pallas backends do not compile for a TPU today (Mosaic rejects
their in-kernel 1-D gather), so on a TPU backend they raise
``NotImplementedError`` with the compiler's reason instead of building a
kernel that cannot run; off-TPU they run in the Pallas interpreter.

``make_matvec(op, backend)`` returns the apply closure;
``make_residual(op, backend)`` returns the fused ``(u, f) ↦ K·u − f``
(the ``ell_pallas`` variant runs the fused
:func:`repro.kernels.ell_residual` kernel — one pass, no extra HBM
round-trip).  Third-party backends register with
:func:`register_matvec_backend`.
"""

from __future__ import annotations

from typing import Callable

import jax

from .. import telemetry
from .sparse import CSR, csr_to_ell

__all__ = [
    "MATVEC_BACKENDS",
    "matvec_backends",
    "register_matvec_backend",
    "make_matvec",
    "make_residual",
]


def _require_csr(op, backend: str) -> CSR:
    if not isinstance(op, CSR):
        raise TypeError(
            f"backend {backend!r} needs an assembled CSR operator, got "
            f"{type(op).__name__} — assemble first, or use backend='matfree'"
        )
    return op


def _require_matfree(op):
    from .operator import LinearOperator

    if isinstance(op, CSR):
        raise TypeError(
            "backend 'matfree' needs a matrix-free operator: build one with "
            "repro.core.matfree_operator(plan, form) instead of assembling"
        )
    if not isinstance(op, LinearOperator):
        raise TypeError(
            f"backend 'matfree' needs a LinearOperator, got {type(op).__name__}"
        )
    return op


# what the TPU compiler (Mosaic, JAX 0.9.0, v5e) answers for each Pallas
# backend's kernel — compiled for a described v5e topology
_MOSAIC_REFUSALS = {
    "ell_pallas": "Mosaic rejects the kernel's 1-D gather x[cols] "
                  "('NotImplementedError: Only 2D gather is supported' in "
                  "f32; 'ZeroDivisionError: integer modulo by zero' in the "
                  "f64 lowering)",
    "ell_stream": "Mosaic rejects the kernel's 1-D gather x[cols] "
                  "('NotImplementedError: Only 2D gather is supported', f32 "
                  "and f64)",
}


def _refuse_on_tpu(backend: str) -> None:
    if jax.default_backend() == "tpu":
        raise NotImplementedError(
            f"matvec backend {backend!r} does not compile for a TPU: "
            f"{_MOSAIC_REFUSALS[backend]}; use 'csr', 'ell' or 'matfree'"
        )


def _csr_matvec(op) -> Callable:
    # CSR / BatchedCSR / LinearOperator all expose matvec; a CSR lays out
    # what its applies share once
    return op.loop_matvec() if isinstance(op, CSR) else op.matvec


def _ell_matvec(op) -> Callable:
    return csr_to_ell(_require_csr(op, "ell")).matvec


def _ell_pallas_matvec(op) -> Callable:
    _refuse_on_tpu("ell_pallas")
    from ..kernels import ell_matvec

    ell = csr_to_ell(_require_csr(op, "ell_pallas"))
    return lambda x: ell_matvec(ell, x)


def _ell_stream_matvec(op) -> Callable:
    _refuse_on_tpu("ell_stream")
    from ..kernels import ell_matvec_stream

    ell = csr_to_ell(_require_csr(op, "ell_stream"))
    return lambda x: ell_matvec_stream(ell, x)


def _matfree_matvec(op) -> Callable:
    return _require_matfree(op).matvec


def _as_sharded(op):
    from .operator import MatFreeOperator, ShardedMatFreeOperator

    op = _require_matfree(op)
    if isinstance(op, ShardedMatFreeOperator):
        return op
    if isinstance(op, MatFreeOperator):
        return op.sharded()
    raise TypeError(
        "backend 'matfree_sharded' needs a MatFreeOperator (or an already "
        f"sharded one), got {type(op).__name__}"
    )


def _matfree_sharded_matvec(op) -> Callable:
    return _as_sharded(op).matvec


def _csr_residual(op) -> Callable:
    return lambda u, f: op.matvec(u) - f


def _ell_residual(op) -> Callable:
    ell = csr_to_ell(_require_csr(op, "ell"))
    return lambda u, f: ell.matvec(u) - f


def _ell_pallas_residual(op) -> Callable:
    _refuse_on_tpu("ell_pallas")
    from ..kernels import ell_residual

    ell = csr_to_ell(_require_csr(op, "ell_pallas"))
    return lambda u, f: ell_residual(ell, u, f)


def _ell_stream_residual(op) -> Callable:
    _refuse_on_tpu("ell_stream")
    from ..kernels import ell_residual_stream

    ell = csr_to_ell(_require_csr(op, "ell_stream"))
    return lambda u, f: ell_residual_stream(ell, u, f)


def _matfree_residual(op) -> Callable:
    mv = _require_matfree(op).matvec
    return lambda u, f: mv(u) - f


def _matfree_sharded_residual(op) -> Callable:
    mv = _as_sharded(op).matvec
    return lambda u, f: mv(u) - f


# name -> (matvec factory, residual factory)
_BACKENDS: dict[str, tuple[Callable, Callable]] = {
    "csr": (_csr_matvec, _csr_residual),
    "ell": (_ell_matvec, _ell_residual),
    "ell_pallas": (_ell_pallas_matvec, _ell_pallas_residual),
    "ell_stream": (_ell_stream_matvec, _ell_stream_residual),
    "matfree": (_matfree_matvec, _matfree_residual),
    "matfree_sharded": (_matfree_sharded_matvec, _matfree_sharded_residual),
}

# the BUILT-IN backends — a constant, never rebound, so every import-time
# copy (repro.core re-export, deprecated transient.stepping forward) stays
# valid.  Custom backends added via register_matvec_backend dispatch through
# make_matvec/make_residual without appearing here; use matvec_backends()
# for the live set.
MATVEC_BACKENDS = tuple(_BACKENDS)


def matvec_backends() -> tuple[str, ...]:
    """The currently registered backend names (built-ins + custom)."""
    return tuple(_BACKENDS)


def register_matvec_backend(name: str, matvec_factory: Callable,
                            residual_factory: Callable | None = None,
                            *, overwrite: bool = False) -> None:
    """Register a custom backend: ``matvec_factory(op) -> (x ↦ A x)`` and an
    optional fused-residual factory (defaults to ``matvec(u) − f``)."""
    if name in _BACKENDS and not overwrite:
        raise ValueError(f"matvec backend {name!r} already registered")
    if residual_factory is None:
        def residual_factory(op, _mf=matvec_factory):
            mv = _mf(op)
            return lambda u, f: mv(u) - f
    _BACKENDS[name] = (matvec_factory, residual_factory)


def _lookup(backend: str):
    entry = _BACKENDS.get(backend)
    if entry is None:
        raise ValueError(
            f"unknown matvec backend {backend!r}; use one of {tuple(_BACKENDS)}"
        )
    return entry


def make_matvec(op, backend: str = "csr") -> Callable:
    """``x ↦ A @ x`` for the chosen inner-loop backend (table above)."""
    telemetry.counter_inc("matvec_backend", 1, backend=backend, role="matvec")
    return _lookup(backend)[0](op)


def make_residual(op, backend: str = "csr") -> Callable:
    """``(u, f) ↦ A·u − f`` — the Galerkin-residual inner op of the
    TensorPILS losses, fused where the backend supports it."""
    telemetry.counter_inc("matvec_backend", 1, backend=backend, role="residual")
    return _lookup(backend)[1](op)
