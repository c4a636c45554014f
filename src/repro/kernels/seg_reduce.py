"""Pallas TPU kernel: Stage-II Sparse-Reduce as a regular gather-sum.

Variable-length segment reduction is TPU-hostile; FEM gives us a bound —
each global nnz entry receives at most ``L`` local contributions (L =
max element valence of an edge/vertex pair).  At routing build time the
sorted segment layout is repacked into a padded ``(nnz, L)`` index table
(pad slots point at a zeroed sentinel), turning the Reduce into a
lane-parallel gather+sum:

    vals[n] = Σ_l  vec(K_local ‖ 0)[ idx[n, l] ]

Grid: (ceil(nnz / BN),); blocks (BN, L) indices + broadcast source vector.

``interpret`` resolves from the platform (:mod:`repro.kernels.interpret`).
The kernel does not compile for a TPU today: Mosaic rejects its 1-D
``jnp.take`` gather (``Only 2D gather is supported``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from ..telemetry import annotate
from .interpret import resolve_interpret

__all__ = ["build_padded_reduce", "seg_reduce"]

BLOCK_N = 4096


def build_padded_reduce(routing) -> np.ndarray:
    """(nnz, L) indices into vec(K_local) with pad → index E·k² (sentinel)."""
    n_in = routing.perm.shape[0]
    counts = np.bincount(routing.seg_ids, minlength=routing.nnz)
    l_max = int(counts.max()) if counts.size else 1
    idx = np.full((routing.nnz, l_max), n_in, dtype=np.int32)  # sentinel
    slot = np.zeros(routing.nnz, dtype=np.int64)
    for pos, seg in zip(routing.perm, routing.seg_ids):
        idx[seg, slot[seg]] = pos
        slot[seg] += 1
    return idx


def _kernel(idx_ref, src_ref, out_ref):
    idx = idx_ref[...]                   # (BN, L)
    src = src_ref[...]                   # (n_in + 1,) zero-padded source
    out_ref[...] = jnp.sum(jnp.take(src, idx, axis=0), axis=1)


def seg_reduce(local_vals: jnp.ndarray, padded_idx: jnp.ndarray, *,
               interpret: bool | None = None, block_n: int = BLOCK_N):
    """local_vals: (E, ka, kb) or flat (E·ka·kb,) → (nnz,) global CSR vals."""
    return _seg_reduce(local_vals, padded_idx,
                       interpret=resolve_interpret(interpret), block_n=block_n)


@functools.partial(jax.jit, static_argnames=("interpret", "block_n"))
def _seg_reduce(local_vals, padded_idx, *, interpret: bool, block_n: int):
    v = local_vals.reshape(-1)
    src = jnp.concatenate([v, jnp.zeros((1,), v.dtype)])       # sentinel 0
    nnz, l = padded_idx.shape
    n_pad = -(-nnz // block_n) * block_n
    idx = jnp.pad(jnp.asarray(padded_idx, jnp.int32),
                  ((0, n_pad - nnz), (0, 0)), constant_values=v.shape[0])
    with annotate("tg.pallas.seg_reduce"):
        out = pl.pallas_call(
            _kernel,
            grid=(n_pad // block_n,),
            in_specs=[
                pl.BlockSpec((block_n, l), lambda i: (i, 0)),
                pl.BlockSpec((src.shape[0],), lambda i: (0,)),
            ],
            out_specs=pl.BlockSpec((block_n,), lambda i: (i,)),
            out_shape=jax.ShapeDtypeStruct((n_pad,), v.dtype),
            interpret=interpret,
        )(idx, src)
    return out[:nnz]
