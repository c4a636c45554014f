"""Shared rollout machinery for the transient integrators.

* :func:`segmented_scan` — ``lax.scan`` with optional ``jax.checkpoint``
  segmentation: long rollouts are split into segments whose intermediate
  states are recomputed (not stored) during the backward pass, bounding
  autodiff memory at O(T/segment + segment) instead of O(T).
* :func:`axpy_csr` — combine two same-pattern CSR operators into a third
  (``α·A + β·B``) without touching the static pattern; this is how the
  θ-method / Newmark effective operators are formed once, outside the loop.
"""

from __future__ import annotations

import dataclasses

import jax

from ..core.sparse import CSR

__all__ = ["segmented_scan", "axpy_csr"]


def segmented_scan(step, init, xs, length: int, checkpoint_every: int | None = None):
    """``lax.scan(step, init, xs, length)`` with gradient-checkpoint segments.

    ``checkpoint_every=None`` is a plain scan.  Otherwise ``length`` must be
    divisible by ``checkpoint_every``; the rollout becomes an outer scan over
    ``length // checkpoint_every`` segments, each an inner scan wrapped in
    ``jax.checkpoint`` — the O(√T) memory trick for differentiating long
    trajectories.
    """
    if checkpoint_every is None or checkpoint_every >= length:
        return jax.lax.scan(step, init, xs, length=length)
    n_seg, rem = divmod(length, checkpoint_every)
    if rem:
        raise ValueError(
            f"checkpoint_every={checkpoint_every} must divide length={length}"
        )
    if xs is not None:
        xs = jax.tree_util.tree_map(
            lambda x: x.reshape(n_seg, checkpoint_every, *x.shape[1:]), xs
        )

    @jax.checkpoint
    def segment(carry, seg_xs):
        return jax.lax.scan(step, carry, seg_xs, length=checkpoint_every)

    carry, ys = jax.lax.scan(segment, init, xs, length=n_seg)
    ys = jax.tree_util.tree_map(
        lambda y: y.reshape(length, *y.shape[2:]), ys
    )
    return carry, ys


def axpy_csr(alpha, a: CSR, beta, b: CSR) -> CSR:
    """``α·A + β·B`` for two CSR operators sharing one sparsity pattern."""
    assert a.indices.shape == b.indices.shape, "CSR patterns must match"
    return dataclasses.replace(a, vals=alpha * a.vals + beta * b.vals)

