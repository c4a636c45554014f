"""Pallas kernel micro-benchmarks.  Interpret mode resolves from the
platform: off-TPU the numbers compare the interpreter with the pure-jnp
reference path and are not TPU projections; on a TPU the kernel goes to
Mosaic, which refuses it today (see ``repro.kernels.local_assembly``), so
the module fails instead of timing the interpreter.  BlockSpec VMEM
footprints are reported as the derived column."""

import numpy as np
import jax
import jax.numpy as jnp

import repro.core  # noqa: F401  x64
from repro.kernels.local_assembly import BLOCK_E, local_stiffness_p1
from repro.kernels.ref import local_stiffness_p1_ref

from .common import emit, time_fn


def main():
    rng = np.random.default_rng(0)
    mode = "mosaic" if jax.default_backend() == "tpu" else "interpret"
    for e in (4096, 16384):
        ident = np.concatenate([np.zeros((1, 3)), np.eye(3)], axis=0)
        coords = jnp.asarray(
            rng.normal(size=(e, 1, 3)) + ident[None] + 0.1 * rng.normal(size=(e, 4, 3))
        )
        rho = jnp.ones(e)
        t_ref = time_fn(lambda: local_stiffness_p1_ref(coords, rho), iters=3)
        t_k = time_fn(lambda: local_stiffness_p1(coords, rho), iters=3)
        vmem_kb = (12 + 1 + 16) * BLOCK_E * 4 / 1024
        emit(
            f"kernel_local_assembly_E{e}", t_k,
            f"ref_us={t_ref:.1f};vmem_per_block_KB={vmem_kb:.0f};mode={mode}",
        )


if __name__ == "__main__":
    main()
