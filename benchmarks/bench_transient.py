"""Transient rollout benchmarks (repro.transient).

Steps/sec and end-to-end wall-clock for heat (θ-method) and wave
(Newmark-β) rollouts on the assembled operators, plus the inner
residual ``K·u − f`` on the CSR.

Emits JSON-lines alongside the CSV rows when ``BENCH_JSON`` is set
(see :mod:`benchmarks.common`).
"""

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import (
    DirichletCondenser,
    FunctionSpace,
    GalerkinAssembler,
    unit_square_tri,
)
from repro.core.mesh import element_for_mesh

try:  # package-relative when run via benchmarks.run, flat when run directly
    from .common import emit_json, time_fn
except ImportError:  # pragma: no cover
    from common import emit_json, time_fn

N_STEPS = 50


def main() -> None:
    from repro.transient import (
        CRANK_NICOLSON,
        NewmarkIntegrator,
        ThetaIntegrator,
        batched_rollout,
    )

    m = unit_square_tri(24)
    sp = FunctionSpace(m, element_for_mesh(m))
    asm = GalerkinAssembler(sp)
    bc = DirichletCondenser(asm, sp.boundary_dofs())
    mass, stiff = asm.assemble_mass(), asm.assemble_stiffness()
    pts = sp.dof_points
    u0 = (
        jnp.sin(np.pi * jnp.asarray(pts[:, 0]))
        * jnp.sin(np.pi * jnp.asarray(pts[:, 1]))
    ) * bc.free_mask
    n = sp.num_dofs

    # -- rollouts (end-to-end wall-clock → steps/sec) --------------------------
    configs = [
        ("transient/heat_be_csr",
         ThetaIntegrator(mass, stiff, dt=1e-3, theta=1.0, bc=bc, tol=1e-10)),
        ("transient/heat_cn_csr",
         ThetaIntegrator(mass, stiff, dt=1e-3, theta=CRANK_NICOLSON, bc=bc,
                         tol=1e-10)),
        ("transient/wave_newmark_csr",
         NewmarkIntegrator(mass, stiff, dt=1e-3, bc=bc, tol=1e-10)),
    ]
    for name, integ in configs:
        fn = jax.jit(lambda u, _integ=integ: _integ.rollout(u, N_STEPS))
        us = time_fn(fn, u0, iters=3)
        steps_per_sec = N_STEPS / (us * 1e-6)
        emit_json(name, us, f"steps_per_sec={steps_per_sec:.0f}",
                  n_dofs=n, n_steps=N_STEPS, steps_per_sec=round(steps_per_sec))

    # -- batched rollout (the pils trajectory-generation shape) ----------------
    integ = configs[0][1]
    u0s = jnp.stack([u0 * s for s in np.linspace(0.5, 1.5, 8)])
    fn_b = jax.jit(lambda b: batched_rollout(integ, b, N_STEPS))
    us = time_fn(fn_b, u0s, iters=3)
    total = 8 * N_STEPS
    emit_json("transient/heat_be_csr_batch8", us,
              f"traj_steps_per_sec={total / (us * 1e-6):.0f}",
              n_dofs=n, n_steps=N_STEPS, batch=8)

    # -- inner residual ------------------------------------------------------
    lhs = integ.lhs
    f = mass.matvec(u0)
    r_csr = jax.jit(lambda u: lhs.matvec(u) - f)
    emit_json("transient/residual_csr", time_fn(r_csr, u0), n_dofs=n)


if __name__ == "__main__":
    main()
