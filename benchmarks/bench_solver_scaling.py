"""Paper Fig. 2: end-to-end (assembly + Krylov solve) runtime vs DoFs for
3D Poisson and 3D elasticity; scipy spsolve as the 'legacy CPU' baseline.
Derived: DoFs, solver iterations, relative residual (must be < 1e-10 to
match the paper's tolerance).

The sharded row (``matfree_sharded``) spans a single matrix-free CG over
every local device."""

import jax
import numpy as np

from repro.core import hollow_cube_tet, unit_cube_tet
from repro.fem import ElasticityProblem, PoissonProblem

from .common import emit, emit_json, is_quick, time_fn


def _sharded_case(quick: bool):
    prob = PoissonProblem(unit_cube_tet(4 if quick else 8))
    res, info = prob.solve(backend="matfree_sharded", tol=1e-10,
                           return_info=True)
    assert res.converged, "sharded matrix-free CG did not converge"
    dofs = prob.space.num_dofs
    t = time_fn(lambda: prob.solve(backend="matfree_sharded", tol=1e-10).u,
                warmup=0, iters=2)
    emit_json(
        f"poisson3d_sharded_solve_n{dofs}", t,
        f"dofs={dofs};devices={len(jax.devices())};iters={res.iters}",
        dofs=dofs, devices=len(jax.devices()), iterations=int(info.iters),
        final_residual=float(info.residual),
        converged=bool(info.converged), relres=res.residual,
    )


def main():
    quick = is_quick()
    for n in (4, 6) if quick else (6, 10, 14):
        prob = PoissonProblem(unit_cube_tet(n))
        res, info = prob.solve(return_info=True)  # warm compile
        t = time_fn(lambda: prob.solve(tol=1e-10).u, warmup=0, iters=3)
        emit_json(
            f"poisson3d_solve_n{prob.space.num_dofs}", t,
            f"dofs={prob.space.num_dofs};iters={res.iters};relres={res.residual:.1e}",
            dofs=prob.space.num_dofs, iterations=int(info.iters),
            final_residual=float(info.residual),
            converged=bool(info.converged), relres=res.residual,
        )
        # scipy direct-solve baseline on the same system
        k, f = prob.assemble()
        ks = k.to_scipy().tocsc()
        import scipy.sparse.linalg as spla

        t_sp = time_fn(lambda: spla.spsolve(ks, np.asarray(f)), warmup=0, iters=2)
        emit(f"poisson3d_scipy_n{prob.space.num_dofs}", t_sp, "baseline=scipy_spsolve")

    _sharded_case(quick)

    for n in (3,) if quick else (4, 8):
        prob = ElasticityProblem(hollow_cube_tet(n))
        res, info = prob.solve(return_info=True)
        t = time_fn(lambda: prob.solve(tol=1e-10).u, warmup=0, iters=2)
        emit_json(
            f"elasticity3d_solve_n{prob.space.num_dofs}", t,
            f"dofs={prob.space.num_dofs};iters={res.iters};relres={res.residual:.1e}",
            dofs=prob.space.num_dofs, iterations=int(info.iters),
            final_residual=float(info.residual),
            converged=bool(info.converged), relres=res.residual,
        )


if __name__ == "__main__":
    main()
