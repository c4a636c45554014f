"""Matrix-free apply/solve vs the assembled-CSR path.

The trade the subsystem sells: the matrix-free operator applies the weak
form element-locally (gather → per-element fused action → scatter-Reduce)
and stores essentially nothing beyond the plan, while the CSR path
materializes 3 nnz-sized arrays (values + column indices + row ids) before
the Krylov loop runs.  Tracked claims (perf-smoke CI gates these rows
against ``BENCH_baseline.json``):

* apply time within ~2× of the CSR matvec at small N (same asymptotic
  work: the fused diffusion action touches O(E·Q·k·d) intermediates, the
  SpMV touches O(nnz));
* operator state at the largest benched mesh: ``matfree_state_bytes`` ≪
  ``csr_bytes`` (JSON extras carry both numbers);
* a full matrix-free CG Poisson solve matching the assembled solve.
"""

import jax
import jax.numpy as jnp
import numpy as np

try:
    from .common import emit_json, is_quick, time_fn
except ImportError:  # flat execution
    from common import emit_json, is_quick, time_fn

from repro.core import (
    FunctionSpace,
    assemble,
    build_plan,
    matfree_operator,
    unit_cube_tet,
    unit_square_tri,
    weakform as wf,
)
from repro.core.mesh import element_for_mesh


def _csr_bytes(k) -> int:
    return int(
        k.vals.nbytes + k.indices.nbytes + k.row_of_nnz.nbytes + k.indptr.nbytes
    )


def _apply_case(mesh, tag: str):
    # tag must encode the problem size: quick and full runs emit different
    # row names, so a baseline recorded at one size never silently gates
    # the other
    space = FunctionSpace(mesh, element_for_mesh(mesh))
    plan = build_plan(space)
    rng = np.random.default_rng(0)
    rho = jnp.asarray(rng.uniform(0.5, 2.0, mesh.num_cells))
    form = wf.diffusion(rho)

    k = assemble(plan, form)
    csr_mv = jax.jit(k.matvec)
    x = jnp.asarray(rng.standard_normal(space.num_dofs))

    op_ctx = matfree_operator(plan, form, store="context")
    op_coords = matfree_operator(plan, form, store="coords")
    np.testing.assert_allclose(
        np.asarray(op_ctx.matvec(x)), np.asarray(k.matvec(x)), atol=1e-12
    )

    # sub-millisecond rows gate CI at 1.5×: medians need real sample counts
    # or scheduler noise alone trips the threshold
    t_csr = time_fn(csr_mv, x, warmup=3, iters=25)
    t_ctx = time_fn(op_ctx.matvec, x, warmup=3, iters=25)
    t_coords = time_fn(op_coords.matvec, x, warmup=3, iters=25)
    csr_b = _csr_bytes(k)
    # reference=True: compare.py normalizes the CI gate's machine scale on
    # these rows (SpMV code the matfree PRs don't touch)
    emit_json(
        f"csr_matvec_{tag}", t_csr, f"nnz={k.nnz};bytes={csr_b}",
        dofs=space.num_dofs, nnz=k.nnz, csr_bytes=csr_b, reference=True,
    )
    emit_json(
        f"matfree_apply_{tag}", t_ctx,
        f"vs_csr={t_ctx / t_csr:.2f}x;state_bytes={op_ctx.state_bytes()}",
        dofs=space.num_dofs, ratio_vs_csr=round(t_ctx / t_csr, 2),
        matfree_state_bytes=op_ctx.state_bytes(), csr_bytes=csr_b,
    )
    emit_json(
        f"matfree_apply_coords_{tag}", t_coords,
        f"vs_csr={t_coords / t_csr:.2f}x;state_bytes={op_coords.state_bytes()}",
        dofs=space.num_dofs, ratio_vs_csr=round(t_coords / t_csr, 2),
        matfree_state_bytes=op_coords.state_bytes(), csr_bytes=csr_b,
    )

    # sharded matrix-free apply (1 device locally; CI runs the 8-device leg)
    import jax as _jax

    sop = op_ctx.sharded()
    np.testing.assert_allclose(
        np.asarray(sop.matvec(x)), np.asarray(k.matvec(x)), atol=1e-12
    )
    t_sh = time_fn(sop.matvec, x, warmup=3, iters=25)
    emit_json(
        f"matfree_sharded_apply_{tag}", t_sh,
        f"vs_csr={t_sh / t_csr:.2f}x;devices={len(_jax.devices())}",
        dofs=space.num_dofs, ratio_vs_csr=round(t_sh / t_csr, 2),
        devices=len(_jax.devices()), csr_bytes=csr_b,
    )


def _solve_case(n: int):
    from repro.fem.tensormesh import PoissonProblem

    prob = PoissonProblem(unit_cube_tet(n))
    res_csr = prob.solve()
    res_mf, info_mf = prob.solve(backend="matfree", return_info=True)
    err = float(jnp.max(jnp.abs(res_csr.u - res_mf.u)))
    assert err < 1e-8, f"matrix-free solve deviates from assembled: {err}"
    assert res_mf.converged, "matrix-free solve did not converge"

    t_csr = time_fn(lambda: prob.solve().u)
    t_mf = time_fn(lambda: prob.solve(backend="matfree").u)
    emit_json(
        f"matfree_poisson_solve_tet{n}", t_mf,
        f"csr_us={t_csr:.1f};iters={res_mf.iters};err={err:.1e}",
        dofs=prob.space.num_dofs, csr_us=round(t_csr, 1),
        iters=res_mf.iters, iterations=int(info_mf.iters),
        final_residual=float(info_mf.residual),
        converged=bool(info_mf.converged), max_err_vs_csr=err,
    )


def main():
    quick = is_quick()
    n_tri = 12 if quick else 16
    n_tet = 6 if quick else 10
    # small N: apply overhead comparison
    _apply_case(unit_square_tri(n_tri), f"tri{n_tri}_small")
    # largest benched mesh: the memory story
    _apply_case(unit_cube_tet(n_tet), f"tet{n_tet}_large")
    _solve_case(4 if quick else 6)


if __name__ == "__main__":
    main()
