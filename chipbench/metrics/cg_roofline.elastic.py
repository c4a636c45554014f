"""Share of the HBM roofline of the Krylov program: the bytes that
textbook Jacobi-PCG moves per iteration on a matrix of 3 × 3 blocks
(``harness/costs_blocks``) times the CG iterations of the solves that the
trace holds whole, over the peak bandwidth, over their Krylov program's
device time (``harness/krylov_program``), in %."""

from harness import costs_blocks, krylov_program


def read(run):
    steps = krylov_program.split(run)
    iters = sum(i for i, _, _ in steps or ())
    seconds = sum(k for _, k, _ in steps or ())
    if not iters or seconds <= 0:
        return None
    ref, n = run.driver.ref, run.driver.n
    per_iter = costs_blocks.jacobi_pcg_block_iteration_bytes(ref.dofs(n),
                                                             ref.nnz(n))
    return 100.0 * per_iter * iters / run.peaks()["hbm_bytes_per_s"] / seconds
