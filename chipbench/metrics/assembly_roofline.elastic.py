"""Share of the HBM roofline of a solve's device work outside the Krylov
program: the least bytes of an assembly (vertex coordinates and cell table
read once, stored values and load written once; ``harness/costs_blocks``)
over the peak bandwidth, over that device time per solve, in %."""

from harness import costs_blocks, krylov_program


def read(run):
    steps = krylov_program.split(run)
    if steps is None:
        return None
    seconds = sum(other for _, _, other in steps) / len(steps)
    if seconds <= 0:
        return None
    ref, n = run.driver.ref, run.driver.n
    points, cells = ref.mesh(n)
    least = costs_blocks.assembly_bytes(
        points.shape[0], cells.shape[0], cells.shape[1], points.shape[1],
        ref.dofs(n), ref.nnz(n))
    return 100.0 * least / run.peaks()["hbm_bytes_per_s"] / seconds
