"""Share of the HBM roofline: the bytes that textbook Jacobi-PCG moves per
iteration (``harness/costs.py``) times the CG iterations of the solves
that the trace holds whole, over the peak bandwidth, over the device time
of their Krylov program (``Run.krylov``), in %.  CG on a sparse matrix is
memory bound, so bandwidth is the bound that counts."""


def read(run):
    krylov = run.krylov()
    if krylov is None or krylov[0] <= 0:
        return None
    seconds, iters = krylov
    least_s = run.cg_iteration_bytes() * iters / run.peaks()["hbm_bytes_per_s"]
    return 100.0 * least_s / seconds
