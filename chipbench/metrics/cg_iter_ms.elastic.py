"""Device time of the Krylov program over the CG iterations of the solves
that the trace holds whole, in ms (``harness/krylov_program``)."""

from harness import krylov_program


def read(run):
    steps = krylov_program.split(run)
    iters = sum(i for i, _, _ in steps or ())
    if not iters:
        return None
    return 1e3 * sum(k for _, k, _ in steps) / iters
