"""Device time per solve outside the Krylov program, in ms: assembly of
the matrix and the load, condensation, the matvec's block layout, the
Jacobi diagonal and the final residual (``harness/krylov_program``)."""

from harness import krylov_program


def read(run):
    steps = krylov_program.split(run)
    if steps is None:
        return None
    return 1e3 * sum(other for _, _, other in steps) / len(steps)
