"""Device time of the Krylov program over the CG iterations of the solves
that the trace holds whole, in ms (``Run.krylov``: the CG loop's own
program, not the assembly, condensation or transfers around it)."""


def read(run):
    krylov = run.krylov()
    if krylov is None:
        return None
    seconds, iters = krylov
    return 1e3 * seconds / iters
