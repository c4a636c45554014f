"""Mean CG iterations per solve in the window (``SolveInfo.iters``)."""


def read(run):
    iters = [s["iters"] for s in run.driver.steps]
    return sum(iters) / len(iters) if iters else None
