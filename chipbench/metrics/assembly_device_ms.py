"""Device busy time in the window per assembly, in ms."""


def read(run):
    if run.trace is None or not run.driver.steps:
        return None
    return 1e3 * run.trace.busy_s / len(run.driver.steps)
