"""Compile time per solve inside the window, in ms: tracing, lowering,
backend compile and persistent-cache reads, from the benchmark's
``jax.monitoring`` listener."""


def read(run):
    if not run.driver.steps:
        return None
    return 1e3 * run.compiles.total_s() / len(run.driver.steps)
