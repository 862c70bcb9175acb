"""A per-layer metric that exists only in the tests' fixtures."""


def read(run):
    return run.window.dispatched
