"""Entry points: the host's clock around the step program's
`lower().compile()` plus the first step (which compiles, or loads from the
cache, whatever else the step dispatches)."""


def read(run):
    return run.compile_s
