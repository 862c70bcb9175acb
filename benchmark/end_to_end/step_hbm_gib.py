"""Device memory one execution of the step program needs, per device, by the
compiler's own `memory_analysis()`: arguments + outputs + temporaries - what
is aliased. Repeats exactly; guards "faster by holding more". Read only where
one program is the whole step (the traffic's path says so by handing the
step program over; the eager path's many programs have no one footprint)."""

from benchmark.harness.runner import program_bytes


def read(run):
    if run.program is None:
        return None
    return program_bytes(run.program) / 2**30
