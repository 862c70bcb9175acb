"""Linear attention: device time per traced step of the step program's
instructions under the `gdn.scan` scope of `models/transformer.py`: from the
convolved q, k, v to the rule's output o (the L2 normalisation of q and k,
beta, the log decay and the gated delta rule of `ops/gated_delta.py`;
forward, remat repeat and backward). By scope alone, so a kernel that later
runs under it is counted without an edit. None for a program without the
scope."""

from benchmark.harness import scope_time

SCOPE = "gdn.scan"


def ms_under(run, prefix: str):
    """Device time per traced step, in ms, of the instructions under a scope
    that starts with `prefix`; None without a trace or such a scope."""
    if not scope_time.traced(run):
        return None
    return scope_time.ms_per_step(run, scope_time.names_under(
        run.program.as_text(), run.instructions, prefix))


def read(run):
    return ms_under(run, SCOPE)
