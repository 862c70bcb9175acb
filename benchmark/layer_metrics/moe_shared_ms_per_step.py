"""Expert layer: device time per traced step of the shared experts, the
gated MLP every token goes through beside the routed experts: the step
program's instructions under the scope `moe.shared`
(`models/transformer.py`), forward, remat repeat and backward. No part of
`moe_ms_per_step`, which reads `parallel/moe.py`'s four scopes."""

from benchmark.harness import scope_time

SCOPE = "moe.shared"


def read(run):
    if not scope_time.traced(run):
        return None
    return scope_time.ms_per_step(run, scope_time.names_under(
        run.program.as_text(), run.instructions, SCOPE))
