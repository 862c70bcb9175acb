"""Expert layer: device time per traced step of everything around the
experts: `moe.route` (router matmul, softmax, top-k, auxiliary losses),
`moe.dispatch` (sort by expert, gather of the rows) and `moe.combine`
(gather back, weighted sum), with their backward passes and remat repeats."""

from benchmark.harness import scopes


def read(run):
    return scopes.part_ms_per_step(run, lambda part: part != "experts")
