"""`samples_per_s_per_chip` of the cells in which the host's path through
the eager optimizer takes most of a step's time (33 of 47 ms in
`resnet50-eager` on the v5e): read the same way, but a metric of its own,
because on a machine that shares its host's cores it spreads a hundred times
wider than in the cells the device paces, and one bound would have to fit
both (PERF.md, section 2)."""

from benchmark.end_to_end.samples_per_s_per_chip import read  # noqa: F401
