"""One module per way of driving a step, found by a traffic file's `path`.
`build(cell, family, seed, devices, span)` makes the state and the batch on
the device(s) from the seed, checks the forward pass against the family's
reference before the training state is allocated, compiles the step, and
returns a `harness.runner.Job`. `span(name)` is a context manager the path
puts around its calls into each layer (a profiler annotation in a traced
run, nothing otherwise)."""
