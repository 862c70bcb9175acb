"""One reader per end-to-end metric, found by the metric's name in
`BENCHMARK.json`: `read(run) -> number or None` (`harness.runner.Run`). All
of them come from the host's clock and the compiled program, never from a
number the program reports about itself."""
