"""One reader per per-layer metric, found by the metric's name in
`BENCHMARK.json`: `read(run) -> number or None` over the reduced trace, the
benchmark's spans, the compiled program's text and the host's clock
(`harness.runner.Run`). A reader that finds nothing to read returns None and
the metric is left out of the line."""
