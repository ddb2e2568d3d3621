"""Per-layer metric readers: benchmark/metrics/<metric>.py defines
`read(run)`, which returns the metric's value from a `benchmark.run.Run`,
or None where the run holds nothing to read it from."""
