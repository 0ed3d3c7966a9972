"""The benchmark of this repository: see perfbench/README.md and PERF.md."""
