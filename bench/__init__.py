"""On-chip benchmark of this repository: one command, cells as data.

    python -m bench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

`BENCHMARK.json` at the repository root names the cells; each cell's
configuration (`bench/configs/`), traffic mix (`bench/traffic/`) and per-layer
metric readers (`bench/metrics/`) are files found by name.
"""
