"""On-chip benchmark of the serving path (see BENCHMARK.json at the root).

`python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>`
runs one cell. Every piece is found by name: configurations in
`configs/`, traffic mixes in `traffic/`, loop drivers in `drivers/`,
per-layer metric readers in `metrics/`, plain references in
`reference/`.
"""
