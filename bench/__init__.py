"""On-chip benchmark of paged serving: one command, data-driven cells.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

``BENCHMARK.json`` at the repository root names the cells; each cell's
configuration, model family, traffic mix, engine shape and per-metric reader
live in files of their own under this directory and are found by name
(``spec.py``).
"""
