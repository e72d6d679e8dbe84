"""Benchmark of the served vector-search path on a TPU.

``python3 -m benchmark.run --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json``. Everything that
belongs to one deployment, traffic mix, load generator, index family or
metric is a file of its own under this directory, found by its name
(``benchmark/cells.py``).
"""
