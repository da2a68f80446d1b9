"""The repository's benchmark: the yardstick later PRs are judged by.

``python benchmark/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell on the TPU and prints one JSON line.
Everything that belongs to one configuration, one cell or one per-layer
metric is a file of its own, found by the name ``BENCHMARK.json`` gives.
"""
