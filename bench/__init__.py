"""Chip benchmark of the partitioned graph database (see ``BENCHMARK.json``).

``python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell on the TPU it is started on and prints one JSON result line.
Configurations, traffic mixes and per-layer metrics are data and reader
files under ``bench/configs``, ``bench/mixes`` and ``bench/metrics``, found
by the names in ``BENCHMARK.json``; ``bench/reference`` holds the plain
reference that decides ``correct``.
"""
