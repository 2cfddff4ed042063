"""On-chip benchmark of the decentralized serving engine.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` on the chip it is started on. Every
configuration, architecture, traffic mix, cell and per-layer metric is a
file of its own under this directory, found by the name that
``BENCHMARK.json`` or a configuration file gives it.
"""
