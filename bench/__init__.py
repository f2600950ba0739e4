"""The on-chip benchmark: one command runs one cell of ``BENCHMARK.json``.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one configuration, traffic mix, traffic kind or
per-layer metric is a file of its own, found by name (``manifest.py``).
"""
