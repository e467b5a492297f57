"""Chip benchmark of the memoized serving path.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` once on the chip it
starts on. Everything that belongs to one configuration, traffic mix,
serving task, reference, per-layer metric or kernel lives in a file of its
own under this directory and is found by its name (``registry.py``).
"""
