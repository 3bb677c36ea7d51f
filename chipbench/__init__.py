"""Chip benchmark of the served compressed-analytics path.

``python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` once on a TPU.  The
configurations, traffic mixes and per-layer metrics it reads live in
``configs/``, ``traffic/`` and ``metrics/``, one file each, found by name.
"""
