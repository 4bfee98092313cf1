"""Chip benchmark of the E->P->D serving path: one cell per run.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace 0|1``
from the root of a checkout. See ``BENCHMARK.json`` for the cells and
``PERF.md`` for what each metric means.
"""
