"""The on-chip benchmark: ``python3 bench/run.py --workload <cell> ...``.

Cells, metrics and bounds are declared in ``BENCHMARK.json``; each
configuration, traffic mix, limits file and per-layer metric is a file of
its own here, found by name.
"""
