"""On-chip benchmark of the serving engine: one command runs one cell of
``BENCHMARK.json`` once (``python3 bench/run.py --workload ...``)."""
