"""The chip benchmark: ``python3 bench/run.py --workload <cell> ...``.

Everything the benchmark measures with lives here and imports nothing of
the engine except in ``harness`` and ``drive``, which drive it.
"""
