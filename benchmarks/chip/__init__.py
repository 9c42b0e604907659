"""The chip benchmark: one cell per run, driven by ``BENCHMARK.json``.

``run_cell.py`` is the entry point.  Configurations (``configs/``),
traffic mixes (``traffic/``) and metric readers (``metrics/``) are files
found by the names ``BENCHMARK.json`` gives them.
"""
