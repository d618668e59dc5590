"""The on-chip benchmark: one cell of BENCHMARK.json per run of ``run.py``.

Configurations (``configs/``), traffic mixes (``traffic/``), per-layer
metric readers (``layer_metrics/``) and the drivers of each traffic kind
(``drivers/``) are found by name, so a cell is added with files and a
BENCHMARK.json entry alone.
"""
