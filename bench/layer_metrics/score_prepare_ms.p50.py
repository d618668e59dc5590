"""Median over ``MTLServer.score`` calls of the time under the program's
``serve.prepare`` (conversions of ids and X, shape checks) and
``serve.pad`` (the last wave's padding) spans of the call (ms)."""
from bench.spans import median_ms


def read(rec):
    return median_ms(rec["trace"], "serve.score",
                     ("serve.prepare", "serve.pad"))
