"""Median over ``MTLServer.score`` calls of the time under the program's
``serve.dispatch`` spans of the call, one a wave (ms)."""
from bench.spans import median_ms


def read(rec):
    return median_ms(rec["trace"], "serve.score", ("serve.dispatch",))
