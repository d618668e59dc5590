"""Median over ``MTLServer.score`` calls of the time under the program's
``serve.sync`` span: the wait for the device and the read-back of the
batch's id-validity flag (ms)."""
from bench.spans import median_ms


def read(rec):
    return median_ms(rec["trace"], "serve.score", ("serve.sync",))
