"""Share of the serving window in which no operation ran on the device (%)."""
from bench.trace_reduce import idle_share


def read(rec):
    share = None if rec["trace"] is None else idle_share(rec["trace"])
    return None if share is None else 100.0 * share
