"""Median host-clock time of one ``MTLServer.score`` call, until its
scores are on the host (ms)."""
import statistics


def read(rec):
    calls = rec["counters"].get("score_call_s")
    return 1e3 * statistics.median(calls) if calls else None
