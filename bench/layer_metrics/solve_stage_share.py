"""Share of the solve window spent staging round programs (%).

Time under the program's ``solve.trace``, ``solve.lower`` and
``solve.compile`` spans (``repro.runtime.base.stage``: tracing the round
program, lowering it, and compiling it or loading it from the persistent
cache) over the window's length.
"""
from bench.spans import inside, seconds

STAGES = ("solve.trace", "solve.lower", "solve.compile")


def read(rec):
    spans = inside(rec["trace"], STAGES)
    if not spans:
        return None
    return 100.0 * seconds(spans) / rec["trace"].window_s
