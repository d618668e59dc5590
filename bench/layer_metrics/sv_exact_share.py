"""Share of the window's rounds whose shrink fell back to the exact SVD (%),
from the program's counter ``extras["sv_exact_rounds"]``."""


def read(rec):
    c = rec["counters"]
    if c.get("sv_exact_rounds") is None or not c.get("rounds"):
        return None
    return 100.0 * c["sv_exact_rounds"] / c["rounds"]
