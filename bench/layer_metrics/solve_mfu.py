"""The whole round's share of the chips' peak (%).

A round's model work is the full-data gradient, 4 m n p operations (two per
entry of X: the prediction X w, then X^T r), counted the same whatever path
computes it.  Times rounds per second of the window, over the chips'
bfloat16 peak.
"""


def round_ops(cfg) -> float:
    pr = cfg["problem"]
    return 4.0 * pr["m"] * pr["n"] * pr["p"]


def read(rec):
    c = rec["counters"]
    if not c.get("rounds") or not c.get("window_s"):
        return None
    rate = c["rounds"] / c["window_s"]
    return 100.0 * round_ops(rec["config"]) * rate / (
        rec["chips"] * rec["peaks"]["bf16_flops_per_s"])
