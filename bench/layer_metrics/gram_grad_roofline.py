"""Share of its roofline that the worker's Gram-path product reaches (%).

On the Gram path each round computes, for the m_c tasks of one chip,
g_j = A_j w_j - b_j from the cached A_j = X_j^T X_j / n (float32, p x p).
Per round it needs 2 m_c p^2 operations and reads A, b and W and writes G
once, in float32.  The operations that read the cache are those whose
HLO text takes an operand of the cache's per-chip shape,
``f32[m_c,p,p]``, less the loop that holds them (a ``while`` takes the
cache in its carried tuple; on a v5e the read is one
``multiply_reduce_fusion`` a round).  Their least time is max(ops / peak FLOP/s, bytes /
peak HBM bytes/s); the share is that bound times their calls, over their
device time in the trace.
"""
import re
from typing import Callable

# The opcode in an HLO instruction's text: `` fusion(``, `` convolution(``.
OPCODE = re.compile(r" ([a-z][a-z0-9-]*)\(")
# Operations whose events span the operations of their computations.
CONTAINERS = ("while", "conditional", "call")


def ops_bytes(cfg, chips: int):
    pr = cfg["problem"]
    m, p = pr["m"] // chips, pr["p"]
    return 2.0 * m * p * p, 4.0 * (m * p * p + m * p + 2 * p * m)


def reads_cache(cfg, chips: int) -> Callable[[str], bool]:
    """A match on an event's HLO text: an operand of the per-chip cache's
    shape (the text before the opcode is the result's shape)."""
    pr = cfg["problem"]
    shape = f"f32[{pr['m'] // chips},{pr['p']},{pr['p']}]"

    def match(text: str) -> bool:
        _, sep, rest = text.partition(" = ")
        code = OPCODE.search(rest) if sep else None
        return (code is not None and code.group(1) not in CONTAINERS
                and shape in rest[code.end():])
    return match


def cache_ops(rec):
    """{plane: [events]} of the operations that read the Gram cache."""
    match = reads_cache(rec["config"], rec["chips"])
    return {k: [e for e in evs if match(e.name)]
            for k, evs in rec["trace"].devices.items()}


def read(rec):
    if rec["trace"] is None or not rec["config"].get("gram"):
        return None
    planes = cache_ops(rec)
    calls = sum(len(v) for v in planes.values())
    ns = sum(e.end - e.start for v in planes.values() for e in v)
    if not calls or ns <= 0:
        return None
    ops, nbytes = ops_bytes(rec["config"], rec["chips"])
    pk = rec["peaks"]
    bound = max(ops / pk["bf16_flops_per_s"], nbytes / pk["hbm_bytes_per_s"])
    return 100.0 * calls * bound / (ns * 1e-9)
