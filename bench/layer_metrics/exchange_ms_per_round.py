"""Device time of the tasks-axis exchange a round (ms).

The exchange is the collective HLO operations (``all-gather``,
``all-reduce``, ``collective-permute``, ``reduce-scatter``,
``all-to-all``, each also as its ``-start``/``-done`` pair) on a chip's
``XLA Ops`` line.  A plane's rounds are counted in the same plane, by the
operations that read the Gram cache (one a round; ``gram_grad_roofline``),
so a plane the profiler stopped recording early counts fewer of both.
The reading is each plane's exchange time over its rounds, averaged over
the chips.
"""
import re

from bench.layer_metrics.gram_grad_roofline import OPCODE, cache_ops

COLLECTIVE = re.compile(r"^(all-gather|all-reduce|collective-permute|"
                        r"reduce-scatter|all-to-all)(-start|-done)?$")


def is_collective(text: str) -> bool:
    _, sep, rest = text.partition(" = ")
    code = OPCODE.search(rest) if sep else None
    return code is not None and COLLECTIVE.match(code.group(1)) is not None


def per_plane(rec):
    """{plane: (exchange seconds, rounds)} of the planes with rounds."""
    rounds = {k: len(v) for k, v in cache_ops(rec).items()}
    out = {}
    for k, evs in rec["trace"].devices.items():
        if rounds.get(k):
            ns = sum(e.end - e.start for e in evs if is_collective(e.name))
            out[k] = (ns * 1e-9, rounds[k])
    return out


def read(rec):
    if rec["trace"] is None or not rec["config"].get("gram"):
        return None
    planes = per_plane(rec)
    if not planes:
        return None
    return 1e3 * sum(s / r for s, r in planes.values()) / len(planes)
