"""Traffic kind ``serve_open_loop``: scoring requests on a schedule.

Set-up makes the served factors from the seed (``bench/gen/serve_factors``),
builds ``MTLServer`` with the configuration's settings, makes a pool of
feature rows and the open-loop schedule of the window (Poisson arrivals,
Zipf task ids; ``bench/gen/schedule``), and scores one call of every batch
size from 1 to the batch limit, which is every shape the window sends.

One dispatcher sends every request already due, up to the batch limit, in
one ``score`` call, and the call ends when its scores are on the host, as
a client gets them; when none is due it sleeps, then spins, until the
next one is.  A request's latency runs from its due time to the end of
the call that served it, so a stall delays every request behind it.  The
window serves every request due in ``[0, seconds)``.

The check compares every score of the window against the plain NumPy
reference (``bench/reference/score.py``).
"""
from __future__ import annotations

import sys
import time

import numpy as np

from bench.common import Check, prng_key
from bench.gen.schedule import open_loop
from bench.gen.serve_factors import serve_factors

# When the next request is due in more than this, sleep until SPIN_S
# before it and spin the rest: sleep alone wakes up to ~1 ms late.
SPIN_S = 0.001


class Cell:
    def __init__(self, ctx):
        import jax
        from repro.serve.mtl import FactoredModel, MTLServer

        self.ctx = ctx
        sv = ctx.config["serve"]
        tr = ctx.traffic
        self.B = int(sv["batch_size"])
        p, m, r = ctx.config["problem"]["p"], sv["m"], sv["r"]
        self.U, self.s, self.V = jax.jit(
            lambda k: serve_factors(k, p, m, r))(prng_key(ctx.seed))
        model = FactoredModel(U=self.U, s=self.s, V=self.V)
        self.server = MTLServer(model, batch_size=self.B,
                                **sv["server_kwargs"])
        rng = np.random.default_rng(ctx.seed)
        self.pool = rng.standard_normal((tr["pool_rows"], p),
                                        dtype=np.float32)
        self._schedule(rng, tr["rate_per_s"], ctx.seconds)
        self.score = self.server.score
        for n in range(1, self.B + 1):           # every batch size
            preds, _ = self.score(np.arange(n, dtype=np.int32) % m,
                                  self.pool[:n])
            jax.block_until_ready(preds)

    def _schedule(self, rng, rate: float, seconds: float) -> None:
        """The window's requests, and room for what it records of each
        call and request, made before the window opens."""
        tr = self.ctx.traffic
        self.due, self.ids, self.rows = open_loop(
            rng, rate=rate, seconds=seconds, m=self.ctx.config["serve"]["m"],
            zipf_a=tr["zipf_a"], pool_rows=tr["pool_rows"])
        n = self.due.shape[0]
        self.preds = np.full(n, np.nan, np.float32)
        self.served = np.full(n, False)
        self.sent, self.done, self.call_s = (np.full(n, np.nan)
                                             for _ in range(3))
        self.calls, self.failed = 0, 0

    def reset_schedule(self, rate: float, seconds: float, seed: int) -> None:
        """A fresh schedule at another rate (the knee sweep)."""
        self._schedule(np.random.default_rng(seed), rate, seconds)


def setup(ctx) -> Cell:
    return Cell(ctx)


def window(cell: Cell) -> None:
    ctx = cell.ctx
    due, ids, rows, pool, B = cell.due, cell.ids, cell.rows, cell.pool, cell.B
    n = due.shape[0]
    clock = time.perf_counter
    i = 0
    t0 = clock()
    with ctx.annotate("bench.window"):
        while i < n:
            now = clock() - t0
            if due[i] > now:
                with ctx.annotate("bench.wait"):
                    if due[i] - now > 2 * SPIN_S:
                        time.sleep(due[i] - now - SPIN_S)
                    while clock() - t0 < due[i]:
                        pass
                now = clock() - t0
            j = min(int(np.searchsorted(due, now, side="right")), i + B)
            c0 = clock()
            with ctx.annotate("bench.score"):
                try:
                    preds, _ = cell.score(ids[i:j], pool[rows[i:j]])
                    cell.preds[i:j] = np.asarray(preds)  # waits for them
                    cell.served[i:j] = True
                except Exception as e:            # counted, not fatal
                    print(f"score call failed: {type(e).__name__}: {e}",
                          file=sys.stderr, flush=True)
                    cell.failed += j - i
            c1 = clock()
            cell.call_s[cell.calls] = c1 - c0
            cell.calls += 1
            cell.sent[i:j] = c0 - t0
            cell.done[i:j] = c1 - t0
            i = j


def latency_s(cell: Cell) -> np.ndarray:
    return cell.done - cell.due


def end_to_end(cell: Cell) -> dict:
    seconds = cell.ctx.seconds
    return {"serve_p99_ms": float(np.percentile(latency_s(cell), 99)) * 1e3,
            "serve_rps": int(np.sum(cell.done <= seconds)) / seconds}


def counters(cell: Cell) -> dict:
    late = cell.sent - cell.due
    lat = latency_s(cell) * 1e3
    calls = cell.call_s[:cell.calls]
    return {"window_s": float(cell.ctx.seconds), "requests": len(cell.due),
            "latency_ms": {f"p{q}": float(np.percentile(lat, q))
                           for q in (50, 90, 95, 99, 99.9)},
            "longest_call_ms": float(np.max(calls)) * 1e3,
            "calls": cell.calls, "score_call_s": calls.tolist(),
            "generator_late_p99_ms": float(np.percentile(late, 99)) * 1e3,
            "mean_batch": len(cell.due) / max(cell.calls, 1)}


def attempted(cell: Cell) -> int:
    return int(cell.due.shape[0])


def failed(cell: Cell) -> int:
    return cell.failed


def release(cell: Cell) -> None:
    cell.server = None
    cell.score = None


def readings(cell: Cell) -> dict:
    """``score_gap``: the worst served request's gap to the reference
    (``bench/reference/score.py``)."""
    from bench.reference import score as ref
    idx = np.nonzero(cell.served)[0]
    if not idx.size:
        return {"score_gap": float("nan")}
    U, s, V = (np.asarray(a) for a in (cell.U, cell.s, cell.V))
    gaps = ref.gaps(cell.preds[idx], U, s, V, cell.pool, cell.ids[idx],
                    cell.rows[idx])
    return {"score_gap": float(np.max(gaps))}


def check(cell: Cell) -> list:
    got = readings(cell)
    return [Check(name, got[name], limit)
            for name, limit in cell.ctx.limits().items()]
