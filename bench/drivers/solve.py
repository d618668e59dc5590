"""Traffic kind ``solve``: whole ``repro.solve`` calls, back to back.

Set-up makes the configuration's §5 problem on the device from the seed
(sharded over the ``tasks`` axis when the cell has several chips), builds
the ``MTLProblem`` and runs one solve, which compiles or loads every
program the window runs.  The window then repeats the same call, each
ending in ``block_until_ready`` on W, until ``seconds`` have passed; it
closes at the end of the call that crosses that time, so every round it
counts is whole and the rate is rounds over the window's own length.

The check compares W of every call in the window against the plain
reference of the configured method (``bench/reference/solvers.py``) by
the worst task column: ||w_j - r_j|| / max(||r_j||, median_k ||r_k||).
"""
from __future__ import annotations

import sys
import time
from functools import partial

import numpy as np

from bench.common import Check, prng_key
from bench.gen.synthetic import generate


class Cell:
    def __init__(self, ctx):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        import repro
        from repro.core.methods import MTLProblem
        from repro.runtime import task_mesh

        self.ctx = ctx
        cfg = ctx.config
        self.mesh = task_mesh(ctx.chips)
        on_tasks = NamedSharding(self.mesh, P("tasks"))

        def make(key):
            Xs, ys, _, _ = generate(key, **cfg["problem"])
            return Xs, ys

        t0 = time.perf_counter()
        self.Xs, self.ys = jax.jit(make, out_shardings=(on_tasks, on_tasks))(
            prng_key(ctx.seed))
        jax.block_until_ready(self.ys)
        t1 = time.perf_counter()
        self.prob = MTLProblem.make(self.Xs, self.ys, cfg["loss"],
                                    gram=cfg["gram"], A=cfg["A"],
                                    r=cfg["problem"]["r"])
        jax.block_until_ready(self.prob.gram_A)
        t2 = time.perf_counter()
        self.hp = dict(method=cfg["method"], **cfg["hp"])
        self._solve = partial(repro.solve, backend="mesh", mesh=self.mesh)
        self.calls = []
        self.window_s = None
        self.call()                               # the warm-up
        print(f"set-up: data {t1 - t0:.3f} s, problem {t2 - t1:.3f} s, "
              f"warm-up solve {time.perf_counter() - t2:.3f} s",
              file=sys.stderr, flush=True)

    def call(self):
        """One timed unit: a whole solve, W on the device when it returns.
        Returns (W, rounds, exact-SVD rounds or None)."""
        import jax
        res = self._solve(self.prob, **self.hp)
        jax.block_until_ready(res.W)
        return res.W, res.comm.rounds, res.extras.get("sv_exact_rounds")


def setup(ctx) -> Cell:
    return Cell(ctx)


def window(cell: Cell) -> None:
    ctx = cell.ctx
    t0 = time.perf_counter()
    with ctx.annotate("bench.window"):
        while True:
            with ctx.annotate("bench.solve_call"):
                out = cell.call()
            elapsed = time.perf_counter() - t0
            cell.calls.append(out)
            if elapsed >= ctx.seconds:
                break
    cell.window_s = elapsed


def end_to_end(cell: Cell) -> dict:
    rounds = sum(r for _, r, _ in cell.calls)
    return {"solve_rounds_per_s": rounds / cell.window_s}


def counters(cell: Cell) -> dict:
    exact = [e for _, _, e in cell.calls if e is not None]
    return {"window_s": cell.window_s, "calls": len(cell.calls),
            "rounds": sum(r for _, r, _ in cell.calls),
            "sv_exact_rounds": sum(exact) if exact else None}


def attempted(cell: Cell) -> int:
    return len(cell.calls)


def release(cell: Cell) -> None:
    """Drop the program's problem (and its Gram cache) before the check."""
    cell.prob = None
    cell._solve = None


def column_gap(W, R) -> float:
    """max_j ||w_j - r_j|| / max(||r_j||, median_k ||r_k||)."""
    W = np.asarray(W, np.float64)
    R = np.asarray(R, np.float64)
    rn = np.linalg.norm(R, axis=0)
    den = np.maximum(rn, max(float(np.median(rn)), 1e-30))
    return float(np.max(np.linalg.norm(W - R, axis=0) / den))


def reference(cell: Cell, dtype=None):
    """The plain reference's W on the first chip (float32 unless ``dtype``)."""
    import jax
    import jax.numpy as jnp
    from bench.reference.solvers import REFERENCES
    cfg = cell.ctx.config
    dev = cell.ctx.devices[0]
    X = jax.device_put(cell.Xs, dev)
    y = jax.device_put(cell.ys, dev)
    kw = dict(cfg["reference_hp"])
    return REFERENCES[cfg["method"]](X, y, loss=cfg["loss"],
                                     dtype=dtype or jnp.float32, **kw)


def failed(cell: Cell) -> int:
    return sum(not np.all(np.isfinite(np.asarray(W))) for W, _, _ in cell.calls)


def readings(cell: Cell) -> dict:
    """``w_gap``: ``column_gap`` of the worst of the window's calls against
    the reference's W."""
    R = np.asarray(reference(cell), np.float64)
    return {"w_gap": max(column_gap(W, R) for W, _, _ in cell.calls)}


def check(cell: Cell) -> list:
    got = readings(cell)
    return [Check(name, got[name], limit)
            for name, limit in cell.ctx.limits().items()]
