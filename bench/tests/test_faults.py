"""A run whose timed path is broken underneath comes out not correct.

Each test drives the rest of a run on the CPU at a tiny size (the look for
a chip skipped), with one fault planted in the program under test, and
sees ``correct`` false: a solve that returns its state unchanged, a worker
that leaves half of each task's rows out, an answer altered where it is
produced; for serving, half of a call's requests left out and one score
altered.  The controls, one precision lower in the program's place (the
plain reference in bfloat16 for a solve, the program's own int8 code
table for serving), fail too, through the same run.
"""
import os
import time

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from conftest import ROOT, tiny_ctx  # noqa: E402


def _run_ctx(ctx):
    from bench.common import load_json
    from bench.run import run_cell
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    peaks = load_json(os.path.join(ROOT, "bench", "peaks.json"))
    return run_cell(ctx, bench, peaks["TPU v5 lite"], time.perf_counter())


def _run(workload, **kw):
    return _run_ctx(tiny_ctx(workload, **kw))


def _unchanged(self, rounds, body, state, **kw):
    return state


def _half_rows(real):
    def grad_columns(loss, W_cols, data, *a, **kw):
        n = data["Xs"].shape[1] // 2
        data = dict(data, Xs=data["Xs"][:, :n], ys=data["ys"][:, :n])
        return real(loss, W_cols, data, *a, **kw)
    return grad_columns


def _altered_answer(real):
    def solve(prob, *a, **kw):
        res = real(prob, *a, **kw)
        res.W = res.W.at[:, 0].multiply(-1.0)
        return res
    return solve


SOLVE_FAULTS = {
    "state unchanged": ("repro.runtime.base.ProtocolRuntime.run_rounds",
                        lambda real: _unchanged),
    "half the rows": ("repro.core.worker_ops.grad_columns", _half_rows),
    "answer altered": ("repro.solve", _altered_answer),
}


def _plant(monkeypatch, target, make):
    import importlib
    mod, _, attr = target.rpartition(".")
    try:
        owner = importlib.import_module(mod)
    except ModuleNotFoundError:
        mod, _, cls = mod.rpartition(".")
        owner = getattr(importlib.import_module(mod), cls)
    monkeypatch.setattr(owner, attr, make(getattr(owner, attr)))


@pytest.mark.parametrize("fault", sorted(SOLVE_FAULTS))
@pytest.mark.parametrize("workload", ["sp2048.solve"])
def test_solve_fault_is_caught(monkeypatch, workload, fault):
    assert _run(workload)["correct"] is True
    _plant(monkeypatch, *SOLVE_FAULTS[fault])
    out = _run(workload)
    assert out["correct"] is False, out["checks"]


def _serve_fault(fault):
    from repro.serve import mtl
    real = mtl._score_batch

    @jax.jit
    def half(U, C, ids, X, m):
        keep = jnp.arange(X.shape[0]) % 2 == 0
        return real(U, C, ids, jnp.where(keep[:, None], X, 0.0), m)

    @jax.jit
    def altered(U, C, ids, X, m):
        preds, ok = real(U, C, ids, X, m)
        return preds.at[0].add(1e-2 * jnp.linalg.norm(X[0])), ok

    return {"half the batch": half, "answer altered": altered}[fault]


@pytest.mark.parametrize("fault", ["half the batch", "answer altered"])
def test_serve_fault_is_caught(monkeypatch, fault):
    from repro.serve import mtl
    assert _run("sp2048.serve-overload")["correct"] is True
    monkeypatch.setattr(mtl, "_score_batch", _serve_fault(fault))
    out = _run("sp2048.serve-overload")
    assert out["correct"] is False, out["checks"]


# The solve control's error grows with the problem: at the tiny size
# bfloat16 stays inside the limit set at the cell's size, at an eighth of
# the cell's data (p=1024, m=192, n=64, the cell's rounds, lam and rank)
# it reads 0.0063-0.0070 on the CPU, over three times the limit.
CONTROL_SIZE = {
    "sp2048.solve": ({"p": 1024, "m": 192, "n": 64, "r": 4},
                     {"rounds": 50, "lam": 0.0013, "sv_rank": 8}),
    "sp2048.serve-overload": (None, None),
}


@pytest.mark.parametrize("workload", sorted(CONTROL_SIZE))
def test_control_fails_the_check(monkeypatch, workload):
    """The control in the program's place, through the harness's own run
    and comparison, comes out not correct."""
    import importlib
    from bench.controls import control_call
    problem, hp = CONTROL_SIZE[workload]

    def ctx():
        c = tiny_ctx(workload, seed=9)
        if problem:
            c.config["problem"].update(problem)
            c.config["hp"].update(hp)
            c.config["reference_hp"].update(
                {k: v for k, v in hp.items() if k in ("rounds", "lam")})
        return c

    assert _run_ctx(ctx())["correct"] is True
    kind = ctx().traffic["kind"]
    driver = importlib.import_module("bench.drivers." + kind)
    real = driver.setup

    def control_setup(c):
        cell = real(c)
        control_call(kind, cell)
        return cell

    monkeypatch.setattr(driver, "setup", control_setup)
    out = _run_ctx(ctx())
    assert out["failed"] == 0
    assert out["correct"] is False, out["checks"]
