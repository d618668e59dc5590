"""The scanned driver's snapshot hand-off (``runtime.base.unstack_snapshots``).

After the round program returns its stacked snapshot buffers, the
scanned driver splits each record's buffers in one jitted dispatch and
hands the pieces to the record's sink:

* the history a sink receives is bit-identical to the eager driver's,
  for the iterates (``record_every`` on and off the final round) and
  for the obs metrics channel, on ``sim`` and on a 4-device ``mesh``
  (one subprocess, the ``test_runtime_parity`` pattern);
* the split is entered once per record per call, never per snapshot,
  and compiles once for a shape: a second solve of that shape reuses it.
"""
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

import repro
from repro.core.methods import MTLProblem
from repro.data.synthetic import SimSpec, generate
from repro.runtime import base

ROUNDS = 11
METHODS = ["proxgd", "admm"]      # admm records Z, not W
CASES = [(b, e, m) for b in ("sim", "mesh") for e in (1, 7)
         for m in METHODS]

SCRIPT = textwrap.dedent("""
    import jax, numpy as np
    assert len(jax.devices()) == 4, jax.devices()
    import repro
    from repro.core.methods import MTLProblem
    from repro.data.synthetic import SimSpec, generate

    Xs, ys, _, _ = generate(jax.random.PRNGKey(0),
                            SimSpec(p=16, m=8, r=3, n=32))
    prob = MTLProblem.make(Xs, ys, "squared", A=2.0, r=3)
    HP = {"proxgd": {"lam": 0.05}, "admm": {"lam": 0.05, "rho": 0.5}}

    def same(a, b):
        return np.array_equal(np.asarray(a), np.asarray(b))

    for backend in ("sim", "mesh"):
        for every in (1, 7):
            for method in ("proxgd", "admm"):
                eager, scanned = (
                    repro.solve(prob, method=method, backend=backend,
                                scan=scan, rounds=%(rounds)d,
                                record_every=every, metrics=True,
                                **HP[method])
                    for scan in (False, True))
                iters = (eager.rounds_axis == scanned.rounds_axis
                         and len(eager.iterates) == len(scanned.iterates)
                         and all(same(a, b) for a, b in
                                 zip(eager.iterates, scanned.iterates)))
                me, ms = eager.extras["metrics"], scanned.extras["metrics"]
                metrics = (me.keys() == ms.keys()
                           and all(same(me[k], ms[k]) for k in me))
                print(f"HANDOFF {backend} {every} {method} "
                      f"iters={int(iters)} metrics={int(metrics)} "
                      f"n={len(scanned.iterates)}")
""") % {"rounds": ROUNDS}


@pytest.fixture(scope="module")
def handoff_lines():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stdout + out.stderr
    lines = {}
    for line in out.stdout.splitlines():
        if line.startswith("HANDOFF "):
            toks = line.split()
            lines[(toks[1], int(toks[2]), toks[3])] = dict(
                kv.split("=") for kv in toks[4:])
    return lines


@pytest.mark.slow
@pytest.mark.parametrize("backend,every,method", CASES)
def test_scanned_iterates_bitidentical_to_eager(handoff_lines, backend,
                                                every, method):
    row = handoff_lines[(backend, every, method)]
    assert row["iters"] == "1", row
    # the initial iterate, the cadence, and the off-cadence final round
    assert int(row["n"]) == 1 + ROUNDS // every + (ROUNDS % every > 0), row


@pytest.mark.slow
@pytest.mark.parametrize("backend,every,method", CASES)
def test_scanned_metrics_equal_eager(handoff_lines, backend, every, method):
    assert handoff_lines[(backend, every, method)]["metrics"] == "1"


@pytest.fixture
def counted(monkeypatch):
    """Count entries into the jitted split, from an empty compile
    cache; ``calls[0]`` is the count, ``compiles()`` its compiled
    programs so far."""
    split = base._unstack
    split.clear_cache()
    calls = [0]

    def counting(bufs):
        calls[0] += 1
        return split(bufs)

    monkeypatch.setattr(base, "_unstack", counting)
    return calls, split._cache_size


@pytest.mark.parametrize("backend", ["sim", "mesh"])
def test_one_split_per_record_per_call(counted, backend):
    calls, compiles = counted
    Xs, ys, _, _ = generate(jax.random.PRNGKey(1),
                            SimSpec(p=13, m=4, r=2, n=20))
    prob = MTLProblem.make(Xs, ys, "squared", A=2.0, r=2)
    runs = [repro.solve(prob, method="proxgd", backend=backend, scan=True,
                        rounds=9, record_every=1, metrics=True, lam=0.05)
            for _ in range(2)]
    records = 2                       # the iterates and the obs metrics
    assert calls[0] == records * len(runs)
    assert compiles() == records         # the first solve's, reused
    for res in runs:
        assert len(res.iterates) == 1 + 9
        assert len(res.extras["metrics"]["round"]) == 9
    for a, b in zip(*(r.iterates for r in runs)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_split_matches_per_snapshot_slices():
    bufs = {"a": jax.random.normal(jax.random.PRNGKey(2), (5, 3, 4)),
            "b": np.arange(5, dtype=np.int32)}
    parts = base.unstack_snapshots(bufs)
    assert len(parts) == 5
    for si, part in enumerate(parts):
        assert part.keys() == bufs.keys()
        for k in bufs:
            np.testing.assert_array_equal(np.asarray(part[k]),
                                          np.asarray(bufs[k][si]))
