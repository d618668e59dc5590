"""Program spans on the profiler's clock (repro.obs.tracing, two tiers).

Each test runs its program under a ``jax.profiler`` trace and reads the
host events back with ``jax.profiler.ProfileData``:

* a solve traces its round program under ``solve.trace`` and runs it
  under ``solve.run``, nested in ``solve``; only the first solve of a
  program in the process lowers and compiles it (``solve.lower``,
  ``solve.compile``), with a profiler trace or without one, and tracing
  changes nothing the solve returns;
* a ``score`` call opens ``serve.score`` holding ``serve.prepare``, a
  ``serve.pad`` only when the last wave is padded, one ``serve.dispatch``
  a wave and one ``serve.sync``; those per-call spans never enter the
  tracer's ring;
* the lowered round program carries the ``worker`` and ``shrink`` named
  scopes.
"""
import glob
import math
import re

import jax
import numpy as np
import pytest

import repro
from repro.core.methods import MTLProblem
from repro.data.synthetic import SimSpec, generate
from repro.obs import default_tracer
from repro.runtime import base
from repro.serve.mtl import FactoredModel, MTLServer

jax.config.update("jax_platform_name", "cpu")

SPEC = SimSpec(p=10, m=8, r=2, n=24)
STAGES = ("solve.trace", "solve.lower", "solve.compile", "solve.run")
B = 4


@pytest.fixture
def cleared():
    """An empty round-program cache, so the test's first solve stages."""
    base.cache_clear()
    yield
    base.cache_clear()


@pytest.fixture(scope="module")
def prob():
    Xs, ys, _, _ = generate(jax.random.PRNGKey(0), SPEC)
    return MTLProblem.make(Xs, ys, "squared", A=2.0, r=SPEC.r)


@pytest.fixture
def profiled(tmp_path):
    """``with profiled() as events: ...`` traces the block; afterwards
    ``events`` holds the host plane's (name, start_ns, end_ns, line)."""
    from contextlib import contextmanager
    from jax.profiler import ProfileData

    @contextmanager
    def run():
        events = []
        jax.profiler.start_trace(str(tmp_path))
        try:
            yield events
        finally:
            jax.profiler.stop_trace()
            path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                             recursive=True)[-1]
            for plane in ProfileData.from_file(path).planes:
                if plane.name != "/host:CPU":
                    continue
                for li, line in enumerate(plane.lines):
                    for e in line.events:
                        events.append((e.name, e.start_ns,
                                       e.start_ns + e.duration_ns, li))
    return run


def _named(events, name):
    return [e for e in events if e[0] == name]


def _inside(child, parent) -> bool:
    return (child[3] == parent[3] and parent[1] <= child[1]
            and child[2] <= parent[2])


def _ledger(res):
    return [(e.round, e.direction, e.vectors, e.dim, e.note)
            for e in res.comm.events]


def _solve(prob, backend):
    return repro.solve(prob, method="proxgd", backend=backend, rounds=3,
                       lam=0.05, scan=True)


@pytest.mark.parametrize("backend", ["sim", "mesh"])
def test_solve_stages_once_each_inside_solve(prob, profiled, cleared,
                                             backend):
    with profiled() as events:
        _solve(prob, backend)
        _solve(prob, backend)
    first, again = sorted(_named(events, "solve"), key=lambda e: e[1])
    for name in STAGES:
        spans = _named(events, name)
        assert _inside(spans[0], first), name
        # the identical second solve traces and runs, and reuses the
        # compiled program
        assert len(spans) == (2 if name in ("solve.trace", "solve.run")
                              else 1), (name, spans)
    # the stages run in order, one after another
    starts = [_named(events, n)[0][1] for n in STAGES]
    assert starts == sorted(starts)
    for name in ("solve.trace", "solve.run"):
        assert _inside(max(_named(events, name), key=lambda e: e[1]),
                       again), name


@pytest.mark.parametrize("backend", ["sim", "mesh"])
def test_trace_changes_nothing_the_solve_returns(prob, profiled, backend):
    bare = _solve(prob, backend)
    with profiled():
        traced = _solve(prob, backend)
    assert np.array_equal(np.asarray(bare.W), np.asarray(traced.W))
    assert _ledger(bare) == _ledger(traced)
    assert bare.comm.summary() == traced.comm.summary()


@pytest.mark.parametrize("backend", ["sim", "mesh"])
def test_snapshot_handoff_one_span_per_record(prob, profiled, backend):
    """The scanned driver hands a record's snapshots over under one
    ``solve.snapshots`` span, after the round program's dispatch."""
    with profiled() as events:
        repro.solve(prob, method="proxgd", backend=backend, rounds=4,
                    lam=0.05, scan=True, metrics=True)
    (call,) = _named(events, "solve")
    (run,) = _named(events, "solve.run")
    spans = _named(events, "solve.snapshots")
    assert len(spans) == 2, spans          # the iterates, the metrics
    for span in spans:
        assert _inside(span, call) and span[1] >= run[2], (span, run)


def test_eager_driver_stages_once(prob, profiled, cleared):
    with profiled() as events:
        res = repro.solve(prob, method="proxgd", rounds=3, lam=0.05,
                          scan=False)
    assert res.comm.rounds == 3
    for name in STAGES[:3]:
        assert len(_named(events, name)) == 1, name
    assert len(_named(events, "solve.run")) == 3     # one dispatch a round


@pytest.mark.parametrize("backend", ["sim", "mesh"])
def test_untraced_solve_keeps_the_jit_call_path(prob, monkeypatch, cleared,
                                                backend):
    # with no profiler trace a solve takes the same path as under one:
    # two identical solves stage their program once
    staged = []
    stage = base.stage
    monkeypatch.setattr(base, "stage",
                        lambda traced: staged.append(traced) or stage(traced))
    ring = default_tracer()
    before = len(ring.events())
    _solve(prob, backend)
    _solve(prob, backend)
    assert len(staged) == 1
    names = [e["name"] for e in ring.events()[before:]]
    assert names.count("solve") == 2 and names.count("solve.trace") == 2
    assert names.count("solve.lower") == names.count("solve.compile") == 1


@pytest.fixture(scope="module")
def server():
    U, s, V = (np.asarray(a, np.float32) for a in (
        np.linalg.qr(np.random.default_rng(0).standard_normal((16, 2)))[0],
        [2.0, 1.0], np.random.default_rng(1).standard_normal((6, 2))))
    srv = MTLServer(FactoredModel(U=U, s=s, V=V), batch_size=B)
    X = np.random.default_rng(2).standard_normal((2 * B + 1, 16))
    for n in (B, 1):                        # warm both wave shapes
        srv.score(np.zeros(n, np.int32), X[:n].astype(np.float32))
    return srv, X.astype(np.float32)


@pytest.mark.parametrize("n", [B, 2 * B + 1])
def test_score_spans(server, profiled, n):
    srv, X = server
    ids = np.arange(n, dtype=np.int32) % 6
    with profiled() as events:
        preds, _ = srv.score(ids, X[:n])
        np.asarray(preds)
    (call,) = _named(events, "serve.score")
    waves = math.ceil(n / B)
    counts = {k: len(_named(events, k)) for k in
              ("serve.prepare", "serve.pad", "serve.dispatch", "serve.sync")}
    assert counts == {"serve.prepare": 1, "serve.pad": int(n % B != 0),
                      "serve.dispatch": waves, "serve.sync": 1}
    for name in counts:
        assert all(_inside(e, call) for e in _named(events, name)), name


def test_bad_ids_raise_and_close_their_spans(server, profiled):
    srv, X = server
    with profiled() as events:
        with pytest.raises(ValueError, match="task ids outside"):
            srv.score(np.array([0, 99, 1, 2], np.int32), X[:4])
    (call,) = _named(events, "serve.score")
    (sync,) = _named(events, "serve.sync")
    assert _inside(sync, call) and call[2] > call[1]


def test_score_calls_leave_the_ring_alone(server):
    srv, X = server
    ring = default_tracer()
    before = ring.events()
    for i in range(100):
        srv.score(np.full(B, i % 6, np.int32), X[:B])
    assert len(ring.events()) == len(before)
    assert ring.events() == before


def test_lowered_round_program_names_worker_and_shrink(prob, monkeypatch,
                                                       profiled, cleared):
    texts = []
    stage = base.stage

    def keep_text(traced):
        texts.append(traced.lower().as_text(debug_info=True))
        return stage(traced)

    monkeypatch.setattr(base, "stage", keep_text)
    with profiled():
        _solve(prob, "sim")
    (text,) = texts
    scopes = {seg for loc in re.findall(r'loc\("([^"]*)"', text)
              for seg in loc.split("/")[:-1]}
    assert {"worker", "shrink"} <= scopes
