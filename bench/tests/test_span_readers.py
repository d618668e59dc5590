"""The readers of the program's own spans (``bench/spans.py``): on hand-made
traces whose answers are known, and on traces recorded on a TPU v5e
(a short traced run of each cell, cut by ``bench/trace_extract.py``)."""
import os

import pytest

pytest.importorskip("jax")

from bench import trace_reduce as tr  # noqa: E402
from bench.run import reader  # noqa: E402

DATA = os.path.join(os.path.dirname(__file__), "data")
SOLVE = os.path.join(DATA, "sp2048_solve_call.xspace.txt")
SERVE = os.path.join(DATA, "sp2048_serve_calls.xspace.txt")

SOLVE_METRICS = ("solve_stage_share", "solve_stagings_per_call")
SERVE_METRICS = ("score_prepare_ms.p50", "score_dispatch_ms.p50",
                 "score_sync_ms.p50")


def _trace(tmp_path, host, device=True):
    """A trace file of one host line (``(name, start, duration)`` in ns)
    and, with ``device``, a TPU plane holding one op."""
    names = sorted({n for n, _, _ in host})
    ids = {n: i for i, n in enumerate(names, 1)}
    evs = " ".join(f"events {{ metadata_id: {ids[n]} offset_ps: {a * 1000} "
                   f"duration_ps: {d * 1000} }}" for n, a, d in host)
    meta = "\n".join(f'event_metadata {{ key: {i} value {{ id: {i} name: '
                     f'"{n}" }} }}' for n, i in ids.items())
    planes = [f'planes {{ id: 1 name: "/host:CPU"\nlines {{ id: 1 name: '
              f'"python3" timestamp_ns: 0 {evs} }}\n{meta}\n}}']
    if device:
        planes.append('planes { id: 2 name: "/device:TPU:0"\nlines { id: 1 '
                      'name: "XLA Ops" timestamp_ns: 0 events { metadata_id:'
                      ' 1 offset_ps: 50000 duration_ps: 10000 } }\n'
                      'event_metadata { key: 1 value { id: 1 name: '
                      '"%copy.1 = f32[2] copy(x)" } }\n}')
    path = tmp_path / "made.xspace.txt"
    path.write_text("\n".join(planes))
    return tr.load(str(path))


def _rec(trace):
    return {"trace": trace, "counters": {}, "config": {}, "chips": 1,
            "peaks": {}}


# Window [0, 10000) ns: two solve calls staged inside it, a third that
# straddles the window's end: only its trace lies inside and counts.
SOLVE_HOST = [
    ("bench.window", 0, 10000),
    ("solve", 100, 4000), ("solve.trace", 200, 500), ("solve.lower", 700, 300),
    ("solve.compile", 1000, 2000), ("solve.run", 3000, 100),
    ("solve", 5000, 4000), ("solve.trace", 5100, 300),
    ("solve.lower", 5400, 200), ("solve.compile", 5600, 2400),
    ("solve.run", 8000, 100),
    ("solve", 9500, 2000), ("solve.trace", 9600, 300),
    ("solve.compile", 9950, 500),
]

# Three score calls in the window (the second of two waves, its last one
# padded) and one that ends after it.
SERVE_HOST = [
    ("bench.window", 0, 10000),
    ("bench.score", 90, 1100), ("serve.score", 100, 1000),
    ("serve.prepare", 110, 190), ("serve.dispatch", 300, 100),
    ("serve.sync", 400, 600),
    ("bench.score", 1990, 1600), ("serve.score", 2000, 1500),
    ("serve.prepare", 2010, 190), ("serve.dispatch", 2200, 100),
    ("serve.pad", 2300, 100), ("serve.dispatch", 2400, 50),
    ("serve.sync", 2450, 950),
    ("bench.score", 4990, 700), ("serve.score", 5000, 600),
    ("serve.prepare", 5000, 100), ("serve.dispatch", 5100, 100),
    ("serve.sync", 5200, 300),
    ("serve.score", 9900, 400), ("serve.prepare", 9910, 50),
    ("serve.dispatch", 9960, 30), ("serve.sync", 9990, 300),
]


def test_solve_readers_on_a_made_trace(tmp_path):
    rec = _rec(_trace(tmp_path, SOLVE_HOST))
    staged = (500 + 300 + 2000) + (300 + 200 + 2400) + 300
    assert reader("solve_stage_share")(rec) == pytest.approx(
        100.0 * staged / 10000)
    assert reader("solve_stagings_per_call")(rec) == pytest.approx(1.0)


def test_serve_readers_on_a_made_trace(tmp_path):
    rec = _rec(_trace(tmp_path, SERVE_HOST))
    # per call: prepare (+ pad) 190, 290, 100; dispatch 100, 150, 100;
    # sync 600, 950, 300 ns
    assert reader("score_prepare_ms.p50")(rec) == pytest.approx(190e-6)
    assert reader("score_dispatch_ms.p50")(rec) == pytest.approx(100e-6)
    assert reader("score_sync_ms.p50")(rec) == pytest.approx(600e-6)


@pytest.mark.parametrize("host,names", [(SOLVE_HOST, SOLVE_METRICS),
                                        (SERVE_HOST, SERVE_METRICS)])
def test_readers_need_a_chip_run(tmp_path, host, names):
    rec = _rec(_trace(tmp_path, host, device=False))
    for name in names:
        assert reader(name)(rec) is None, name


@pytest.mark.parametrize("host,names", [(SOLVE_HOST, SOLVE_METRICS),
                                        (SERVE_HOST, SERVE_METRICS)])
def test_readers_of_a_program_without_spans(tmp_path, host, names):
    """A program that opens no such span (an older one) reads as nothing,
    and so does a run without a trace."""
    bare = [e for e in host if e[0].startswith("bench.")]
    for rec in (_rec(_trace(tmp_path, bare)), _rec(None)):
        for name in names:
            assert reader(name)(rec) is None, name


def test_recorded_solve_call():
    t = tr.load(SOLVE)
    rec = _rec(t)
    assert reader("solve_stagings_per_call")(rec) == pytest.approx(1.0)
    share = reader("solve_stage_share")(rec)
    assert 50.0 < share < 100.0
    # the chip waits on the staging: the longest idle gap is named by a
    # staging span, not by JAX's PjitFunction
    (name, secs), = tr.idle_gaps(t, k=1)
    assert name in ("solve.trace", "solve.lower", "solve.compile"), name
    assert secs > 0.5 * share / 100.0 * t.window_s


def test_recorded_serve_calls():
    t = tr.load(SERVE)
    rec = _rec(t)
    got = {name: reader(name)(rec) for name in SERVE_METRICS}
    assert all(v is not None and v > 0 for v in got.values()), got
    # the parts of a call fit inside the driver's own span around it
    calls = sorted(e.end - e.start for e in t.host if e.name == "bench.score")
    assert sum(got.values()) <= 1e-6 * calls[len(calls) // 2] * 1.05
