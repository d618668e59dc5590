"""The trace reduction, on a trace recorded on a TPU v5e (a 2-round proxgd
solve at p=256, m=16, n=64, cut by ``bench/trace_extract.py``) and on a
hand-made trace whose answers are known."""
import os

import pytest

pytest.importorskip("jax")

from bench import trace_reduce as tr  # noqa: E402

DATA = os.path.join(os.path.dirname(__file__), "data")
RECORDED = os.path.join(DATA, "proxgd_2rounds.xspace.txt")


def _plane(pid, name, lines, names):
    body = "\n".join(lines)
    meta = "\n".join(f'event_metadata {{ key: {i} value {{ id: {i} name: '
                     f'"{n}" }} }}' for i, n in enumerate(names, 1))
    return f'planes {{ id: {pid} name: "{name}"\n{body}\n{meta}\n}}'


def _line(lid, name, events):
    evs = " ".join(f"events {{ metadata_id: {m} offset_ps: {a * 1000} "
                   f"duration_ps: {d * 1000} }}" for m, a, d in events)
    return f'lines {{ id: {lid} name: "{name}" timestamp_ns: 0 {evs} }}'


@pytest.fixture
def made(tmp_path):
    """Window [100, 1100) ns.  Device 0: a while op over [200, 600) holding
    two fusions [250, 300) and [400, 500), a kernel [700, 800), and an op
    [1050, 1200) cut by the window's end.  Device 1: one op [100, 300)."""
    host = _plane(1, "/host:CPU", [_line(1, "python3", [
        (1, 100, 1000), (2, 150, 500), (3, 700, 250)])],
        ["bench.window", "bench.solve_call", "bench.score"])
    dev0 = _plane(2, "/device:TPU:0", [_line(1, "XLA Ops", [
        (1, 200, 400), (2, 250, 50), (3, 400, 100), (4, 700, 100),
        (5, 1050, 150), (6, 10, 20)])],
        ["%while.1 = (f32[2]) while(x)", "%fusion.2 = f32[2] fusion(a)",
         "%fusion.3 = f32[2] fusion(%task_gradients_mnp.4)",
         '%task_gradients_mnp.4 = f32[2] custom-call(x), '
         'custom_call_target=\\"tpu_custom_call\\"',
         "%all-gather.5 = f32[4] all-gather(x)", "%early.6 = f32[1] copy(x)"])
    dev1 = _plane(3, "/device:TPU:1", [_line(1, "XLA Ops", [(1, 100, 200)])],
                  ["%copy.1 = f32[2] copy(x)"])
    path = tmp_path / "made.xspace.txt"
    path.write_text("\n".join([host, dev0, dev1]))
    return tr.load(str(path))


def test_window_busy_and_idle(made):
    assert made.window == (100, 1100)
    assert made.window_s == pytest.approx(1e-6)
    # device 0: [200, 600) + [700, 800) + [1050, 1100) = 550 ns; device 1:
    # 200 ns; the op before the window is dropped
    assert tr.busy_s(made) == pytest.approx((550 + 200) / 2 * 1e-9)
    assert tr.idle_share(made) == pytest.approx(1 - 375 / 1000)


def test_op_seconds_match_the_op_not_its_operands(made):
    # one event each, on device 0 of two: averaged over the chips
    secs, _ = tr.op_seconds(made, lambda op: op.startswith("task_grad"))
    assert secs == pytest.approx(100e-9 / 2)
    secs, _ = tr.op_seconds(made, lambda op: op.startswith("all-gather"))
    assert secs == pytest.approx(150e-9 / 2)


def test_top_ops_rank_self_time(made):
    ranked = dict(tr.top_ops(made))
    # the while op holds 150 ns of children: its self time is 250 ns
    assert ranked["while.1 (while)"] == pytest.approx(250e-9 / 2)
    assert ranked["task_gradients_mnp.4 (custom-call tpu_custom_call)"] \
        == pytest.approx(100e-9 / 2)
    assert sum(ranked.values()) == pytest.approx(
        (400 + 100 + 150 + 200) / 2 * 1e-9)   # the early op is out


def test_idle_gaps_named_by_innermost_host_event(made):
    gaps = tr.idle_gaps(made)
    # device 0's gaps: [100,200) in solve_call, [600,700) past it,
    # [800,1050) in score
    assert gaps == [["bench.score", pytest.approx(250e-9)],
                    ["bench.solve_call", pytest.approx(100e-9)],
                    ["bench.window", pytest.approx(100e-9)]]


def test_recorded_trace():
    t = tr.load(RECORDED)
    assert list(t.devices) == ["/device:TPU:0"]
    assert 0.5 < t.window_s < 1.0
    busy = tr.busy_s(t)
    assert 0 < busy < t.window_s
    # one mtl_grad kernel call per round
    secs, calls = tr.op_seconds(t, lambda op: op.startswith(
        "task_gradients"))
    assert calls == 2 and 0 < secs < busy
    ranked = tr.top_ops(t)
    assert len(ranked) == 10
    assert sum(s for _, s in ranked) <= busy
    gaps = tr.idle_gaps(t)
    assert [g[1] for g in gaps] == sorted((g[1] for g in gaps), reverse=True)
    assert gaps[0][1] <= t.window_s - busy
