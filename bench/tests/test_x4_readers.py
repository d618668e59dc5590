"""The readers of sp2048x4.solve (``exchange_ms_per_round``,
``gram_grad_roofline``): on hand-made traces whose answers are known, on
one call recorded on a TPU v5e four-chip host (cut by
``bench/trace_extract.py``), and a CPU rehearsal of the cell on four host
devices."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

pytest.importorskip("jax")

from bench import trace_reduce as tr  # noqa: E402
from bench.run import reader  # noqa: E402
from conftest import ROOT  # noqa: E402

DATA = os.path.join(os.path.dirname(__file__), "data")
CALL = os.path.join(DATA, "sp2048x4_solve_call.xspace.txt")
CELL = "sp2048x4.solve"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _config():
    from bench.run import load_cell
    return load_cell(CELL)[2]


def _rec(trace, config=None, counters=None):
    return {"trace": trace, "counters": counters or {},
            "config": config or _config(), "chips": 4, "peaks": PEAKS}


CACHE_OP = ("%multiply_reduce_fusion = f32[192,2048]{1,0:T(8,128)} fusion("
            "f32[192,2048,2048]{2,1,0:T(8,128)} %get-tuple-element.3, "
            "f32[192,2048]{1,0:T(8,128)} %bitcast.3), kind=kLoop")
BUILD_OP = ("%fusion.2 = f32[192,2048,2048]{2,1,0:T(8,128)} fusion("
            "f32[192,64,2048]{2,1,0:T(8,128)} %x), kind=kOutput")
GATHER = ("%all-gather-start = (f32[2048,192]{0,1}, f32[2048,768]{0,1}) "
          "all-gather-start(f32[2048,192]{0,1:T(8,128)} %fusion.7), "
          "replica_groups={0,1,2,3}, dimensions={1}")
GATHER_DONE = ("%all-gather-done = f32[2048,768]{0,1:T(8,128)} "
               "all-gather-done((f32[2048,192]{0,1}, f32[2048,768]{0,1}) "
               "%all-gather-start)")
REDUCE = ("%all-reduce.1 = f32[]{:T(128)} all-reduce(f32[]{:T(128)} %x), "
          "replica_groups={0,1,2,3}, to_apply=%add")
LOOP = ("%while.601 = (s32[]{:T(128)}, f32[192,2048,2048]{2,1,0:T(8,128)}) "
        "while((s32[]{:T(128)}, f32[192,2048,2048]{2,1,0:T(8,128)}) "
        "%tuple.5), condition=%cond, body=%body")
NOT_COLLECTIVE = ("%fusion.9 = f32[2048,768]{0,1:T(8,128)} fusion("
                  "f32[2048,768]{0,1:T(8,128)} %all-gather-done), "
                  "kind=kLoop")


def _made(tmp_path, planes):
    """A trace of a 0..100000 ns window and TPU planes of
    ``(name, start ns, duration ns)`` events."""
    out = ['planes { id: 1 name: "/host:CPU"\nlines { id: 1 name: '
           '"python3" timestamp_ns: 0 events { metadata_id: 1 offset_ps: 0 '
           'duration_ps: 100000000 } }\nevent_metadata { key: 1 value { '
           'id: 1 name: "bench.window" } }\n}']
    for pid, evs in enumerate(planes, 2):
        names = sorted({n for n, _, _ in evs})
        ids = {n: i for i, n in enumerate(names, 1)}
        body = " ".join(f"events {{ metadata_id: {ids[n]} offset_ps: "
                        f"{a * 1000} duration_ps: {d * 1000} }}"
                        for n, a, d in evs)
        meta = "\n".join(
            f"event_metadata {{ key: {i} value {{ id: {i} name: "
            f"{json.dumps(n)} }} }}" for n, i in ids.items())
        out.append(f'planes {{ id: {pid} name: "/device:TPU:{pid - 2}"\n'
                   f'lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0 {body} }}'
                   f'\n{meta}\n}}')
    path = tmp_path / "made.xspace.txt"
    path.write_text("\n".join(out))
    return tr.load(str(path))


def _round(t0, gather_ns):
    """One round's events from t0: the cache read (4000 ns), then the
    exchange (start + done, ``gather_ns`` in all) and a fusion."""
    return [(CACHE_OP, t0, 4000), (GATHER, t0 + 4000, gather_ns // 2),
            (GATHER_DONE, t0 + 4000 + gather_ns // 2, gather_ns // 2),
            (NOT_COLLECTIVE, t0 + 4000 + gather_ns, 100)]


def test_exchange_per_round_cancels_a_truncated_plane(tmp_path):
    # plane 0: 4 rounds of 200 ns exchange plus one 100 ns all-reduce;
    # plane 1: stopped after 2 rounds of 600 ns exchange
    full = [e for k in range(4) for e in _round(k * 10000, 200)]
    full.append((REDUCE, 50000, 100))
    cut = [e for k in range(2) for e in _round(k * 10000, 600)]
    trace = _made(tmp_path, [full, cut])
    want = ((4 * 200 + 100) / 4 + 600) / 2 * 1e-6
    assert reader("exchange_ms_per_round")(_rec(trace)) == pytest.approx(want)


def test_gram_roofline_counts_only_the_cache_readers(tmp_path):
    evs = [e for k in range(3) for e in _round(k * 10000, 200)]
    evs.append((BUILD_OP, 60000, 9000))       # writes the cache: not read
    evs.append((LOOP, 0, 30000))              # holds the rounds: not read
    trace = _made(tmp_path, [evs, evs[:4]])
    m, p = 192, 2048
    bound = max(2 * m * p * p / PEAKS["bf16_flops_per_s"],
                4 * (m * p * p + m * p + 2 * p * m) / PEAKS["hbm_bytes_per_s"])
    want = 100 * 4 * bound / (4 * 4000e-9)
    assert reader("gram_grad_roofline")(_rec(trace)) == pytest.approx(want)


def test_readers_find_nothing_without_the_gram_path(tmp_path):
    trace = _made(tmp_path, [_round(0, 200)])
    raw = dict(_config(), gram=False)
    for name in ("exchange_ms_per_round", "gram_grad_roofline"):
        assert reader(name)(_rec(trace, config=raw)) is None
        assert reader(name)(_rec(None)) is None
    # a plane with collectives but no cache read has no rounds to divide by
    other = _made(tmp_path, [[(GATHER, 0, 100), (REDUCE, 200, 100)]])
    assert reader("exchange_ms_per_round")(_rec(other)) is None
    assert reader("gram_grad_roofline")(_rec(other)) is None


def test_collective_matcher_reads_the_opcode():
    from bench.layer_metrics.exchange_ms_per_round import is_collective
    assert is_collective(GATHER) and is_collective(GATHER_DONE)
    assert is_collective(REDUCE)
    assert not is_collective(NOT_COLLECTIVE)
    assert not is_collective(CACHE_OP) and not is_collective("all-gather")


# -- one call recorded on a TPU v5e four-chip host ---------------------------
@pytest.fixture(scope="module")
def call():
    return tr.load(CALL)


def test_recorded_call_has_four_chips_of_fifty_rounds(call):
    from bench.layer_metrics.gram_grad_roofline import cache_ops
    assert len(call.devices) == 4
    ops = cache_ops(_rec(call))
    assert [len(v) for v in ops.values()] == [_config()["hp"]["rounds"]] * 4
    assert {e.op.split(".")[0] for v in ops.values() for e in v} == {
        "multiply_reduce_fusion"}


def test_recorded_call_exchange(call):
    from bench.layer_metrics.exchange_ms_per_round import per_plane
    planes = per_plane(_rec(call))
    assert len(planes) == 4 and all(s > 0 for s, _ in planes.values())
    # one all-gather of the (2048, 768) messages a round, ~56 us
    got = reader("exchange_ms_per_round")(_rec(call))
    assert got == pytest.approx(0.0558282, rel=1e-4)


def test_recorded_call_gram_roofline(call):
    # 4.266 ms a round against the 3.939 ms HBM bound
    got = reader("gram_grad_roofline")(_rec(call))
    assert got == pytest.approx(92.3396, rel=1e-4)


# -- the cell on four host devices ---------------------------------------------
REHEARSAL = textwrap.dedent("""
    import json, sys, time
    import jax
    assert len(jax.devices()) == 4, jax.devices()
    sys.path[:0] = [{root!r}, {tests!r}]
    from conftest import tiny_ctx
    from bench.common import load_json
    from bench.run import run_cell
    bench = load_json({bench!r})
    peaks = {peaks!r}
    out = {{}}
    for trace in (False, True):
        ctx = tiny_ctx("sp2048x4.solve", seed=2**31 + 9, trace=trace,
                       devices=jax.devices())
        out[str(trace)] = run_cell(ctx, bench, peaks, time.perf_counter())
    print(json.dumps(out))
""")


@pytest.fixture(scope="module")
def rehearsal():
    script = REHEARSAL.format(root=ROOT, tests=os.path.dirname(__file__),
                              bench=os.path.join(ROOT, "BENCHMARK.json"),
                              peaks=PEAKS)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", script], env=env, cwd=ROOT,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_x4_cell_rehearsal(rehearsal):
    out = rehearsal["False"]
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == {"solve_rounds_per_s", "setup_s"}
    assert out["device"]["count"] == 4
    assert out["checks"]["w_gap"]["value"] < 1e-4


def test_x4_traced_rehearsal_reads_the_counter_only(rehearsal):
    """On the CPU no TPU plane: the trace readers are left out."""
    out = rehearsal["True"]
    assert out["correct"] is True
    assert set(out["metrics"]) == {"sv_exact_share"}
