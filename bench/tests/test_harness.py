"""Rehearsals of every cell's run on the CPU at a tiny size, and the
checks the harness makes of BENCHMARK.json and of the device."""
import json
import os
import re
import subprocess
import sys

import pytest

pytest.importorskip("jax")

from conftest import ROOT, tiny_ctx  # noqa: E402

CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device",
                 "checks"}
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def _bench():
    from bench.common import load_json
    return load_json(os.path.join(ROOT, "BENCHMARK.json"))


def _peaks():
    from bench.common import load_json
    return load_json(os.path.join(ROOT, "bench", "peaks.json"))["TPU v5 lite"]


def _run(workload, trace=False):
    import time
    from bench.run import run_cell
    return run_cell(tiny_ctx(workload, seed=2**31 + 7, trace=trace),
                    _bench(), _peaks(), time.perf_counter())


ONE_CHIP = [w["name"] for w in _bench()["workloads"] if w["chips"] == 1]


@pytest.mark.parametrize("workload", ONE_CHIP)
def test_cell_rehearsal(workload):
    out = _run(workload)
    assert set(out) == CONTRACT_KEYS
    assert list(out)[-1] == "checks"
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    bench = _bench()
    want = {m["name"] for m in bench["end_to_end"]
            if workload in m.get("workloads", [workload])}
    assert set(out["metrics"]) == want
    for m in out["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    dev = out["device"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(dev)
    json.dumps(out)


def test_traced_rehearsal_reads_what_needs_no_device_plane():
    """On the CPU the trace has no TPU plane: the trace readers find nothing
    and their metrics are left out; the counters' readers still report."""
    out = _run("sp2048.solve", trace=True)
    assert out["correct"] is True
    assert set(out["metrics"]) == {"solve_mfu", "sv_exact_share"}
    assert out["device"]["busy_s"] == 0.0 and out["device"]["window_s"] > 0
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}


def test_refuses_a_machine_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "sp2048.solve",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_unknown_workload_is_refused():
    from bench.run import load_cell
    with pytest.raises(SystemExit):
        load_cell("no.such-cell")


def test_benchmark_json_meets_the_contract():
    bench = _bench()
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["bench"]
    assert 1 <= bench["run_seconds"] <= 51
    assert all(not w.startswith("/") and ".." not in w
               for w in bench["command"])
    names = set()
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
        assert c["file"].startswith("bench/")
        names.add(c["name"])
    used = {w["config"] for w in bench["workloads"]}
    assert used == names
    metrics = {m["name"]: m for m in bench["end_to_end"] + bench["per_layer"]}
    assert len(metrics) == len(bench["end_to_end"]) + len(bench["per_layer"])
    cells = {w["name"] for w in bench["workloads"]}
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(cells) // 2)
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert os.path.isfile(os.path.join(ROOT, "bench", "traffic",
                                           w["traffic"] + ".json"))
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    assert "setup_s" in metrics and metrics["setup_s"]["bound"] == 0.25
    for m in bench["per_layer"]:
        assert m["moves"] in {e["name"] for e in bench["end_to_end"]}
        assert set(m["workloads"]) <= cells
        assert os.path.isfile(os.path.join(ROOT, "bench", "layer_metrics",
                                           m["name"] + ".py"))
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for n in list(metrics) + list(cells) + list(names):
        assert NAME.match(n), n
    assert len(json.dumps(bench)) < 64 * 1024


def test_every_config_names_its_limits_for_its_traffic():
    from bench.run import load_cell
    for w in _bench()["workloads"]:
        _, _, config, traffic = load_cell(w["name"])
        limits = config["limits"][traffic["kind"]]
        assert limits and all(v > 0 for v in limits.values())


def test_layer_readers_on_a_record():
    """The readers that need no trace, on a hand-made record."""
    from bench.run import reader
    cfg = tiny_ctx("sp2048.solve").config
    rec = {"trace": None, "config": cfg, "chips": 1, "peaks": _peaks(),
           "counters": {"rounds": 100, "window_s": 10.0,
                        "sv_exact_rounds": 2, "score_call_s": [1e-3, 3e-3,
                                                               2e-3]}}
    pr = cfg["problem"]
    mfu = 100 * 4 * pr["m"] * pr["n"] * pr["p"] * 10 / 197e12
    assert reader("solve_mfu")(rec) == pytest.approx(mfu)
    assert reader("sv_exact_share")(rec) == pytest.approx(2.0)
    assert reader("score_call_ms.p50")(rec) == pytest.approx(2.0)
    for name in ("device_idle.solve", "device_idle.serve",
                 "mtl_grad_roofline"):
        assert reader(name)(rec) is None
