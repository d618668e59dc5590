"""Preemption-safe solves (DESIGN.md §12): segmented resumable round
loops, fault-injected recovery, and multi-process kill-and-resume.

The acceptance invariant everywhere: a solve that checkpoints, dies,
and resumes must finish with a final ``W``, snapshot history, CommLog
ledger, and measured collective floats BIT-IDENTICAL to the same solve
run uninterrupted — on both drivers (eager / scanned) and every mesh
layout (sim / mesh × 1-D / 2-D).  The sim half of the matrix runs
in-process; the mesh half runs once in a 4-device subprocess (the
``test_mesh2d`` pattern); the fault kinds and the 2-process recipe go
through the ``repro.faults`` subprocess harness so every kill is a real
process death.
"""
import json
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
from repro.runtime import base as runtime_base
from repro.train import checkpoint


def _problem():
    spec = SimSpec(p=16, m=8, r=3, n=32)
    Xs, ys, _, _ = generate(jax.random.PRNGKey(0), spec)
    return MTLProblem.make(Xs, ys, "squared", A=2.0, r=3)


def _ledger(res):
    return [(e.round, e.direction, e.vectors, e.dim, e.note)
            for e in res.comm.events]


def _assert_identical(base, other):
    np.testing.assert_array_equal(np.asarray(base.W), np.asarray(other.W))
    assert _ledger(base) == _ledger(other)
    assert base.rounds_axis == other.rounds_axis
    for a, b in zip(base.iterates, other.iterates):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for key in ("collective_floats_per_chip",
                "data_collective_floats_per_chip"):
        assert base.extras[key] == other.extras[key], key


KW = dict(method="proxgd", lam=0.05, rounds=11, record_every=3)


# ---------------------------------------------------------------------------
# sim matrix, in-process: segmented == uninterrupted, resume == both
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("scan", [True, False], ids=["scan", "eager"])
@pytest.mark.parametrize("data_shards", [1, 2], ids=["1d", "2d"])
def test_sim_segmented_and_resumed_bitidentical(tmp_path, scan,
                                                data_shards):
    """proxgd (spectral-engine carry rides in the state) checkpointed
    every 4 of 11 rounds: the segmented run, and a resume from a
    mid-solve segment, both reproduce the uninterrupted run exactly."""
    base = repro.solve(_problem(), backend="sim", scan=scan,
                       data_shards=data_shards, **KW)
    d = str(tmp_path / "store")
    seg = repro.solve(_problem(), backend="sim", scan=scan,
                      data_shards=data_shards, checkpoint_every=4,
                      ckpt_dir=d, **KW)
    _assert_identical(base, seg)
    assert seg.extras["checkpoint"]["segments_run"] == 3
    assert checkpoint.available_steps(d) == [4, 8, 11]

    # emulate a mid-solve kill: only the first segment survives
    for s in (8, 11):
        os.remove(os.path.join(d, f"step_{s:08d}.npz"))
    with pytest.warns(UserWarning, match="rolling back"):
        res = repro.resume(d)
    _assert_identical(base, res)
    assert res.extras["checkpoint"]["resumed_from"] == 4
    assert res.extras["checkpoint"]["rolled_back_from"] == 11

    # resuming a FINISHED store executes zero rounds, same result
    done = repro.resume(d)
    _assert_identical(base, done)
    assert done.extras["checkpoint"]["segments_run"] == 0


@pytest.mark.parametrize("scan", [True, False], ids=["scan", "eager"])
def test_segments_of_several_snapshots_resume_bitidentical(tmp_path,
                                                           monkeypatch,
                                                           scan):
    """Segments of 5 rounds holding 2, 3 and 1 snapshots (record_every
    2 of 11): a scanned segment hands its snapshots over in one split
    per record, an eager one-round segment in one split per snapshot,
    and a resume from the first segment reproduces the uninterrupted
    run's iterates exactly."""
    kw = dict(KW, record_every=2)
    base = repro.solve(_problem(), backend="sim", scan=scan, **kw)
    split, calls = runtime_base._unstack, []
    monkeypatch.setattr(runtime_base, "_unstack",
                        lambda bufs: calls.append(1) or split(bufs))
    d = str(tmp_path / "store")
    seg = repro.solve(_problem(), backend="sim", scan=scan,
                      checkpoint_every=5, ckpt_dir=d, **kw)
    _assert_identical(base, seg)
    assert checkpoint.available_steps(d) == [5, 10, 11]
    # one per segment; one per snapshot round under the eager driver
    assert len(calls) == (3 if scan else 6)

    for s in (10, 11):
        os.remove(os.path.join(d, f"step_{s:08d}.npz"))
    with pytest.warns(UserWarning, match="rolling back"):
        res = repro.resume(d)
    _assert_identical(base, res)
    assert len(res.iterates) == len(base.iterates) == 1 + 6
    assert res.extras["checkpoint"]["resumed_from"] == 5


def test_resume_rejects_config_drift(tmp_path):
    d = str(tmp_path / "store")
    repro.solve(_problem(), backend="sim", checkpoint_every=4,
                ckpt_dir=d, **KW)
    with pytest.raises(checkpoint.CheckpointError, match="DIFFERENT"):
        repro.solve(_problem(), backend="sim", checkpoint_every=4,
                    ckpt_dir=d, method="dgsp", rounds=11, record_every=3)


def test_corrupt_segment_falls_back_one_segment(tmp_path):
    """A bit-flipped newest segment degrades to the previous intact one
    and still finishes bit-identically."""
    base = repro.solve(_problem(), backend="sim", **KW)
    d = str(tmp_path / "store")
    repro.solve(_problem(), backend="sim", checkpoint_every=4,
                ckpt_dir=d, **KW)
    from repro.faults import corrupt_npz
    corrupt_npz(os.path.join(d, "step_00000011.npz"), seed=0)
    with pytest.warns(UserWarning, match="skipping corrupt"):
        res = repro.resume(d)
    _assert_identical(base, res)
    assert res.extras["checkpoint"]["resumed_from"] == 8
    assert res.extras["checkpoint"]["skipped_corrupt"] == [11]


# ---------------------------------------------------------------------------
# mesh matrix: one 4-device subprocess runs mesh × eager/scan × 1-D/2-D
# ---------------------------------------------------------------------------
MESH_SCRIPT = textwrap.dedent("""
    import json, os, tempfile, warnings
    import numpy as np, jax
    assert len(jax.devices()) == 4, jax.devices()
    import repro
    from repro.core.methods import MTLProblem
    from repro.data.synthetic import SimSpec, generate
    import repro.train.checkpoint as ck

    spec = SimSpec(p=16, m=8, r=3, n=32)
    Xs, ys, _, _ = generate(jax.random.PRNGKey(0), spec)
    KW = dict(method="proxgd", lam=0.05, rounds=11, record_every=3)

    def prob():
        return MTLProblem.make(Xs, ys, "squared", A=2.0, r=3)

    def ledger(res):
        return json.dumps([[e.round, e.direction, e.vectors, e.dim,
                            e.note] for e in res.comm.events])

    for ds in (1, 2):
        for scan in (True, False):
            base = repro.solve(prob(), backend="mesh", data_shards=ds,
                               scan=scan, **KW)
            # TemporaryDirectory (not mkdtemp): the scratch store is
            # removed even when an assertion/exception aborts this
            # case — a leaked store must never survive into a rerun
            with tempfile.TemporaryDirectory() as d:
                seg = repro.solve(prob(), backend="mesh", data_shards=ds,
                                  scan=scan, checkpoint_every=4,
                                  ckpt_dir=d, **KW)
                ok_seg = (np.array_equal(np.asarray(base.W),
                                         np.asarray(seg.W))
                          and ledger(base) == ledger(seg))
                for s in ck.available_steps(d)[1:]:
                    os.remove(os.path.join(d, f"step_{s:08d}.npz"))
                with warnings.catch_warnings():
                    warnings.simplefilter("ignore")
                    res = repro.resume(d)
                ok_res = (np.array_equal(np.asarray(base.W),
                                         np.asarray(res.W))
                          and ledger(base) == ledger(res)
                          and base.extras["collective_floats_per_chip"]
                              == res.extras["collective_floats_per_chip"]
                          and base.extras["data_collective_floats_per_chip"]
                              == res.extras[
                                  "data_collective_floats_per_chip"]
                          and all(np.array_equal(np.asarray(a),
                                                 np.asarray(b))
                                  for a, b in zip(base.iterates,
                                                  res.iterates)))
                print(f"RCASE ds={ds} scan={int(scan)} seg={int(ok_seg)} "
                      f"res={int(ok_res)} from="
                      f"{res.extras['checkpoint']['resumed_from']}")
    print("MESH_RECOVERY_DONE")
""")


@pytest.fixture(scope="module")
def mesh_lines(tmp_path_factory):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    # scratch under pytest's pruned basetemp: even a SIGKILLed
    # subprocess cannot leak stores into the shared system tmpdir
    env["TMPDIR"] = str(tmp_path_factory.mktemp("mesh_recovery"))
    out = subprocess.run([sys.executable, "-c", MESH_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=1800)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "MESH_RECOVERY_DONE" in out.stdout
    lines = {}
    for line in out.stdout.splitlines():
        if line.startswith("RCASE "):
            row = dict(kv.split("=") for kv in line.split()[1:])
            lines[(row["ds"], row["scan"])] = row
    return lines


@pytest.mark.slow
@pytest.mark.parametrize("ds", ["1", "2"], ids=["1d", "2d"])
@pytest.mark.parametrize("scan", ["1", "0"], ids=["scan", "eager"])
def test_mesh_segmented_and_resumed_bitidentical(mesh_lines, ds, scan):
    row = mesh_lines[(ds, scan)]
    assert row["seg"] == "1", row
    assert row["res"] == "1", row
    assert row["from"] == "4", row


# ---------------------------------------------------------------------------
# fault kinds: real process deaths through the repro.faults harness
# ---------------------------------------------------------------------------
@pytest.mark.slow
@pytest.mark.parametrize("kind", ["sigkill", "crash_rename", "corrupt",
                                  "stale_manifest"])
def test_fault_kind_recovered_exactly_once(tmp_path, kind):
    """Each planned fault kills a real subprocess solve; ONE resume must
    reproduce the uninterrupted baseline bit-for-bit."""
    from repro.faults import run_case
    report = run_case(kind, backend="sim", scan=True,
                      workdir=str(tmp_path))
    assert report["killed"], report
    assert report["bit_identical"], report
    assert report["recovered"], report


@pytest.mark.slow
def test_crash_rename_leaves_no_partial_step(tmp_path):
    """The crash_rename fault dies between npz write and rename: the
    store must show the orphan tmp file and NO truncated step."""
    from repro.faults import run_case
    report = run_case("crash_rename", backend="sim", scan=True,
                      workdir=str(tmp_path))
    assert report["recovered"], report
    store = tmp_path / "store"
    names = os.listdir(store)
    assert any(n.endswith(".tmp") for n in names), names
    # segment 2 (round 8... step 6 here) never became visible
    steps = checkpoint.available_steps(str(store))
    assert steps and steps[-1] == 11  # resume completed the store


# ---------------------------------------------------------------------------
# multi-process: 2 procs × 4 devices, kill rank 1, resume, parity
# ---------------------------------------------------------------------------
@pytest.mark.slow
def test_multiprocess_kill_one_process_and_resume(tmp_path):
    from repro.faults import run_multiprocess_case
    report = run_multiprocess_case(workdir=str(tmp_path))
    assert report["killed"], report
    assert report["bit_identical"], report
    assert report["recovered"], report


# ---------------------------------------------------------------------------
# serving degradation: maybe_reload never raises into the score path
# ---------------------------------------------------------------------------
def _model(seed):
    rng = np.random.default_rng(seed)
    W = rng.standard_normal((12, 6)).astype(np.float32)
    from repro.serve.mtl import FactoredModel
    return FactoredModel.from_W(W, 3)


def test_maybe_reload_pins_previous_on_corrupt_store(tmp_path):
    from repro.faults import corrupt_npz
    from repro.serve.mtl import MTLServer
    store = str(tmp_path)
    m0 = _model(0)
    m0.save(store)
    server = MTLServer(m0)
    server.maybe_reload(store)          # adopt step 0
    v0 = server.version

    # a newer but corrupt version must be skipped, previous pinned
    _model(1).save(store)
    corrupt_npz(os.path.join(store, "step_00000001.npz"), seed=1)
    with pytest.warns(UserWarning, match="failed to load"):
        assert server.maybe_reload(store, retries=1,
                                   backoff_s=0.01) is False
    assert server.version == v0
    ids = np.asarray([0, 1]); X = np.ones((2, 12), np.float32)
    _, ver = server.score(ids, X)       # score path alive on v0
    assert ver == v0

    # an intact newer version still wins, even past the corrupt one
    m2 = _model(2)
    m2.save(store)
    assert server.maybe_reload(store, retries=0) is True
    assert server.version == m2.version
    assert server._state.step == 2


def test_maybe_reload_falls_back_to_older_intact_newer_step(tmp_path):
    """Newest step corrupt, an INTACT step between it and the served
    one: degrade to the intact middle step, not all the way back."""
    from repro.faults import corrupt_npz
    from repro.serve.mtl import MTLServer
    store = str(tmp_path)
    m0 = _model(0)
    m0.save(store)                       # step 0
    server = MTLServer(m0)
    server.maybe_reload(store)
    m1 = _model(1)
    m1.save(store)                       # step 1 (intact)
    _model(2).save(store)                # step 2, then damaged
    corrupt_npz(os.path.join(store, "step_00000002.npz"), seed=2)
    with pytest.warns(UserWarning, match="step 2 failed"):
        assert server.maybe_reload(store, retries=0) is True
    assert server.version == m1.version
    assert server._state.step == 1


# ---------------------------------------------------------------------------
# train_loop resume
# ---------------------------------------------------------------------------
def test_train_loop_resumes_from_latest(tmp_path):
    """A restarted train_loop picks up at the newest checkpoint and
    fast-forwards the batch stream — final state equals the never-
    interrupted run's."""
    from repro.train.loop import train_loop

    def step_fn(state, batch):
        x = state["x"] + batch["v"]
        return {"x": x}, {"loss": x.sum()}

    def stream():
        i = 0
        while True:
            yield {"v": np.full((2,), float(i), np.float32)}
            i += 1

    logs = []
    full = train_loop(step_fn, {"x": np.zeros(2, np.float32)}, stream(),
                      8, ckpt_dir=None, log_fn=logs.append)

    d = str(tmp_path / "ck")
    train_loop(step_fn, {"x": np.zeros(2, np.float32)}, stream(), 4,
               ckpt_dir=d, ckpt_every=2, log_fn=logs.append)
    assert checkpoint.available_steps(d) == [2, 4]

    # "preempted at step 4, relaunched with the same stream"
    hist = train_loop(step_fn, {"x": np.zeros(2, np.float32)}, stream(),
                      8, ckpt_dir=d, ckpt_every=2, log_fn=logs.append)
    assert any("resume: restarting from checkpoint step 4" in s
               for s in logs)
    step, state = checkpoint.load_checkpoint(d)
    assert step == 8
    np.testing.assert_array_equal(
        np.asarray(state["x"]),
        np.full((2,), sum(range(8)), np.float32))
    assert hist["step"], "resumed run logged metrics"

    # a fully-finished store is a no-op
    hist2 = train_loop(step_fn, {"x": np.zeros(2, np.float32)}, stream(),
                       8, ckpt_dir=d, log_fn=logs.append)
    assert hist2 == {"step": [], "loss": [], "nll": []}
    assert any("nothing to do" in s for s in logs)
