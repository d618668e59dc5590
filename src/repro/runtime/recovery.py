"""Preemption-safe solves: segmented resumable round loops (DESIGN.md §12).

A solver's round loop is a pure carry: state_{k+1} = body(k, state_k,
data), with the data and the per-round communication template constant
across rounds (the static round structure of every Table-1 protocol).
That makes a killed solve resumable EXACTLY: persist the full carry —
solver state, spectral-engine carry, snapshot history, ledger cursor +
comm-template — at segment boundaries, and replay the remaining rounds
from the same round indices.  ``ProtocolRuntime.run_rounds`` runs
every solve as segments of one round program, which feeds the body the
same ``k`` values through the same per-round HLO however the rounds are
split, so the final ``W``, the CommLog ledger, and the measured
``collective_floats_per_chip`` of a resumed solve are bit-identical to
an uninterrupted one (tests/test_recovery.py asserts this across
sim/mesh × eager/scan × 1-D/2-D).  This module holds the store side:
what a segment persists, how a solve restores it, and the checks that
refuse a drifted configuration.

Layout of a solve store (one directory per solve)::

    ckpt_dir/
      MANIFEST.json        solve config + problem/config fingerprint +
                           "latest" segment pointer (atomic rewrite)
      problem.npz          the MTLProblem's arrays (so ``repro.resume``
                           is a one-argument front door)
      step_XXXXXXXX.npz    one checkpoint per completed segment
                           (train/checkpoint store: atomic, content-
                           hashed, corrupt files detected + skipped)

``repro.resume(ckpt_dir)`` rebuilds the problem, restarts from the
newest INTACT segment (corrupt or rolled-back newer steps are skipped
with a warning — the stale-manifest case), replays the ledger for the
already-completed rounds from the STORED template, then verifies the
freshly-traced template hash against the stored one so a config drift
cannot silently produce a wrong-but-plausible ledger.

Multi-process bring-up: :func:`init_cluster` wraps
``jax.distributed.initialize`` with the CPU gloo collectives config and
coordinator retry/backoff; checkpoints are written by process 0 only
(every process computes them — the replicated master makes the carry
identical everywhere by construction).
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import tempfile
import time
import warnings
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..obs.tracing import emit_event as obs_event, trace_span
from ..train import checkpoint as ckpt_store
from ..train.checkpoint import CheckpointError
from .base import _DataEvent, _WireEvent, unstack_snapshots

# Segment length when ``ckpt_dir`` is given without ``checkpoint_every``
# — small enough that a preemption loses little work, large enough that
# the per-segment host sync + npz write stays well under the 10%
# overhead budget benchmarks/solver_bench.py enforces.
DEFAULT_SEGMENT = 25

MANIFEST = "MANIFEST.json"
PROBLEM_NPZ = "problem.npz"


# ----------------------------------------------------------------------
# small utilities
# ----------------------------------------------------------------------
def is_primary() -> bool:
    """True on the process that owns the checkpoint writes."""
    return jax.process_index() == 0


def _write_json_atomic(path: str, obj: Dict[str, Any]) -> None:
    d = os.path.dirname(path) or "."
    fd, tmp = tempfile.mkstemp(dir=d, suffix=".tmp")
    with os.fdopen(fd, "w") as f:
        json.dump(obj, f, indent=2, sort_keys=True)
    os.replace(tmp, path)


def _read_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def _host_leaf(mesh, x) -> np.ndarray:
    """Fetch one (possibly mesh-sharded) array to a full host copy.
    Under multi-controller jax a sharded global array is not fully
    addressable from one process; an identity jit with replicated
    out_shardings over its ``mesh`` all-gathers it first."""
    if isinstance(x, jax.Array) and not x.is_fully_addressable:
        from jax.sharding import NamedSharding, PartitionSpec
        sh = NamedSharding(mesh, PartitionSpec())
        x = jax.jit(lambda a: a, out_shardings=sh)(x)
    return np.asarray(x)


def template_hash(template: List[_WireEvent],
                  data_template: List[_DataEvent]) -> str:
    """sha256 over the per-round communication template — the solve's
    protocol fingerprint.  A resumed solve re-traces its template and
    must reproduce the stored hash, proving the ledger continuation
    extends the SAME protocol the killed solve was running."""
    blob = json.dumps(
        [[dataclasses.asdict(e) for e in template],
         [dataclasses.asdict(e) for e in data_template]],
        sort_keys=True).encode()
    return hashlib.sha256(blob).hexdigest()


# ----------------------------------------------------------------------
# manifest + problem persistence (the `repro.resume` front door's food)
# ----------------------------------------------------------------------
def solve_fingerprint(prob, config: Dict[str, Any]) -> str:
    """sha256 binding a store to ONE (problem, solve-config) pair, so a
    different problem or method cannot silently resume from a stale
    store directory."""
    h = hashlib.sha256()
    for arr in (prob.Xs, prob.ys):
        a = np.ascontiguousarray(np.asarray(arr))
        h.update(str(a.dtype).encode())
        h.update(repr(a.shape).encode())
        h.update(a.tobytes())
    h.update(json.dumps({"loss": prob.loss.name, "A": prob.A,
                         "r": prob.r, "l2": prob.l2,
                         "gram": prob.gram_A is not None},
                        sort_keys=True).encode())
    h.update(_config_json(config).encode())
    return h.hexdigest()


def _config_json(config: Dict[str, Any]) -> str:
    """Canonical JSON of the solve config; ndarray hyper-parameters are
    replaced by a content digest (their values live in problem.npz)."""
    def enc(v):
        if isinstance(v, (np.ndarray, jnp.ndarray)):
            a = np.ascontiguousarray(np.asarray(v))
            return {"__array_digest__":
                    hashlib.sha256(a.tobytes()).hexdigest()}
        if isinstance(v, (np.integer,)):
            return int(v)
        if isinstance(v, (np.floating,)):
            return float(v)
        return v
    def walk(o):
        if isinstance(o, dict):
            return {k: walk(v) for k, v in o.items()}
        if isinstance(o, (list, tuple)):
            return [walk(v) for v in o]
        return enc(o)
    return json.dumps(walk(config), sort_keys=True)


def write_store(ckpt_dir: str, prob, config: Dict[str, Any]) -> None:
    """Create (or validate) a solve store's MANIFEST.json + problem.npz.

    An existing manifest must fingerprint-match the requested solve —
    resuming a DIFFERENT problem/config from a stale directory is an
    error, not a silent wrong answer.
    """
    fp = solve_fingerprint(prob, config)
    man_path = os.path.join(ckpt_dir, MANIFEST)
    if os.path.exists(man_path):
        man = _read_json(man_path)
        if man.get("fingerprint") != fp:
            raise CheckpointError(
                f"{ckpt_dir} already holds a solve store for a DIFFERENT "
                f"problem/config (fingerprint {man.get('fingerprint', '?')[:12]}"
                f"… vs requested {fp[:12]}…) — refusing to mix stores; "
                f"use a fresh ckpt_dir or repro.resume(ckpt_dir) with no "
                f"overrides")
        return
    if not is_primary():
        return
    os.makedirs(ckpt_dir, exist_ok=True)
    # problem arrays (+ ndarray hyper-parameters) for repro.resume
    arrays = {"Xs": np.asarray(prob.Xs), "ys": np.asarray(prob.ys)}
    hp = config.get("hp", {})
    hp_meta = {}
    for k, v in hp.items():
        if isinstance(v, (np.ndarray, jnp.ndarray)):
            arrays[f"hp_{k}"] = np.asarray(v)
            hp_meta[k] = {"__hp_array__": f"hp_{k}"}
        elif isinstance(v, np.integer):
            hp_meta[k] = int(v)
        elif isinstance(v, np.floating):
            hp_meta[k] = float(v)
        else:
            hp_meta[k] = v
    fd, tmp = tempfile.mkstemp(dir=ckpt_dir, suffix=".tmp")
    with os.fdopen(fd, "wb") as f:
        np.savez(f, **arrays)
    os.replace(tmp, os.path.join(ckpt_dir, PROBLEM_NPZ))
    man = {
        "version": 1,
        "fingerprint": fp,
        "latest": None,               # newest completed segment's step
        "problem": {"loss": prob.loss.name, "A": prob.A, "r": prob.r,
                    "l2": prob.l2, "gram": prob.gram_A is not None},
        "config": {k: v for k, v in config.items() if k != "hp"},
        "hp": hp_meta,
    }
    _write_json_atomic(man_path, man)


def load_store(ckpt_dir: str):
    """Rebuild (problem, config, hp) from a solve store."""
    man_path = os.path.join(ckpt_dir, MANIFEST)
    if not os.path.exists(man_path):
        raise FileNotFoundError(f"no {MANIFEST} in {ckpt_dir} — not a "
                                f"solve store (repro.solve(..., ckpt_dir=) "
                                f"creates one)")
    man = _read_json(man_path)
    with np.load(os.path.join(ckpt_dir, PROBLEM_NPZ)) as data:
        arrays = {k: data[k] for k in data.files}
    from ..core.methods.base import MTLProblem
    pm = man["problem"]
    prob = MTLProblem.make(arrays["Xs"], arrays["ys"],
                           loss_name=pm["loss"], gram=pm["gram"],
                           A=pm["A"], r=pm["r"], l2=pm["l2"])
    hp = {}
    for k, v in man.get("hp", {}).items():
        if isinstance(v, dict) and "__hp_array__" in v:
            hp[k] = jnp.asarray(arrays[v["__hp_array__"]])
        else:
            hp[k] = v
    return prob, man, hp


def _touch_manifest_latest(ckpt_dir: str, step: int) -> None:
    man_path = os.path.join(ckpt_dir, MANIFEST)
    if not os.path.exists(man_path):
        return
    man = _read_json(man_path)
    man["latest"] = int(step)
    _write_json_atomic(man_path, man)


# ----------------------------------------------------------------------
# the solve's store
# ----------------------------------------------------------------------
class SolveCheckpointer:
    """The store side of ONE checkpointed solve.

    Attached to a runtime as ``rt._ckpt`` by ``repro.solve(...,
    ckpt_dir=)``.  ``run_rounds`` runs the rounds in segments of
    ``every`` and calls three methods: :meth:`begin` before the first
    segment (check and restore the newest intact segment, if any),
    :meth:`seal` after the first trace (the template-hash check) and
    :meth:`save` at every segment's end (persist the full carry).
    """

    def __init__(self, ckpt_dir: str, every: int = DEFAULT_SEGMENT,
                 keep: Optional[int] = 3):
        if every < 1:
            raise ValueError(f"checkpoint_every={every} must be >= 1")
        self.ckpt_dir = ckpt_dir
        self.every = int(every)
        self.keep = keep
        self._resume: Optional[Dict[str, Any]] = None
        self._solve: Dict[str, Any] = {}
        self.info: Dict[str, Any] = {"dir": ckpt_dir, "every": self.every,
                                     "resumed_from": 0, "segments_run": 0,
                                     "skipped_corrupt": [],
                                     "rolled_back_from": None}

    # -- resume state ---------------------------------------------------
    def load_resume(self) -> bool:
        """Pick up the newest intact segment, if any.  Corrupt newer
        steps are skipped (warned); a manifest whose ``latest`` pointer
        outruns the intact steps on disk — the stale-manifest crash —
        rolls back to what verifies."""
        steps = ckpt_store.available_steps(self.ckpt_dir)
        if not steps:
            return False
        step, tree, skipped = ckpt_store.load_latest_intact(self.ckpt_dir)
        self.info["skipped_corrupt"] = skipped
        man_path = os.path.join(self.ckpt_dir, MANIFEST)
        if os.path.exists(man_path):
            latest = _read_json(man_path).get("latest")
            if latest is not None and latest != step:
                warnings.warn(
                    f"solve store manifest points at step {latest} but the "
                    f"newest INTACT checkpoint is step {step} — rolling "
                    f"back (stale manifest after a partial failure)")
                self.info["rolled_back_from"] = latest
                obs_event("recovery.rollback", ckpt_dir=self.ckpt_dir,
                          manifest_step=int(latest), restored_step=int(step))
        meta = json.loads(bytes(np.asarray(tree["meta_json"])))
        self._resume = {"step": step, "meta": meta,
                        "carry": tree.get("carry", []),
                        "tree": tree}
        self.info["resumed_from"] = meta["rounds_done"]
        obs_event("recovery.segment_restored", ckpt_dir=self.ckpt_dir,
                  step=int(step), rounds_done=int(meta["rounds_done"]),
                  skipped_corrupt=list(skipped))
        return True

    # -- the three calls of run_rounds ----------------------------------
    def begin(self, rounds: int, state, records, count_rounds: bool,
              scan: bool, mesh=None):
        """Open the solve on this store.

        Returns ``(rounds done, state, snapshot history, template)``:
        with no stored segment, ``(0, state, empty histories, None)``;
        otherwise the stored carry in the solver-built state's treedef,
        each record spec's ``[(round, value)]`` history so far, and the
        stored per-round ``(wire, data)`` template whose replay catches
        the ledger up.  A stored segment of another round count,
        snapshot cadence or carry refuses to resume (config drift)."""
        self._solve = {"rounds": int(rounds), "records": tuple(records),
                       "count_rounds": bool(count_rounds),
                       "scan": bool(scan), "mesh": mesh,
                       "stored_hash": None}
        history: List[List[Tuple[int, Any]]] = [[] for _ in records]
        if self._resume is None:
            return 0, state, history, None
        meta = self._resume["meta"]
        if meta["rounds"] != rounds:
            raise CheckpointError(
                f"checkpoint in {self.ckpt_dir} was written by a "
                f"{meta['rounds']}-round solve; this solve runs "
                f"{rounds} rounds — config drift, refusing to resume")
        want_rec = [{"every": r.every, "key": r.key}
                    for r in records] or None
        got_rec = meta["record"]
        if isinstance(got_rec, dict):     # pre-multi-spec store
            got_rec = [got_rec]
        if got_rec != want_rec:
            raise CheckpointError(
                f"checkpoint snapshot cadence {meta['record']} does "
                f"not match this solve's {want_rec} — config drift")
        # restore the carry into the solver-built state's treedef
        leaves0, treedef = jax.tree.flatten(state)
        loaded = self._resume["carry"]
        if len(loaded) != len(leaves0):
            raise CheckpointError(
                f"checkpoint carry has {len(loaded)} leaves; the "
                f"solver built {len(leaves0)} — config drift")
        news = []
        for a, b in zip(leaves0, loaded):
            b = jnp.asarray(b)
            if (jnp.shape(a) != jnp.shape(b)
                    or jnp.asarray(a).dtype != b.dtype):
                raise CheckpointError(
                    f"checkpoint carry leaf {jnp.shape(b)}/{b.dtype} "
                    f"does not match solver state "
                    f"{jnp.shape(a)}/{jnp.asarray(a).dtype}")
            news.append(b)
        state = jax.tree.unflatten(treedef, news)
        # snapshot histories up to the resume point
        stored_tree = self._resume.get("tree") or {}
        for i, r in enumerate(records):
            ts = stored_tree.get(f"snap_rounds_{i}")
            if ts is None:
                continue
            vals0, vdef = jax.tree.flatten(state[r.key])
            bufs = [jnp.asarray(stored_tree[f"snaps_{i}_{j}"])
                    for j in range(len(vals0))]
            history[i].extend(zip(
                (int(t) for t in np.asarray(ts)),
                unstack_snapshots(jax.tree.unflatten(vdef, bufs))))
        self._solve["stored_hash"] = meta["template_hash"]
        template = ([_WireEvent(**d) for d in meta["template"]],
                    [_DataEvent(**d) for d in meta["data_template"]])
        return meta["rounds_done"], state, history, template

    def seal(self, template: List[_WireEvent],
             data_template: List[_DataEvent]) -> None:
        """Take the per-round template the solve's first trace recorded;
        a resumed solve must reproduce the stored one's hash."""
        fresh = template_hash(template, data_template)
        stored = self._solve["stored_hash"]
        if stored is not None and fresh != stored:
            raise CheckpointError(
                f"resumed solve traced a DIFFERENT per-round "
                f"communication template (hash {fresh[:12]}… vs "
                f"stored {stored[:12]}…) — the protocol changed "
                f"between the killed solve and this resume; the "
                f"ledger continuation would be meaningless")
        self._solve.update(template=list(template),
                           data_template=list(data_template),
                           template_hash=fresh)

    def save(self, end: int, state, history) -> None:
        """Persist the carry after round ``end``: the state, every
        record spec's snapshot history and the sealed template."""
        self._persist(end, state, history)
        self.info["segments_run"] += 1

    def _persist(self, end: int, state, history) -> None:
        solve = self._solve
        rounds, records, mesh = solve["rounds"], solve["records"], \
            solve["mesh"]
        final = end == rounds
        if is_primary():
            with trace_span("ckpt.save", step=int(end), final=bool(final),
                            ckpt_dir=self.ckpt_dir):
                leaves = jax.tree.flatten(state)[0]
                tree: Dict[str, Any] = {
                    "carry": [_host_leaf(mesh, x) for x in leaves]}
                # per-spec snapshot histories: the recorded value may be
                # a pytree, so each spec stores its snap rounds plus one
                # stacked array per flattened leaf
                for i, hist in enumerate(history):
                    if not hist:
                        continue
                    tree[f"snap_rounds_{i}"] = np.asarray(
                        [t for t, _ in hist], np.int64)
                    flat = [jax.tree.flatten(v)[0] for _, v in hist]
                    for j in range(len(flat[0])):
                        tree[f"snaps_{i}_{j}"] = np.stack(
                            [_host_leaf(mesh, fs[j]) for fs in flat])
                meta = {
                    "version": 1,
                    "rounds": int(rounds),
                    "rounds_done": int(end),
                    "count_rounds": solve["count_rounds"],
                    "scan": solve["scan"],
                    "record": [{"every": r.every, "key": r.key}
                               for r in records] or None,
                    "template": [dataclasses.asdict(e)
                                 for e in solve["template"]],
                    "data_template": [dataclasses.asdict(e)
                                      for e in solve["data_template"]],
                    "template_hash": solve["template_hash"],
                }
                tree["meta_json"] = np.frombuffer(
                    json.dumps(meta, sort_keys=True).encode(),
                    np.uint8).copy()
                ckpt_store.save_checkpoint(self.ckpt_dir, end, tree,
                                           keep=self.keep)
                _touch_manifest_latest(self.ckpt_dir, end)
        # the fault hook fires on EVERY process (a preemption does not
        # politely pick the writer), after the store write is durable
        ckpt_store._fire("segment_saved", step=end, ckpt_dir=self.ckpt_dir,
                         final=final)


# ----------------------------------------------------------------------
# multi-process bring-up
# ----------------------------------------------------------------------
def init_cluster(coordinator_address: Optional[str] = None,
                 num_processes: Optional[int] = None,
                 process_id: Optional[int] = None, *,
                 timeout_s: float = 60.0, backoff_s: float = 0.5,
                 retries: int = 5) -> None:
    """``jax.distributed.initialize`` with the CPU collectives config
    and coordinator retry/backoff.

    On CPU, cross-process collectives need the gloo implementation
    selected BEFORE initialize (without it the first multi-process jit
    dies with "Multiprocess computations aren't implemented on the CPU
    backend").  The coordinator (process 0) may come up later than its
    workers under a real launcher, so non-coordinator processes retry
    with exponential backoff instead of failing the job.

    The 2-process × 4-device CPU recipe (DESIGN.md §12)::

        XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        python worker.py   # calls init_cluster("localhost:12345", 2, pid)

    Arguments default to the ``REPRO_COORDINATOR`` /
    ``REPRO_NUM_PROCESSES`` / ``REPRO_PROCESS_ID`` environment
    variables, so one script serves every rank.
    """
    coordinator_address = coordinator_address or \
        os.environ.get("REPRO_COORDINATOR")
    if num_processes is None:
        num_processes = int(os.environ.get("REPRO_NUM_PROCESSES", "0")) or None
    if process_id is None:
        pid = os.environ.get("REPRO_PROCESS_ID")
        process_id = int(pid) if pid is not None else None
    if coordinator_address is None or num_processes is None \
            or process_id is None:
        raise ValueError("init_cluster needs coordinator_address, "
                         "num_processes and process_id (arguments or "
                         "REPRO_* environment)")
    # must precede initialize(); harmless on non-CPU backends
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    last = None
    for attempt in range(retries + 1):
        try:
            jax.distributed.initialize(
                coordinator_address=coordinator_address,
                num_processes=num_processes, process_id=process_id,
                initialization_timeout=int(timeout_s))
            return
        except Exception as e:          # coordinator not up yet, or busy
            last = e
            if attempt == retries:
                break
            time.sleep(backoff_s * (2 ** attempt))
    raise RuntimeError(
        f"could not join the jax.distributed cluster at "
        f"{coordinator_address} as process {process_id}/{num_processes} "
        f"after {retries + 1} attempts: {last}") from last


# ----------------------------------------------------------------------
# the resume front door
# ----------------------------------------------------------------------
def resume(ckpt_dir: str, *, mesh=None):
    """Restart a checkpointed solve from its store directory.

    Rebuilds the problem and solve configuration from ``MANIFEST.json``
    + ``problem.npz``, restores the newest intact segment, and runs the
    remaining rounds — returning the same :class:`MTLResult` (final
    ``W``, iterates, CommLog ledger, measured collective floats) the
    uninterrupted ``repro.solve`` call would have returned,
    bit-identically.  A store whose solve already finished loads its
    final segment and replays the ledger without executing any rounds.

    ``mesh`` optionally supplies the device mesh for a mesh-backend
    resume (the store records the backend and ``data_shards``; device
    OBJECTS are per-process and cannot be serialized).
    """
    prob, man, hp = load_store(ckpt_dir)
    cfg = man["config"]
    from ..api import solve
    return solve(prob, method=cfg["method"], backend=cfg["backend"],
                 mesh=mesh, axis=cfg.get("axis", "tasks"),
                 data_shards=cfg.get("data_shards", 1),
                 data_axis=cfg.get("data_axis", "data"),
                 checkpoint_every=cfg.get("checkpoint_every",
                                          DEFAULT_SEGMENT),
                 ckpt_dir=ckpt_dir, ckpt_keep=cfg.get("ckpt_keep", 3),
                 **hp)
