"""Backend-agnostic runtime for the paper's master/worker protocol.

Every algorithm in the paper (Table 1) is an instance of one round
structure:

    workers:  compute a per-task message from local data      (worker_map)
    send:     task-columns flow to the master                 (gather_columns /
                                                               gather_tasks /
                                                               sum_tasks)
    master:   a small dense computation on the gathered state (plain jax ops)
    reply:    the master's answer returns to the workers      (broadcast)

A :class:`ProtocolRuntime` provides exactly those primitives, plus a
driver (:meth:`run_rounds` / :meth:`one_shot`) that executes the round
body and keeps the communication ledger.  Two backends implement the
primitives:

* ``SimRuntime``  — the simulated cluster: the "worker view" holds all
  ``m`` tasks, ``worker_map`` is a vmap over the full task axis and the
  collectives are identities.
* ``MeshRuntime`` — the task axis is a REAL mesh axis: the round body
  runs under ``shard_map``, ``worker_map`` vmaps over the per-chip task
  shard and ``gather_columns`` is a ``lax.all_gather`` (the
  replicated-master pattern, DESIGN.md §4).

Solvers are written ONCE against the primitives and run unchanged on
either backend; the two can only disagree by a floating-point rounding
margin because they execute the same per-task ops in the same order.

The data axis (DESIGN.md §8).  The paper pins each task to one
"machine", but nothing stops a machine from being a GROUP of devices
that shard the task's ``n`` samples.  ``data_shards > 1`` turns the
runtime into a 2-D ``("tasks", "data")`` mesh: each task's ``(n, p)``
rows are split into ``data_shards`` blocks along the sample axis, and
per-task sample statistics (gradients, Hessians, Gram matrices) are
reduced over the data axis with :meth:`pmean_data` / :meth:`psum_data`
(``lax.pmean``/``psum`` under the mesh backend, identities when
``data_shards == 1``).  ``SimRuntime`` emulates the second axis with a
reshaped ``vmap(axis_name="data")`` so 2-D semantics are testable on a
single CPU device.

Communication accounting (the paper's unit: p-dimensional vectors per
machine, Table 1) is emitted by the primitives themselves at trace time
and replayed into the :class:`~repro.core.comm.CommLog` once per
executed round — the ledger and the physical collective traffic share a
single source of truth and cannot drift apart.  The ledger charges
ONLY tasks-axis traffic (so it stays in the paper's Table-1 units and
is bit-identical for any ``data_shards``); data-axis collectives are
measured separately into ``data_collective_floats_per_chip``.
"""
from __future__ import annotations

import dataclasses
import hashlib
import re
import threading
from collections import OrderedDict, namedtuple
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax._src import config as jax_config
from jax._src import dispatch as jax_dispatch
from jax.extend import core as jcore

from ..core.comm import CommLog
from ..obs.tracing import annotate, trace_span

# A round body: (k, state, data) -> state.  ``k`` is the (traced) round
# index, ``state`` a dict whose entries are arrays or small pytrees of
# arrays (e.g. a solver-private spectral-engine carry, DESIGN.md §9 —
# every leaf of an entry shares that entry's sharding), ``data`` the
# worker-local data view — a dict with at least ``Xs`` (m,n,p) / ``ys``
# (m,n) plus any cached per-task statistics (``gram_A``/``gram_b``),
# every leaf stacked over the task axis (the full stack under sim; the
# per-chip shard under mesh).  With ``data_shards > 1`` the leaves
# named in ``SAMPLE_AXIS_LEAVES`` are additionally split along their
# sample axis (axis 1), so the body sees ``(L, n/data_shards, ...)``
# blocks.  Solvers whose round bodies read only a subset of the data
# leaves declare it via ``run_rounds(..., data_leaves=...)`` so the
# driver never binds — or lays out across the mesh — arrays no round
# touches (the Gram-cached fast paths never re-read the raw designs).
RoundBody = Callable[[jnp.ndarray, Dict[str, jnp.ndarray],
                      Dict[str, jnp.ndarray]], Dict[str, jnp.ndarray]]


def stage(traced):
    """Lower and compile a traced round program, each step under its
    own span (``solve.lower``; ``solve.compile`` — an XLA compile, or
    the persistent cache's key, read and deserialize).  The work of a
    first ``jit`` call after its trace, done once per process per
    traced program (:func:`round_jit`)."""
    with trace_span("solve.lower"):
        lowered = traced.lower()
    with trace_span("solve.compile"):
        return lowered.compile()


# The compiled round programs of this process, least recently used
# first, keyed on the traced program (:func:`_program_key`).  Only
# ``Compiled`` objects live here: never a jitted closure or a ``Traced``,
# which would pin the problem that closure captured.
_PROGRAMS: "OrderedDict[tuple, jax.stages.Compiled]" = OrderedDict()
_PROGRAMS_MAX = 8
_programs_lock = threading.Lock()
_counts = {"hits": 0, "misses": 0}

CacheInfo = namedtuple("CacheInfo", "hits misses maxsize currsize")

# ``<function f at 0x7f...>``: an object named by its address, which a
# later object can reuse once this one is freed
_ADDRESS = re.compile(r" at 0x[0-9a-fA-F]+")


def cache_info() -> CacheInfo:
    """Hits and misses of the round-program cache since the last
    :func:`cache_clear`, as ``functools.lru_cache`` reports its own."""
    with _programs_lock:
        return CacheInfo(_counts["hits"], _counts["misses"], _PROGRAMS_MAX,
                         len(_PROGRAMS))


def cache_clear() -> None:
    """Drop every cached round program and zero the counters."""
    with _programs_lock:
        _PROGRAMS.clear()
        _counts.update(hits=0, misses=0)


def _arg_signature(leaves, tree) -> tuple:
    """What a compiled program fixes of its arguments: the pytree, and
    each leaf's aval (shape, dtype, weak type), sharding (its devices
    included) and whether it is committed to them."""
    return tree, tuple(
        (jax.typeof(x), x.sharding, x.committed) if isinstance(x, jax.Array)
        else (jax.typeof(x), None, None) for x in leaves)


def _value(x) -> str:
    """A constant's or literal's dtype, shape and bytes."""
    x = np.asarray(x)
    return f"{x.dtype}{x.shape}{np.ascontiguousarray(x).tobytes().hex()}"


def _program_text(closed) -> Optional[str]:
    """The closed jaxpr written out whole, or None when it names an
    object by its address (a host callback): the address of a freed
    object can be reused.

    What ``str(closed)`` prints — every equation, the avals of its
    binders, each parameter's ``str`` (literals such as a solver's
    ``lam`` or step included, the Pallas calls' kernel bodies, grids and
    block shapes) — and what it leaves out: the values of constants and
    literals at every depth (a nested ``jit`` keeps the constants it
    closed over in its own jaxpr, which ``str`` prints as bare
    variables), and each equation's context.  About half the time of
    ``str``, which lays the text out for reading."""
    out, contexts = [], {}

    def param(p):
        if isinstance(p, jcore.ClosedJaxpr):
            out.extend(_value(c) for c in p.consts)
            jaxpr(p.jaxpr)
        elif isinstance(p, jcore.Jaxpr):
            jaxpr(p)
        elif isinstance(p, (tuple, list)) and any(
                isinstance(q, (jcore.Jaxpr, jcore.ClosedJaxpr)) for q in p):
            out.append("(")
            for q in p:
                param(q)
            out.append(")")
        else:
            out.append(str(p))

    def jaxpr(j):
        names = {}

        def bind(vs):
            for v in vs:
                names[v] = len(names)
                out.append(str(v.aval))

        def use(vs):
            out.extend(_value(v.val) if isinstance(v, jcore.Literal)
                       else str(names[v]) for v in vs)

        out.append("{")
        bind(j.constvars)
        out.append(";")
        bind(j.invars)
        for e in j.eqns:
            out.append(e.primitive.name)
            use(e.invars)
            if id(e.ctx) not in contexts:
                contexts[id(e.ctx)] = repr(e.ctx)
            out.append(contexts[id(e.ctx)])
            for k, p in e.params.items():
                out.append(k)
                param(p)
            out.append("->")
            bind(e.outvars)
        out.append("in")
        use(j.outvars)
        out.append(f"{j.effects}}}")

    param(closed)
    text = "\n".join(out)
    return None if _ADDRESS.search(text) else text


def _program_key(traced, signature, jit_kwargs) -> Optional[tuple]:
    """The cache key of a traced round program, or None when it has none
    (:func:`_program_text`).

    Everything that decides the executable: the program written out
    whole (:func:`_program_text`), the shardings inside it (a
    ``shard_map``'s mesh prints without its devices: a mesh of the same
    shape over other chips prints the same text), the output pytree, the
    arguments' signature (:func:`_arg_signature`), the ``jax.jit``
    options (``donate_argnums``), the backend, and JAX's trace-context
    configuration (``default_matmul_precision`` and every other setting
    that decides how a program traces and lowers)."""
    text = _program_text(traced.jaxpr)
    if text is None:
        return None
    inside = tuple(dict.fromkeys(
        sh for sh, _ in jax_dispatch.get_intermediate_shardings(
            traced.jaxpr.jaxpr)))
    return (hashlib.sha256(text.encode()).hexdigest(), inside,
            traced.out_tree, signature, tuple(sorted(jit_kwargs.items())),
            jax.default_backend(), jax_config.trace_context())


def _lookup(jitted, args, signature, jit_kwargs):
    """The compiled program for ``args``: traced under ``solve.trace``
    (the round body runs abstractly here, so the primitives record their
    ledger template on every call), then taken from the process's cache
    (the span's ``hit``) or staged and stored."""
    with trace_span("solve.trace") as span:
        traced = jitted.trace(*args)
        key = _program_key(traced, signature, jit_kwargs)
        with _programs_lock:
            compiled = None if key is None else _PROGRAMS.get(key)
            if compiled is None:
                _counts["misses"] += 1
            else:
                _counts["hits"] += 1
                _PROGRAMS.move_to_end(key)
        span["hit"] = compiled is not None
    if compiled is None:
        compiled = stage(traced)
        if key is not None:
            with _programs_lock:
                _PROGRAMS[key] = compiled
                while len(_PROGRAMS) > _PROGRAMS_MAX:
                    _PROGRAMS.popitem(last=False)
    return compiled


class _Staged:
    """A round program that finds its compiled program on each new
    argument signature (:func:`_lookup`), keeps it for the object's
    later calls (the later segments of one solve: one a round under the
    eager driver) and dispatches under ``solve.run``."""

    def __init__(self, jitted, jit_kwargs):
        self.jitted, self.jit_kwargs = jitted, jit_kwargs
        self.compiled = {}

    def __call__(self, *args):
        leaves, tree = jax.tree.flatten(args)
        if any(isinstance(x, jax.core.Tracer) for x in leaves):
            return self.jitted(*args)            # inside make_jaxpr
        signature = _arg_signature(leaves, tree)
        compiled = self.compiled.get(signature)
        if compiled is None:
            compiled = self.compiled[signature] = _lookup(
                self.jitted, args, signature, self.jit_kwargs)
        with annotate("solve.run"):
            return compiled(*args)


def round_jit(fn, **jit_kwargs):
    """``jax.jit(fn, **jit_kwargs)`` for a round program, staged once
    per process per traced program.

    Every solve builds fresh closures over its runtime, problem and
    hyper-parameters, so ``jax.jit``'s own caches, keyed on the function
    object, never see a program twice.  Here each new argument signature
    traces the program (tens of milliseconds, and where the ledger
    template is recorded) and keys a process-level cache on what was
    traced (:func:`_program_key`): a later solve that traces to the same
    program — the same solver, hyper-parameters and shapes on other data
    — reuses its compiled program and skips lowering and compiling.  A
    literal baked into the program, such as ``lam``, a data-derived step
    or a stochastic solve's ``batch_seed``, is part of the key, so
    changing it stages anew.  :func:`cache_info` counts hits and misses;
    the cache holds the last eight programs."""
    return _Staged(jax.jit(fn, **jit_kwargs), jit_kwargs)


@jax.jit
def _unstack(bufs):
    n = jax.tree.leaves(bufs)[0].shape[0]
    return [jax.tree.map(lambda b: b[i], bufs) for i in range(n)]


def unstack_snapshots(bufs) -> list:
    """Split one record's stacked snapshot buffers (a pytree whose
    leaves share a leading snapshot axis) into one pytree per snapshot,
    in a single dispatch under ``solve.snapshots``.  Slicing each
    snapshot eagerly costs a ``slice`` and a ``squeeze`` dispatch per
    snapshot, on the host while the device waits; the values are the
    same bits either way, and the outputs keep the buffers' sharding."""
    with annotate("solve.snapshots"):
        return _unstack(bufs)


# Worker-data leaves whose axis 1 is the per-task SAMPLE axis — these
# are the leaves a 2-D runtime shards along the "data" mesh axis.
# Derived statistics (``gram_A``/``gram_b``) carry no sample axis and
# stay replicated across data shards.
SAMPLE_AXIS_LEAVES = frozenset({"Xs", "ys"})


@dataclasses.dataclass
class RecordSpec:
    """Snapshot cadence for one state entry, driver-mode agnostic.

    ``sink.record(round, value)`` receives ``state[key]`` after every
    ``every``-th round (and always after the final round), from the
    round program's stacked outputs, whatever the dispatch granularity.
    Replaces the old ``on_round`` callback, which could not exist inside
    a fused ``lax.scan`` round loop.

    The recorded entry may be a PYTREE of arrays (e.g. the ``"obs"``
    round-metrics dict, repro.obs): the program stacks each leaf.
    ``run_rounds(record=...)`` accepts a single spec or a sequence of
    them, so an iterate history and a metrics channel ride the same
    scan without interfering.
    """
    sink: object          # anything with .record(rnd: int, value)
    every: int = 1
    key: str = "W"

    def snap_rounds(self, rounds: int) -> List[int]:
        """0-indexed rounds whose post-state is snapshotted (static)."""
        return [t for t in range(rounds)
                if (t + 1) % self.every == 0 or t == rounds - 1]


@dataclasses.dataclass
class _WireEvent:
    """One primitive call recorded while tracing a round body."""
    direction: str      # "worker->master" | "master->worker"
    vectors: int        # ledger: vectors per machine (paper accounting)
    dim: int            # ledger: dimension of each vector
    note: str
    wire_floats: int    # protocol floats this chip's simulated machines
                        # contribute to the collective = L x per-machine
                        # payload (for psum the physical wire bytes can be
                        # lower — the chip pre-reduces its L tasks locally;
                        # 0 for broadcast under the replicated master and
                        # for everything under SimRuntime)
    kind: str = "none"  # the jax collective this call lowers to
                        # ("all_gather" | "psum" | "none" when the backend
                        # issues no collective) — what repro.analysis
                        # matches against the traced jaxpr's equations
    payload: int = 0    # physical floats per chip in THAT collective's
                        # operand (psum: after the chip's local pre-reduce,
                        # so payload == wire_floats / local_tasks there)


@dataclasses.dataclass
class _DataEvent:
    """One data-axis collective recorded while tracing a round body.

    ``floats`` is the per-call operand size; ``repeats`` the number of
    executions per round when the call sits inside ``lax`` control flow
    (a ``fori_loop`` Newton refit traces once but runs ``iters`` times).
    The measured per-round traffic is ``floats * repeats``; the static
    analyzer additionally matches ``(kind, floats)`` against the round
    jaxpr's data-axis equations with loop-length multipliers.
    """
    kind: str           # "psum" | "all_gather"
    floats: int         # operand floats per chip per call
    repeats: int = 1    # executions per round (lax control-flow multiplier)
    note: str = ""


class ProtocolRuntime:
    """Abstract backend. Holds the problem, the ledger, and the driver."""

    name = "abstract"

    def __init__(self, prob):
        self.prob = prob
        self.comm = CommLog(m=prob.m)
        # worker->master protocol floats contributed by this chip's
        # simulated machines across all collectives so far — the ledger's
        # per-machine uplink times tasks-per-chip (mesh backend; stays 0
        # under sim where no collective runs).  For all_gather this IS
        # the physical payload; for psum the chip pre-reduces locally so
        # physical wire bytes are payload/L.
        self.collective_floats_per_chip = 0
        # data-axis collective floats contributed by this chip (psum /
        # pmean / all_gather over the "data" mesh axis).  NEVER charged
        # to the CommLog — the ledger stays in the paper's Table-1
        # tasks-axis units — but measured here so 2-D runs can report
        # their within-task sharding traffic (DESIGN.md §8).  0 under
        # sim and whenever data_shards == 1.
        self.data_collective_floats_per_chip = 0
        # number of shards along the data axis; subclasses overwrite
        self.data_shards = 1
        self.data_axis = "data"
        self._recording = False
        self._template: list[_WireEvent] = []
        self._data_template: list[_DataEvent] = []
        self._data_leaves: Optional[Tuple[str, ...]] = None
        self._used = False
        # data-axis floats accounted OUTSIDE the per-round template (the
        # one-per-solve Gram-cache psum) — kept separate so the static
        # analyzer can reconcile setup traffic independently of rounds
        self.setup_data_floats = 0
        # when set (repro.analysis.StaticCapture), run_rounds TRACES the
        # round program instead of executing it: the ledger/template are
        # recorded exactly as in a real solve, the jaxpr is stored on
        # the capture, and the initial state is returned unchanged
        self._capture = None
        # when set (repro.runtime.recovery.SolveCheckpointer), run_rounds
        # starts from the store's latest intact segment and persists the
        # full carry every checkpoint_every rounds, so a preempted solve
        # restarts with a bit-identical W + ledger continuation
        # (DESIGN.md §12)
        self._ckpt = None

    # ------------------------------------------------------------------
    # topology
    # ------------------------------------------------------------------
    @property
    def num_tasks(self) -> int:
        return self.prob.m

    @property
    def local_tasks(self) -> int:
        """Tasks held by one worker view (m under sim, m/devices under mesh)."""
        raise NotImplementedError

    @property
    def local_samples(self) -> int:
        """Per-task samples held by one worker view: n / data_shards."""
        return self.prob.n // self.data_shards

    # ------------------------------------------------------------------
    # protocol primitives — call these inside a round body only
    # ------------------------------------------------------------------
    def worker_map(self, fn, in_axes, out_axes=0):
        """Lift a per-task computation over the worker-local task axis.

        Identical on both backends (a vmap); what differs is the extent
        of the mapped axis: all m tasks under sim, the per-chip shard
        under mesh.  With ``data_shards > 1`` the per-task leaves of the
        ``data`` dict hold only this shard's ``n / data_shards`` rows;
        sample statistics computed from them must be reduced with
        :meth:`pmean_data` / :meth:`psum_data` afterwards (the
        ``repro.core.worker_ops`` helpers do this when handed the
        runtime).  The mapped call runs under the ``worker`` named
        scope, which the lowered program's op names carry.
        """
        mapped = jax.vmap(fn, in_axes=in_axes, out_axes=out_axes)

        def call(*args, **kwargs):
            with jax.named_scope("worker"):
                return mapped(*args, **kwargs)
        return call

    def axis_index(self) -> jnp.ndarray:
        """Index of this worker view along the task axis (0 under sim)."""
        raise NotImplementedError

    def data_index(self) -> jnp.ndarray:
        """Index of this shard along the data axis (0 when
        ``data_shards == 1``).  The stochastic batch sampler folds it
        into its key chain so each shard of a 2-D layout draws its own
        rows of a mini-batch (``worker_ops.batch_indices``, DESIGN.md
        §13); both backends expose the same named axis when sharded
        (a mesh axis, or the sim emulation's vmapped axis)."""
        if self.data_shards == 1:
            return jnp.int32(0)
        return jax.lax.axis_index(self.data_axis)

    def local_slice(self, x: jnp.ndarray, axis: int = -1) -> jnp.ndarray:
        """This worker view's task-columns of a replicated master array.

        Free in both backends (the replicated master already lives on
        every chip); charged nothing — the charge sits on ``broadcast``
        when the master publishes updated state.
        """
        raise NotImplementedError

    def gather_columns(self, x: jnp.ndarray, note: str = "") -> jnp.ndarray:
        """workers -> master: stack per-task column messages to (d, m).

        ``x`` is (d, L) with L = ``local_tasks``; the result is the full
        (d, m) matrix on the (replicated) master.  Ledger: each machine
        sends 1 vector of dimension d.
        """
        raise NotImplementedError

    def gather_tasks(self, x: jnp.ndarray, note: str = "") -> jnp.ndarray:
        """workers -> master: gather a per-task payload along axis 0.

        ``x`` is (L, ...); result is (m, ...).  Ledger: each machine
        sends prod(shape[1:-1]) vectors of dimension shape[-1] (e.g. the
        Centralize baseline shipping its (n, p) design = n p-vectors).
        Under 2-D sharding, reassemble sharded sample axes with
        :meth:`gather_samples` FIRST so the charged event keeps its 1-D
        shape (bit-identical ledger across mesh layouts).
        """
        raise NotImplementedError

    def sum_tasks(self, x: jnp.ndarray, note: str = "") -> jnp.ndarray:
        """workers -> master: sum a per-task payload over ALL m tasks.

        ``x`` is (L, ...); result is the (...)-shaped global sum on the
        (replicated) master.  Ledger: each machine sends its payload
        once, as prod(shape[1:-1]) vectors of dimension shape[-1].
        """
        raise NotImplementedError

    def broadcast(self, x: jnp.ndarray, note: str = "",
                  vectors: Optional[int] = None,
                  dim: Optional[int] = None) -> jnp.ndarray:
        """master -> workers: publish master state.

        A no-op computationally (the replicated master is already
        everywhere) but it is the protocol's downlink and is charged:
        a (d,) vector costs 1 vector of dim d per machine; a (d, m)
        matrix costs each machine its own column (1 vector of dim d);
        any other matrix is charged column-wise to every machine.
        Pass ``vectors``/``dim`` (both) to override (e.g. AltMin's (p, r)
        basis counted as r p-vectors).
        """
        if (vectors is None) != (dim is None):
            raise ValueError("broadcast accounting override needs both "
                             "vectors= and dim=, or neither")
        if vectors is None:
            if x.ndim == 1:
                vectors, dim = 1, x.shape[0]
            elif x.ndim == 2 and x.shape[1] == self.prob.m:
                vectors, dim = 1, x.shape[0]
            elif x.ndim == 2:
                vectors, dim = x.shape[1], x.shape[0]
            else:
                vectors, dim = int(x.size // x.shape[-1]), x.shape[-1]
        self._charge("master->worker", vectors, dim, note, wire=0)
        return x

    # ------------------------------------------------------------------
    # data-axis primitives — within-task sharding (DESIGN.md §8)
    # ------------------------------------------------------------------
    @staticmethod
    def _norm_collective(x: jnp.ndarray) -> jnp.ndarray:
        """Normalize a reduction result's aval to the mesh collective's
        semantics.  ``lax.psum``/``pmean`` under ``shard_map`` STRIP the
        weak-type flag from their output, while the ``data_shards == 1``
        identity branch — and the sim emulation's vmapped collectives —
        PRESERVE it.  Left alone, the same solver carries subtly
        different avals per layout (a weak-typed scalar statistic drifts
        a scan carry), which ``test_runtime_parity`` used to paper over
        with float tolerance.
        A same-dtype ``convert_element_type`` is a no-op on values but
        pins the aval, making every layout agree by construction."""
        return jax.lax.convert_element_type(x, jnp.asarray(x).dtype)

    def psum_data(self, x: jnp.ndarray, note: str = "",
                  repeats: int = 1) -> jnp.ndarray:
        """Sum a per-shard partial statistic over the data axis.

        The reduction that reassembles a per-task quantity whose shards
        were each computed over ``n / data_shards`` rows with a GLOBAL
        ``1/n`` normalization (e.g. partial Gram matrices
        ``X_s^T X_s / n``).  Identity when ``data_shards == 1`` (up to
        aval normalization — every layout returns the same, non-weak
        dtype, :meth:`_norm_collective`).

        Not charged to the CommLog (the ledger stays in tasks-axis
        Table-1 units); the per-chip payload ``x.size * repeats`` floats
        is measured into ``data_collective_floats_per_chip``.  Pass
        ``repeats`` when the call sits inside ``lax`` control flow that
        executes it more than once per round (e.g. a Newton refit loop)
        so the measurement stays honest despite single-trace recording.
        """
        with jax.named_scope("exchange"):
            if self.data_shards == 1:
                return self._norm_collective(x)
            if self._count_data_wire:
                self._charge_data("psum", x.size, repeats, note)
            return self._norm_collective(self._psum_data(x))

    def pmean_data(self, x: jnp.ndarray, note: str = "",
                   repeats: int = 1) -> jnp.ndarray:
        """Average a per-shard sample statistic over the data axis.

        The reduction for quantities normalized by the LOCAL row count
        (e.g. ``lm.task_grad``'s ``(1/n_local) X_s^T l'``): the mean of
        the per-shard values equals the full-data statistic.  Identity
        when ``data_shards == 1``; accounting and aval normalization as
        :meth:`psum_data`.
        """
        with jax.named_scope("exchange"):
            if self.data_shards == 1:
                return self._norm_collective(x)
            if self._count_data_wire:
                self._charge_data("psum", x.size, repeats, note)
            return self._norm_collective(self._pmean_data(x))

    def gather_samples(self, x: jnp.ndarray, axis: int = 1,
                       note: str = "") -> jnp.ndarray:
        """Reassemble the full sample axis from its data shards.

        ``x`` is a per-task stack whose ``axis`` holds this shard's
        ``n / data_shards`` rows; the result carries all ``n`` rows (in
        sample order) on every shard.  Identity when
        ``data_shards == 1``.  Used by protocols that ship raw samples
        (the Centralize baseline) — call it BEFORE the tasks-axis
        gather so the charged ledger event keeps its 1-D shape.
        Measured, never charged, like the other data-axis primitives.
        """
        if self.data_shards == 1:
            return x
        if self._count_data_wire:
            self._charge_data("all_gather", x.size, 1, note)
        with jax.named_scope("exchange"):
            return self._gather_samples(x, axis)

    # Whether this backend moves real bytes over the data axis (mesh
    # collectives: yes; the sim emulation: no, mirroring the tasks-axis
    # wire convention where sim measures 0).
    _count_data_wire = False

    def _psum_data(self, x):
        raise NotImplementedError

    def _pmean_data(self, x):
        raise NotImplementedError

    def _gather_samples(self, x, axis):
        raise NotImplementedError

    # ------------------------------------------------------------------
    # ledger plumbing
    # ------------------------------------------------------------------
    @staticmethod
    def _payload_vectors(x) -> Tuple[int, int]:
        """Ledger (vectors, dim) of one task's payload in a per-task
        stack ``x`` of shape (L, ...): prod(shape[1:-1]) vectors of
        dimension shape[-1]."""
        payload = x.shape[1:]
        vectors = 1
        for s in payload[:-1]:
            vectors *= int(s)
        return vectors, int(payload[-1])

    def _charge(self, direction: str, vectors: int, dim: int, note: str,
                wire: int, kind: str = "none", payload: int = 0) -> None:
        if self._recording:
            self._template.append(
                _WireEvent(direction, int(vectors), int(dim), note,
                           int(wire), kind, int(payload)))

    def _charge_data(self, kind: str, floats: int, repeats: int = 1,
                     note: str = "") -> None:
        """Measure data-axis collective payload (never enters the
        CommLog).  While the round body is being traced the event joins
        the per-round template (replayed once per executed round);
        outside a trace — the one-time Gram-cache setup — the floats
        accumulate directly (and into ``setup_data_floats`` so the
        static analyzer can reconcile setup separately from rounds)."""
        if self._recording:
            self._data_template.append(
                _DataEvent(kind, int(floats), int(repeats), note))
        else:
            self.data_collective_floats_per_chip += int(floats) * int(repeats)
            self.setup_data_floats += int(floats) * int(repeats)

    def _replay_round(self, count_round: bool) -> None:
        if count_round:
            self.comm.begin_round()
        for ev in self._template:
            self.comm.send(ev.direction, ev.vectors, ev.dim, ev.note)
            self.collective_floats_per_chip += ev.wire_floats
        self.data_collective_floats_per_chip += sum(
            ev.floats * ev.repeats for ev in self._data_template)

    # ------------------------------------------------------------------
    # drivers
    # ------------------------------------------------------------------
    def _worker_data(self) -> Dict[str, jnp.ndarray]:
        """The data dict bound as step arguments (never closure
        constants, so XLA cannot constant-fold cached Gram matrices)."""
        wd = getattr(self.prob, "worker_data", None)
        return wd() if wd is not None else {"Xs": self.prob.Xs,
                                            "ys": self.prob.ys}

    def _gram2d_memo(self, key, compute):
        """Get-or-build the shard-summed 2-D Gram cache via the
        problem's per-layout memo (``MTLProblem.gram2d_cache``): the
        result is bit-identical for every solve of one problem on one
        layout, so only the first solve pays the full-design pass.
        Callers still account the setup traffic once per solve."""
        memo = getattr(self.prob, "gram2d_cache", None)
        if memo is not None and key in memo:
            return memo[key]
        out = compute()
        if memo is not None:
            memo[key] = out
        return out

    def _round_data(self) -> Dict[str, jnp.ndarray]:
        """The worker-data leaves actually bound into the round loop.

        The full ``_worker_data`` dict, pruned to the solver-declared
        ``data_leaves`` subset when one was given.  Pruning happens
        AFTER the backend's data build (the 2-D Gram-cache psum still
        reads the raw ``Xs``/``ys``) but BEFORE device binding, so
        gram-only solvers never pay sample-axis layout or transfer
        cost for the raw designs no round touches.
        """
        data = self._worker_data()
        if self._data_leaves is None:
            return data
        keep = set(self._data_leaves)
        return {k: v for k, v in data.items() if k in keep}

    @staticmethod
    def _as_records(record) -> Tuple[RecordSpec, ...]:
        """Normalize ``run_rounds``'s ``record=`` argument: None, one
        RecordSpec, or a sequence of them -> a tuple of specs."""
        if record is None:
            return ()
        if isinstance(record, RecordSpec):
            return (record,)
        return tuple(record)

    @staticmethod
    def _snap_write(bufs, value, slot):
        """Write one snapshot ``value`` (a pytree) into its stacked
        per-leaf buffers at ``slot`` (no-op when slot < 0)."""
        return jax.lax.cond(
            slot >= 0,
            lambda b: jax.tree.map(
                lambda buf, leaf: jax.lax.dynamic_update_index_in_dim(
                    buf, leaf, slot, 0), b, value),
            lambda b: b, bufs)

    @staticmethod
    def _snap_zeros(n_snaps: int, value):
        """Preallocated (n_snaps, ...) snapshot buffers for one
        recorded state entry (a pytree: one buffer per leaf)."""
        return jax.tree.map(
            lambda leaf: jnp.zeros((n_snaps,) + jnp.shape(leaf),
                                   jnp.asarray(leaf).dtype), value)

    def _scan_program(self, body: RoundBody, seg_len: int,
                      layout: Tuple[Tuple[str, int], ...]):
        """The backend-shared round program: program(state, data, start,
        slot_of) -> (state, snaps), running ``seg_len`` rounds of
        ``body`` in one ``lax.scan`` from global round ``start``.

        Every solve runs it (:meth:`run_rounds`): a scanned solve as one
        segment of all its rounds, a checkpointed one as a segment per
        checkpoint, an eager one as a one-round segment per round.  The
        body sees round index ``start + i`` wherever the segment begins,
        through the same per-round HLO, so every split of a solve into
        segments computes the same bits (DESIGN.md §12).  ``start`` and
        ``slot_of`` — an (n_specs, seg_len) table of snapshot slots, -1
        where a round takes none — are arguments, not trace constants:
        every segment of one length and one snapshot count per spec
        shares one program.

        ``layout`` is one ``(state key, snapshots in this segment)`` pair
        per record spec, and each spec takes one of three forms:

        * no snapshot: a ``()`` placeholder (a dynamic update into a
          zero-length buffer would not even compile);
        * a snapshot every round (``record_every=1``, the obs channel):
          streamed through the scan's stacked ``ys`` — no buffer, no
          per-round ``cond``, no slot table;
        * otherwise a preallocated (n, ...) buffer per leaf, carried
          through the scan and written at the slot ``slot_of`` names.
        """
        buffered = [0 < n < seg_len for _, n in layout]

        def program(state, data, start, slot_of):
            ks = start + jnp.arange(seg_len, dtype=jnp.int32)
            bufs0 = tuple(self._snap_zeros(n, state[key]) if b else ()
                          for (key, n), b in zip(layout, buffered))

            def step(carry, xs):
                (st, bufs), (k, slots) = carry, xs
                st = body(k, st, data)
                bufs = tuple(
                    self._snap_write(buf, st[key], slots[i]) if b else buf
                    for i, ((key, _), b, buf) in enumerate(
                        zip(layout, buffered, bufs)))
                ys = tuple(st[key] if n == seg_len else ()
                           for key, n in layout)
                return (st, bufs), ys

            slots = jnp.transpose(slot_of) if any(buffered) else None
            (state, bufs), ys = jax.lax.scan(step, (state, bufs0),
                                             (ks, slots))
            return state, tuple(y if n == seg_len else buf
                                for (_, n), buf, y in zip(layout, bufs, ys))

        return program

    def _compile_segment(self, body: RoundBody, state, sharded, data,
                         seg_len: int, layout: Tuple[Tuple[str, int], ...]):
        """Return fn(state, start, slot_of) -> (state, snaps) running
        one :meth:`_scan_program` segment on the device, with ``data``
        bound as an argument (backend-specific)."""
        raise NotImplementedError

    @staticmethod
    def _segment(records, snap_lists, s: int, e: int, scan: bool):
        """The rounds each record spec hands back from the segment
        [s, e), the segment's :meth:`_scan_program` layout and its slot
        table.

        A scanned segment hands back the spec's snapshots in it; an
        eager one-round segment hands back its round for every spec,
        which the driver keeps only on the spec's cadence, so that every
        round of an eager solve runs one program."""
        emit = [[t for t in sl if s <= t < e] if scan else list(range(s, e))
                for sl in snap_lists]
        slots = np.full((len(emit), e - s), -1, np.int32)
        for i, ts in enumerate(emit):
            for j, t in enumerate(ts):
                slots[i, t - s] = j
        layout = tuple((r.key, len(ts)) for r, ts in zip(records, emit))
        return emit, layout, slots

    @staticmethod
    def _state_donation():
        """argnums donating the state arg of a round program (arg 0).
        CPU jit does not support buffer donation; skip there."""
        return () if jax.default_backend() == "cpu" else (0,)

    @staticmethod
    def _shield_donated(state, donate):
        """Copy state leaves once before a donating call.  The round
        program consumes its ``state`` argument, but callers may still
        hold references to the INITIAL leaves (e.g. the round-0 snapshot
        in an MTLResult) — a one-time (p, m) copy against ``rounds`` of
        in-place carry updates."""
        if not donate:
            return state
        return jax.tree.map(lambda x: jnp.array(x, copy=True), state)

    def _claim(self) -> None:
        if self._used:
            raise RuntimeError(
                "a ProtocolRuntime carries one solve's ledger and cannot "
                "be reused — its CommLog and collective-traffic counters "
                "would accumulate across solves; construct a fresh runtime "
                "(or let repro.solve build one) per call")
        self._used = True

    def run_rounds(self, rounds: int, body: RoundBody,
                   state: Dict[str, jnp.ndarray],
                   sharded: Sequence[str] = (),
                   record=None,        # RecordSpec | sequence of them
                   count_rounds: bool = True, scan: bool = False,
                   data_leaves: Optional[Sequence[str]] = None
                   ) -> Dict[str, jnp.ndarray]:
        """Execute ``rounds`` protocol rounds of ``body``.

        ``state`` is a dict of GLOBAL arrays (or small pytrees of
        arrays — e.g. a spectral-engine carry — sharded as a unit);
        entries named in ``sharded`` live on the workers, split along
        their LAST axis (task columns) under the mesh backend;
        everything else is replicated master state.  Returned/recorded
        state is always global, so callers never see backend-specific
        shapes.  ``data_leaves`` names the subset of worker-data leaves
        the body reads (None = all): leaves outside it are not bound
        into the round loop at all (:meth:`_round_data`).

        The one round loop.  It dispatches segments of one device
        program (:meth:`_scan_program`): ``scan=True`` runs all rounds
        in one dispatch (one per ``checkpoint_every`` rounds when a
        checkpointer is attached), ``scan=False`` one dispatch a round.
        The first dispatch traces the body once; the primitive calls
        recorded during that trace become the per-round communication
        template, and the loop replays ``template × rounds`` into
        ``self.comm`` — valid because every round of one solver runs
        the same collectives (the static round structure of all
        Table-1 protocols, DESIGN.md §5), so the ledger is bit-identical
        whatever the dispatch granularity.  ``record`` snapshots state
        entries on their ``record_every`` cadences (one RecordSpec or a
        sequence — e.g. the W iterate history next to the obs
        round-metrics channel); the sinks receive them after the last
        round.

        With a checkpointer (``self._ckpt``, DESIGN.md §12) the loop
        starts from its newest intact segment, has it check the traced
        template against the stored one, and has it persist the carry
        at every ``checkpoint_every``-round boundary.

        Under 2-D sharding (``data_shards > 1``) the scan sits inside
        the 2-D ``shard_map`` (or inside the sim emulation's data-axis
        vmap), tasks-axis collectives replicate across data shards, and
        the recorded tasks-axis template — hence the CommLog — is
        bit-identical to the 1-D run.
        """
        self._claim()
        self._data_leaves = None if data_leaves is None else \
            tuple(data_leaves)
        records = self._as_records(record)
        sharded = tuple(sharded)
        # the data build comes first: its one-per-solve setup accounting
        # (the 2-D Gram cache) must not depend on how many segments run
        data = self._round_data()
        snap_lists = [r.snap_rounds(rounds) for r in records]
        if self._capture is not None:
            return self._capture_rounds(rounds, body, state, sharded, data,
                                        records, snap_lists, count_rounds,
                                        scan)
        ckpt, start = self._ckpt, 0
        history: List[list] = [[] for _ in records]   # [(round, value)]
        if ckpt is not None:
            start, state, history, stored = ckpt.begin(
                rounds, state, records, count_rounds, scan,
                getattr(self, "mesh", None))
            if stored is not None:
                # ledger catch-up from the STORED template, so the
                # continuation is event-for-event the uninterrupted run's
                self._template, self._data_template = stored
            for _ in range(start):
                self._replay_round(count_rounds)
        every = max(rounds, 1) if ckpt is None else ckpt.every
        seg = every if scan else 1
        snap_sets = [set(sl) for sl in snap_lists]
        programs = {}
        for s in range(start, rounds, seg):
            e = min(s + seg, rounds)
            emit, layout, slots = self._segment(records, snap_lists, s, e,
                                                scan)
            key = (e - s, layout)
            if key not in programs:
                programs[key] = self._compile_segment(
                    body, state, sharded, data, e - s, layout)
            if s == start:
                # the first dispatch traces the body, and takes the
                # caller's leaves: copy them before the program donates
                state = self._shield_donated(state, self._state_donation())
                self._template, self._data_template = [], []
                self._recording = True
            state, snaps = programs[key](state, s, slots)
            if self._recording:
                self._recording = False
                if ckpt is not None:
                    ckpt.seal(self._template, self._data_template)
            for _ in range(e - s):
                self._replay_round(count_rounds)
            for ts, snap, sset, hist in zip(emit, snaps, snap_sets, history):
                if any(t in sset for t in ts):
                    hist.extend((t, v) for t, v in
                                zip(ts, unstack_snapshots(snap)) if t in sset)
            if ckpt is not None and (e % ckpt.every == 0 or e == rounds):
                ckpt.save(e, state, history)
        for r, hist in zip(records, history):
            for t, value in hist:
                r.sink.record(t + 1, value)
        return state

    def _capture_rounds(self, rounds: int, body: RoundBody, state, sharded,
                        data, records, snap_lists, count_rounds: bool,
                        scan: bool):
        """The static-analysis driver (``repro.analysis``): trace the
        program of the solve's first dispatch — all ``rounds`` under
        ``scan``, one round otherwise, with the same jit / vmap /
        shard_map wrapping and donation decision as :meth:`run_rounds`
        — but never run it.

        Tracing executes the round body abstractly, so the primitives
        record the same per-round communication template a real solve
        records, and the ledger below is replayed from it identically;
        the traced ClosedJaxpr (plus the template and the abstract
        output state) is handed to ``self._capture`` for the
        collective-accounting verifier and the sharding/donation lints.
        The initial state is returned unchanged — zero rounds execute —
        and snapshot sinks receive it as a placeholder so solver
        post-processing stays oblivious.
        """
        seg_len = rounds if scan else 1
        _, layout, slots = self._segment(records, snap_lists, 0, seg_len,
                                         scan)
        fn = self._compile_segment(body, state, sharded, data, seg_len,
                                   layout)
        donate = self._state_donation()
        self._recording = True
        closed, out_shapes = jax.make_jaxpr(
            lambda s: fn(self._shield_donated(s, donate), 0, slots),
            return_shape=True)(state)
        self._recording = False                  # template recorded above
        for _ in range(rounds):
            self._replay_round(count_rounds)
        for r, sl in zip(records, snap_lists):
            for t in sl:
                r.sink.record(t + 1, state[r.key])
        self._capture.absorb(self, closed, state, out_shapes[0],
                             rounds=rounds, scan=scan)
        return state

    def one_shot(self, body: RoundBody, state: Dict[str, jnp.ndarray],
                 sharded: Sequence[str] = (), count_round: bool = True,
                 scan: bool = False,
                 data_leaves: Optional[Sequence[str]] = None
                 ) -> Dict[str, jnp.ndarray]:
        """Single protocol exchange (the one-shot baselines)."""
        return self.run_rounds(1, body, state, sharded=sharded,
                               count_rounds=count_round, scan=scan,
                               data_leaves=data_leaves)


def make_runtime(backend: str, prob, *, mesh=None, axis: str = "tasks",
                 data_axis: str = "data", data_shards: int = 1
                 ) -> ProtocolRuntime:
    """Construct a fresh runtime for one solve.

    ``backend``: "sim" | "mesh".  ``data_shards > 1`` shards each
    task's samples across that many devices (mesh) or emulated shards
    (sim) along a second ``data_axis`` — see DESIGN.md §8.  ``mesh``
    may be a prebuilt 1-D or 2-D device mesh; when omitted one is built
    from all local devices.
    """
    if backend == "sim":
        from .sim import SimRuntime
        return SimRuntime(prob, data_shards=data_shards)
    if backend == "mesh":
        from .mesh import MeshRuntime
        return MeshRuntime(prob, mesh=mesh, axis=axis, data_axis=data_axis,
                           data_shards=data_shards)
    raise ValueError(f"unknown backend {backend!r}; have 'sim', 'mesh'")
