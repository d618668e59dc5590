"""Mesh backend: the task axis is a REAL device-mesh axis ("tasks").

The paper's messages become collectives under ``shard_map``:

  workers send columns to master   ->  lax.all_gather over "tasks"
  master broadcasts a vector       ->  free — every chip already holds
                                       the gathered matrix and runs the
                                       master computation redundantly,
                                       the "replicated master" pattern.
                                       On a TPU torus this replaces a
                                       hub hop with one all-gather, the
                                       communication-optimal choice
                                       (DESIGN.md §4).

Traffic per round per chip is exactly the per-chip task columns fed
into the all-gather (matching the paper's "worker->master: 1 vector"
per machine) — the runtime counts those floats as they are traced, so
``collective_floats_per_chip`` and the CommLog ledger derive from the
same primitive calls and cannot disagree.

With ``data_shards > 1`` the mesh grows a second axis ("data",
DESIGN.md §8): each task's ``(n, p)`` rows are sharded across
``data_shards`` chips (``PartitionSpec("tasks", "data", None)``), the
per-task Gram cache is rebuilt once per solve as a ``psum`` of
per-shard partial Grams, raw-path sample statistics reduce over the
data axis via ``pmean_data``/``psum_data``, and every tasks-axis
collective (and the replicated master) simply replicates across the
data shards — the CommLog still charges ONLY tasks-axis traffic while
the data-axis payloads are measured into
``data_collective_floats_per_chip``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import AxisType, Mesh, PartitionSpec as P

from ..core.worker_ops import GRAM_PRECISION
from .base import SAMPLE_AXIS_LEAVES, ProtocolRuntime, round_jit

# Every shard_map here passes check_vma=False: replicated-master state
# is identical on all chips by construction (deterministic ops on
# all-gathered inputs), which the varying-axis checker cannot see.


def auto_mesh(shape, axes, devices=None) -> Mesh:
    """A mesh whose axes GSPMD partitions on its own (``AxisType.Auto``).
    ``jax.make_mesh`` defaults to Explicit axes, under which a plain
    ``jnp.take`` on a tasks-sharded code table refuses to trace."""
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))


def task_mesh(n_devices: int | None = None, axis: str = "tasks") -> Mesh:
    """A 1-D mesh: every device is one worker group on the task axis."""
    devs = jax.devices() if n_devices is None else jax.devices()[:n_devices]
    return auto_mesh((len(devs),), (axis,), devs)


def task_data_mesh(data_shards: int, n_devices: int | None = None,
                   axis: str = "tasks", data_axis: str = "data") -> Mesh:
    """A 2-D ``(tasks, data)`` mesh: ``n_devices / data_shards`` worker
    groups, each sharding its tasks' samples across ``data_shards``
    chips (DESIGN.md §8)."""
    devs = jax.devices() if n_devices is None else jax.devices()[:n_devices]
    if len(devs) % data_shards:
        raise ValueError(f"{len(devs)} devices cannot form a mesh with "
                         f"data_shards={data_shards}")
    return auto_mesh((len(devs) // data_shards, data_shards),
                     (axis, data_axis), devs)


@functools.lru_cache(maxsize=8)
def _shard_gram_fn(mesh: Mesh, axis: str, data_axis: str):
    """Compiled per-shard-partial-Gram psum for one mesh layout.

    Cached at module level: a MeshRuntime lives for ONE solve (its
    ledger is single-use), so a per-runtime closure would recompile
    this program — a pass over the full (m, n, p) design — on every
    2-D solve.  The global 1/n normalization is derived from the shard
    shape inside the program (n = n_local × data_shards), keeping the
    cache key to the mesh layout alone.
    """
    D = mesh.shape[data_axis]

    def program(Xs, ys):                # (L, n/D, p), (L, n/D)
        n = Xs.shape[1] * D
        ein = functools.partial(jnp.einsum, precision=GRAM_PRECISION)
        A = ein("jni,jnk->jik", Xs, Xs) / n
        b = ein("jni,jn->ji", Xs, ys) / n
        return (jax.lax.psum(A, data_axis),
                jax.lax.psum(b, data_axis))

    fn = shard_map(
        program, mesh=mesh,
        in_specs=(P(axis, data_axis, None), P(axis, data_axis)),
        out_specs=(P(axis, None, None), P(axis, None)),
        check_vma=False)
    return jax.jit(fn)


class MeshRuntime(ProtocolRuntime):
    name = "mesh"

    def __init__(self, prob, mesh: Mesh | None = None, axis: str = "tasks",
                 data_axis: str = "data", data_shards: int = 1):
        super().__init__(prob)
        if mesh is None:
            mesh = (task_data_mesh(data_shards, axis=axis,
                                   data_axis=data_axis)
                    if data_shards > 1 else task_mesh(axis=axis))
        if data_axis in mesh.axis_names:
            mesh_shards = mesh.shape[data_axis]
            if data_shards not in (1, mesh_shards):
                raise ValueError(
                    f"data_shards={data_shards} contradicts the mesh's "
                    f"{data_axis!r} axis of size {mesh_shards}")
            data_shards = mesh_shards
        elif data_shards > 1:
            raise ValueError(f"data_shards={data_shards} needs a mesh with "
                             f"a {data_axis!r} axis (task_data_mesh)")
        self.mesh = mesh
        self.axis = axis
        self.data_axis = data_axis
        self.data_shards = int(data_shards)
        ndev = self.mesh.shape[axis]
        if prob.m % ndev:
            raise ValueError(f"m={prob.m} tasks must be divisible by the "
                             f"{ndev} devices on axis {axis!r} (each chip "
                             f"simulates m/devices machines)")
        if prob.n % self.data_shards:
            raise ValueError(f"n={prob.n} samples per task must be "
                             f"divisible by data_shards={self.data_shards}")
        self._per_chip = prob.m // ndev
        self._gram2d = None

    @property
    def local_tasks(self) -> int:
        return self._per_chip

    def axis_index(self):
        return jax.lax.axis_index(self.axis)

    def local_slice(self, x, axis: int = -1):
        per = x.shape[axis] // self.mesh.shape[self.axis]
        start = jax.lax.axis_index(self.axis) * per
        return jax.lax.dynamic_slice_in_dim(x, start, per, axis=axis)

    def gather_columns(self, x, note: str = ""):
        # x: (d, L) local columns -> (d, m); each machine ships 1 d-vector.
        self._charge("worker->master", 1, x.shape[0], note, wire=x.size,
                     kind="all_gather", payload=x.size)
        with jax.named_scope("exchange"):
            return jax.lax.all_gather(x, self.axis, axis=x.ndim - 1,
                                      tiled=True)

    def gather_tasks(self, x, note: str = ""):
        vectors, dim = self._payload_vectors(x)
        self._charge("worker->master", vectors, dim, note, wire=x.size,
                     kind="all_gather", payload=x.size)
        with jax.named_scope("exchange"):
            return jax.lax.all_gather(x, self.axis, axis=0, tiled=True)

    def sum_tasks(self, x, note: str = ""):
        vectors, dim = self._payload_vectors(x)
        # charged wire: every simulated machine ships its payload; the
        # physical psum operand is the chip's LOCAL pre-reduction, L
        # times smaller — the analyzer matches the latter in the jaxpr
        self._charge("worker->master", vectors, dim, note, wire=x.size,
                     kind="psum", payload=x.size // x.shape[0])
        with jax.named_scope("exchange"):
            return jax.lax.psum(jnp.sum(x, axis=0), self.axis)

    # -- data axis: real collectives over the mesh's "data" axis -------
    _count_data_wire = True

    def _psum_data(self, x):
        return jax.lax.psum(x, self.data_axis)

    def _pmean_data(self, x):
        return jax.lax.pmean(x, self.data_axis)

    def _gather_samples(self, x, axis):
        return jax.lax.all_gather(x, self.data_axis, axis=axis, tiled=True)

    # ------------------------------------------------------------------
    # worker data: shard-built Gram cache (2-D only)
    # ------------------------------------------------------------------
    def _worker_data(self):
        data = dict(super()._worker_data())
        if self.data_shards > 1 and "gram_A" in data:
            if self._gram2d is None:
                self._gram2d = self._gram2d_memo(
                    ("mesh", self.mesh, self.axis, self.data_axis),
                    lambda: self._shard_gram(data["Xs"], data["ys"]))
                # setup traffic: each chip contributes its L (p, p) +
                # (p,) partials to the psum, accounted ONCE PER SOLVE —
                # the protocol builds its cache per solve even when the
                # per-problem memo above reuses the bit-identical
                # result.  Added directly (not via _charge_data):
                # run_rounds may already be recording its per-round
                # template when the lazy data build fires.
                p = self.prob.p
                setup = self.local_tasks * (p * p + p)
                self.data_collective_floats_per_chip += setup
                self.setup_data_floats += setup
            data["gram_A"], data["gram_b"] = self._gram2d
        return data

    def _shard_gram(self, Xs, ys):
        """The per-task Gram statistics as a psum of per-shard partial
        Grams — the 2-D replacement for the monolithic make-time
        ``gram_stats`` (identical to it up to float rounding; the
        sharded-vs-unsharded agreement is tested)."""
        return _shard_gram_fn(self.mesh, self.axis, self.data_axis)(Xs, ys)

    def _leaf_spec(self, leaf, shard_it: bool):
        nd = jnp.ndim(leaf)
        if shard_it and nd:
            return P(*([None] * (nd - 1)), self.axis)  # task columns last
        return P(*([None] * nd))

    def _specs(self, state, sharded, data):
        axis = self.axis

        # state entries may be pytrees (a solver's spectral-engine
        # carry rides next to W); every leaf of an entry shares the
        # entry's sharding decision
        state_specs = {}
        for n, v in state.items():
            shard_it = n in sharded
            state_specs[n] = jax.tree.map(
                lambda leaf, s=shard_it: self._leaf_spec(leaf, s), v)

        def data_spec(name, v):
            # every data leaf is a per-task stack: sharded along axis 0;
            # sample leaves additionally shard their row axis (axis 1)
            # across the data axis of a 2-D mesh.  Derived statistics
            # (the Gram cache) replicate across data shards.
            rest = [None] * (jnp.ndim(v) - 1)
            if self.data_shards > 1 and name in SAMPLE_AXIS_LEAVES:
                rest[0] = self.data_axis
            return P(axis, *rest)

        data_specs = {n: data_spec(n, v) for n, v in data.items()}
        return state_specs, data_specs

    # ------------------------------------------------------------------
    # multi-controller input binding
    # ------------------------------------------------------------------
    def _put_global(self, x, spec):
        """Commit one host value to its global mesh sharding.  Under
        multi-controller jax (``jax.process_count() > 1``) jit inputs
        must be globally-addressable Arrays; every process holds the
        full value (the problem is built deterministically on each
        host), so the callback just slices its local block."""
        from jax.sharding import NamedSharding
        sh = NamedSharding(self.mesh, spec)
        if isinstance(x, jax.Array) and not x.is_fully_addressable:
            return x            # already a global array (prior segment out)
        host = np.asarray(x)
        return jax.make_array_from_callback(
            host.shape, sh, lambda idx: host[idx])

    def _bind_data(self, data, data_specs):
        if jax.process_count() == 1:
            return data
        return {n: self._put_global(v, data_specs[n])
                for n, v in data.items()}

    def _bind_state(self, state, sharded):
        if jax.process_count() == 1:
            return state
        out = {}
        for n, v in state.items():
            shard_it = n in sharded
            out[n] = jax.tree.map(
                lambda leaf, s=shard_it: self._put_global(
                    leaf, self._leaf_spec(leaf, s)), v)
        return out

    def _bind_scalar(self, x, spec=P()):
        if jax.process_count() == 1:
            return jnp.asarray(x, jnp.int32)
        return self._put_global(np.asarray(x, np.int32), spec)

    @staticmethod
    def _snaps_spec(entry_spec):
        """PartitionSpecs of one recorded entry's stacked snapshot
        buffers: the entry's own per-leaf specs behind a leading
        (replicated) snapshot axis."""
        return jax.tree.map(lambda ls: P(None, *ls), entry_spec,
                            is_leaf=lambda x: isinstance(x, P))

    def _compile_segment(self, body, state, sharded, data, seg_len, layout):
        state_specs, data_specs = self._specs(state, sharded, data)
        data = self._bind_data(data, data_specs)
        snaps_spec = tuple(self._snaps_spec(state_specs[key]) if n else ()
                           for key, n in layout)
        fn = shard_map(self._scan_program(body, seg_len, layout),
                       mesh=self.mesh,
                       in_specs=(state_specs, data_specs, P(),
                                 P(None, None)),
                       out_specs=(state_specs, snaps_spec),
                       check_vma=False)
        step = round_jit(fn, donate_argnums=self._state_donation())
        return lambda s, start, slots: step(
            self._bind_state(s, sharded), data, self._bind_scalar(start),
            self._bind_scalar(np.asarray(slots), P(None, None)))
