"""Simulated-cluster backend: one process plays all m machines.

The worker view holds every task, ``worker_map`` vmaps over the full
task axis and the collectives are identities — today's semantics of the
``core/methods`` registry, now expressed through the protocol
primitives so the exact same solver body also runs on a device mesh.

``data_shards > 1`` emulates the 2-D ``("tasks", "data")`` mesh
(DESIGN.md §8) without any devices: every per-task sample leaf is
reshaped ``(m, n, ...) -> (D, m, n/D, ...)`` and the whole round
program runs under ``vmap(axis_name="data")`` over the leading shard
axis, so ``pmean_data`` / ``psum_data`` / ``gather_samples`` lower to
the SAME ``lax`` collectives the mesh backend issues (over the vmapped
axis instead of a mesh axis).  Replicated state rides in unbatched and
comes out identical on every shard; the driver returns shard 0's copy.
This makes every solver's sim ≡ mesh-1D ≡ mesh-2D parity testable on a
single CPU device (``tests/test_mesh2d.py``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .base import SAMPLE_AXIS_LEAVES, ProtocolRuntime, round_jit


class SimRuntime(ProtocolRuntime):
    name = "sim"

    def __init__(self, prob, data_shards: int = 1):
        super().__init__(prob)
        if data_shards < 1 or prob.n % data_shards:
            raise ValueError(f"n={prob.n} samples per task must be "
                             f"divisible by data_shards={data_shards}")
        self.data_shards = int(data_shards)
        self._gram2d = None

    @property
    def local_tasks(self) -> int:
        return self.prob.m

    def axis_index(self):
        return jnp.int32(0)

    def local_slice(self, x, axis: int = -1):
        return x

    def gather_columns(self, x, note: str = ""):
        # (d, m) already global; ledger: 1 d-vector per machine.
        self._charge("worker->master", 1, x.shape[0], note, wire=0)
        return x

    def gather_tasks(self, x, note: str = ""):
        vectors, dim = self._payload_vectors(x)
        self._charge("worker->master", vectors, dim, note, wire=0)
        return x

    def sum_tasks(self, x, note: str = ""):
        vectors, dim = self._payload_vectors(x)
        self._charge("worker->master", vectors, dim, note, wire=0)
        with jax.named_scope("exchange"):
            return jnp.sum(x, axis=0)

    # -- data axis: lax collectives over the emulation's vmapped axis --
    def _psum_data(self, x):
        return jax.lax.psum(x, self.data_axis)

    def _pmean_data(self, x):
        return jax.lax.pmean(x, self.data_axis)

    def _gather_samples(self, x, axis):
        return jax.lax.all_gather(x, self.data_axis, axis=axis, tiled=True)

    # ------------------------------------------------------------------
    # worker data: per-shard layout + shard-summed Gram cache
    # ------------------------------------------------------------------
    def _worker_data(self):
        data = dict(super()._worker_data())
        if self.data_shards == 1:
            return data
        D = self.data_shards
        if "gram_A" in data:
            # the Gram cache as the 2-D runtime defines it: a sum of
            # per-shard partial Grams (== the mesh backend's psum), not
            # the monolithic make-time statistics — agrees with them to
            # float rounding (worker_ops.gram_stats).  Memoized on the
            # problem per shard count (a full pass over the designs,
            # identical every solve — runtime/mesh.py does the same).
            if self._gram2d is None:
                from ..core.worker_ops import gram_stats
                self._gram2d = self._gram2d_memo(
                    ("sim", D),
                    lambda: gram_stats(data["Xs"], data["ys"],
                                       data_shards=D))
            data["gram_A"], data["gram_b"] = self._gram2d
        for name in SAMPLE_AXIS_LEAVES & set(data):
            v = data[name]
            m, n = v.shape[0], v.shape[1]
            # (m, n, ...) -> (D, m, n/D, ...): shard d holds rows
            # [d n/D, (d+1) n/D) — the same contiguous blocks the mesh
            # backend's PartitionSpec assigns.
            v = v.reshape((m, D, n // D) + v.shape[2:])
            data[name] = jnp.moveaxis(v, 1, 0)
        return data

    def _data_in_axes(self, data):
        return {name: 0 if name in SAMPLE_AXIS_LEAVES else None
                for name in data}

    def _unreplicate(self, tree):
        """Collapse the emulation's shard axis; every leaf is replicated
        across shards by construction (reduced statistics + identical
        master computation), so shard 0 is THE value."""
        return jax.tree.map(lambda x: x[0], tree)

    # ------------------------------------------------------------------
    # drivers
    # ------------------------------------------------------------------
    def _compile_segment(self, body, state, sharded, data, seg_len, layout):
        # Data enters as jit ARGUMENTS (not closure constants) so XLA
        # does not constant-fold per-task Gram matrices at compile time.
        program = self._scan_program(body, seg_len, layout)
        if self.data_shards == 1:
            step = round_jit(program, donate_argnums=self._state_donation())
        else:
            # axis_size keeps the emulated data axis alive even when
            # pruning left no sample leaves to map over (gram-only round
            # bodies, run_rounds(data_leaves=...))
            vprog = jax.vmap(program,
                             in_axes=(None, self._data_in_axes(data), None,
                                      None),
                             out_axes=0, axis_name=self.data_axis,
                             axis_size=self.data_shards)
            # no donation: the emulated program's outputs are (D, ...)
            # batched, so the (global-shaped) input buffers cannot be
            # reused
            step = round_jit(lambda s, d, k0, sl: self._unreplicate(
                vprog(s, d, k0, sl)))
        return lambda s, start, slots: step(
            s, data, jnp.int32(start), jnp.asarray(slots, jnp.int32))
