"""Common API for the paper's multi-task solvers.

A problem instance bundles the per-task datasets (stacked over the task
axis — the "machines") plus the structural constants of Assumption 2.1 /
2.3. Every solver returns an MTLResult carrying the final predictor
matrix, the per-round iterates (for the excess-error-vs-communication
plots of Figs 1-3), and the communication ledger.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp

from ...obs.tracing import trace_span
from ..comm import CommLog
from ..losses import Loss, get_loss


@dataclasses.dataclass
class MTLProblem:
    Xs: jnp.ndarray            # (m, n, p) per-machine designs
    ys: jnp.ndarray            # (m, n)    per-machine labels
    loss: Loss
    A: float = 1.0             # predictor-norm bound, Assumption 2.1
    r: int = 5                 # assumed rank bound, Assumption 2.3
    l2: float = 0.0            # optional ridge (real-data experiments, App. H)
    # Cached per-task Gram statistics A_j = X_j^T X_j / n (m, p, p) and
    # b_j = X_j^T y_j / n (m, p), computed once in `make` for the
    # squared loss — every round's gradient/Hessian/ridge solve then
    # costs O(p^2) per task instead of O(n p) (repro.core.worker_ops).
    gram_A: Optional[jnp.ndarray] = None
    gram_b: Optional[jnp.ndarray] = None
    # Per-layout memo of the 2-D (shard-summed) Gram cache, filled by
    # the runtimes on first use (runtime/{mesh,sim}.py): one problem is
    # typically solved many times on one mesh, and the shard-partial
    # psum rebuild is a full pass over the (m, n, p) designs.  The
    # rebuild's data-axis traffic is still ACCOUNTED once per solve —
    # the protocol builds its cache per solve; the memo only reuses the
    # bit-identical result (cf. the charged-but-free broadcast of the
    # replicated master, DESIGN.md §4/§8).
    gram2d_cache: Dict = dataclasses.field(default_factory=dict,
                                           repr=False, compare=False)

    @property
    def m(self) -> int:
        return self.Xs.shape[0]

    @property
    def n(self) -> int:
        return self.Xs.shape[1]

    @property
    def p(self) -> int:
        return self.Xs.shape[2]

    @property
    def nuclear_radius(self) -> float:
        # ||W*||_* <= sqrt(r m) A, eq. (2.2)
        return float(jnp.sqrt(self.r * self.m) * self.A)

    def worker_data(self) -> Dict[str, jnp.ndarray]:
        """The per-task data leaves the runtime binds into round bodies
        (each stacked over the task axis; sharded along it under mesh).
        ``Xs``/``ys`` carry the per-task SAMPLE axis at position 1 —
        under a 2-D runtime (``data_shards > 1``) that axis is
        additionally sharded across the "data" mesh axis, and the Gram
        leaves are REPLACED by a psum of per-shard partial Grams
        (``runtime.SAMPLE_AXIS_LEAVES``, DESIGN.md §8).  ``task_ids``
        carries each task's GLOBAL index (sharded along the task axis
        under mesh, like every per-task leaf): the stochastic batch
        sampler folds it into its key chain so a task draws the same
        mini-batch rows on every backend and layout (DESIGN.md §13)."""
        d = {"Xs": self.Xs, "ys": self.ys,
             "task_ids": jnp.arange(self.m, dtype=jnp.int32)}
        if self.gram_A is not None:
            d["gram_A"], d["gram_b"] = self.gram_A, self.gram_b
        return d

    @classmethod
    def make(cls, Xs, ys, loss_name: str = "squared", gram: bool = True,
             **kw) -> "MTLProblem":
        """Build a problem from stacked per-task data.

        ``gram=True`` (default) precomputes the per-task Gram cache for
        the squared loss, making every solver round O(p²) per task
        independent of n; ``gram=False`` keeps the raw-data path (the
        pre-cache baseline, kept for benchmarks and fallback — and the
        path exercised per-round by the data axis, DESIGN.md §7-8)."""
        Xs, ys = jnp.asarray(Xs), jnp.asarray(ys)
        loss = get_loss(loss_name)
        prob = cls(Xs=Xs, ys=ys, loss=loss, **kw)
        if gram and loss.name == "squared":
            from ..worker_ops import gram_stats
            # the cache lies where Xs does: split along the task axis
            # over the chips of a tasks-sharded Xs
            m, _, p = Xs.shape
            rows = Xs.sharding.shard_shape(Xs.shape)[0]
            per_chip = rows * (p * p + p) * Xs.dtype.itemsize
            with trace_span("problem.gram", bytes_per_chip=per_chip,
                            shards=m // rows):
                prob.gram_A, prob.gram_b = gram_stats(Xs, ys)
                jax.block_until_ready(prob.gram_b)
        return prob


@dataclasses.dataclass
class MTLResult:
    name: str
    W: jnp.ndarray                     # (p, m) final predictors
    comm: CommLog
    # iterates[k] = W after round rounds_axis[k]; one-shot methods have a
    # single entry at round 0 (Local) or 1 (Centralize / SVD-trunc).
    iterates: List[jnp.ndarray] = dataclasses.field(default_factory=list)
    rounds_axis: List[int] = dataclasses.field(default_factory=list)
    extras: Dict = dataclasses.field(default_factory=dict)

    def record(self, rnd: int, W: jnp.ndarray) -> None:
        self.rounds_axis.append(rnd)
        self.iterates.append(W)

    def factorize(self, rank: int, loss: Optional[str] = None,
                  task_keys=None):
        """Extract the factored serving artifact ``(U, s, V)`` at the
        given rank — the O((p + m) r) form the online system stores and
        scores from (``repro.serve.mtl``, DESIGN.md §10).

        One code path: delegates to ``FactoredModel.from_W``, which
        routes through ``repro.core.spectral.truncate_factors`` (the
        same residual-tested engine behind the svd_trunc master) — no
        ad-hoc SVDs.  ``loss`` names the loss the predictors were
        trained under (it selects the onboarding/prediction math and is
        recorded in the artifact manifest); it defaults to the loss
        ``repro.solve`` stamped into ``extras`` ("squared" for results
        built outside the front door).  ``task_keys`` optionally names
        the m tasks for key-based request routing.
        """
        from ...serve.mtl import FactoredModel
        if loss is None:
            loss = self.extras.get("loss", "squared")
        return FactoredModel.from_W(self.W, rank, loss=loss,
                                    task_keys=task_keys)


# Registry names of the gradient-served solvers that accept the
# stochastic worker path (``repro.solve(..., batch_size=, local_steps=)``,
# DESIGN.md §13): mini-batch gradients + communication-free local steps.
# The one-shot baselines and DFW (whose Frank-Wolfe step is defined on
# the exact gradient) stay full-batch.
STOCHASTIC_SOLVERS = ("accproxgd", "admm", "dgsp", "dnsp", "proxgd")


def stochastic_config(prob: MTLProblem, batch_size, local_steps,
                      data_shards: int = 1):
    """Normalize a solver's ``(batch_size, local_steps)`` pair.

    Returns ``(B, L)`` for a genuinely stochastic configuration, or
    ``None`` when the solver must run its EXACT full-batch program.

    The degeneracy rule (DESIGN.md §13): ``batch_size == n`` and
    ``local_steps == 1`` IS the full-batch algorithm, so it
    canonicalizes — at trace time, on static ints — to the historical
    full-batch code path.  That makes the stochastic front door
    bit-identical there by construction: same HLO, same ledger, same
    measured collective floats on every backend, driver and layout.

    ``batch_size`` is the GLOBAL per-task mini-batch; under a 2-D
    layout each data shard samples ``batch_size / data_shards`` of its
    local rows (hence the divisibility requirement), and the per-shard
    mini-batch gradients are pmean-reduced over the data axis exactly
    like the full-batch raw path.
    """
    if batch_size is None and local_steps in (None, 1):
        return None
    B = prob.n if batch_size is None else int(batch_size)
    L = 1 if local_steps is None else int(local_steps)
    if not 1 <= B <= prob.n:
        raise ValueError(f"batch_size={B} outside [1, n={prob.n}]")
    if L < 1:
        raise ValueError(f"local_steps={L} must be >= 1")
    if B % data_shards:
        raise ValueError(f"batch_size={B} must be divisible by "
                         f"data_shards={data_shards} (each shard samples "
                         f"batch_size/data_shards of its local rows)")
    if B == prob.n and L == 1:
        return None
    return B, L


def stochastic_round_leaves(prob: MTLProblem):
    """Data leaves a stochastic round body reads: the raw samples plus
    the global task ids that key the sampler's fold_in chain — never
    the Gram cache (a mini-batch gradient is computed from sampled
    rows, not from full-data sufficient statistics)."""
    return ("Xs", "ys", "task_ids")


def gram_round_leaves(prob: MTLProblem):
    """Data leaves a round body reads when the Gram cache serves every
    worker path (squared loss, cache built): the cached statistics
    only.  ``None`` (= bind everything) otherwise — raw-path and
    logistic bodies stream the samples every round.

    Passed to ``run_rounds(data_leaves=...)`` so gram-served solvers do
    not keep the raw ``(n, p)`` designs in the device-resident
    round-loop data: at large n — and especially on a 2-D mesh, where
    ``Xs``/``ys`` would shard along the data axis — that binding is
    pure layout/transfer cost for arrays no round touches.
    """
    if prob.loss.name == "squared" and prob.gram_A is not None:
        return ("gram_A", "gram_b")
    return None


def iterate_recorder(res: "MTLResult", record_every: int, key: str = "W"):
    """RecordSpec snapshotting one state leaf into the result every
    ``record_every`` rounds (and always the final round) — the shared
    cadence for every iterative solver's Fig 1-3 curves, honored by both
    the eager and the scanned driver (runtime.RecordSpec)."""
    from ...runtime.base import RecordSpec
    return RecordSpec(sink=res, every=record_every, key=key)


def metrics_channel(metrics: bool):
    """The device-resident round-metrics channel (repro.obs, DESIGN.md
    §15): ``(initial obs entry, RecordSpec, sink)`` when ``metrics`` is
    on, else ``None``.

    The solver adds the entry to its round-loop state (replicated — it
    must never enter ``sharded``), updates it in the body via
    ``obs_round`` from master-visible quantities only (no new
    collectives, so the ledger and the static-verification matrix are
    untouched), passes the RecordSpec next to its iterate recorder, and
    stamps ``sink.finalize(rt)`` into ``extras["metrics"]``.
    """
    if not metrics:
        return None
    from ...obs.device import OBS_KEY, RoundMetricsSink, obs_init
    from ...runtime.base import RecordSpec
    sink = RoundMetricsSink()
    return obs_init(), RecordSpec(sink=sink, every=1, key=OBS_KEY), sink


def compose_records(base, channel):
    """``run_rounds(record=...)`` argument from the iterate recorder
    plus an optional metrics channel."""
    return base if channel is None else (base, channel[1])


def default_runtime(prob: MTLProblem, runtime=None):
    """The runtime a solver executes on; defaults to the simulated cluster.

    Every registered solver takes ``runtime=None`` and resolves it here,
    so calling a solver directly keeps today's vmap semantics while
    ``repro.solve(..., backend="mesh")`` hands in a MeshRuntime.
    """
    if runtime is not None:
        return runtime
    from ...runtime.sim import SimRuntime
    return SimRuntime(prob)


SolverFn = Callable[..., MTLResult]
_REGISTRY: Dict[str, SolverFn] = {}


def register(name: str):
    def deco(fn: SolverFn) -> SolverFn:
        _REGISTRY[name] = fn
        fn.solver_name = name
        return fn
    return deco


def get_solver(name: str) -> SolverFn:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(f"unknown solver {name!r}; have {sorted(_REGISTRY)}")


def solver_names() -> List[str]:
    return sorted(_REGISTRY)
