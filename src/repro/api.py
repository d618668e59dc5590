"""The front door: ``repro.solve(prob, method=..., backend="sim"|"mesh")``.

One call signature for every solver in the registry on every execution
backend, returning an :class:`~repro.core.methods.base.MTLResult`
uniformly (predictors, per-round iterates, communication ledger).

The result is also the hand-off to the ONLINE half of the system
(``repro.serve.mtl``, DESIGN.md §10)::

    res = repro.solve(prob, method="proxgd", rounds=50, lam=0.01)
    model = res.factorize(rank=prob.r)       # (U, s, V) artifact
    model.save("store/")                     # atomic npz + manifest
    server = repro.serve.MTLServer(model)    # O(p r) batched scoring
"""
from __future__ import annotations

from typing import Optional

from .runtime.base import ProtocolRuntime, make_runtime


def solve(prob, method: str = "dgsp", backend: str = "sim", *,
          mesh=None, axis: str = "tasks", data_shards: int = 1,
          data_axis: str = "data", rounds: Optional[int] = None,
          scan: Optional[bool] = None, sv_engine: Optional[str] = None,
          batch_size: Optional[int] = None,
          local_steps: Optional[int] = None, batch_seed: int = 0,
          runtime: Optional[ProtocolRuntime] = None,
          verify: Optional[str] = None,
          checkpoint_every: Optional[int] = None,
          ckpt_dir: Optional[str] = None,
          ckpt_keep: Optional[int] = 3,
          metrics: bool = False, **hp):
    """Run one registered solver on one backend.

    Parameters
    ----------
    prob: MTLProblem — the per-task datasets + structural constants.
        Built with ``MTLProblem.make(..., gram=True)`` (the default)
        the squared-loss worker path uses cached per-task Gram
        statistics, making every round O(p²) per task independent of n;
        ``gram=False`` keeps the raw ``(n, p)`` path (DESIGN.md §7).
    method: registry name (``repro.core.solver_names()``).
    backend: "sim" (vmap over the task axis, single process) or "mesh"
        (shard_map over a real "tasks" mesh axis, replicated master).
    mesh / axis: mesh backend only — the device mesh (defaults to all
        devices) and the task axis name.  Pass a 2-D mesh from
        ``repro.runtime.task_data_mesh`` (or just ``data_shards=``) to
        shard within tasks.
    data_shards: shard each task's n samples across this many devices
        along a second ``data_axis`` mesh axis (DESIGN.md §8) — the
        large-n scaling lever: per-task sample statistics are reduced
        over the data axis (Gram cache: one psum of per-shard partial
        Grams per solve; raw paths: pmean per use), while tasks-axis
        semantics — and the CommLog — are unchanged.  Under
        ``backend="sim"`` the data axis is emulated with a reshaped
        ``vmap`` so 2-D runs are testable on one device.  Default 1
        (the paper's one-machine-per-task layout).
    data_axis: name of the data mesh axis (2-D mesh backend only).
    rounds: communication rounds, forwarded when given (one-shot
        baselines take none).
    scan: the dispatch granularity of the one round program.  True
        (the default inside every solver) runs the whole round loop in
        one device-resident ``lax.scan`` dispatch (one per
        ``checkpoint_every`` rounds with ``ckpt_dir``); False dispatches
        it one round at a time.  Ledger, snapshots and results are
        identical either way (``tests/test_runtime_parity.py``).
    sv_engine: "lazy" (the default inside the prox-family solvers) runs
        the master's singular-value shrinkage / truncation on the
        warm-started randomized spectral engine
        (``repro.core.spectral``, DESIGN.md §9): matvec-only rounds on
        a carried top-(k+oversample) basis with residual-tested exact
        fallback.  "exact" forces the full ``jnp.linalg.svd`` master.
        Results agree to the engine's residual tolerance and the
        CommLog is bit-identical (the master is replicated: the engine
        is compute-only).  Forwarded only when given, to solvers that
        take it (prox family, centralize, svd_trunc); a per-solver
        ``sv_rank=`` hyper-parameter overrides the carried rank hint
        (default: the problem's assumed rank bound r).
    batch_size / local_steps / batch_seed: the stochastic worker path
        (DESIGN.md §13), for the gradient-served solvers
        (``repro.core.methods.base.STOCHASTIC_SOLVERS`` — proxgd,
        accproxgd, admm, dgsp, dnsp).  ``batch_size`` rows per task per
        gradient (sampled with replacement from a seeded,
        device-resident sampler keyed on ``(batch_seed, task id,
        round, local step, data shard)`` — no RNG state in the solver
        loop, so draws are identical across backends, drivers and
        layouts); ``local_steps`` communication-free worker steps
        between charged rounds (arXiv 1802.03830).  The CommLog keeps
        charging ONLY the tasks-axis rounds in Table-1 units — local
        steps issue no tasks-axis collective, which ``verify="static"``
        proves on the traced program.  ``batch_size=n`` with
        ``local_steps=1`` canonicalizes to the exact full-batch code
        path (bit-identical W, ledger, and measured collective floats —
        the degeneracy rule).  Under ``data_shards=D > 1``,
        ``batch_size`` must be divisible by D (each shard samples
        batch_size/D of its local rows; mini-batch gradients
        pmean-reduce over the data axis like the full-batch raw path).
    runtime: pass an explicit ProtocolRuntime instead of backend/mesh.
    checkpoint_every / ckpt_dir / ckpt_keep: preemption-safe solves
        (DESIGN.md §12).  With ``ckpt_dir`` set, the round loop runs in
        ``checkpoint_every``-round segments (default
        ``runtime.recovery.DEFAULT_SEGMENT``) whose full carry — solver
        state, spectral-engine carry, snapshot history, ledger cursor +
        comm-template hash — persists through the atomic content-hashed
        ``train/checkpoint`` store after every segment, keeping the last
        ``ckpt_keep`` segments (None = all).  A killed solve restarts
        via ``repro.resume(ckpt_dir)`` — or by re-issuing the SAME
        ``solve`` call, which picks up the newest intact segment instead
        of starting over — and finishes with ``W``, ledger, and measured
        collective floats bit-identical to an uninterrupted run.
        ``result.extras["checkpoint"]`` reports the segment bookkeeping.
    verify: ``"static"`` statically verifies THIS solve configuration
        before running it (``repro.analysis``, DESIGN.md §11): the
        round program is traced — zero rounds executed — its jaxpr's
        named-axis collectives are checked equation-by-equation
        against the CommLog template, and the sharding / donation /
        carry-drift lints run over the same trace.  Raises
        ``repro.analysis.AnalysisError`` (findings name the offending
        equation and axis) instead of executing a mis-accounted
        program; on success the real solve proceeds and
        ``result.extras["static_verify"] == "ok"``.  Requires the
        declarative backend/mesh arguments (not ``runtime=`` — the
        verifier needs to build a twin runtime for the trace).
    metrics: ``True`` collects device-resident per-round metrics
        (``repro.obs``, DESIGN.md §15) into
        ``result.extras["metrics"]`` — objective term, gradient /
        step norms, spectral fallback count, per-round arrays stacked
        over rounds, plus the ledger's per-round charged floats.  The
        metric channel rides the scan carry (no host callbacks, no new
        collectives), so ``W`` and the ledger stay bit-identical to a
        ``metrics=False`` run on every backend, driver and layout.
    **hp: solver hyper-parameters (lam, eta, damping, ...).

    Returns the solver's MTLResult; ``result.comm`` is the protocol
    ledger — ALWAYS in the paper's Table-1 tasks-axis units, and
    bit-identical across backends, drivers and ``data_shards`` —
    and ``result.extras`` carries:

    * ``backend`` / ``data_shards`` — how the solve executed;
    * ``collective_floats_per_chip`` — measured worker->master protocol
      floats the chip's simulated machines fed into tasks-axis
      collectives (the all-gather payload; psum contributions counted
      before the chip's local pre-reduction).  Equals the ledger's
      worker->master floats x tasks-per-chip by construction; 0 under
      sim where no collective runs.
    * ``data_collective_floats_per_chip`` — measured data-axis
      collective floats per chip (Gram-cache psum + raw-path
      reductions).  Never charged to the ledger; 0 under sim or when
      ``data_shards == 1``.
    """
    from .core.methods import get_solver

    if batch_size is not None or local_steps is not None:
        from .core.methods.base import STOCHASTIC_SOLVERS
        if method not in STOCHASTIC_SOLVERS:
            raise ValueError(
                f"batch_size/local_steps need a gradient-served solver "
                f"{STOCHASTIC_SOLVERS}; {method!r} is full-batch only")
        # normalized and validated (against n, data_shards) inside the
        # solver via stochastic_config — batch_size == n, local_steps
        # == 1 canonicalizes to the exact full-batch program there
        hp["batch_size"] = batch_size
        hp["local_steps"] = local_steps
        hp["batch_seed"] = batch_seed

    if metrics:
        # set before the verify / checkpoint blocks so the static
        # verifier traces the instrumented program and a resumed solve
        # replays the same configuration
        hp["metrics"] = True

    if verify is not None:
        if verify != "static":
            raise ValueError(f"unknown verify mode {verify!r}; "
                             f"have 'static'")
        if runtime is not None:
            raise ValueError("verify='static' needs the declarative "
                             "backend/mesh arguments, not runtime=")
        from .analysis import verify_static
        vhp = dict(hp)
        if rounds is not None:
            vhp["rounds"] = rounds
        if sv_engine is not None:
            vhp["sv_engine"] = sv_engine
        verify_static(prob, method, backend=backend, mesh=mesh, axis=axis,
                      data_shards=data_shards, data_axis=data_axis,
                      scan=scan, **vhp)
    if runtime is None:
        runtime = make_runtime(backend, prob, mesh=mesh, axis=axis,
                               data_axis=data_axis, data_shards=data_shards)
    if rounds is not None:
        hp["rounds"] = rounds
    if scan is not None:
        hp["scan"] = scan
    if sv_engine is not None:
        hp["sv_engine"] = sv_engine
    ckpt = None
    if ckpt_dir is not None or checkpoint_every is not None:
        if ckpt_dir is None:
            raise ValueError("checkpoint_every needs ckpt_dir= (where "
                             "the solve store lives)")
        from .runtime.recovery import (DEFAULT_SEGMENT, SolveCheckpointer,
                                       write_store)
        every = DEFAULT_SEGMENT if checkpoint_every is None \
            else checkpoint_every
        config = {"method": method, "backend": backend, "axis": axis,
                  "data_axis": data_axis, "data_shards": data_shards,
                  "checkpoint_every": every, "ckpt_keep": ckpt_keep,
                  "hp": hp}
        write_store(ckpt_dir, prob, config)
        ckpt = SolveCheckpointer(ckpt_dir, every=every, keep=ckpt_keep)
        ckpt.load_resume()      # no-op on a fresh store
        runtime._ckpt = ckpt
    from .obs.tracing import trace_span
    with trace_span("solve", method=method, backend=runtime.name,
                    data_shards=runtime.data_shards,
                    metrics=bool(metrics)):
        res = get_solver(method)(prob, runtime=runtime, **hp)
    # stamp the trained loss so res.factorize() builds the serving
    # artifact with the right prediction/onboarding math by default
    res.extras.setdefault("loss", prob.loss.name)
    res.extras["backend"] = runtime.name
    res.extras["data_shards"] = runtime.data_shards
    res.extras["collective_floats_per_chip"] = \
        runtime.collective_floats_per_chip
    res.extras["data_collective_floats_per_chip"] = \
        runtime.data_collective_floats_per_chip
    if verify is not None:
        res.extras["static_verify"] = "ok"
    if ckpt is not None:
        res.extras["checkpoint"] = dict(ckpt.info)
    return res


def resume(ckpt_dir: str, *, mesh=None):
    """Restart a checkpointed solve from its store (DESIGN.md §12).

    The one-argument recovery front door: rebuilds the problem + solve
    configuration from the store's manifest, restores the newest intact
    segment and finishes the solve — see
    :func:`repro.runtime.recovery.resume`.
    """
    from .runtime.recovery import resume as _resume
    return _resume(ckpt_dir, mesh=mesh)
