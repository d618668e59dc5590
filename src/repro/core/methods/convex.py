"""Distributed convex-optimization methods over the nuclear-norm objective.

ProxGD   (Algorithm 4): workers send gradient columns; master does
                        singular-value shrinkage.         2p per round.
AccProxGD (Algorithm 5): Nesterov two-sequence variant.   2p per round.
ADMM     (Algorithm 2 / Appendix A): workers solve regularized local ERM;
                        master shrinkage + dual update.   3p per round.
DFW      (Algorithm 3 / Appendix B): master computes only the LEADING
                        singular pair of the gradient.    2p per round.

Each solver is a round body against the runtime primitives: workers
compute on their local task columns (local_slice + the worker_ops
dispatch layer — Gram fast path for squared loss, Pallas kernel on TPU,
XLA reference elsewhere), the gradient matrix is assembled with
gather_columns, the master step runs on the (replicated) gathered state,
and broadcast publishes the update.  ``scan=True`` (default) fuses the
whole round loop into one device-resident lax.scan; the driver snapshots
the iterate every ``record_every`` rounds in either mode (rounds are the
unit of the paper's plots).

The shrinkage masters run on the spectral engine
(:mod:`repro.core.spectral`, ``sv_engine="lazy"`` by default): a
warm-started randomized SVT whose basis carry rides in the solver's
scan state — matvec-only rounds with an exact-SVD fallback, identical
communication either way (DESIGN.md §9).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .. import spectral, worker_ops
from ..spectral import leading_sv
from ...obs.device import obs_round
from .base import (MTLProblem, MTLResult, compose_records, default_runtime,
                   gram_round_leaves, iterate_recorder, metrics_channel,
                   register, stochastic_config, stochastic_round_leaves)


def data_smoothness(prob: MTLProblem) -> float:
    """Per-task smoothness H * max_j ||X_j^T X_j / n||_2.

    Assumption 2.1 bounds ||x|| <= 1 which gives H; the paper's own
    simulations use Gaussian features with ||x||^2 ~ p, so a safe step
    needs the empirical spectral norm (one-time local computation, no
    extra communication: each worker can send its scalar with its first
    gradient; we charge nothing, consistent with the paper's accounting
    of vectors only). Identical on every backend, so sim and mesh runs
    share the step size.  Uses the cached Gram matrices when present —
    no pass over the raw (n, p) designs.
    """
    def spec(C):
        mv = functools.partial(jnp.matmul, C,
                               precision=worker_ops.GRAM_PRECISION)
        v = jnp.ones((C.shape[0],), C.dtype) / jnp.sqrt(C.shape[0])
        def body(_, v):
            w = mv(v)
            return w / jnp.maximum(jnp.linalg.norm(w), 1e-30)
        v = jax.lax.fori_loop(0, 50, body, v)
        return v @ mv(v)

    if prob.gram_A is not None:
        lmax = jnp.max(jax.vmap(spec)(prob.gram_A))
    else:
        # matvec-only power iteration on the IMPLICIT Gram operator
        # v -> X^T (X v) / n: never materializes the (p, p) per-task
        # Gram (m p^2 floats — 12 GB at the spectral bench spec)
        def spec_raw(X):
            n = X.shape[0]
            v = jnp.ones((X.shape[1],), X.dtype) / jnp.sqrt(X.shape[1])
            def body(_, v):
                w = X.T @ (X @ v) / n
                return w / jnp.maximum(jnp.linalg.norm(w), 1e-30)
            v = jax.lax.fori_loop(0, 50, body, v)
            return v @ (X.T @ (X @ v)) / n
        lmax = jnp.max(jax.vmap(spec_raw)(prob.Xs))
    return float(prob.loss.smoothness * lmax)


def _init_W(prob: MTLProblem, init: str,
            init_W: jnp.ndarray = None) -> jnp.ndarray:
    if init_W is not None:
        # an explicit (p, m) warm start — the streaming re-solver hands
        # in the previous solve's predictors (DESIGN.md §13); worker-
        # local state, no communication, like the "local" init
        init_W = jnp.asarray(init_W, prob.Xs.dtype)
        if init_W.shape != (prob.p, prob.m):
            raise ValueError(f"init_W shape {init_W.shape} != "
                             f"{(prob.p, prob.m)}")
        return init_W
    if init == "zeros":
        return jnp.zeros((prob.p, prob.m), prob.Xs.dtype)
    if init == "local":
        # Paper §5: "For ProxGD and AccProxGD, we initialized from Local."
        # A worker-local computation (no communication), identical on both
        # backends, so it runs host-side once.
        from .baselines import _local_W
        return _local_W(prob, max(prob.l2, 1e-6))
    raise ValueError(init)


def _sv_carry0(sv, sv_carry):
    """The spectral engine's initial carry: a fresh cold probe, or —
    streaming warm start — the carry a previous solve of the SAME
    (m, rank) geometry finished with (its converged right basis makes
    round one a warm sweep instead of the cold exact fallback)."""
    if sv_carry is None:
        return sv.init_carry()
    cold = sv.init_carry()
    if jax.tree.structure(sv_carry) != jax.tree.structure(cold):
        raise ValueError("sv_carry does not match this solve's spectral "
                         "engine (engine mode or rank differ)")
    return sv_carry


def _grad_columns(rt, prob, Z, data, note):
    """Workers differentiate their local columns of Z; master gathers.

    The worker_ops dispatch receives the runtime so raw-path gradients
    computed on a data shard are pmean-reduced over the data axis
    before the (tasks-axis, charged) gather."""
    Z_local = rt.local_slice(Z)
    G_local = worker_ops.grad_columns(prob.loss, Z_local, data,
                                      prob.l2, rt=rt) / prob.m
    return rt.gather_columns(G_local, note)


@register("proxgd")
def proxgd(prob: MTLProblem, lam: float = 1e-3, rounds: int = 200,
           eta: float = None, init: str = "local", record_every: int = 1,
           runtime=None, scan: bool = True, sv_engine: str = "lazy",
           sv_rank: int = None, batch_size: int = None,
           local_steps: int = None, batch_seed: int = 0, init_W=None,
           sv_carry=None, keep_sv_carry: bool = False,
           metrics: bool = False, **_) -> MTLResult:
    rt = default_runtime(prob, runtime)
    if eta is None:
        eta = 1.0 / data_smoothness(prob)
    m = prob.m
    sv = spectral.shrink_engine(prob, sv_engine, rank=sv_rank)
    sgd = stochastic_config(prob, batch_size, local_steps, rt.data_shards)
    mc = metrics_channel(metrics)

    if sgd is None:
        def body(k, state, data):
            G = _grad_columns(rt, prob, state["W"], data, "gradient column")
            # master prox step (3.3); grad of (1/m)sum L_nj carries 1/m,
            # the per-task smoothness is H/m so the per-W step uses eta*m
            W_new, nn, svc = sv.shrink(state["W"] - eta * m * G,
                                       eta * m * lam, state["sv"])
            out = {"W": rt.broadcast(W_new, "updated predictor"),
                   "sv": svc}
            if metrics:
                out["obs"] = obs_round(state["W"], W_new, grad=G,
                                       objective=lam * nn,
                                       sv_stats=sv.device_stats(svc))
            return out
    else:
        B, L = sgd

        def body(k, state, data):
            # L communication-free local steps on the worker's OWN task
            # columns (arXiv 1802.03830): the nuclear-norm coupling only
            # acts at communication time, so workers descend their local
            # smooth losses between rounds and the master's charged
            # shrinkage is applied to the gathered locally-stepped
            # columns.  The unrolled loop calls no tasks-axis primitive
            # — statically provable communication-freeness.
            Wl = rt.local_slice(state["W"])
            for i in range(L):
                # fused gradient + descent step (worker_ops dispatch:
                # Pallas kernel on TPU, the historical two-dispatch XLA
                # update elsewhere — bit-identical on CPU)
                Wl = worker_ops.minibatch_prox_step_columns(
                    prob.loss, Wl, data, prob.l2, rt=rt, seed=batch_seed,
                    round_k=k, local_step=i, batch_size=B, eta=eta * m,
                    m=m)
            W_gath = rt.gather_columns(Wl, "locally stepped columns")
            W_new, nn, svc = sv.shrink(W_gath, eta * m * lam, state["sv"])
            out = {"W": rt.broadcast(W_new, "updated predictor"),
                   "sv": svc}
            if metrics:
                # no full-batch gradient in a stochastic round
                out["obs"] = obs_round(state["W"], W_new,
                                       objective=lam * nn,
                                       sv_stats=sv.device_stats(svc))
            return out

    state = {"W": _init_W(prob, init, init_W),
             "sv": _sv_carry0(sv, sv_carry)}
    if mc is not None:
        state["obs"] = mc[0]
    res = MTLResult("proxgd", state["W"], rt.comm,
                    extras={"lam": lam, "eta": eta, "sv_engine": sv.mode})
    if sgd is not None:
        res.extras.update(batch_size=sgd[0], local_steps=sgd[1])
    res.record(0, state["W"])
    state = rt.run_rounds(rounds, body, state, scan=scan,
                          record=compose_records(
                              iterate_recorder(res, record_every), mc),
                          data_leaves=gram_round_leaves(prob) if sgd is None
                          else stochastic_round_leaves(prob))
    res.W = state["W"]
    res.extras.update(sv.stats(state["sv"]))
    if mc is not None:
        res.extras["metrics"] = mc[2].finalize(rt)
    if keep_sv_carry:
        res.extras["sv_carry"] = state["sv"]
    return res


@register("accproxgd")
def accproxgd(prob: MTLProblem, lam: float = 1e-3, rounds: int = 200,
              eta: float = None, init: str = "local", record_every: int = 1,
              runtime=None, scan: bool = True, sv_engine: str = "lazy",
              sv_rank: int = None, batch_size: int = None,
              local_steps: int = None, batch_seed: int = 0, init_W=None,
              sv_carry=None, keep_sv_carry: bool = False,
              metrics: bool = False, **_) -> MTLResult:
    rt = default_runtime(prob, runtime)
    if eta is None:
        eta = 1.0 / data_smoothness(prob)
    m = prob.m
    sv = spectral.shrink_engine(prob, sv_engine, rank=sv_rank)
    sgd = stochastic_config(prob, batch_size, local_steps, rt.data_shards)
    mc = metrics_channel(metrics)

    if sgd is None:
        def body(k, state, data):
            W, Z, t = state["W"], state["Z"], state["t"]
            G = _grad_columns(rt, prob, Z, data, "gradient at Z")
            W_new, nn, svc = sv.shrink(Z - eta * m * G, eta * m * lam,
                                       state["sv"])             # (3.4)
            t_new = 0.5 * (1.0 + jnp.sqrt(1.0 + 4.0 * t * t))
            Z_new = W_new + ((t - 1.0) / t_new) * (W_new - W)   # (3.5)
            out = {"W": W_new,
                   "Z": rt.broadcast(Z_new, "updated Z column"),
                   "t": t_new, "sv": svc}
            if metrics:
                out["obs"] = obs_round(W, W_new, grad=G,
                                       objective=lam * nn,
                                       sv_stats=sv.device_stats(svc))
            return out
    else:
        B, L = sgd

        def body(k, state, data):
            # local steps descend the momentum sequence Z's columns
            # (the point (3.4) evaluates the gradient at); the master
            # shrinks the gathered locally-stepped columns and rebuilds
            # the Nesterov extrapolation — 2 charged vectors per round,
            # exactly Table 1.
            W, Z, t = state["W"], state["Z"], state["t"]
            Zl = rt.local_slice(Z)
            for i in range(L):
                Zl = worker_ops.minibatch_prox_step_columns(
                    prob.loss, Zl, data, prob.l2, rt=rt, seed=batch_seed,
                    round_k=k, local_step=i, batch_size=B, eta=eta * m,
                    m=m)
            Z_stepped = rt.gather_columns(Zl, "locally stepped Z columns")
            W_new, nn, svc = sv.shrink(Z_stepped, eta * m * lam,
                                       state["sv"])
            t_new = 0.5 * (1.0 + jnp.sqrt(1.0 + 4.0 * t * t))
            Z_new = W_new + ((t - 1.0) / t_new) * (W_new - W)
            out = {"W": W_new,
                   "Z": rt.broadcast(Z_new, "updated Z column"),
                   "t": t_new, "sv": svc}
            if metrics:
                out["obs"] = obs_round(W, W_new, objective=lam * nn,
                                       sv_stats=sv.device_stats(svc))
            return out

    W0 = _init_W(prob, init, init_W)
    sv0 = _sv_carry0(sv, sv_carry)
    state = {"W": W0, "Z": W0, "t": jnp.array(1.0, W0.dtype), "sv": sv0}
    if mc is not None:
        state["obs"] = mc[0]
    res = MTLResult("accproxgd", state["W"], rt.comm,
                    extras={"lam": lam, "eta": eta, "sv_engine": sv.mode})
    if sgd is not None:
        res.extras.update(batch_size=sgd[0], local_steps=sgd[1])
    res.record(0, state["W"])
    state = rt.run_rounds(rounds, body, state, scan=scan,
                          record=compose_records(
                              iterate_recorder(res, record_every), mc),
                          data_leaves=gram_round_leaves(prob) if sgd is None
                          else stochastic_round_leaves(prob))
    res.W = state["W"]
    res.extras.update(sv.stats(state["sv"]))
    if mc is not None:
        res.extras["metrics"] = mc[2].finalize(rt)
    if keep_sv_carry:
        res.extras["sv_carry"] = state["sv"]
    return res


@register("admm")
def admm(prob: MTLProblem, lam: float = 1e-3, rho: float = 1.0,
         rounds: int = 200, record_every: int = 1, newton_iters: int = 8,
         runtime=None, scan: bool = True, sv_engine: str = "lazy",
         sv_rank: int = None, batch_size: int = None,
         local_steps: int = None, batch_seed: int = 0,
         sv_carry=None, keep_sv_carry: bool = False,
         metrics: bool = False, **_) -> MTLResult:
    """Appendix A. Worker step (A.1) is a regularized ERM:
        w_j+ = argmin_w L_nj(w)/m + <w - z_j, q_j> + rho/2 ||w - z_j||^2.
    Squared loss: closed form (from the Gram cache when present —
    per-round cost independent of n; from data-axis-reduced moments
    under 2-D sharding). Logistic: a few Newton steps (strongly convex
    objective, Newton converges fast), reducing per step across data
    shards.  All of it dispatched by ``worker_ops.prox_columns``.

    Stochastic path (``batch_size``/``local_steps``, DESIGN.md §13):
    the closed-form (A.1) solve is replaced by ``local_steps``
    prox-gradient steps on the SAME augmented Lagrangian, each using a
    seeded mini-batch gradient — an inexact-ADMM worker, still 3
    charged vectors per round.
    """
    rt = default_runtime(prob, runtime)
    loss, m, p = prob.loss, prob.m, prob.p
    sv = spectral.shrink_engine(prob, sv_engine, rank=sv_rank)
    sgd = stochastic_config(prob, batch_size, local_steps, rt.data_shards)
    mc = metrics_channel(metrics)

    if sgd is None:
        def body(k, state, data):
            W_local, Z, Q = state["W"], state["Z"], state["Q"]
            z_loc, q_loc = rt.local_slice(Z), rt.local_slice(Q)
            W_local = worker_ops.prox_columns(loss, data, z_loc, q_loc,
                                              W_local, rho, m, prob.l2,
                                              iters=newton_iters, rt=rt)
            W_full = rt.gather_columns(W_local, "local w")
            Z_new, nn, svc = sv.shrink(W_full + Q / rho, lam / rho,
                                       state["sv"])               # (A.2)
            Q_new = Q + rho * (W_full - Z_new)                    # (A.3)
            out = {"W": W_local,
                   "Z": rt.broadcast(Z_new, "z columns"),
                   "Q": rt.broadcast(Q_new, "q columns"), "sv": svc}
            if metrics:
                # grad slot reports the primal residual W - Z (the
                # gathered W_full is master-visible; local W is sharded)
                out["obs"] = obs_round(Z, Z_new, grad=W_full - Z_new,
                                       objective=lam * nn,
                                       sv_stats=sv.device_stats(svc))
            return out
    else:
        B, L = sgd
        # the augmented Lagrangian's per-column smoothness is the data
        # smoothness of L_nj/m plus the rho-quadratic's curvature
        eta_w = 1.0 / (data_smoothness(prob) / m + rho)

        def body(k, state, data):
            W_local, Z, Q = state["W"], state["Z"], state["Q"]
            z_loc, q_loc = rt.local_slice(Z), rt.local_slice(Q)
            Wl = W_local
            for i in range(L):
                # fused inexact-ADMM worker step on the augmented
                # Lagrangian (gradient + step + residual in one kernel
                # on TPU; the historical XLA ops, same order, on CPU)
                Wl = worker_ops.minibatch_prox_step_columns(
                    loss, Wl, data, prob.l2, rt=rt, seed=batch_seed,
                    round_k=k, local_step=i, batch_size=B, eta=eta_w,
                    m=m, Z_cols=z_loc, Q_cols=q_loc, rho=rho)
            W_full = rt.gather_columns(Wl, "local w")
            Z_new, nn, svc = sv.shrink(W_full + Q / rho, lam / rho,
                                       state["sv"])
            Q_new = Q + rho * (W_full - Z_new)
            out = {"W": Wl,
                   "Z": rt.broadcast(Z_new, "z columns"),
                   "Q": rt.broadcast(Q_new, "q columns"), "sv": svc}
            if metrics:
                out["obs"] = obs_round(Z, Z_new, grad=W_full - Z_new,
                                       objective=lam * nn,
                                       sv_stats=sv.device_stats(svc))
            return out

    W0 = jnp.zeros((p, m), prob.Xs.dtype)
    state = {"W": W0, "Z": W0, "Q": W0, "sv": _sv_carry0(sv, sv_carry)}
    if mc is not None:
        state["obs"] = mc[0]
    res = MTLResult("admm", state["W"], rt.comm,
                    extras={"lam": lam, "rho": rho, "sv_engine": sv.mode})
    if sgd is not None:
        res.extras.update(batch_size=sgd[0], local_steps=sgd[1])
    res.record(0, state["W"])
    # consensus variable Z is the estimator
    state = rt.run_rounds(rounds, body, state, sharded=("W",), scan=scan,
                          record=compose_records(
                              iterate_recorder(res, record_every, key="Z"),
                              mc),
                          data_leaves=gram_round_leaves(prob) if sgd is None
                          else stochastic_round_leaves(prob))
    res.W = state["Z"]
    res.extras.update(sv.stats(state["sv"]))
    if mc is not None:
        res.extras["metrics"] = mc[2].finalize(rt)
    if keep_sv_carry:
        res.extras["sv_carry"] = state["sv"]
    return res


@register("dfw")
def dfw(prob: MTLProblem, radius: float = None, rounds: int = 200,
        record_every: int = 1, sv_iters: int = 60, runtime=None,
        scan: bool = True, metrics: bool = False, **_) -> MTLResult:
    """Appendix B: Frank-Wolfe over {||W||_* <= R}; master only needs the
    leading singular pair of the gradient — the K = 1 case of the
    spectral engine (power iteration, residual-based early exit with
    ``sv_iters`` as the worst-case budget)."""
    rt = default_runtime(prob, runtime)
    if radius is None:
        radius = prob.nuclear_radius
    mc = metrics_channel(metrics)

    def body(k, state, data):
        W = state["W"]
        G = _grad_columns(rt, prob, W, data, "gradient column")
        u, s, v = leading_sv(G, iters=sv_iters)
        gamma = 2.0 / (k.astype(W.dtype) + 2.0)
        # w_j <- (1-gamma) w_j - gamma R v_j u  (B.1)
        W_new = (1.0 - gamma) * W - gamma * radius * jnp.outer(u, v)
        out = {"W": rt.broadcast(W_new, "v_j * u direction")}
        if metrics:
            # constraint form: no regularizer term, no shrink engine
            out["obs"] = obs_round(W, W_new, grad=G)
        return out

    state = {"W": jnp.zeros((prob.p, prob.m), prob.Xs.dtype)}
    if mc is not None:
        state["obs"] = mc[0]
    res = MTLResult("dfw", state["W"], rt.comm, extras={"radius": radius})
    res.record(0, state["W"])
    state = rt.run_rounds(rounds, body, state, scan=scan,
                          record=compose_records(
                              iterate_recorder(res, record_every), mc),
                          data_leaves=gram_round_leaves(prob))
    res.W = state["W"]
    if mc is not None:
        res.extras["metrics"] = mc[2].finalize(rt)
    return res
