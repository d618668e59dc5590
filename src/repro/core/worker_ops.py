"""Per-loss dispatch for the worker hot path.

Every round of every gradient-based solver has each worker evaluate
per-task quantities of its local data — the gradient column
``(1/n) X_j^T l'(X_j w_j)`` above all.  This module picks the cheapest
correct implementation per loss and per backend:

* ``gram``   — squared loss with cached per-task Gram statistics
               ``A_j = X_j^T X_j / n``, ``b_j = X_j^T y_j / n``
               (computed ONCE at :meth:`MTLProblem.make`): the gradient
               is ``A_j w_j - b_j``, the Hessian is ``A_j`` — per-round
               cost independent of ``n`` and no HBM traffic over the raw
               ``(n, p)`` designs.
* ``pallas`` — the fused :mod:`repro.kernels.mtl_grad` TPU kernel for
               the raw path (logistic, or squared without Gram cache):
               one streaming pass over ``X_j``, residuals never
               round-trip to HBM.
* ``xla``    — the reference vmap over :mod:`repro.core.linear_model`,
               the CPU fallback and the oracle the other two are tested
               against (``tests/test_kernels.py``).

Every function takes the worker-local ``data`` dict the runtime binds
into the round body (``Xs``/``ys`` plus ``gram_A``/``gram_b`` when
cached), so the same call works inside vmap (sim) and shard_map (mesh).

Data-axis sharding (DESIGN.md §8).  Under a 2-D ``("tasks", "data")``
runtime the ``Xs``/``ys`` leaves hold only ``n / data_shards`` rows per
task.  Pass the runtime as ``rt=`` and every raw-path sample statistic
is reduced over the data axis (``rt.pmean_data`` — identity when
``data_shards == 1``, a real collective on the 2-D mesh): gradients and
Hessians are averaged across shards before any solve, iterative refits
reduce once per Newton/gradient step, and the Pallas kernel's per-shard
output is reduced exactly like the XLA reference's.  The Gram path
needs no reduction — the 2-D runtime rebuilds the cache as a psum of
per-shard partial Grams before the round loop, so ``gram_A``/``gram_b``
are already global.  ``rt=None`` keeps the historical single-shard
behaviour bit-for-bit.

Each per-round entry point runs under the ``worker`` named scope, as the
runtime's ``worker_map`` does, so the lowered round program's op names
tell the worker's ops from the master's.  The products that read the
Gram cache run under ``gram_grad`` inside it.

Precision.  Every product that builds or reads the Gram cache runs at
full float32: the per-round ``A_j w_j`` as a multiply-and-reduce, the
others at ``GRAM_PRECISION``.  On a TPU the default float32 matmul
rounds its operands to bfloat16; the cached path would then compute in
a lower precision than the raw path's float32 kernel, and its W drifts
from a float32 reference by ~1e-3 (relative) after 50 proxgd rounds at
p=2048.  On the CPU the precision setting changes nothing.  A solve
(``jnp.linalg.solve``) takes its products at HIGHEST on its own.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from . import linear_model as lm
from .losses import Loss


# The matmul precision of every product on the Gram cache.
GRAM_PRECISION = jax.lax.Precision.HIGHEST


def _worker_step(fn):
    """Trace ``fn`` under the ``worker`` named scope."""
    @functools.wraps(fn)
    def scoped(*args, **kwargs):
        with jax.named_scope("worker"):
            return fn(*args, **kwargs)
    return scoped


def gram_stats(Xs: jnp.ndarray, ys: jnp.ndarray, data_shards: int = 1
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-task sufficient statistics for the squared loss.

    Xs: (m, n, p); ys: (m, n)  ->  A (m, p, p), b (m, p) with
    A_j = X_j^T X_j / n and b_j = X_j^T y_j / n.

    ``data_shards > 1`` computes the SAME statistics as a sum of
    per-shard partial Grams over contiguous row blocks of n — the
    reduction order of the 2-D runtime's psum (DESIGN.md §8), which
    agrees with the monolithic order only to float rounding.  Used by
    ``SimRuntime``'s 2-D emulation so sim and mesh shard identically.
    """
    m, n, p = Xs.shape
    ein = functools.partial(jnp.einsum, precision=GRAM_PRECISION)
    if data_shards == 1:
        A = ein("jni,jnk->jik", Xs, Xs) / n
        b = ein("jni,jn->ji", Xs, ys) / n
        return A, b
    if n % data_shards:
        raise ValueError(f"n={n} not divisible by data_shards={data_shards}")
    Xr = Xs.reshape(m, data_shards, n // data_shards, p)
    yr = ys.reshape(m, data_shards, n // data_shards)
    A = (ein("jsni,jsnk->jsik", Xr, Xr) / n).sum(axis=1)
    b = (ein("jsni,jsn->jsi", Xr, yr) / n).sum(axis=1)
    return A, b


def has_gram(data: Dict[str, jnp.ndarray]) -> bool:
    return "gram_A" in data


def _sharded(rt) -> bool:
    return rt is not None and rt.data_shards > 1


def _pmean(rt, x, note, repeats: int = 1):
    """Average ``x`` over the data axis; identity off the 2-D runtimes."""
    return rt.pmean_data(x, note, repeats=repeats) if _sharded(rt) else x


def _moments(rt, Xs, ys, note) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-task second moments of (possibly data-sharded) rows:
    A (L, d, d) = X^T X / n, b (L, d) = X^T y / n — each shard's einsum
    over its local rows, pmean-reduced over the data axis (identity,
    with local n == global n, off the 2-D runtimes).  The ONE reduction
    convention every closed-form sharded solve goes through."""
    n_loc = Xs.shape[1]
    A = _pmean(rt, jnp.einsum("jni,jnk->jik", Xs, Xs) / n_loc,
               note + " gram shards")
    b = _pmean(rt, jnp.einsum("jni,jn->ji", Xs, ys) / n_loc,
               note + " Xty shards")
    return A, b


def _grad_hess(loss: Loss, W_cols: jnp.ndarray, Xs: jnp.ndarray,
               ys: jnp.ndarray, l2: float
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Stacked per-task gradient (d, L) and Hessian (L, d, d) over the
    local rows — the pair every Newton-style sharded path pmean-reduces
    before its solve."""
    g = jax.vmap(lambda w, X, y: lm.task_grad(loss, w, X, y, l2),
                 in_axes=(1, 0, 0), out_axes=1)(W_cols, Xs, ys)
    H = jax.vmap(lambda w, X, y: lm.task_hessian(loss, w, X, y, l2),
                 in_axes=(1, 0, 0), out_axes=0)(W_cols, Xs, ys)
    return g, H


def _resolve_impl(loss: Loss, data: Dict[str, jnp.ndarray],
                  impl: Optional[str]) -> str:
    if impl is not None:
        return impl
    if loss.name == "squared" and has_gram(data):
        return "gram"
    if jax.default_backend() == "tpu" and loss.name in ("squared",
                                                        "logistic"):
        return "pallas"
    return "xla"


@_worker_step
def grad_columns(loss: Loss, W_cols: jnp.ndarray,
                 data: Dict[str, jnp.ndarray], l2: float = 0.0,
                 impl: Optional[str] = None, rt=None) -> jnp.ndarray:
    """Per-task gradient columns ``grad L_nj(w_j)``: (p, L) from (p, L).

    Callers apply the global objective's 1/m factor themselves (the
    convention of :mod:`repro.core.linear_model`).  ``impl`` forces a
    raw-path implementation ("gram" | "pallas" | "xla"); by default the
    cheapest correct one is picked at trace time.  With ``rt=`` a 2-D
    runtime, the raw paths (Pallas kernel included) compute on this
    shard's rows and are ``pmean``-reduced over the data axis.
    """
    impl = _resolve_impl(loss, data, impl)
    if impl == "gram":
        # A_j w_j as a float32 multiply-and-reduce: one pass over the
        # cache on the vector unit.  On a v5e (192 tasks, p=2048) it
        # reaches 91.9% of its HBM roofline, an einsum at HIGHEST 90.0%.
        with jax.named_scope("gram_grad"):
            AW = jnp.sum(data["gram_A"] * W_cols.T[:, None, :], axis=2)
            G = (AW - data["gram_b"]).T
    elif impl == "pallas":
        from ..kernels.mtl_grad import task_gradients
        G = task_gradients(data["Xs"], data["ys"], W_cols.T,
                           loss=loss.name).T.astype(W_cols.dtype)
        G = _pmean(rt, G, "gradient shards")
    elif impl == "xla":
        G = jax.vmap(lambda w, X, y: lm.task_grad(loss, w, X, y),
                     in_axes=(1, 0, 0), out_axes=1)(
            W_cols, data["Xs"], data["ys"])
        G = _pmean(rt, G, "gradient shards")
    else:
        raise ValueError(f"unknown gradient impl {impl!r}; "
                         "have 'gram', 'pallas', 'xla'")
    if l2:
        G = G + l2 * W_cols
    return G


# ---------------------------------------------------------------------------
# stochastic worker path (DESIGN.md §13): a seeded, device-resident
# batch sampler + the mini-batch gradient/Newton messages built on it
# ---------------------------------------------------------------------------
def batch_indices(seed: int, task_ids: jnp.ndarray, round_k, local_step,
                  batch_size: int, n_local: int, shard=0) -> jnp.ndarray:
    """Per-task mini-batch row indices ``(L, batch_size)`` into this
    shard's ``n_local`` local rows.

    Deterministic by construction: each task's key is a fold_in chain
    over ``(seed, global task id, round, local step, data-shard
    index)`` — no carried RNG state rides in the solver loop, so the
    draw is identical across backends, drivers and layouts (sim and
    mesh fold the same global ids; a 1-D layout folds shard 0, a 2-D
    layout folds each shard's index over the same named axis).

    ``batch_size == n_local`` returns ``arange(n_local)`` — the natural
    row order, so the degenerate mini-batch touches exactly the rows of
    the full-batch raw path in the same order and its gradient is
    bit-identical to ``grad_columns``'s (the anchor of the degeneracy
    rule; property-tested).  Smaller batches sample WITH replacement
    (the unbiased-SGD convention of arXiv 1802.03830).
    """
    B, n_local = int(batch_size), int(n_local)
    L = task_ids.shape[0]
    if B == n_local:
        return jnp.broadcast_to(jnp.arange(n_local, dtype=jnp.int32),
                                (L, n_local))

    def one(tid):
        key = jax.random.fold_in(jax.random.PRNGKey(seed), tid)
        key = jax.random.fold_in(key, round_k)
        key = jax.random.fold_in(key, local_step)
        key = jax.random.fold_in(key, shard)
        return jax.random.randint(key, (B,), 0, n_local, dtype=jnp.int32)

    return jax.vmap(one)(task_ids)


def _sample_batch(data: Dict[str, jnp.ndarray], rt, seed: int, round_k,
                  local_step, batch_size: int
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Gather one seeded mini-batch ``(Xb (L, B_loc, p), yb (L, B_loc))``
    from the worker-local rows.  ``batch_size`` is the GLOBAL per-task
    batch; each data shard draws ``batch_size / data_shards`` of its
    local rows under its own folded shard index."""
    Xs, ys = data["Xs"], data["ys"]
    D = rt.data_shards if rt is not None else 1
    idx = batch_indices(seed, data["task_ids"], round_k, local_step,
                        batch_size // D, Xs.shape[1],
                        shard=rt.data_index() if rt is not None else 0)
    Xb = jax.vmap(lambda X, i: X[i])(Xs, idx)
    yb = jax.vmap(lambda y, i: y[i])(ys, idx)
    return Xb, yb


@_worker_step
def minibatch_grad_columns(loss: Loss, W_cols: jnp.ndarray,
                           data: Dict[str, jnp.ndarray], l2: float = 0.0,
                           rt=None, *, seed: int, round_k, local_step,
                           batch_size: int) -> jnp.ndarray:
    """Per-task MINI-BATCH gradient columns (p, L): ``grad_columns`` on
    a seeded batch of sampled rows instead of the full local data.

    Communication-free along the tasks axis by construction — the body
    of a local step calls no runtime primitive there (the static
    verifier proves it on the traced program); under a 2-D layout the
    per-shard batch gradients pmean-reduce over the data axis exactly
    like the full-batch raw path.  Callers apply the global 1/m factor
    themselves, as with ``grad_columns``.
    """
    Xb, yb = _sample_batch(data, rt, seed, round_k, local_step, batch_size)
    G = jax.vmap(lambda w, X, y: lm.task_grad(loss, w, X, y),
                 in_axes=(1, 0, 0), out_axes=1)(W_cols, Xb, yb)
    G = _pmean(rt, G, "minibatch gradient shards")
    if l2:
        G = G + l2 * W_cols
    return G


def _resolve_step_impl(loss: Loss, impl: Optional[str]) -> str:
    """The fused prox step has no Gram path (it runs on sampled rows),
    so the choice is Pallas-on-TPU vs the XLA reference."""
    if impl is not None:
        return impl
    if jax.default_backend() == "tpu" and loss.name in ("squared",
                                                        "logistic"):
        return "pallas"
    return "xla"


@_worker_step
def minibatch_prox_step_columns(loss: Loss, W_cols: jnp.ndarray,
                                data: Dict[str, jnp.ndarray],
                                l2: float = 0.0, rt=None, *, seed: int,
                                round_k, local_step, batch_size: int,
                                eta, m: int, Z_cols=None, Q_cols=None,
                                rho=0.0, impl: Optional[str] = None
                                ) -> jnp.ndarray:
    """One fused prox-family local step on a seeded mini-batch:

        W <- W - eta (G/m + Q + rho (W - Z)),   G the mini-batch
                                                 gradient (+ l2 W)

    — the inner update of the stochastic ProxGD / AccProxGD / ADMM
    round bodies.  ``Q_cols=None`` is the plain-descent special case
    (ProxGD/AccProxGD pass ``eta * m`` so the 1/m cancels; the rho/Z
    terms are skipped STRUCTURALLY, not multiplied by zero, keeping the
    XLA path bit-identical to the historical two-dispatch update).

    * ``xla``    — ``minibatch_grad_columns`` followed by the step:
                   exactly the ops the solver bodies used to inline,
                   in the same order (the CPU/verification path).
    * ``pallas`` — :mod:`repro.kernels.prox_step`: gradient and step in
                   one kernel, the (L, p) gradient never leaves VMEM.

    Under 2-D sharding the Pallas path pmean-reduces the STEPPED
    columns instead of the gradient — the update is affine in G with
    W/Z/Q replicated across the data axis, so the average commutes,
    the payload shape (p, L) is unchanged, and the CommLog ledger
    entry is identical to the XLA path's (DESIGN.md §14).
    """
    impl = _resolve_step_impl(loss, impl)
    if impl == "xla":
        G = minibatch_grad_columns(loss, W_cols, data, l2, rt=rt,
                                   seed=seed, round_k=round_k,
                                   local_step=local_step,
                                   batch_size=batch_size)
        if Q_cols is None:
            return W_cols - eta * (G / m)
        return W_cols - eta * (G / m + Q_cols + rho * (W_cols - Z_cols))
    if impl != "pallas":
        raise ValueError(f"unknown prox step impl {impl!r}; "
                         "have 'pallas', 'xla'")
    from ..kernels.prox_step import prox_step as fused_prox
    Xb, yb = _sample_batch(data, rt, seed, round_k, local_step, batch_size)
    Z = W_cols if Z_cols is None else Z_cols
    Q = jnp.zeros_like(W_cols) if Q_cols is None else Q_cols
    W_new = fused_prox(Xb, yb, W_cols.T, Z.T, Q.T, eta=eta, rho=rho,
                       inv_m=1.0 / m, l2=l2,
                       loss=loss.name).T.astype(W_cols.dtype)
    return _pmean(rt, W_new, "minibatch gradient shards")


@_worker_step
def minibatch_newton_columns(loss: Loss, W_cols: jnp.ndarray,
                             data: Dict[str, jnp.ndarray], l2: float = 0.0,
                             damping: float = 1e-6, rt=None, *, seed: int,
                             round_k, local_step, batch_size: int
                             ) -> jnp.ndarray:
    """DNSP's stochastic worker messages: the Newton direction of the
    MINI-BATCH objective — gradient and Hessian both evaluated on the
    same seeded batch (each pmean-reduced over the data axis before the
    solve under 2-D, mirroring ``newton_columns``'s raw path)."""
    Xb, yb = _sample_batch(data, rt, seed, round_k, local_step, batch_size)
    p = W_cols.shape[0]
    eye = jnp.eye(p, dtype=W_cols.dtype)
    g, H = _grad_hess(loss, W_cols, Xb, yb, l2)
    g = _pmean(rt, g, "minibatch newton grad shards")
    H = _pmean(rt, H, "minibatch newton hess shards")
    return jax.vmap(lambda Hj, gj: jnp.linalg.solve(Hj + damping * eye, gj),
                    in_axes=(0, 1), out_axes=1)(H, g)


@_worker_step
def newton_columns(loss: Loss, W_cols: jnp.ndarray,
                   data: Dict[str, jnp.ndarray], l2: float = 0.0,
                   damping: float = 1e-6, rt=None) -> jnp.ndarray:
    """DNSP worker messages ``(hess L_nj)^-1 grad L_nj``: (p, L).

    Squared loss with Gram cache: Hessian IS ``A_j`` — one (p, p) solve
    per task, no pass over the raw data.  Raw path under a 2-D runtime:
    per-shard gradients and Hessians are ``pmean``-reduced over the
    data axis BEFORE the solve (the Newton direction is nonlinear in
    the data, so the reduction cannot commute past it).
    """
    if loss.name == "squared" and has_gram(data):
        p = W_cols.shape[0]
        eye = jnp.eye(p, dtype=W_cols.dtype)

        def one(A, b, w):
            g = jnp.matmul(A, w, precision=GRAM_PRECISION) - b + l2 * w
            return jnp.linalg.solve(A + (l2 + damping) * eye, g)

        with jax.named_scope("gram_grad"):
            return jax.vmap(one, in_axes=(0, 0, 1), out_axes=1)(
                data["gram_A"], data["gram_b"], W_cols)
    if _sharded(rt):
        p = W_cols.shape[0]
        eye = jnp.eye(p, dtype=W_cols.dtype)
        g, H = _grad_hess(loss, W_cols, data["Xs"], data["ys"], l2)
        g = rt.pmean_data(g, "newton grad shards")
        H = rt.pmean_data(H, "newton hess shards")
        return jax.vmap(lambda Hj, gj: jnp.linalg.solve(Hj + damping * eye,
                                                        gj),
                        in_axes=(0, 1), out_axes=1)(H, g)
    return jax.vmap(
        lambda w, X, y: lm.newton_direction(loss, w, X, y, l2, damping),
        in_axes=(1, 0, 0), out_axes=1)(W_cols, data["Xs"], data["ys"])


@_worker_step
def ridge_columns(data: Dict[str, jnp.ndarray], l2: float) -> jnp.ndarray:
    """Per-task ridge solutions (p, L) from the Gram cache (squared loss).

    The Local baseline / proxgd "local" init without an O(n p^2) refit
    per solve.  Requires ``gram_A``/``gram_b`` in ``data`` (already
    global under 2-D sharding — the runtime psums the cache).
    """
    A, b = data["gram_A"], data["gram_b"]
    p = A.shape[-1]
    eye = jnp.eye(p, dtype=A.dtype)
    return jax.vmap(lambda Aj, bj: jnp.linalg.solve(Aj + l2 * eye, bj),
                    in_axes=(0, 0), out_axes=1)(A, b)


def _newton_cols(loss: Loss, Xs: jnp.ndarray, ys: jnp.ndarray, l2: float,
                 iters: int, rt, damping: float = 1e-8) -> jnp.ndarray:
    """Stacked damped-Newton ERM over (possibly data-sharded) rows.

    Xs: (L, n_loc, d); ys: (L, n_loc) -> V (d, L).  The data-axis
    reduction happens once per Newton step (two pmeans: gradient +
    Hessian), charged with ``repeats=iters`` since the loop body is
    traced once.
    """
    L, _, d = Xs.shape
    eye = jnp.eye(d, dtype=Xs.dtype)

    def body(_, V):
        g, H = _grad_hess(loss, V, Xs, ys, l2)
        g = _pmean(rt, g, "erm newton grad", repeats=iters)
        H = _pmean(rt, H, "erm newton hess", repeats=iters)
        step = jax.vmap(
            lambda Hj, gj: jnp.linalg.solve(Hj + damping * eye, gj),
            in_axes=(0, 1), out_axes=1)(H, g)
        return V - step

    return jax.lax.fori_loop(0, iters, body, jnp.zeros((d, L), Xs.dtype))


@_worker_step
def erm_columns(loss: Loss, data: Dict[str, jnp.ndarray], l2: float,
                rt=None, iters: int = 25) -> jnp.ndarray:
    """Per-task unconstrained ERM solutions (p, L) — the Local baseline's
    worker computation, dispatched like the gradients:

    * Gram cache present: one (p, p) ridge solve per task.
    * squared, raw: closed form from (data-axis-reduced) moments.
    * smooth non-quadratic: damped Newton, reducing per step under 2-D.
    """
    if loss.name == "squared" and has_gram(data):
        return ridge_columns(data, l2)
    Xs, ys = data["Xs"], data["ys"]
    if not _sharded(rt):
        return jax.vmap(lambda X, y: lm.erm(loss, X, y, l2, iters),
                        in_axes=(0, 0), out_axes=1)(Xs, ys)
    if loss.name == "squared":
        A, b = _moments(rt, Xs, ys, "erm")
        p = A.shape[-1]
        eye = jnp.eye(p, dtype=A.dtype)
        return jax.vmap(lambda Aj, bj: jnp.linalg.solve(Aj + l2 * eye, bj),
                        in_axes=(0, 0), out_axes=1)(A, b)
    return _newton_cols(loss, Xs, ys, l2, iters, rt)


@_worker_step
def prox_columns(loss: Loss, data: Dict[str, jnp.ndarray],
                 Z_cols: jnp.ndarray, Q_cols: jnp.ndarray,
                 W0_cols: jnp.ndarray, rho: float, m: int, l2: float = 0.0,
                 iters: int = 8, rt=None) -> jnp.ndarray:
    """The ADMM worker step (Appendix A.1), per task:

        w_j+ = argmin_w  L_nj(w)/m + <w - z_j, q_j> + rho/2 ||w - z_j||^2

    Z_cols/Q_cols/W0_cols: (p, L) -> (p, L).  Squared loss: closed form
    (from the Gram cache when present; otherwise from raw — or
    data-axis-reduced — moments).  Smooth non-quadratic losses: a few
    damped Newton steps on the strongly convex subproblem, reducing the
    data-dependent gradient/Hessian across shards per step under 2-D.
    """
    p = Z_cols.shape[0]
    eye = jnp.eye(p, dtype=Z_cols.dtype)
    if loss.name == "squared":
        if has_gram(data):
            A, b = data["gram_A"], data["gram_b"]
        else:
            A, b = _moments(rt, data["Xs"], data["ys"], "prox")

        def one(Aj, bj, z, q):
            Amat = Aj / m + (rho + l2 / m) * eye
            return jnp.linalg.solve(Amat, bj / m + rho * z - q)

        return jax.vmap(one, in_axes=(0, 0, 1, 1), out_axes=1)(
            A, b, Z_cols, Q_cols)

    Xs, ys = data["Xs"], data["ys"]

    def newton(_, W):
        g, H = _grad_hess(loss, W, Xs, ys, l2)
        g = _pmean(rt, g, "prox newton grad", repeats=iters)
        H = _pmean(rt, H, "prox newton hess", repeats=iters)
        g = g / m + Q_cols + rho * (W - Z_cols)
        step = jax.vmap(
            lambda Hj, gj: jnp.linalg.solve(Hj / m + rho * eye, gj),
            in_axes=(0, 1), out_axes=1)(H, g)
        return W - step

    return jax.lax.fori_loop(0, iters, newton, W0_cols)


@_worker_step
def projected_solves(loss: Loss, U: jnp.ndarray,
                     data: Dict[str, jnp.ndarray], l2: float = 0.0,
                     iters: int = 25, rt=None
                     ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The DGSP/DNSP/AltMin re-fit ``v_j = argmin_v L_nj(U v)``.

    Returns (W_cols (p, L), V (k, L)) with ``W = U V``.  Squared loss
    with Gram cache: the projected normal equations are
    ``U^T A_j U v = U^T b_j`` — cost k^2 p per task instead of n p k.
    Raw paths under a 2-D runtime project the LOCAL rows (``X_j U`` on
    the shard) and reduce the k-dimensional normal equations — or each
    Newton step for non-quadratic losses — over the data axis.
    """
    if loss.name == "squared" and has_gram(data):
        k = U.shape[1]
        eye = jnp.eye(k, dtype=U.dtype)

        mm = functools.partial(jnp.matmul, precision=GRAM_PRECISION)

        def one(A, b):
            Ak = mm(U.T, mm(A, U)) + max(l2, 1e-9) * eye
            return jnp.linalg.solve(Ak, mm(U.T, b))

        with jax.named_scope("gram_grad"):
            V = jax.vmap(one, in_axes=(0, 0), out_axes=1)(
                data["gram_A"], data["gram_b"])
            return mm(U, V), V

    if _sharded(rt):
        Xs, ys = data["Xs"], data["ys"]
        XU = jax.vmap(lambda X: X @ U)(Xs)          # (L, n_loc, k)
        k = U.shape[1]
        if loss.name == "squared":
            Ak, bk = _moments(rt, XU, ys, "projected")
            eye = jnp.eye(k, dtype=U.dtype)
            V = jax.vmap(lambda Aj, bj: jnp.linalg.solve(
                Aj + max(l2, 1e-9) * eye, bj),
                in_axes=(0, 0), out_axes=1)(Ak, bk)
        else:
            V = _newton_cols(loss, XU, ys, max(l2, 1e-9), iters, rt)
        return U @ V, V

    def one(X, y):
        return lm.projected_erm(loss, U, X, y, l2, iters)

    W, V = jax.vmap(one, in_axes=(0, 0), out_axes=(1, 1))(
        data["Xs"], data["ys"])
    return W, V
