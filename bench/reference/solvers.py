"""Plain references of the solvers the solve cells time.

``proxgd``: Algorithm 4 of arXiv:1603.02185 with the repo's step-size rule
(eta = 1 / (H max_j lambda_max(X_j^T X_j / n)), 50 power steps from
ones / sqrt(p)) and an exact-SVD singular-value shrinkage each round.

``dgsp``: Algorithm 1 with the repo's master (the leading left singular
vector of the gathered gradient by power iteration on G^T G: a fixed probe,
at most ``sv_iters`` steps, exit at residual <= 1e-6 lambda) and the exact
projected refit v_j = argmin_v L_nj(U v) by 25 damped Newton steps.

Both take W from zeros, the global objective (1/m) sum_j L_nj(w_j) with
L_nj(w) = (1/n) sum_i l(<w, x_ji>, y_ji).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

_TINY = 1e-30
SMOOTHNESS = {"squared": 1.0, "logistic": 0.25}


def _d1(loss, a, y):
    if loss == "squared":
        return a - y
    return -y * jax.nn.sigmoid(-y * a)


def _d2(loss, a, y):
    if loss == "squared":
        return jnp.ones_like(a)
    s = jax.nn.sigmoid(y * a)
    return s * (1.0 - s)


def _grad(loss, X, y, W, dt):
    """(p, m) columns (1/n) X_j^T l'(X_j w_j, y_j), computed in ``dt``."""
    n = X.shape[1]
    pred = jnp.einsum("mnp,pm->mn", X, W).astype(dt)
    r = _d1(loss, pred, y).astype(dt)
    return (jnp.einsum("mnp,mn->pm", X, r) / n).astype(dt)


def _lambda_max(X, dt, iters: int = 50):
    """max_j of the top eigenvalue of X_j^T X_j / n (power iteration)."""
    n, p = X.shape[1], X.shape[2]

    def one(Xj):
        def body(_, v):
            w = (Xj.T @ (Xj @ v) / n).astype(dt)
            return (w / jnp.maximum(jnp.linalg.norm(w), _TINY)).astype(dt)
        v = jax.lax.fori_loop(0, iters, body,
                              (jnp.ones((p,), dt) / jnp.sqrt(p)).astype(dt))
        return v @ (Xj.T @ (Xj @ v)) / n

    return jnp.max(jax.vmap(one)(X)).astype(jnp.float32)


@partial(jax.jit, static_argnames=("loss", "rounds", "dt"))
def _proxgd(X, y, eta, *, loss, rounds, lam, dt):
    m = X.shape[0]
    X, y = X.astype(dt), y.astype(dt)
    if eta is None:
        eta = 1.0 / (SMOOTHNESS[loss] * _lambda_max(X, dt))
    tau = eta * m * lam

    def body(_, W):
        G = _grad(loss, X, y, W, dt) / m
        M = (W - (eta * m) * G).astype(jnp.float32)
        U, S, Vt = jnp.linalg.svd(M, full_matrices=False)
        return ((U * jnp.maximum(S - tau, 0.0)[None, :]) @ Vt).astype(dt)

    W0 = jnp.zeros((X.shape[2], m), dt)
    return jax.lax.fori_loop(0, rounds, body, W0).astype(jnp.float32)


def proxgd(X, y, *, loss: str, rounds: int, lam: float, eta=None,
           dtype=jnp.float32):
    """W (p, m) after ``rounds`` rounds of proxgd from zeros; ``eta`` None
    takes the step-size rule above."""
    with jax.default_matmul_precision("highest"):
        return _proxgd(X, y, eta, loss=loss, rounds=int(rounds),
                       lam=float(lam), dt=jnp.dtype(dtype))


def lambda_max(X):
    """H-free step-size denominator max_j lambda_max(X_j^T X_j / n), f32."""
    with jax.default_matmul_precision("highest"):
        return jax.jit(_lambda_max, static_argnames=("dt",))(
            X, dt=jnp.dtype(jnp.float32))


def _leading_u(G, iters: int, tol: float = 1e-6):
    """Leading left singular vector of G (p, m), sign fixed by sum(u) >= 0."""
    m = G.shape[1]
    probe = (1.0 + 0.1 * jnp.cos(jnp.arange(m, dtype=G.dtype))) / jnp.sqrt(m)
    v0 = G.T @ (G @ probe) + 1e-12 * probe
    v0 = v0 / jnp.maximum(jnp.linalg.norm(v0), _TINY)

    def cond(st):
        i, _, done = st
        return (i < iters) & jnp.logical_not(done)

    def body(st):
        i, v, _ = st
        w = G.T @ (G @ v)
        lam = w @ v
        done = jnp.linalg.norm(w - lam * v) <= tol * jnp.maximum(lam, _TINY)
        return i + 1, w / jnp.maximum(jnp.linalg.norm(w), _TINY), done

    _, v, _ = jax.lax.while_loop(cond, body,
                                 (jnp.int32(0), v0, jnp.zeros((), bool)))
    u = G @ v
    u = u / jnp.maximum(jnp.linalg.norm(u), _TINY)
    return u * jnp.where(jnp.sum(u) >= 0, 1.0, -1.0).astype(G.dtype)


def _refit(loss, XU, y, l2, iters: int = 25, damping: float = 1e-8):
    """argmin_v (1/n) sum_i l(<XU_i, v>, y_i) + l2/2 |v|^2, damped Newton."""
    n, k = XU.shape
    eye = jnp.eye(k, dtype=XU.dtype)

    def body(_, v):
        a = XU @ v
        g = XU.T @ _d1(loss, a, y) / n + l2 * v
        H = (XU * _d2(loss, a, y)[:, None]).T @ XU / n + (l2 + damping) * eye
        return v - jnp.linalg.solve(H.astype(jnp.float32),
                                    g.astype(jnp.float32)).astype(XU.dtype)

    return jax.lax.fori_loop(0, iters, body, jnp.zeros((k,), XU.dtype))


@partial(jax.jit, static_argnames=("loss", "rounds", "sv_iters", "dt"))
def _dgsp(X, y, *, loss, rounds, sv_iters, dt):
    m, _, p = X.shape
    X, y = X.astype(dt), y.astype(dt)

    def body(k, st):
        U, mask, W = st
        G = _grad(loss, X, y, W, dt) / m
        u = _leading_u(G, sv_iters).astype(dt)
        U = U.at[:, k].set(u)
        mask = mask.at[k].set(1.0)
        Um = U * mask[None, :]
        V = jax.vmap(lambda Xj, yj: _refit(loss, Xj @ Um, yj, 1e-9))(X, y)
        return U, mask, (Um @ V.T).astype(dt)

    st = (jnp.zeros((p, rounds), dt), jnp.zeros((rounds,), dt),
          jnp.zeros((p, m), dt))
    return jax.lax.fori_loop(0, rounds, body, st)[2].astype(jnp.float32)


def dgsp(X, y, *, loss: str, rounds: int, sv_iters: int = 60,
         dtype=jnp.float32):
    """W (p, m) after ``rounds`` rounds of dgsp from zeros."""
    with jax.default_matmul_precision("highest"):
        return _dgsp(X, y, loss=loss, rounds=int(rounds),
                     sv_iters=int(sv_iters), dt=jnp.dtype(dtype))


REFERENCES = {"proxgd": proxgd, "dgsp": dgsp}
