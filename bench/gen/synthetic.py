"""The §5 simulation of arXiv:1603.02185, as the benchmark makes its data.

A copy of ``repro.data.synthetic.generate`` (with ``sample_chunks``), kept
here so that the benchmark's inputs do not move when the program does:

  * W* = U S V^T, U, V the top-r singular vectors of A B^T (A: p x r,
    B: m x r, standard normal), diag(S) = [1, 1/1.5, 1/1.5^2, ...];
  * x ~ N(0, Sigma), Sigma_ab = 2^{-c |a-b|};
  * regression: y = <w*_j, x> + noise * N(0, 1); classification:
    y ~ Bernoulli(sigmoid(<w*_j, x>)) in {-1, +1}.

The program takes W*'s factors from its own spectral engine; here they
come from ``jnp.linalg.svd``.  The two agree up to rounding (W* is the
sign-invariant product U diag(s) V^T); ``tests/test_copies.py`` pins it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def make_wstar(key, p: int, m: int, r: int, dtype=jnp.float32):
    ka, kb = jax.random.split(key)
    A = jax.random.normal(ka, (p, r), dtype)
    B = jax.random.normal(kb, (m, r), dtype)
    U, _, Vt = jnp.linalg.svd(A @ B.T, full_matrices=False)
    s = (1.0 / 1.5) ** jnp.arange(r, dtype=dtype)
    return (U[:, :r] * s[None, :]) @ Vt[:r, :]


def feature_cov(p: int, corr_decay: float, dtype=jnp.float32):
    idx = jnp.arange(p)
    return (2.0 ** (-corr_decay * jnp.abs(idx[:, None] - idx[None, :]))
            ).astype(dtype)


def _features(key, m: int, n: int, chol, chunks: int):
    p = chol.shape[0]
    if chunks == 1:
        return jax.random.normal(key, (m, n, p), chol.dtype) @ chol.T
    if n % chunks:
        raise ValueError(f"n={n} not divisible by sample_chunks={chunks}")
    parts = [jax.random.normal(k, (m, n // chunks, p), chol.dtype) @ chol.T
             for k in jax.random.split(key, chunks)]
    return jnp.concatenate(parts, axis=1)


def generate(key, *, p: int, m: int, r: int, n: int, corr_decay: float = 1.0,
             task: str = "regression", noise: float = 1.0,
             sample_chunks: int = 1):
    """(Xs (m, n, p), ys (m, n), W* (p, m), Sigma (p, p))."""
    kw, kx, ky = jax.random.split(key, 3)
    Wstar = make_wstar(kw, p, m, r)
    Sigma = feature_cov(p, corr_decay)
    chol = jnp.linalg.cholesky(Sigma + 1e-9 * jnp.eye(p))
    Xs = _features(kx, m, n, chol, sample_chunks)
    margins = jnp.einsum("mnp,pm->mn", Xs, Wstar)
    if task == "regression":
        ys = margins + noise * jax.random.normal(ky, margins.shape)
    elif task == "classification":
        ys = jnp.where(jax.random.uniform(ky, margins.shape)
                       < jax.nn.sigmoid(margins), 1.0, -1.0)
    else:
        raise ValueError(task)
    return Xs, ys, Wstar, Sigma
