"""Factors of a served model, made from the seed.

A copy of ``benchmarks/serve_bench.py``'s ``_synthetic_model`` seeding: an
orthonormal basis U (p, r) from the QR of a normal draw, per-task right
factors V (m, r) normal over sqrt(r), and the spectrum s = linspace(2, 1, r).
The served code of task j is c_j = s * V[j].
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def serve_factors(key, p: int, m: int, r: int):
    """(U (p, r), s (r,), V (m, r)), float32, on the default device."""
    ku, kv = jax.random.split(key)
    U = jnp.linalg.qr(jax.random.normal(ku, (p, r)))[0]
    V = jax.random.normal(kv, (m, r)) / jnp.sqrt(r)
    s = jnp.linspace(2.0, 1.0, r)
    return U, s, V
