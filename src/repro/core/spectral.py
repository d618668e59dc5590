"""Matvec-only spectral master: warm-started randomized SVT engine.

PRs 2-3 made the worker side device-resident and O(p²)/round, which
left one LAPACK-shaped master cost per round: the full
``jnp.linalg.svd`` inside the shrinkage/truncation primitives of
:mod:`repro.core.svd_ops` — an O(min(p,m)·p·m) factorization that
lowers poorly on TPU and ignores the paper's own structural premise
that the predictor matrix is low rank (r ≪ min(p, m)).  This module
replaces it with warm-started, rank-adaptive block subspace iteration:

* the solver carries the top-(k + oversample) right basis ``V`` (and
  the matching Ritz spectrum) across rounds inside its scan state —
  the iterate moves O(η) per round, so one or two refinement sweeps
  per round suffice once the basis is warm;
* the effective rank is read off the shrink threshold: Ritz values
  ``s_i ≤ τ`` never materialize in the output, so only the subspace
  ABOVE the shrinkage frontier needs to be converged;
* acceptance is decided from the explicit deflation
  ``E = M − U_r diag(s) V_rᵀ``: kept-triplet residuals bound the error
  of the reconstructed part, ``σ_{K+1}(M) ≤ ‖E‖₂ ≤ ‖E‖_F`` (Weyl)
  bounds what the block failed to see.  Any failed test — including
  the cold first round — falls back to the exact ``jnp.linalg.svd``
  inside the same traced program (``lax.cond``), which also reseeds
  the carried basis.

Everything on the lazy path is gemm/QR work on (p, K) panels with
K = k + oversample — pure MXU matvec work, no full factorization —
and it is deterministic (fixed cosine probes, no PRNG), so every
replica of the replicated master computes bit-identical results and
the CommLog is untouched: the engine is compute-only (DESIGN.md §9).

``leading_sv`` is the K = 1 case of the same machinery: a power
iteration with residual-based early exit under ``lax.while_loop``
(the DFW / DGSP / DNSP master step).
"""
from __future__ import annotations

from functools import partial, wraps
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

Carry = Dict[str, jnp.ndarray]

_TINY = 1e-30


def _f32_matmuls(fn):
    """Trace ``fn`` with full-f32 matmuls.  The acceptance tests below
    resolve residuals near ``res_tol`` (5e-5) of the spectrum, and a
    TPU's default f32 matmul takes one bfloat16 pass (about 4e-3
    relative): under it no lazy round certifies and every round falls
    back to the exact SVD.  On the CPU f32 matmuls are f32 already."""
    @wraps(fn)
    def traced(*args, **kwargs):
        with jax.default_matmul_precision("highest"):
            return fn(*args, **kwargs)
    return traced


# ---------------------------------------------------------------------------
# deterministic probes and small shared pieces
# ---------------------------------------------------------------------------
def _probe(n: int, K: int, dtype) -> jnp.ndarray:
    """Deterministic dense (n, K) probe with orthonormal columns.

    No PRNG: a cosine lattice at incommensurate frequencies (no column
    is sparse, none repeats), orthonormalized once.  A deterministic
    start keeps every replica of the replicated master bit-identical
    with zero extra communication — the same reason ``leading_sv``
    uses a fixed probe.
    """
    i = jnp.arange(n, dtype=dtype)[:, None]
    j = jnp.arange(K, dtype=dtype)[None, :]
    P = jnp.cos(0.37 + i * (1.0 + 0.61803398875 * j)) + 0.1
    return jnp.linalg.qr(P)[0]


def _colnorms(X: jnp.ndarray) -> jnp.ndarray:
    return jnp.sqrt(jnp.sum(X * X, axis=0))


def _sweeps(M: jnp.ndarray, V0: jnp.ndarray, s0: jnp.ndarray,
            max_sweeps: int, drift_tol: float
            ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Block subspace refinement ``V ← qr(Mᵀ qr(M V))`` with early exit.

    Runs under ``lax.while_loop`` until the Ritz spectrum stops moving
    (relative drift ≤ ``drift_tol``) or ``max_sweeps`` is hit.  ``s0``
    is the previous round's spectrum: with a warm basis the first sweep
    usually lands within the drift tolerance, so warm rounds pay one or
    two sweeps.  Returns ``(U (p,K), V (m,K), R (K,K), sweeps_run)``
    with ``Mᵀ U = V R`` — the projected block ``B = Uᵀ M V = Rᵀ`` falls
    out of the last QR for free.
    """
    p, _ = M.shape
    K = V0.shape[1]

    def cond(st):
        i, _, _, _, s, s_prev = st
        drift = jnp.max(jnp.abs(s - s_prev))
        scale = jnp.maximum(s[0], _TINY)
        return (i < max_sweeps) & ((i < 1) | (drift > drift_tol * scale))

    def body(st):
        i, _, V, _, s, _ = st
        U, _ = jnp.linalg.qr(M @ V)
        Vn, R = jnp.linalg.qr(M.T @ U)
        sn = jnp.linalg.svd(R, compute_uv=False)
        return i + 1, U, Vn, R, sn, s

    st0 = (jnp.int32(0), jnp.zeros((p, K), M.dtype), V0,
           jnp.zeros((K, K), M.dtype), s0, jnp.full((K,), jnp.inf, M.dtype))
    i, U, V, R, _, _ = jax.lax.while_loop(cond, body, st0)
    return U, V, R, i


def _ritz_from_R(U: jnp.ndarray, V: jnp.ndarray, R: jnp.ndarray
                 ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Rayleigh–Ritz extraction from the last sweep's QR factor: the
    projected block is B = Uᵀ M V = Rᵀ, so the approximate singular
    triplets are (U Ub, s, V Vb) for the small SVD Rᵀ = Ub s Vbᵀ —
    no further product with M needed."""
    Ub, s, Vbt = jnp.linalg.svd(R.T)
    return U @ Ub, s, V @ Vbt.T


def _tail_power(E: jnp.ndarray, W0: jnp.ndarray, iters: int
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Block-power estimate of ‖E‖₂, warm-started from ``W0`` (m, b).

    A small block (not a single vector) because the deflated remainder
    of a shrinkage iterate is typically a dense noise bulk with a soft
    edge: block iteration resolves the edge in a couple of sweeps
    where a single vector's Rayleigh quotient can lag it by several
    percent — and the acceptance margin on this estimate is thin by
    design (tail values just BELOW the threshold are harmless).  The
    refined block is returned so the caller can carry it across rounds
    (the bulk drifts as slowly as the iterate).
    """
    def body(_, Wb):
        return jnp.linalg.qr(E.T @ (E @ Wb))[0]

    Wb = jax.lax.fori_loop(0, iters, body, W0)
    return jnp.max(_colnorms(E @ Wb)), Wb


def _residuals(E: jnp.ndarray, Ur: jnp.ndarray, Vr: jnp.ndarray
               ) -> jnp.ndarray:
    """Two-sided per-triplet residuals from the explicit deflation:
    with M = U_r diag(s) V_rᵀ + E and orthonormal Ritz bases,
    ``M v_i − s_i u_i = E v_i`` and ``Mᵀ u_i − s_i v_i = Eᵀ u_i``."""
    return jnp.maximum(_colnorms(E @ Vr), _colnorms(E.T @ Ur))


def _simplex_cap(S: jnp.ndarray, radius) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Project a DESCENDING spectrum onto the l1 ball (Duchi et al.).

    Returns (projected spectrum, water level θ).  Shared by the exact
    ``svd_ops.project_nuclear_ball`` and the lazy engine (which applies
    it to the top-K Ritz spectrum once the tail is certified below θ).
    """
    k = S.shape[0]
    css = jnp.cumsum(S)
    idx = jnp.arange(1, k + 1)
    cond = S - (css - radius) / idx.astype(S.dtype) > 0
    rho = jnp.max(jnp.where(cond, idx, 0))
    theta = (css[rho - 1] - radius) / rho.astype(S.dtype)
    return jnp.maximum(S - theta, 0.0), theta


# ---------------------------------------------------------------------------
# the k = 1 case: leading singular triplet with residual early exit
# ---------------------------------------------------------------------------
@partial(jax.jit, static_argnames=("iters",))
def leading_sv(G: jnp.ndarray, iters: int = 60, tol: float = 1e-6,
               seed: int = 0) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Top singular triplet (u, s, v) of G (p, m) — the K = 1 engine case.

    Power iteration on GᵀG (one matvec pair and one normalization per
    step) under ``lax.while_loop``: exits as soon as the eigen-residual
    ‖GᵀG v − λ v‖ ≤ tol·λ, capped at ``iters`` steps — the old fixed
    ``iters=60`` budget becomes a worst-case bound.  Deterministic,
    data-derived start (no PRNG) so every replica of the replicated
    master computes bit-identical vectors without extra communication.
    """
    p, m = G.shape
    probe = (1.0 + 0.1 * jnp.cos(jnp.arange(m, dtype=G.dtype))) / jnp.sqrt(m)
    v0 = G.T @ (G @ probe) + 1e-12 * probe
    v0 = v0 / jnp.maximum(jnp.linalg.norm(v0), _TINY)

    def cond(st):
        i, _, done = st
        return (i < iters) & jnp.logical_not(done)

    def body(st):
        i, v, _ = st
        w = G.T @ (G @ v)
        lam = w @ v                       # Rayleigh quotient of GᵀG
        done = jnp.linalg.norm(w - lam * v) <= tol * jnp.maximum(lam, _TINY)
        return i + 1, w / jnp.maximum(jnp.linalg.norm(w), _TINY), done

    _, v, _ = jax.lax.while_loop(
        cond, body, (jnp.int32(0), v0, jnp.zeros((), bool)))
    u = G @ v
    s = jnp.linalg.norm(u)
    u = u / jnp.maximum(s, _TINY)
    # Sign convention: first nonzero-ish entry of u positive (determinism).
    sign = jnp.where(jnp.sum(u) >= 0, 1.0, -1.0).astype(G.dtype)
    return u * sign, s, v * sign


# ---------------------------------------------------------------------------
# the shrinkage engine (ProxGD / AccProxGD / ADMM / Centralize masters)
# ---------------------------------------------------------------------------
class ShrinkEngine:
    """Per-solver spectral master for the prox-family shrinkage step.

    ``shrink(M, tau, carry)`` is a drop-in for ``svd_ops.sv_shrink``
    that additionally returns the nuclear norm of its output (the
    shrunk spectrum is already in hand, so objective logging never pays
    a second SVD) and threads the warm-start carry — a small pytree the
    solver keeps in its scan state next to ``W``.

    ``mode="exact"`` — or a block K = rank + oversample that already
    covers min(p, m) — degenerates to the plain full-SVD master with an
    empty carry, so the two engines are interchangeable in solver
    bodies.  Neither engine communicates (the master is replicated),
    so the CommLog is identical by construction.
    """

    def __init__(self, p: int, m: int, dtype=jnp.float32, mode: str = "lazy",
                 rank: int = 5, oversample: int = 8, max_sweeps: int = 5,
                 drift_tol: float = 1e-5, res_tol: float = 5e-5,
                 tail_iters: int = 3, tail_block: int = 4,
                 tail_margin: float = 0.97, fro_margin: float = 0.95):
        if mode not in ("lazy", "exact"):
            raise ValueError(
                f"unknown sv_engine {mode!r}; have 'lazy', 'exact'")
        self.p, self.m = int(p), int(m)
        self.dtype = dtype
        self.K = min(int(rank) + int(oversample), min(self.p, self.m))
        # a block as wide as the spectrum is a full SVD with extra steps
        self.lazy = (mode == "lazy") and self.K < min(self.p, self.m)
        self.mode = "lazy" if self.lazy else "exact"
        self.max_sweeps = int(max_sweeps)
        self.drift_tol = float(drift_tol)
        self.res_tol = float(res_tol)
        self.tail_iters = int(tail_iters)
        self.tail_block = min(int(tail_block), self.m)
        self.tail_margin = float(tail_margin)
        # the rigorous (Frobenius/Weyl) arm of the tail test; kept
        # strictly at or below tail_margin so tightening tail_margin
        # cannot be silently overridden by the OR'd fro arm
        self.fro_margin = float(min(fro_margin, tail_margin))

    # -- carry ---------------------------------------------------------
    def init_carry(self) -> Carry:
        """The solver-private auxiliary state threaded through the round
        loop: the carried right basis, its Ritz spectrum (for the
        drift-based sweep exit), a warm flag (cold ⇒ exact fallback on
        round one), and a fallback counter (diagnostics)."""
        if not self.lazy:
            return {}
        return {"V": _probe(self.m, self.K, self.dtype),
                "s": jnp.zeros((self.K,), self.dtype),
                "T": _probe(self.m, self.tail_block, self.dtype),
                "warm": jnp.zeros((), jnp.int32),
                "exact_rounds": jnp.zeros((), jnp.int32)}

    def stats(self, carry: Carry) -> Dict[str, int]:
        """Host-side diagnostics from a final carry (extras-friendly)."""
        if not self.lazy:
            return {}
        return {"sv_exact_rounds": int(carry["exact_rounds"])}

    def device_stats(self, carry: Carry) -> Dict[str, jnp.ndarray]:
        """Device-side counters for the round-metrics channel
        (repro.obs): cumulative exact-SVD fallback rounds as a traced
        i32 scalar — usable INSIDE a round body, unlike :meth:`stats`
        which needs a concrete carry."""
        if not self.lazy:
            return {"sv_exact": jnp.zeros((), jnp.int32)}
        return {"sv_exact": jnp.asarray(carry["exact_rounds"], jnp.int32)}

    # -- the master step ----------------------------------------------
    def _exact_shrink(self, M, tau):
        U, S, Vt = jnp.linalg.svd(M, full_matrices=False)
        s = jnp.maximum(S - tau, 0.0)
        return (U * s[None, :]) @ Vt, jnp.sum(s), S, Vt

    @_f32_matmuls
    def shrink(self, M: jnp.ndarray, tau, carry: Carry
               ) -> Tuple[jnp.ndarray, jnp.ndarray, Carry]:
        """prox_{tau‖·‖_*}(M) → (W, ‖W‖_*, carry').

        Lazy path: refine the carried basis (1–2 warm sweeps), Ritz-
        extract, shrink the top-K spectrum, and accept iff (i) the
        shrink-weighted residual of every surviving triplet is
        ≤ res_tol·s₁ (see the inline comment: weight (s_i − τ)₊ / s_i)
        and (ii) the deflated remainder sits below the threshold: singular
        values ≤ τ contribute exactly zero to the prox, so the tail
        test is against τ itself — ``‖E‖_F ≤ 0.95 τ`` (rigorous:
        σ_{K+1} ≤ ‖E‖₂ ≤ ‖E‖_F by Weyl) or the block-power estimate
        ``≤ tail_margin·τ`` with a margin (default 0.97) that only
        covers the estimator's underestimation, NOT a rank-safety
        buffer — a noise bulk whose edge hugs τ from below (exactly
        where a statistically-tuned λ puts it) must still be accepted.
        Anything else — including the cold first call — takes the
        exact branch, which also reseeds the carry with the true top-K
        basis.
        """
        with jax.named_scope("shrink"):
            if not self.lazy:
                W, nn, _, _ = self._exact_shrink(M, tau)
                return W, nn, carry

            K = self.K
            U, V, R, _ = _sweeps(M, carry["V"], carry["s"],
                                 self.max_sweeps, self.drift_tol)
            Ur, s, Vr = _ritz_from_R(U, V, R)
            shr = jnp.maximum(s - tau, 0.0)
            scale = jnp.maximum(s[0], _TINY)
            # explicit deflation: everything the block failed to capture
            E = M - (Ur * s[None, :]) @ Vr.T
            res = _residuals(E, Ur, Vr)
            # Shrink-weighted convergence: a triplet enters the output with
            # weight (s_i − τ)₊, so its subspace error matters in that
            # proportion — triplets hugging the threshold (the block
            # boundary inside a noise bulk, which NEVER converges
            # individually) are output-insensitive and must not block
            # acceptance, while dominant signal triplets are held to the
            # full tolerance.  (Ritz VALUES converge quadratically in the
            # residual, so the (s_i − τ)₊ weights themselves are accurate
            # well before the vectors are.)
            conv_ok = jnp.max(res * shr / jnp.maximum(s, _TINY)) <= \
                self.res_tol * scale
            fro = jnp.linalg.norm(E)
            t_est, Tb = _tail_power(E, carry["T"], self.tail_iters)
            tail_ok = (fro <= self.fro_margin * tau) | \
                (t_est <= self.tail_margin * tau)
            good = (carry["warm"] > 0) & conv_ok & tail_ok

            def lazy_branch(_):
                return ((Ur * shr[None, :]) @ Vr.T, jnp.sum(shr), Vr, s,
                        jnp.int32(0))

            def exact_branch(_):
                # one factorization serves both the shrink and the carry
                # reseed (true top-K right subspace)
                W, nn, S, Vt = self._exact_shrink(M, tau)
                return W, nn, Vt[:K].T, S[:K], jnp.int32(1)

            W, nn, Vc, sc, ex = jax.lax.cond(good, lazy_branch, exact_branch,
                                             None)
            return W, nn, {"V": Vc, "s": sc, "T": Tb,
                           "warm": jnp.ones((), jnp.int32),
                           "exact_rounds": carry["exact_rounds"] + ex}

    @_f32_matmuls
    def project(self, M: jnp.ndarray, radius, carry: Carry
                ) -> Tuple[jnp.ndarray, Carry]:
        """Euclidean projection onto {‖·‖_* ≤ radius} → (W, carry').

        Lazy path: with the Ritz spectrum s and deflation E in hand,
        either (a) certify the matrix inside the ball —
        ``Σs + √(min(p,m)−K)·‖E‖_F ≤ radius`` bounds the full nuclear
        norm — and return it unchanged, or (b) certify the projection
        rank-limited — ``Σs > radius`` forces a projection whose water
        level θ (from the top-K spectrum) exceeds the certified tail,
        so tail directions contribute nothing — or fall back to exact.
        """
        if not self.lazy:
            from . import svd_ops
            return svd_ops.project_nuclear_ball(M, radius), carry

        K = self.K
        U, V, R, _ = _sweeps(M, carry["V"], carry["s"],
                             self.max_sweeps, self.drift_tol)
        Ur, s, Vr = _ritz_from_R(U, V, R)
        scale = jnp.maximum(s[0], _TINY)
        E = M - (Ur * s[None, :]) @ Vr.T
        res = _residuals(E, Ur, Vr)
        fro = jnp.linalg.norm(E)
        t_est, Tb = _tail_power(E, carry["T"], self.tail_iters)
        s_proj, theta = _simplex_cap(s, radius)
        # ‖M‖_* ≤ Σs + ‖E‖_*, and rank(E) is only bounded by min(p, m)
        # (E is M minus a rank-K matrix; the tighter min(p,m)−K would
        # require the Ritz factors to be exact), so the rigorous
        # inside-ball certificate uses √min(p,m)·‖E‖_F
        q = min(self.p, self.m)
        nuc_ub = jnp.sum(s) + jnp.sqrt(jnp.asarray(q, M.dtype)) * fro
        # shrink-weighted, as in `shrink`: sensitivity is the retained
        # weight s_proj_i, so water-line-straddling triplets (clustered
        # with the tail, individually non-convergent) don't block
        conv_ok = jnp.max(res * s_proj / jnp.maximum(s, _TINY)) <= \
            self.res_tol * scale
        inside = (carry["warm"] > 0) & (nuc_ub <= radius)
        tail_below = (fro <= self.fro_margin * theta) | \
            (t_est <= self.tail_margin * theta)
        proj_ok = (carry["warm"] > 0) & (jnp.sum(s) > radius) & \
            conv_ok & tail_below
        branch = jnp.where(inside, 0, jnp.where(proj_ok, 1, 2))

        def inside_branch(_):
            return M, Vr, s, jnp.int32(0)

        def proj_branch(_):
            return ((Ur * s_proj[None, :]) @ Vr.T, Vr, s, jnp.int32(0))

        def exact_branch(_):
            # one factorization serves both the projection and the
            # carry reseed
            Ue, Se, Vte = jnp.linalg.svd(M, full_matrices=False)
            S_proj = jax.lax.cond(jnp.sum(Se) > radius,
                                  lambda S: _simplex_cap(S, radius)[0],
                                  lambda S: S, Se)
            W = (Ue * S_proj[None, :]) @ Vte
            return W, Vte[:K].T, Se[:K], jnp.int32(1)

        W, Vc, sc, ex = jax.lax.switch(
            branch, [inside_branch, proj_branch, exact_branch], None)
        return W, {"V": Vc, "s": sc, "T": Tb,
                   "warm": jnp.ones((), jnp.int32),
                   "exact_rounds": carry["exact_rounds"] + ex}


def shrink_engine(prob, engine: str = "lazy", rank=None,
                  oversample: int = 8, **kw) -> ShrinkEngine:
    """Build the shrinkage master for one solve of ``prob``.

    ``rank`` defaults to the problem's assumed rank bound (Assumption
    2.3); the carried block is rank + oversample wide.  Solvers expose
    this as ``sv_engine=`` / ``sv_rank=`` (``repro.solve`` forwards).
    """
    r = int(prob.r if rank is None else rank)
    return ShrinkEngine(prob.p, prob.m, prob.Xs.dtype, mode=engine,
                        rank=r, oversample=oversample, **kw)


# ---------------------------------------------------------------------------
# one-shot rank-r truncation (the §5 estimator) and its factored form
# ---------------------------------------------------------------------------
def _factor_exact(M: jnp.ndarray, r: int
                  ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    U, S, Vt = jnp.linalg.svd(M, full_matrices=False)
    return U[:, :r], S[:r], Vt[:r, :].T


@partial(jax.jit, static_argnames=("r", "oversample", "max_sweeps"))
@_f32_matmuls
def truncate_factors(M: jnp.ndarray, r: int, oversample: int = 8,
                     max_sweeps: int = 24, drift_tol: float = 1e-6,
                     res_tol: float = 5e-6
                     ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Rank-r factors ``(U (p,r), s (r,), V (m,r))`` of the best rank-r
    approximation ``M ≈ U diag(s) Vᵀ``, by cold randomized subspace
    iteration.

    The factored form of :func:`truncate` — THE code path for "give me
    the learned subspace": the §5 one-shot estimator composes it back
    to a matrix, the serving artifact (``repro.serve.mtl``) keeps the
    factors.  The one-shot call has no warm carry, so the sweep loop
    starts from the deterministic probe and runs to residual
    convergence (early exit, ``max_sweeps`` cap).  Accepts iff every
    KEPT triplet's residual is ≤ res_tol·s₁.  NEAR-tied values at the
    truncation boundary keep the residuals high and route to the exact
    fallback; EXACTLY tied values make the best rank-r approximation
    non-unique (any basis of the tied cluster has zero residual), so
    there the contract is optimal approximation error, not factor
    equality with LAPACK's arbitrary choice (tests/test_spectral.py).

    ``r`` is clamped to min(p, m): the solvers pass the Assumption-2.3
    rank BOUND, which may exceed a narrow problem's spectrum (m < r
    tasks), and the historical exact path clamped by slicing — a
    narrow matrix simply has fewer factors.
    """
    p, m = M.shape
    r = min(r, p, m)
    K = min(r + oversample, min(p, m))
    if K >= min(p, m):
        return _factor_exact(M, r)
    V0 = _probe(m, K, M.dtype)
    U, V, R, _ = _sweeps(M, V0, jnp.zeros((K,), M.dtype), max_sweeps,
                         drift_tol)
    Ur, s, Vr = _ritz_from_R(U, V, R)
    E = M - (Ur * s[None, :]) @ Vr.T
    res = _residuals(E, Ur, Vr)
    keep = jnp.arange(K) < r
    scale = jnp.maximum(s[0], _TINY)
    conv_ok = jnp.max(jnp.where(keep, res, 0.0)) <= res_tol * scale
    # Tail check: a top direction the probe never excited leaves ZERO
    # residual on the kept triplets (it is orthogonal to all of them)
    # but shows up whole in the deflation — a valid truncation has
    # ‖E‖₂ ≈ σ_{K+1} ≤ σ_r, so an estimate above the r-th Ritz value
    # means the block is missing spectrum and must fall back.
    t_est, _ = _tail_power(E, _probe(m, 4, M.dtype), 6)
    tail_ok = t_est <= jnp.maximum(s[r - 1], res_tol * scale)
    good = conv_ok & tail_ok

    def lazy_branch(_):
        return Ur[:, :r], s[:r], Vr[:, :r]

    def exact_branch(_):
        return _factor_exact(M, r)

    return jax.lax.cond(good, lazy_branch, exact_branch, None)


@partial(jax.jit, static_argnames=("r", "oversample", "max_sweeps"))
def truncate(M: jnp.ndarray, r: int, oversample: int = 8,
             max_sweeps: int = 24, drift_tol: float = 1e-6,
             res_tol: float = 5e-6) -> jnp.ndarray:
    """Best rank-r approximation (the §5 ``svd_trunc`` master): the
    composed form of :func:`truncate_factors` — see there for the
    acceptance / exact-fallback contract."""
    U, s, V = truncate_factors(M, r, oversample, max_sweeps, drift_tol,
                               res_tol)
    return (U * s[None, :]) @ V.T
