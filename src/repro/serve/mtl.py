"""Factored MTL serving: the online half of the shared-representation
system.

A fitted multi-task model is really ``W = U diag(s) Vᵀ`` — a shared
rank-r basis ``U (p, r)`` plus per-task codes (rows of ``V (m, r)``
scaled by the spectrum) — so per-task predictors cost O((p + m) r)
floats to store instead of O(p m), a mixed-task request batch is scored
by ONE gemm against the shared basis plus a tiny code gather
(O(p r) per request, independent of m), and an UNSEEN task is learnable
from a handful of samples by solving an r-dimensional problem inside
the frozen subspace (the transfer setting of Wang–Kolar–Srebro,
arXiv:1510.00633 §2.3, and the few-shot subspace-regression analysis of
arXiv:2501.18975).  Three pieces:

* :class:`FactoredModel` — the serving artifact.  Built from a solver
  result via :meth:`MTLResult.factorize` (which routes every rank
  truncation through ``repro.core.spectral.truncate_factors`` — no
  ad-hoc SVDs), saved/loaded atomically through the npz machinery of
  :mod:`repro.train.checkpoint` with a JSON manifest (rank, m, p, loss,
  content-hash version id).
* :class:`MTLServer` — fixed batch slots in the style of
  ``serve/engine.py``: requests are (task_id, x) pairs, waves of B are
  scored by one jit'd ``gather(codes, task_ids) · (x U)`` hot path; the
  code table optionally shards over a ``"tasks"`` mesh axis for huge m;
  model versions hot-swap atomically (serve v_k while a background
  re-solve produces v_{k+1}) — every ``score`` call is served entirely
  by one version and reports its id.
* few-shot onboarding — :meth:`MTLServer.onboard` fits a new r-vector
  code for an unseen task by closed-form ridge (squared loss) or a few
  damped Newton steps (logistic) in the frozen subspace — the DGSP/
  DNSP worker re-fit, :func:`repro.core.linear_model.projected_erm`,
  on the projected design ``X U`` — and appends it to the code table
  without touching U.

DESIGN.md §10 documents the artifact format, the O(p r) scoring path,
the onboarding math and the hot-swap semantics.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import threading
import time
import warnings
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core.losses import get_loss
from ..obs.metrics import default_registry
from ..obs.tracing import annotate, emit_event, trace_span
from ..train import checkpoint

_MANIFEST_VERSION = 1


# ---------------------------------------------------------------------------
# the factored artifact
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class FactoredModel:
    """The serving artifact: ``W ≈ U diag(s) Vᵀ``.

    ``U (p, r)`` is the shared orthonormal basis, ``s (r,)`` the
    spectrum, ``V (m, r)`` the per-task right factors (row j is task
    j's coordinates).  The per-task CODE is ``c_j = s ⊙ V[j]`` so that
    ``w_j = U c_j`` — the scoring and onboarding paths work in code
    space and never materialize the dense ``(p, m)`` predictor matrix.

    ``version`` is a content hash over the factors + loss, computed at
    construction: two models with identical factors share an id, so
    save → load round-trips keep the id and hot-swap consumers can
    tell versions apart without trusting file names.
    """

    U: jnp.ndarray                     # (p, r) shared basis
    s: jnp.ndarray                     # (r,)   spectrum
    V: jnp.ndarray                     # (m, r) per-task right factors
    loss: str = "squared"
    task_keys: Optional[Tuple[str, ...]] = None
    version: str = ""

    def __post_init__(self):
        if self.U.ndim != 2 or self.V.ndim != 2 or self.s.ndim != 1:
            raise ValueError("FactoredModel wants U (p,r), s (r,), V (m,r)")
        r = self.U.shape[1]
        if self.s.shape[0] != r or self.V.shape[1] != r:
            raise ValueError(
                f"rank mismatch: U {self.U.shape}, s {self.s.shape}, "
                f"V {self.V.shape}")
        if self.task_keys is not None and len(self.task_keys) != self.m:
            raise ValueError(f"{len(self.task_keys)} task_keys for "
                             f"{self.m} tasks")
        get_loss(self.loss)            # fail early on unknown loss names
        if not self.version:
            object.__setattr__(self, "version", self._content_hash())

    # -- shapes --------------------------------------------------------
    @property
    def p(self) -> int:
        return self.U.shape[0]

    @property
    def m(self) -> int:
        return self.V.shape[0]

    @property
    def rank(self) -> int:
        return self.U.shape[1]

    @property
    def codes(self) -> jnp.ndarray:
        """The (m, r) code table ``C`` with ``w_j = U C[j]``."""
        return self.V * self.s[None, :]

    def _content_hash(self) -> str:
        h = hashlib.sha256()
        for arr in (self.U, self.s, self.V):
            h.update(np.asarray(arr).tobytes())
        h.update(self.loss.encode())
        # task_keys are part of the served contract (they route
        # requests to code rows), so a permuted or edited key list must
        # fail the load-time hash check like a tampered factor would
        h.update(repr(self.task_keys).encode())
        return h.hexdigest()[:12]

    def manifest(self) -> Dict:
        """The artifact's self-description, stored alongside the factors."""
        return {"format": _MANIFEST_VERSION, "rank": self.rank,
                "m": self.m, "p": self.p, "loss": self.loss,
                "version": self.version,
                "task_keys": (None if self.task_keys is None
                              else list(self.task_keys))}

    # -- construction --------------------------------------------------
    @classmethod
    def from_W(cls, W, rank: int, loss: str = "squared",
               task_keys: Optional[Sequence[str]] = None) -> "FactoredModel":
        """Factor a dense (p, m) predictor matrix at the given rank.

        THE code path for "give me the learned subspace": routes
        through ``repro.core.spectral.truncate_factors`` (cold
        randomized subspace iteration with residual-tested exact
        fallback) — the same engine the solvers' masters use, no
        ad-hoc ``jnp.linalg.svd`` calls.
        """
        from ..core.spectral import truncate_factors
        U, s, V = truncate_factors(jnp.asarray(W), int(rank))
        return cls(U=U, s=s, V=V, loss=loss,
                   task_keys=None if task_keys is None
                   else tuple(task_keys))

    # -- dense views ---------------------------------------------------
    def dense(self) -> jnp.ndarray:
        """Materialize the (p, m) predictor matrix (diagnostics only —
        serving never needs it)."""
        return self.U @ self.codes.T

    def task_predictor(self, task_id: int) -> jnp.ndarray:
        """w_j = U c_j for one task: (p,)."""
        return self.U @ self.codes[task_id]

    # -- onboarding (the transfer setting) -----------------------------
    def onboard(self, task_key: Optional[str], X, y, l2: float = 1e-3,
                iters: int = 25) -> "FactoredModel":
        """Fit an UNSEEN task inside the frozen subspace and append it.

        Solves the r-dimensional problem
        ``min_c L(X U c, y) + (l2/2)‖c‖²``
        on the projected design ``Z = X U`` — closed-form ridge for the
        squared loss, ``iters`` damped Newton steps for logistic —
        through :func:`repro.core.linear_model.projected_erm` (the same
        re-fit the DGSP/DNSP workers run).  U and the existing
        m code rows are untouched; the new model has m + 1 tasks.

        The stored right factor is ``c / s`` (so ``codes`` recovers c);
        directions with s ≈ 0 are absent from the LEARNED subspace and
        their coordinates are dropped.
        """
        c = onboard_code(self.U, X, y, loss=self.loss, l2=l2, iters=iters)
        safe = jnp.abs(self.s) > 1e-12
        v_new = jnp.where(safe, c / jnp.where(safe, self.s, 1.0), 0.0)
        keys = None
        if self.task_keys is not None:
            if task_key is None:
                raise ValueError("model carries task_keys; onboard needs one")
            if task_key in self.task_keys:
                raise ValueError(f"task key {task_key!r} already onboarded")
            keys = self.task_keys + (task_key,)
        elif task_key is not None:
            # silently dropping the key would make the new task
            # unroutable by the name the caller just supplied
            raise ValueError("model has no task_keys; onboard with "
                             "task_key=None and route by id")
        return FactoredModel(U=self.U, s=self.s,
                             V=jnp.concatenate([self.V, v_new[None, :]]),
                             loss=self.loss, task_keys=keys)

    # -- persistence (train/checkpoint npz machinery) ------------------
    def save(self, store_dir: str, step: Optional[int] = None,
             keep: Optional[int] = None) -> int:
        """Atomically write this model as version ``step`` of a store.

        A store directory is a checkpoint directory
        (``step_XXXXXXXX.npz`` files, tmp-file + rename atomic writes,
        optional ``keep=`` pruning); ``step`` defaults to
        latest + 1 so a background re-solve publishes v_{k+1} with a
        plain ``model.save(store)``.  Returns the step written.
        """
        steps = checkpoint.available_steps(store_dir)
        if step is None:
            step = (steps[-1] + 1) if steps else 0
        man = np.frombuffer(json.dumps(self.manifest()).encode(), np.uint8)
        state = {"U": np.asarray(self.U), "s": np.asarray(self.s),
                 "V": np.asarray(self.V), "manifest": man.copy()}
        checkpoint.save_checkpoint(store_dir, step, state, keep=keep)
        return step

    @classmethod
    def load(cls, store_dir: str, step: Optional[int] = None
             ) -> Tuple[int, "FactoredModel"]:
        """Load version ``step`` (default: latest) from a store.

        Validates the factors against the manifest — a truncated or
        mixed-up artifact fails loudly instead of serving garbage.
        """
        step, state = checkpoint.load_checkpoint(store_dir, step)
        man = json.loads(bytes(np.asarray(state["manifest"])).decode())
        if man["format"] != _MANIFEST_VERSION:
            raise ValueError(f"unknown artifact format {man['format']}")
        model = cls(U=state["U"], s=state["s"], V=state["V"],
                    loss=man["loss"],
                    task_keys=None if man["task_keys"] is None
                    else tuple(man["task_keys"]))
        got = (model.p, model.m, model.rank)
        want = (man["p"], man["m"], man["rank"])
        if got != want:
            raise ValueError(f"artifact shape {got} contradicts its "
                             f"manifest {want}")
        if model.version != man["version"]:
            raise ValueError(
                f"artifact content hash {model.version} does not match "
                f"manifest version {man['version']} — corrupt store?")
        return step, model


def onboard_code(U: jnp.ndarray, X, y, loss: str = "squared",
                 l2: float = 1e-3, iters: int = 25) -> jnp.ndarray:
    """The r-vector code of a new task in the frozen subspace ``U``.

    ``min_c L(X U c, y) + (l2/2)‖c‖²`` on the projected design — an
    r-dimensional problem, so a handful of samples suffice where a full
    p-dimensional per-task fit would be hopeless (the Fig-4-style
    onboarding comparison in ``benchmarks/serve_bench.py``).  Exactly
    the DGSP/DNSP worker re-fit, so it IS that code path:
    :func:`repro.core.linear_model.projected_erm` — closed form for
    squared, damped Newton for logistic.
    """
    from ..core.linear_model import projected_erm
    return projected_erm(get_loss(loss), jnp.asarray(U), jnp.asarray(X),
                         jnp.asarray(y), l2, iters)[1]


# ---------------------------------------------------------------------------
# the batched scoring server
# ---------------------------------------------------------------------------
@jax.jit
def _score_batch(U: jnp.ndarray, C: jnp.ndarray, ids: jnp.ndarray,
                 X: jnp.ndarray, m) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The hot path: one mixed-task batch in O(B p r).

    ``(X @ U)`` hits the shared (p, r) basis — one gemm, the basis
    stays resident — and the per-request code is a gather from the
    (m, r) table; no (p, m) matrix anywhere.  Works unchanged when C
    is sharded over a mesh axis (the gather lowers to a collective
    under GSPMD).

    Also returns an id-validity scalar: ``jnp.take`` would silently
    CLAMP out-of-range ids (and a sharded table's zero pad rows would
    disagree with the clamped single-device answer), so the kernel
    reports ``all(0 <= ids < m)`` in the SAME dispatch — the caller
    rejects bad batches without paying a separate device round-trip
    on the hot path.
    """
    ok = jnp.all((ids >= 0) & (ids < m))
    return jnp.einsum("br,br->b", X @ U, jnp.take(C, ids, axis=0)), ok


@jax.jit
def _score_batch_quant(U: jnp.ndarray, C: jnp.ndarray, S: jnp.ndarray,
                       ids: jnp.ndarray, X: jnp.ndarray, m
                       ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The quantized-table hot path: gather the int8/fp8 code rows AND
    their per-code scales, dequantize with one multiply, reduce.  Same
    contract as :func:`_score_batch` (validity flag in the same
    dispatch; works unchanged on a sharded table — both gathers lower
    to collectives under GSPMD)."""
    ok = jnp.all((ids >= 0) & (ids < m))
    z = X @ U
    codes = (jnp.take(C, ids, axis=0).astype(jnp.float32)
             * jnp.take(S, ids, axis=0))
    return jnp.einsum("br,br->b", z, codes), ok


_FUSED_SCORE = None


def _fused_score():
    """Lazy jit'd fused-kernel dispatch (imports the Pallas stack only
    when a server actually asks for ``kernel="pallas"``)."""
    global _FUSED_SCORE
    if _FUSED_SCORE is None:
        from ..kernels.mtl_score import mtl_score

        @jax.jit
        def fused(U, C, S, ids, X, m):
            ok = jnp.all((ids >= 0) & (ids < m))
            return mtl_score(U, C, S, ids, X), ok

        _FUSED_SCORE = fused
    return _FUSED_SCORE


@dataclasses.dataclass(frozen=True)
class _ServeState:
    """One immutable served version — swapped as a unit, never mutated,
    so a score wave that grabbed it can never observe a half-update."""
    model: FactoredModel
    U: jnp.ndarray                     # device copy of the basis
    C: jnp.ndarray                     # device copy of the code table
                                       # (padded to the mesh multiple;
                                       # int8/fp8 when quantized)
    Cs: Optional[jnp.ndarray] = None   # (m_pad, 1) f32 per-code scales
                                       # (None on the plain f32 XLA
                                       # path — exact 1.0 under pallas)
    version: str = ""
    step: Optional[int] = None         # store step, when loaded/saved
    key_index: Optional[Dict[str, int]] = None   # task_key -> id (O(1)
                                       # resolve on the serving path)
    gen: int = 0                       # install generation — bumped on
                                       # every rebind, the CAS token
                                       # maybe_reload checks so a slow
                                       # store load can never overwrite
                                       # a concurrently installed model


class MTLServer:
    """Batched factored scoring with hot-swap and few-shot onboarding.

    Fixed batch slots in the style of :class:`repro.serve.ServeEngine`:
    requests are processed in waves of ``batch_size`` through one jit'd
    kernel (the last wave is padded, never re-traced).  ``mesh=``
    shards the code table's task axis across devices for huge m; the
    basis U is replicated (it is what every request touches).

    Hot-swap semantics: ``swap``/``onboard``/``maybe_reload`` replace
    the served state ATOMICALLY (a single reference rebind of an
    immutable snapshot under a lock); every ``score`` call reads that
    reference exactly once, so a call is served entirely by one model
    version — never a torn mix — and reports the version id it used.

    ``kernel="pallas"`` scores through the fused
    :mod:`repro.kernels.mtl_score` kernel (interpret mode on CPU) —
    one streaming pass, no (B, r) HBM round-trip.  It is single-device
    by design: combined with ``mesh=`` the server warns and serves the
    XLA path (the sharded gather is already a collective; DESIGN.md
    §14).  ``code_dtype="int8"|"fp8"`` stores the code table quantized
    with per-code scales (``kernels.mtl_score.quantize_codes``);
    onboarding requantizes the appended row on install.

    SLO telemetry (DESIGN.md §15): every scoring call reports into
    ``registry`` (default: the process-wide
    ``repro.obs.default_registry()``) — a ``serve_latency_seconds``
    histogram (p50/p99 via its snapshot), ``serve_requests_total`` /
    ``serve_waves_total`` / ``serve_swaps_total`` counters — measured
    AROUND the jit'd dispatch on the host, never inside it (LINT102:
    no callbacks on the hot path).  ``swap_log`` is bounded at
    ``swap_log_limit`` installs; evicted entries leave as
    ``serve.swap_evicted`` obs events, so a long-lived server's
    install history stays inspectable without unbounded host memory.
    """

    def __init__(self, model: FactoredModel, *, batch_size: int = 64,
                 mesh=None, axis: str = "tasks", kernel: str = "xla",
                 code_dtype: str = "f32", registry=None,
                 swap_log_limit: int = 256):
        from ..kernels.mtl_score import CODE_DTYPES
        if kernel not in ("xla", "pallas"):
            raise ValueError(f"kernel must be 'xla' or 'pallas', "
                             f"got {kernel!r}")
        if code_dtype not in CODE_DTYPES:
            raise ValueError(f"code_dtype must be one of {CODE_DTYPES}, "
                             f"got {code_dtype!r}")
        if kernel == "pallas" and mesh is not None:
            warnings.warn(
                "kernel='pallas' is single-device; a sharded code table "
                "serves through the XLA collective-gather path instead")
            kernel = "xla"
        if swap_log_limit < 1:
            raise ValueError(f"swap_log_limit must be >= 1, got "
                             f"{swap_log_limit}")
        self.kernel, self.code_dtype = kernel, code_dtype
        self.B = int(batch_size)
        self.mesh, self.axis = mesh, axis
        self._lock = threading.Lock()
        self.registry = default_registry() if registry is None else registry
        self._lat = self.registry.histogram("serve_latency_seconds")
        self._req = self.registry.counter("serve_requests_total")
        self._wav = self.registry.counter("serve_waves_total")
        self._swp = self.registry.counter("serve_swaps_total")
        self._bad = self.registry.counter("serve_invalid_batches_total")
        # (monotonic install time, version id) per install — the
        # streaming loop's staleness probe (sample arrival -> the swap
        # that first serves a model trained on it, DESIGN.md §13);
        # bounded: the oldest entries are evicted as obs events
        self.swap_log: list = []
        self.swap_log_limit = int(swap_log_limit)
        self._state: _ServeState = self._prepare(model)
        self._log_swap(self._state.version)

    # -- state building / swapping -------------------------------------
    def _prepare(self, model: FactoredModel,
                 step: Optional[int] = None) -> _ServeState:
        C = jnp.asarray(model.codes)       # device-resident even when the
        U = jnp.asarray(model.U)           # model holds numpy factors
        Cs = None
        if self.code_dtype != "f32" or self.kernel == "pallas":
            # quantize (or, f32-under-pallas, scale by an exact 1.0)
            # from the model's float codes — onboarding reinstalls
            # through here, so an appended row is requantized with the
            # same per-code scheme as the original table
            from ..kernels.mtl_score import quantize_codes
            C, Cs = quantize_codes(C, self.code_dtype)
        if self.mesh is not None:
            ndev = self.mesh.shape[self.axis]
            pad = (-C.shape[0]) % ndev
            if pad:                    # zero rows no valid id reaches
                C = jnp.concatenate(
                    [C, jnp.zeros((pad, C.shape[1]), C.dtype)])
                if Cs is not None:     # scale 1.0: pad rows stay exact
                    Cs = jnp.concatenate(
                        [Cs, jnp.ones((pad, 1), Cs.dtype)])
            C = jax.device_put(
                C, NamedSharding(self.mesh, P(self.axis, None)))
            if Cs is not None:
                Cs = jax.device_put(
                    Cs, NamedSharding(self.mesh, P(self.axis, None)))
            U = jax.device_put(U, NamedSharding(self.mesh, P(None, None)))
        keys = model.task_keys
        return _ServeState(model=model, U=U, C=C, Cs=Cs,
                           version=model.version, step=step,
                           key_index=None if keys is None else
                           {k: i for i, k in enumerate(keys)})

    def _log_swap(self, version: str) -> None:
        """Append an install record, evicting the oldest past the ring
        limit (each eviction leaves as an obs event, so the probe
        history survives in the run's JSONL timeline)."""
        self.swap_log.append((time.monotonic(), version))
        self._swp.inc()
        while len(self.swap_log) > self.swap_log_limit:
            t_inst, v_old = self.swap_log.pop(0)
            emit_event("serve.swap_evicted", version=v_old,
                       t_install_monotonic_s=t_inst)

    def _install(self, state: _ServeState) -> None:
        """Rebind the served state (CALL UNDER self._lock): every
        install bumps the generation token."""
        self._state = dataclasses.replace(state, gen=self._state.gen + 1)
        self._log_swap(self._state.version)

    def swap(self, model: FactoredModel, step: Optional[int] = None) -> str:
        """Install a new model version; in-flight waves finish on the
        old one.  Returns the new version id."""
        with trace_span("serve.swap", version=model.version, step=step):
            state = self._prepare(model, step)
            with self._lock:
                self._install(state)
        return state.version

    @property
    def model(self) -> FactoredModel:
        return self._state.model

    @property
    def version(self) -> str:
        return self._state.version

    def maybe_reload(self, store_dir: str, *, retries: int = 2,
                     backoff_s: float = 0.05) -> bool:
        """Hot-swap to the store's newest version if it is newer than
        the one being served (the background-re-solve handoff).  False
        when already current or the store is empty.

        Reloading replaces the served model WHOLESALE: tasks onboarded
        since the served step but never published to the store are
        dropped with it — persist them (``server.model.save(store)``)
        if they must survive a re-solve.  The load happens outside the
        lock (it is slow I/O); the final rebind is guarded by the
        install-generation token captured BEFORE the load, so a reload
        can never overwrite ANY model installed concurrently (a newer
        store step, a ``swap``, an ``onboard``) — it simply loses the
        race and returns False.

        Degradation (DESIGN.md §12): a store version that fails to load
        — truncated/bit-flipped npz (the checkpoint content hash), a
        manifest/factor mismatch, or plain I/O errors — NEVER raises
        into the serving path.  Each candidate step is retried
        ``retries`` times with ``backoff_s`` exponential backoff (a
        concurrent writer may be mid-publish), then skipped with a
        warning in favor of the next older step; when nothing newer
        verifies, the server pins the version it is already serving and
        returns False.
        """
        with trace_span("serve.maybe_reload", store=store_dir) as span:
            span["swapped"] = False
            start = self._state
            steps = checkpoint.available_steps(store_dir)
            newer = [s for s in steps
                     if start.step is None or s > start.step]
            if not newer:
                return False
            step = model = None
            for cand in reversed(newer):   # newest first, degrade older
                err = None
                for attempt in range(retries + 1):
                    try:
                        step, model = FactoredModel.load(store_dir, cand)
                        err = None
                        break
                    except (checkpoint.CheckpointError, ValueError,
                            KeyError, OSError, json.JSONDecodeError) as e:
                        err = e
                        if attempt < retries:
                            time.sleep(backoff_s * (2 ** attempt))
                if err is None:
                    break
                warnings.warn(
                    f"serve store {store_dir} step {cand} failed to load "
                    f"after {retries + 1} attempts ({type(err).__name__}: "
                    f"{err}) — skipping it (pinning the served version if "
                    f"nothing older verifies)")
            if model is None:
                return False              # every newer step is damaged
            if model.version == start.version:
                # already serving this exact artifact (e.g. from memory,
                # before its save): adopt the store step, report no swap
                with self._lock:
                    if self._state.gen == start.gen:
                        self._install(dataclasses.replace(self._state,
                                                          step=step))
                return False
            state = self._prepare(model, step)
            with self._lock:
                if self._state.gen != start.gen:
                    return False          # lost the race to another install
                self._install(state)
            span["swapped"] = True
            span["version"] = state.version
            return True

    # -- scoring -------------------------------------------------------
    def resolve(self, task_key: str) -> int:
        """Task id of a key in the CURRENTLY served version (models
        built without keys use raw ids).  O(1) — the key index is
        built once per installed version.

        Introspection only: a hot-swap between ``resolve`` and a later
        ``score`` can remap the id.  Key-routed REQUESTS should go
        through :meth:`score_keyed`, which resolves and scores under
        one state snapshot.
        """
        idx = self._state.key_index
        if idx is None:
            raise ValueError("model has no task_keys; pass integer ids")
        try:
            return idx[task_key]
        except KeyError:
            raise ValueError(f"unknown task key {task_key!r}") from None

    def score_keyed(self, task_keys: Sequence[str], X
                    ) -> Tuple[jnp.ndarray, str]:
        """Key-routed scoring under ONE state snapshot: the keys are
        resolved and scored against the same model version, so a
        concurrent hot-swap cannot skew ids between resolution and the
        code gather (a ``resolve()`` + ``score()`` pair cannot promise
        that).  Returns (margins, version id) like :meth:`score`."""
        st = self._state                       # the one atomic read
        if st.key_index is None:
            raise ValueError("model has no task_keys; use score()")
        try:
            ids = jnp.asarray([st.key_index[k] for k in task_keys],
                              jnp.int32)
        except KeyError as e:
            raise ValueError(f"unknown task key {e.args[0]!r}") from None
        return self._score_with(st, ids, X), st.version

    def _score_dispatch(self, st: _ServeState, wid, wX):
        """Route one padded wave to the configured hot path.  All three
        return (preds, ok) from a single dispatch; f32-XLA stays the
        historical :func:`_score_batch` bit-for-bit."""
        if self.kernel == "pallas":
            return _fused_score()(st.U, st.C, st.Cs, wid, wX, st.model.m)
        if st.Cs is not None:
            return _score_batch_quant(st.U, st.C, st.Cs, wid, wX,
                                      st.model.m)
        return _score_batch(st.U, st.C, wid, wX, st.model.m)

    def _score_with(self, st: _ServeState, task_ids, X) -> jnp.ndarray:
        """Score a batch against ONE state snapshot (hot-swap safe).

        Each step runs under a profiler-only span (``repro.obs.annotate``):
        ``serve.score`` around the call, holding ``serve.prepare`` (the
        conversions and shape checks), ``serve.pad`` (the last wave's
        padding), one ``serve.dispatch`` per wave and ``serve.sync`` (the
        validity flag's read-back, which waits for the device)."""
        B = self.B
        with annotate("serve.score"):
            with annotate("serve.prepare"):
                ids = jnp.asarray(task_ids, jnp.int32)
                X = jnp.asarray(X)
                if (ids.ndim != 1 or X.ndim != 2
                        or X.shape[0] != ids.shape[0]):
                    raise ValueError(f"want ids (N,) and X (N, p); got "
                                     f"{ids.shape} and {X.shape}")
                if X.shape[1] != st.model.p:
                    raise ValueError(f"feature dim {X.shape[1]} != model "
                                     f"p {st.model.p}")
            n = ids.shape[0]
            if n == 0:
                return jnp.zeros((0,), X.dtype)
            # SLO latency window: the jit'd dispatch loop + the one host
            # validity sync — perf_counter (monotonic, high-res) measured
            # on the host AROUND the device work, not inside it
            t0 = time.perf_counter()
            outs: List[jnp.ndarray] = []
            oks: List[jnp.ndarray] = []
            one_wave = n == B                  # the common serving case:
            for lo in range(0, n, B):          # no slicing, no reassembly
                wid = ids if one_wave else ids[lo:lo + B]
                wX = X if one_wave else X[lo:lo + B]
                fill = B - wid.shape[0]
                if fill:                       # pad the last wave
                    with annotate("serve.pad"):
                        wid = jnp.concatenate(
                            [wid, jnp.zeros((fill,), wid.dtype)])
                        wX = jnp.concatenate(
                            [wX, jnp.zeros((fill, wX.shape[1]), wX.dtype)])
                with annotate("serve.dispatch"):
                    preds, ok = self._score_dispatch(st, wid, wX)
                outs.append(preds[:B - fill] if fill else preds)
                oks.append(ok)
            # ONE host round-trip validates every wave of the call
            with annotate("serve.sync"):
                ok_all = oks[0] if len(oks) == 1 else \
                    jnp.all(jnp.stack(oks))
                valid = bool(ok_all)
            if not valid:
                self._bad.inc()
                raise ValueError(f"task ids outside [0, {st.model.m}) in "
                                 "this model version")
            self._lat.observe(time.perf_counter() - t0)
            self._req.inc(n)
            self._wav.inc(len(outs))
            return outs[0] if len(outs) == 1 else jnp.concatenate(outs)

    def score(self, task_ids, X) -> Tuple[jnp.ndarray, str]:
        """Score a mixed-task request batch: (N,) margins + the version
        id that served it.

        ``task_ids (N,)`` int, ``X (N, p)``.  Processed in padded waves
        of ``batch_size`` through the jit'd hot path; the served state
        is read ONCE for the whole call (hot-swap atomicity).
        """
        st = self._state                       # the one atomic read
        return self._score_with(st, task_ids, X), st.version

    def predict(self, task_ids, X) -> Tuple[jnp.ndarray, str]:
        """Margins mapped to predictions: identity for squared loss,
        P(y = +1) for logistic.  One state read serves BOTH the scores
        and the loss mapping (same hot-swap atomicity as ``score``)."""
        st = self._state                       # the one atomic read
        margins = self._score_with(st, task_ids, X)
        if st.model.loss == "logistic":
            return jax.nn.sigmoid(margins), st.version
        return margins, st.version

    # -- onboarding ----------------------------------------------------
    def onboard(self, task_key: Optional[str], X, y, l2: float = 1e-3,
                iters: int = 25) -> int:
        """Few-shot onboard an unseen task and serve it immediately.

        Fits the r-code in the frozen subspace (``FactoredModel
        .onboard``) and atomically swaps the grown model in.  Returns
        the new task's id.  Concurrent onboards serialize on the
        server lock so none is lost.
        """
        with trace_span("serve.onboard", task_key=task_key):
            with self._lock:
                model = self._state.model.onboard(task_key, X, y, l2=l2,
                                                  iters=iters)
                self._install(self._prepare(model, self._state.step))
        return model.m - 1
