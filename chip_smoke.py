#!/usr/bin/env python3
"""Smoke run of the solve -> factorize -> serve path on a TPU.

    python chip_smoke.py              # one chip: every phase below
    python chip_smoke.py --chips 4    # four chips: the tasks-sharded solve
                                      # and serving against one device, only

Phases, each a function of a size (``FULL`` on the chip; the tests
rehearse them at ``TINY`` on the CPU):

* ``solve``      proxgd, squared loss, p=2048 m=768 n=64, rank-4 W*, 50
                 scanned rounds on the mesh backend (the large-p spec of
                 ``benchmarks/solver_bench.py``), with round metrics;
* ``solve_sgd``  the same problem with minibatches and local steps, so
                 the fused ``prox_step`` kernel runs;
* ``solve_raw``  dgsp with logistic loss at the same widths: the raw
                 worker path, so the ``mtl_grad`` kernel runs;
* ``serve``      factorize the solve at rank r, save, load and serve it
                 through ``MTLServer`` over kernel {xla, pallas} x code
                 table {f32, int8}, onboard one task, hot-reload a second
                 version; and the serving acceptance point p=2048 m=4096
                 r=4 with factors seeded as ``benchmarks/serve_bench.py``
                 seeds them;
* ``mesh``       (``--chips N``) the solve on N chips over the tasks axis
                 and on an (N/2)x2 tasks x data mesh, and a tasks-sharded
                 server, each against the same work on one device.

Every score is checked against a NumPy float32 reference, every kernel
against a NumPy float64 one.  The script stops at once when JAX's first
device is not a TPU, exits non-zero when any phase fails, and prints as
its last line ``{"ok": true, "device": {...}}``.  It writes only under
``results/chip_smoke/``; JAX's compile cache goes where
``repro.compile_cache`` puts it.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
for _path in (ROOT, os.path.join(ROOT, "src")):   # repro + benchmarks
    if _path not in sys.path:
        sys.path.insert(0, _path)
OUT_DIR = os.path.join(ROOT, "results", "chip_smoke")

FULL = dict(p=2048, m=768, n=64, r=4, noise=0.05, chunks=4, rounds=50,
            lam=0.0013, sv_rank=8, batch_size=32, local_steps=2,
            dgsp_rounds=8, serve_m=4096, serve_B=64, waves=4, shots=16)
TINY = dict(p=64, m=24, n=160, r=2, noise=0.05, chunks=1, rounds=12,
            lam=0.02, sv_rank=2, batch_size=16, local_steps=2,
            dgsp_rounds=3, serve_m=40, serve_B=8, waves=3, shots=8)

# The solve's excess risk is at most this fraction of the zero
# predictor's.  At n=64 << p=2048, fifty proxgd rounds from zero leave
# most of it (0.78 on a v5e, at either f32 matmul precision): the bound
# catches a solve that learns nothing or diverges.
RISK_FRACTION_MAX = 0.9
# The Gram cache is used only where this many times its bytes, plus X,
# fit the device: an ahead-of-time compile of the round program for a
# v5e needed 18.04 GB of its 15.75 GB for the 12.9 GB cache of FULL.
GRAM_WORKING_SET = 1.5
# Kernels against NumPy float64, relative to the reference's max |.|.
KERNEL_REL_TOL = 1e-3
# Scores against NumPy float32: the chip's default f32 matmul takes one
# bfloat16 pass (8 mantissa bits), relative to the wave's max |score|.
# A quantized table adds its half step per code entry, weighted by
# |X @ U| (the bound of ``quantize_codes``).
SCORE_REL_TOL = 1e-2
# Mesh solves against the one-device solve, and served scores sharded
# vs not, relative to the reference's max |.|.  The solves differ only
# in summation order (the data axis splits each task's rows).
MESH_W_REL_TOL = 1e-3
MESH_SCORE_REL_TOL = 1e-4
# The Gram path against the raw path on four chips: both compute in full
# float32 (the cache built at matmul precision 'highest' and read by a
# float32 multiply-and-reduce, the raw kernel on the vector unit), so
# they differ in summation order only, as the mesh layouts do.
GRAM_W_REL_TOL = 1e-3
# Onboarding a task drawn from the learned subspace recovers its code.
ONBOARD_CODE_TOL = 5e-2


def log(msg: str) -> None:
    print(msg, flush=True)


def _rel_err(got, ref) -> float:
    import numpy as np
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    return float(np.max(np.abs(got - ref)) / max(np.max(np.abs(ref)), 1e-30))


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def _timed(fn):
    import jax
    t0 = time.perf_counter()
    out = fn()
    jax.block_until_ready(getattr(out, "W", out))   # an MTLResult's W
    return out, time.perf_counter() - t0


# ---------------------------------------------------------------------------
# problems
# ---------------------------------------------------------------------------
def make_data(sz: dict, seed: int, task: str = "regression"):
    """(Xs, ys, W*, Sigma) of the §5 simulation at ``sz``, on device."""
    import jax
    from repro.data.synthetic import SimSpec, generate
    spec = SimSpec(p=sz["p"], m=sz["m"], r=sz["r"], n=sz["n"],
                   noise=sz["noise"], task=task)
    return generate(jax.random.PRNGKey(seed), spec,
                    sample_chunks=sz["chunks"])


def gram_fits(sz: dict, hbm_bytes) -> bool:
    """Whether the squared-loss Gram cache (m p^2 floats) fits a device
    of ``hbm_bytes`` (None: unknown, as on the CPU -> no)."""
    m, n, p = sz["m"], sz["n"], sz["p"]
    need = GRAM_WORKING_SET * m * p * p * 4 + m * n * p * 4
    return hbm_bytes is not None and need <= hbm_bytes


def device_hbm_bytes(dev=None):
    import jax
    stats = (dev or jax.devices()[0]).memory_stats()
    return None if not stats else stats.get("bytes_limit")


def solve_hp(sz: dict) -> dict:
    return dict(method="proxgd", rounds=sz["rounds"], lam=sz["lam"],
                init="zeros", sv_rank=sz["sv_rank"])


def _ledger_ok(res, rounds: int) -> None:
    _check(res.comm.rounds == rounds,
           f"ledger counts {res.comm.rounds} rounds, want {rounds}")


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------
def phase_solve(sz: dict, seed: int, hbm_bytes) -> dict:
    """proxgd on the mesh backend over the visible devices, cold then
    warm; checks the excess risk against RISK_FRACTION_MAX."""
    import jax
    import jax.numpy as jnp
    import repro
    from repro.core.methods import MTLProblem
    from repro.data.synthetic import excess_risk_regression
    from repro.runtime import task_mesh

    Xs, ys, Wstar, Sigma = make_data(sz, seed)
    m, p = sz["m"], sz["p"]
    gram = gram_fits(sz, hbm_bytes)
    hbm = "unknown" if hbm_bytes is None else f"{hbm_bytes / 1e9:.2f} GB"
    path = ("gram=True (the cached Gram path)" if gram else
            "gram=False (the raw path: the cache does not fit)")
    log(f"solve: Gram cache m*p^2*4 = {m * p * p * 4 / 1e9:.2f} GB, "
        f"x{GRAM_WORKING_SET} plus X against the device's {hbm} -> {path}")
    prob = MTLProblem.make(Xs, ys, "squared", A=2.0, r=sz["r"], gram=gram)
    mesh = task_mesh(min(len(jax.devices()), m))
    hp = solve_hp(sz)

    def run():
        return repro.solve(prob, backend="mesh", mesh=mesh, metrics=True,
                           **hp)

    res, cold = _timed(run)
    res, warm = _timed(run)
    W = res.W
    _check(W.shape == (p, m), f"W shape {W.shape}")
    _check(bool(jnp.all(jnp.isfinite(W))), "W has non-finite entries")
    _ledger_ok(res, sz["rounds"])
    risk = float(excess_risk_regression(W, Wstar, Sigma))
    risk0 = float(excess_risk_regression(jnp.zeros_like(W), Wstar, Sigma))
    sv_exact = int(res.extras["sv_exact_rounds"])
    sv_metric = int(res.extras["metrics"]["sv_exact"][-1])
    log(f"solve: cold {cold:.3f} s, warm {warm:.3f} s, "
        f"rounds {sz['rounds']}, sv_exact {sv_exact}/{sz['rounds']} "
        f"(metrics {sv_metric})")
    log(f"solve: excess risk {risk:.6g}, zero predictor {risk0:.6g}, "
        f"ratio {risk / risk0:.4g} (bound {RISK_FRACTION_MAX})")
    _check(sv_exact == sv_metric, "sv_exact metric disagrees with carry")
    _check(risk <= RISK_FRACTION_MAX * risk0,
           f"excess risk {risk} above {RISK_FRACTION_MAX} x {risk0}")
    return dict(prob=prob, res=res, Wstar=Wstar, Sigma=Sigma, mesh=mesh)


def phase_solve_sgd(sz: dict, seed: int, prob, mesh, Wstar, Sigma):
    """The solve with minibatches + local steps (the fused prox_step
    kernel on a TPU); checks the kernel against NumPy on the chip."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import repro
    from repro.data.synthetic import excess_risk_regression
    from repro.kernels.prox_step import prox_step

    hp = solve_hp(sz)

    def run():
        return repro.solve(prob, backend="mesh", mesh=mesh,
                           batch_size=sz["batch_size"],
                           local_steps=sz["local_steps"], batch_seed=seed,
                           **hp)

    res, cold = _timed(run)
    res, warm = _timed(run)
    _check(bool(jnp.all(jnp.isfinite(res.W))), "sgd W non-finite")
    _ledger_ok(res, sz["rounds"])
    risk = float(excess_risk_regression(res.W, Wstar, Sigma))
    risk0 = float(excess_risk_regression(jnp.zeros_like(res.W), Wstar,
                                         Sigma))
    log(f"solve_sgd: cold {cold:.3f} s, warm {warm:.3f} s, "
        f"batch {sz['batch_size']} x {sz['local_steps']} local steps, "
        f"excess risk {risk:.6g} (ratio {risk / risk0:.4g})")
    _check(risk <= RISK_FRACTION_MAX * risk0,
           f"sgd excess risk {risk} above {RISK_FRACTION_MAX} x {risk0}")

    # the kernel at the phase's per-step shape against float64 NumPy
    B = sz["batch_size"]
    X = prob.Xs[:, :B]
    y = prob.ys[:, :B]
    W = res.W.T
    k = jax.random.split(jax.random.PRNGKey(seed + 7), 2)
    Z = W + 0.01 * jax.random.normal(k[0], W.shape)
    Q = 0.01 * jax.random.normal(k[1], W.shape)
    eta, rho, inv_m, l2 = 0.5, 0.1, 1.0 / sz["m"], 1e-3
    got = prox_step(X, y, W, Z, Q, eta=eta, rho=rho, inv_m=inv_m, l2=l2)
    Xd, yd, Wd = (np.asarray(a, np.float64) for a in (X, y, W))
    r = np.einsum("lnp,lp->ln", Xd, Wd) - yd
    g = np.einsum("lnp,ln->lp", Xd, r) / B + l2 * Wd
    ref = Wd - eta * (g * inv_m + np.asarray(Q, np.float64)
                      + rho * (Wd - np.asarray(Z, np.float64)))
    err = _rel_err(got, ref)
    log(f"solve_sgd: prox_step kernel {tuple(X.shape)} vs float64 "
        f"rel err {err:.3e} (tol {KERNEL_REL_TOL})")
    _check(err <= KERNEL_REL_TOL, f"prox_step rel err {err}")
    return res


def phase_solve_raw(sz: dict, seed: int) -> None:
    """dgsp with logistic loss: the raw worker path (mtl_grad on a
    TPU); checks the training loss fell and the kernel against NumPy."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import repro
    from repro.core.methods import MTLProblem
    from repro.kernels.mtl_grad import task_gradients
    from repro.runtime import task_mesh

    Xs, ys, _, _ = make_data(sz, seed + 1, task="classification")
    prob = MTLProblem.make(Xs, ys, "logistic", A=2.0, r=sz["r"])
    mesh = task_mesh(min(len(jax.devices()), sz["m"]))
    rounds = sz["dgsp_rounds"]

    def run():
        return repro.solve(prob, method="dgsp", backend="mesh", mesh=mesh,
                           rounds=rounds)

    res, cold = _timed(run)
    res, warm = _timed(run)
    _check(bool(jnp.all(jnp.isfinite(res.W))), "dgsp W non-finite")
    _ledger_ok(res, rounds)
    margins = jnp.einsum("mnp,pm->mn", Xs, res.W)
    train = float(jnp.mean(jax.nn.softplus(-ys * margins)))
    log(f"solve_raw: cold {cold:.3f} s, warm {warm:.3f} s, dgsp "
        f"{rounds} rounds, logistic train loss {train:.6g} "
        f"(zero predictor {np.log(2):.6g})")
    _check(train < np.log(2), f"train loss {train} not below log 2")

    got = task_gradients(Xs, ys, res.W.T, loss="logistic")
    Xd, yd, Wd = (np.asarray(a, np.float64) for a in (Xs, ys, res.W.T))
    pred = np.einsum("mnp,mp->mn", Xd, Wd)
    r = -yd * 0.5 * (1.0 - np.tanh(0.5 * yd * pred))   # -y sigmoid(-y pred)
    ref = np.einsum("mnp,mn->mp", Xd, r) / sz["n"]
    err = _rel_err(got, ref)
    log(f"solve_raw: mtl_grad kernel {tuple(Xs.shape)} vs float64 "
        f"rel err {err:.3e} (tol {KERNEL_REL_TOL})")
    _check(err <= KERNEL_REL_TOL, f"mtl_grad rel err {err}")


def _requests(seed: int, B: int, m: int, p: int):
    import numpy as np
    rng = np.random.default_rng(seed)
    return (rng.integers(0, m, size=B).astype(np.int32),
            rng.standard_normal((B, p), dtype=np.float32))


def _score_bound(U, C, ids, X, code_dtype: str):
    """NumPy float32 reference scores and the per-row error bound."""
    import numpy as np
    U, C, X = (np.asarray(a, np.float32) for a in (U, C, X))
    Z = X @ U
    ref = np.sum(Z * C[ids], axis=1)
    bound = np.full(ref.shape, SCORE_REL_TOL * max(np.max(np.abs(ref)),
                                                    1e-30))
    if code_dtype != "f32":
        from repro.kernels.mtl_score.ref import _QMAX
        half_step = 0.5 * np.max(np.abs(C), axis=1) / _QMAX[code_dtype]
        bound = bound + half_step[ids] * np.sum(np.abs(Z), axis=1)
    return ref, bound


def _serve_waves(server, model, sz: dict, seed: int, tag: str) -> dict:
    """``waves`` score calls of B requests, each checked; returns the
    first and the later calls' seconds and the worst error/bound."""
    import jax
    import numpy as np
    times, worst = [], 0.0
    for w in range(sz["waves"]):
        ids, X = _requests(seed * 1000 + w, sz["serve_B"], model.m, model.p)
        t0 = time.perf_counter()
        preds, version = server.score(ids, X)
        jax.block_until_ready(preds)
        times.append(time.perf_counter() - t0)
        _check(version == model.version, f"{tag}: served {version}")
        ref, bound = _score_bound(model.U, model.codes, ids, X,
                                  server.code_dtype)
        err = np.abs(np.asarray(preds, np.float64) - ref)
        worst = max(worst, float(np.max(err / bound)))
        _check(bool(np.all(err <= bound)),
               f"{tag}: wave {w} max err {float(np.max(err)):.3e} over "
               f"bound")
    return dict(first_s=times[0],
                later_s=float(np.mean(times[1:])) if len(times) > 1 else None,
                err_over_bound=worst)


def _kernel_on_chip(server) -> None:
    """On a TPU the fused scorer must compile to a Mosaic kernel, never
    the interpreter."""
    import jax
    if server.kernel != "pallas" or jax.default_backend() != "tpu":
        return
    from repro.serve.mtl import _fused_score
    st = server._state
    B = server.B
    ids = jax.numpy.zeros((B,), jax.numpy.int32)
    X = jax.numpy.zeros((B, st.model.p), jax.numpy.float32)
    text = _fused_score().lower(st.U, st.C, st.Cs, ids, X,
                                st.model.m).compile().as_text()
    _check("tpu_custom_call" in text, "fused scorer is not a TPU kernel")


def phase_serve(sz: dict, seed: int, res, res_next) -> None:
    """factorize -> save -> load -> MTLServer (kernel x code dtype) on
    the solve's model and the acceptance-point model; onboard, then
    maybe_reload."""
    import numpy as np
    from repro.serve.mtl import FactoredModel, MTLServer
    from benchmarks.serve_bench import _synthetic_model

    store = os.path.join(OUT_DIR, "store")
    shutil.rmtree(store, ignore_errors=True)
    r = sz["r"]
    model = res.factorize(rank=r)
    step = model.save(store)
    step_l, loaded = FactoredModel.load(store)
    _check(step_l == step and loaded.version == model.version,
           "store round trip changed the model")
    accept = _synthetic_model(sz["p"], sz["serve_m"], r, seed)
    for name, mdl in (("solve", loaded), ("accept", accept)):
        for kernel in ("xla", "pallas"):
            for dt in ("f32", "int8"):
                tag = f"{name} m={mdl.m} {kernel}/{dt}"
                server = MTLServer(mdl, batch_size=sz["serve_B"],
                                   kernel=kernel, code_dtype=dt)
                _kernel_on_chip(server)
                t = _serve_waves(server, mdl, sz, seed, tag)
                later = "n/a" if t["later_s"] is None else \
                    f"{t['later_s'] * 1e3:.3f} ms"
                log(f"serve: {tag} B={sz['serve_B']}: first "
                    f"{t['first_s'] * 1e3:.3f} ms, later {later}, "
                    f"err/bound {t['err_over_bound']:.3g}")

    # onboard a task drawn from the learned subspace, then hot-reload
    server = MTLServer(loaded, batch_size=sz["serve_B"], kernel="pallas",
                       code_dtype="int8")
    server.maybe_reload(store)             # adopts the store step
    rng = np.random.default_rng(seed + 11)
    c_true = np.asarray(loaded.codes[0]) + 0.1 * rng.standard_normal(r)
    Xn = rng.standard_normal((sz["shots"], sz["p"]), dtype=np.float32)
    yn = Xn @ (np.asarray(loaded.U) @ c_true)
    new_id = server.onboard(None, Xn, yn, l2=1e-6)
    c_fit = np.asarray(server.model.codes[new_id])
    code_err = float(np.max(np.abs(c_fit - c_true))
                     / np.max(np.abs(c_true)))
    log(f"serve: onboard id {new_id} from {sz['shots']} shots, code rel "
        f"err {code_err:.3e} (tol {ONBOARD_CODE_TOL})")
    _check(new_id == loaded.m, f"onboarded id {new_id}")
    _check(code_err <= ONBOARD_CODE_TOL, f"onboard code err {code_err}")
    _serve_waves(server, server.model, sz, seed + 1, "onboarded")
    ids = np.full((sz["serve_B"],), new_id, np.int32)
    Xq = rng.standard_normal((sz["serve_B"], sz["p"]), dtype=np.float32)
    preds, _ = server.score(ids, Xq)
    ref, bound = _score_bound(server.model.U, server.model.codes, ids, Xq,
                              "int8")
    _check(bool(np.all(np.abs(np.asarray(preds) - ref) <= bound)),
           "onboarded task scores out of bound")

    nxt = res_next.factorize(rank=r)
    nxt.save(store)
    swapped = server.maybe_reload(store)
    log(f"serve: maybe_reload -> swapped {swapped}, version "
        f"{server.version} (want {nxt.version})")
    _check(swapped and server.version == nxt.version, "reload did not swap")
    _serve_waves(server, nxt, sz, seed + 2, "reloaded")


def phase_mesh(sz: dict, seed: int, n_chips: int) -> None:
    """The solve on n_chips over the tasks axis and on an
    (n_chips/2) x 2 tasks x data mesh, each against the same solve on
    one device; the default Gram path on n_chips against the raw one; a
    tasks-sharded server against the one-device server; peak bytes per
    device."""
    import jax
    import repro
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core.methods import MTLProblem
    from repro.runtime import task_data_mesh, task_mesh
    from repro.serve.mtl import MTLServer
    from benchmarks.serve_bench import _synthetic_model

    devs = jax.devices()
    _check(len(devs) >= n_chips, f"{len(devs)} devices < {n_chips}")
    Xs, ys, _, _ = make_data(sz, seed)
    # the one-device reference decides the path for every layout
    gram = gram_fits(sz, device_hbm_bytes(devs[0]))
    log(f"mesh: gram={gram} for every layout (decided by one device)")
    prob = MTLProblem.make(Xs, ys, "squared", A=2.0, r=sz["r"], gram=gram)
    hp = solve_hp(sz)
    one, t_one = _timed(lambda: repro.solve(prob, backend="mesh",
                                            mesh=task_mesh(1), **hp))
    log(f"mesh: 1 device: {t_one:.3f} s")
    w2m = one.comm.floats_by_direction("worker->master")
    for label, mesh, shards in (
            (f"{n_chips}x1", task_mesh(n_chips), 1),
            (f"{n_chips // 2}x2", task_data_mesh(2, n_chips), 2)):
        res, secs = _timed(lambda: repro.solve(
            prob, backend="mesh", mesh=mesh, data_shards=shards, **hp))
        per_chip = sz["m"] // mesh.shape["tasks"]
        diff = _rel_err(res.W, one.W)
        ledger_eq = res.comm.ledger() == one.comm.ledger()
        floats = res.extras["collective_floats_per_chip"]
        log(f"mesh: {label} ({per_chip} tasks/chip, data_shards {shards}): "
            f"{secs:.3f} s, W rel err vs 1 device {diff:.3e} "
            f"(tol {MESH_W_REL_TOL}), ledger identical {ledger_eq}, "
            f"collective floats/chip {floats} = {w2m} x {per_chip}: "
            f"{floats == w2m * per_chip}")
        _check(diff <= MESH_W_REL_TOL, f"{label}: W rel err {diff}")
        _check(ledger_eq, f"{label}: ledger differs")
        _check(floats == w2m * per_chip, f"{label}: floats {floats}")
        if shards == 1:
            W_tasks = res.W

    # the default Gram path where a chip holds its share of the cache:
    # data placed on the mesh first, so the cache is built shard by
    # shard (from unsharded data it would be built whole on device 0)
    mesh = task_mesh(n_chips)
    on_mesh = NamedSharding(mesh, P("tasks"))
    gprob = MTLProblem.make(jax.device_put(Xs, on_mesh),
                            jax.device_put(ys, on_mesh), "squared", A=2.0,
                            r=sz["r"])
    res, secs = _timed(lambda: repro.solve(gprob, backend="mesh", mesh=mesh,
                                           **hp))
    rows = sorted((sh.device.id, sh.data.shape[0])
                  for sh in gprob.gram_A.addressable_shards)
    diff = _rel_err(res.W, W_tasks)
    log(f"mesh: {n_chips}x1 Gram path (cache rows per device {rows}): "
        f"{secs:.3f} s, W rel err vs raw path {diff:.3e} "
        f"(tol {GRAM_W_REL_TOL}), ledger identical "
        f"{res.comm.ledger() == one.comm.ledger()}")
    _check([r for _, r in rows] == [sz["m"] // n_chips] * n_chips,
           f"Gram cache rows per device {rows}")
    _check(diff <= GRAM_W_REL_TOL, f"Gram path W rel err {diff}")
    _check(res.comm.ledger() == one.comm.ledger(), "Gram ledger differs")
    del gprob, res

    for name, mdl in (("solve", one.factorize(rank=sz["r"])),
                      ("accept", _synthetic_model(sz["p"], sz["serve_m"],
                                                  sz["r"], seed))):
        for dt in ("f32", "int8"):
            single = MTLServer(mdl, batch_size=sz["serve_B"], code_dtype=dt)
            shard = MTLServer(mdl, batch_size=sz["serve_B"], code_dtype=dt,
                              mesh=mesh)
            worst = 0.0
            for w in range(sz["waves"]):
                ids, X = _requests(seed * 1000 + w, sz["serve_B"], mdl.m,
                                   mdl.p)
                a, _ = single.score(ids, X)
                b, _ = shard.score(ids, X)
                worst = max(worst, _rel_err(b, a))
            log(f"mesh: served {name} m={mdl.m} {dt} sharded over "
                f"{n_chips} vs 1 device: rel err {worst:.3e} "
                f"(tol {MESH_SCORE_REL_TOL})")
            _check(worst <= MESH_SCORE_REL_TOL,
                   f"sharded serving rel err {worst}")

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devs[:n_chips]]
    log("mesh: peak bytes in use per device: "
        + ", ".join(f"{d.id}: {pk}" for d, pk in zip(devs, peaks)))


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------
def run_phases(sz: dict, seed: int, chips: int, hbm_bytes) -> list:
    """Run the phases for ``chips``; returns the names of those that
    failed (their tracebacks go to stderr)."""
    failed = []

    def attempt(name, fn, *args):
        t0 = time.perf_counter()
        try:
            out = fn(*args)
        except Exception:
            traceback.print_exc()
            log(f"phase {name}: FAILED after {time.perf_counter() - t0:.1f} s")
            failed.append(name)
            return None
        log(f"phase {name}: ok in {time.perf_counter() - t0:.1f} s")
        return out

    if chips > 1:
        attempt("mesh", phase_mesh, sz, seed, chips)
        return failed
    solve = attempt("solve", phase_solve, sz, seed, hbm_bytes)
    if solve is None:
        failed += ["solve_sgd", "serve"]
    else:
        sgd = attempt("solve_sgd", phase_solve_sgd, sz, seed, solve["prob"],
                      solve["mesh"], solve["Wstar"], solve["Sigma"])
        nxt = solve["res"] if sgd is None else sgd
        attempt("serve", phase_serve, sz, seed, solve["res"], nxt)
    attempt("solve_raw", phase_solve_raw, sz, seed)
    return failed


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1,
                    help="1: every phase on one chip; N > 1: only the "
                         "N-chip mesh phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (first device: "
              f"{devs[0].platform}); refusing to run", file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devs)} devices",
              file=sys.stderr)
        return 2
    from repro.compile_cache import enable_compile_cache
    log(f"device: {devs[0].device_kind} x {len(devs)}, jax "
        f"{jax.__version__}, compile cache {enable_compile_cache(ROOT)}")
    os.makedirs(OUT_DIR, exist_ok=True)
    t0 = time.perf_counter()
    failed = run_phases(FULL, args.seed, args.chips,
                        device_hbm_bytes(devs[0]))
    log(f"total {time.perf_counter() - t0:.1f} s")
    if failed:
        print(f"chip_smoke: failed phases: {', '.join(failed)}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
