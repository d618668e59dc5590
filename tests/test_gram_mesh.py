"""The Gram worker path on a 4x1 tasks mesh, and its precision.

The mesh checks run in one subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=4 (the parent pytest
process keeps its single-device view); the tests read its JSON report.
The precision checks walk the jaxprs of the Gram-path entry points of
``repro.core.worker_ops`` in this process.
"""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import worker_ops
from repro.core.losses import get_loss
from repro.core.methods import MTLProblem

P, M, N, R = 32, 8, 6, 2
ROUNDS, LAM, ETA = 30, 0.02, 0.05

SCRIPT = textwrap.dedent(f"""
    import json
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    assert len(jax.devices()) == 4, jax.devices()
    import repro
    from repro.core.methods import MTLProblem
    from repro.data.synthetic import SimSpec, generate
    from repro.obs.tracing import default_tracer
    from repro.runtime import task_mesh

    mesh = task_mesh(4)
    on_tasks = NamedSharding(mesh, P("tasks"))
    Xs, ys, _, _ = generate(jax.random.PRNGKey(11),
                            SimSpec(p={P}, m={M}, r={R}, n={N}))
    Xs, ys = jax.device_put(Xs, on_tasks), jax.device_put(ys, on_tasks)
    default_tracer().clear()
    prob = MTLProblem.make(Xs, ys, "squared", A=2.0, r={R})
    spans = [e for e in default_tracer().events()
             if e["name"] == "problem.gram"]
    rows = sorted((sh.device.id, sh.data.shape[0])
                  for sh in prob.gram_A.addressable_shards)
    res = repro.solve(prob, method="proxgd", backend="mesh", mesh=mesh,
                      rounds={ROUNDS}, lam={LAM}, eta={ETA}, init="zeros")
    print(json.dumps({{
        "X": np.asarray(Xs).tolist(), "y": np.asarray(ys).tolist(),
        "W": np.asarray(res.W).tolist(), "rows": rows,
        "spans": [{{"attrs": e["attrs"], "dur_s": e["dur_s"]}}
                  for e in spans],
        "impl": res.extras.get("backend")}}))
""")


@pytest.fixture(scope="module")
def mesh_run():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stdout + out.stderr
    return json.loads(out.stdout.strip().splitlines()[-1])


def reference_proxgd(X, y):
    """Plain proxgd in float32 NumPy: from zeros, the gradient of
    (1/m) sum_j (1/2n) ||X_j w_j - y_j||^2 from the raw designs, and an
    exact-SVD shrink at eta m lam each round."""
    X, y = np.asarray(X, np.float32), np.asarray(y, np.float32)
    m, n, p = X.shape
    W = np.zeros((p, m), np.float32)
    for _ in range(ROUNDS):
        resid = np.einsum("jni,ij->jn", X, W) - y
        G = np.einsum("jni,jn->ij", X, resid) / np.float32(n * m)
        U, S, Vt = np.linalg.svd(W - np.float32(ETA * m) * G,
                                 full_matrices=False)
        W = ((U * np.maximum(S - np.float32(ETA * m * LAM), 0)) @ Vt
             ).astype(np.float32)
    return W


def test_mesh_gram_proxgd_matches_a_float32_reference(mesh_run):
    W = np.asarray(mesh_run["W"], np.float64)
    R_ = reference_proxgd(mesh_run["X"], mesh_run["y"]).astype(np.float64)
    assert np.linalg.norm(R_) > 0
    rn = np.linalg.norm(R_, axis=0)
    gap = np.max(np.linalg.norm(W - R_, axis=0)
                 / np.maximum(rn, np.median(rn)))
    # Both sides compute in float32 and differ only in the order of their
    # sums (A_j w_j from the cache against X_j^T (X_j w_j), a subspace
    # shrink against a full SVD): 3.5e-6 after 30 rounds.  The reference
    # with the cache and W rounded to bfloat16 before each product, as a
    # TPU's default matmul rounds them, reads 4.4e-3.
    assert gap < 1e-4, gap


def test_cache_holds_a_quarter_of_the_tasks_per_device(mesh_run):
    assert mesh_run["rows"] == [[d, M // 4] for d in range(4)]


def test_problem_gram_span_lands_in_the_ring(mesh_run):
    (span,) = mesh_run["spans"]
    assert span["attrs"] == {"bytes_per_chip": (M // 4) * (P * P + P) * 4,
                             "shards": 4}
    assert span["dur_s"] > 0


def test_problem_gram_span_on_one_device():
    from repro.obs.tracing import default_tracer
    Xs = jnp.ones((M, N, P), jnp.float32)
    ys = jnp.ones((M, N), jnp.float32)
    default_tracer().clear()
    MTLProblem.make(Xs, ys, "squared")
    MTLProblem.make(Xs, ys, "squared", gram=False)
    spans = [e for e in default_tracer().events()
             if e["name"] == "problem.gram"]
    assert [e["attrs"] for e in spans] == [
        {"bytes_per_chip": M * (P * P + P) * 4, "shards": 1}]


# -- precision: every product on the Gram cache at HIGHEST -----------------
def _dots(jaxpr):
    """Every dot_general equation of a closed jaxpr, sub-jaxprs included."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            out.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            out.extend(_dots(sub))
    return out


def _gram_data():
    k = jax.random.PRNGKey(0)
    Xs = jax.random.normal(k, (M, N, P), jnp.float32)
    ys = jax.random.normal(jax.random.fold_in(k, 1), (M, N), jnp.float32)
    A, b = worker_ops.gram_stats(Xs, ys)
    return {"Xs": Xs, "ys": ys, "gram_A": A, "gram_b": b}


SQ = get_loss("squared")
W0 = jnp.ones((P, M), jnp.float32) * 0.01
U0 = jnp.linalg.qr(jnp.ones((P, R)) + jnp.eye(P, R))[0]
GRAM_ENTRY_POINTS = {
    "gram_stats": lambda d: worker_ops.gram_stats(d["Xs"], d["ys"]),
    "gram_stats_2_shards": lambda d: worker_ops.gram_stats(
        d["Xs"], d["ys"], data_shards=2),
    "grad_columns": lambda d: worker_ops.grad_columns(SQ, W0, d),
    "newton_columns": lambda d: worker_ops.newton_columns(SQ, W0, d),
    "ridge_columns": lambda d: worker_ops.ridge_columns(d, 0.1),
    "prox_columns": lambda d: worker_ops.prox_columns(
        SQ, d, W0, W0, W0, rho=1.0, m=M),
    "projected_solves": lambda d: worker_ops.projected_solves(SQ, U0, d),
}


@pytest.mark.parametrize("name", sorted(GRAM_ENTRY_POINTS))
def test_gram_path_products_run_at_highest(name):
    jaxpr = jax.make_jaxpr(GRAM_ENTRY_POINTS[name])(_gram_data()).jaxpr
    dots = _dots(jaxpr)
    # the per-round gradient reads the cache by a multiply-and-reduce;
    # a ridge solve has only the solver's own dots
    if name != "grad_columns":
        assert dots
    highest = (jax.lax.Precision.HIGHEST, jax.lax.Precision.HIGHEST)
    assert all(e.params["precision"] == highest for e in dots), [
        (str(e.source_info.name_stack), e.params["precision"]) for e in dots]


def test_gram_gradient_is_a_float32_reduce_under_its_scope():
    jaxpr = jax.make_jaxpr(GRAM_ENTRY_POINTS["grad_columns"])(
        _gram_data()).jaxpr
    assert not _dots(jaxpr)
    (red,) = [e for e in jaxpr.eqns if e.primitive.name == "reduce_sum"]
    assert str(red.source_info.name_stack) == "worker/gram_grad"
    assert red.invars[0].aval.shape == (M, P, P)
    assert red.outvars[0].aval.dtype == jnp.float32


def test_gram_grad_equals_the_raw_gradient():
    d = _gram_data()
    got = worker_ops.grad_columns(SQ, W0, d)
    raw = worker_ops.grad_columns(SQ, W0, d, impl="xla")
    np.testing.assert_allclose(np.asarray(got), np.asarray(raw),
                               rtol=1e-5, atol=1e-6)
