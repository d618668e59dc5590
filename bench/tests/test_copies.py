"""The benchmark's copies of the generators and its plain references,
pinned against the program at tiny sizes on the CPU."""
import numpy as np
import pytest

jax = pytest.importorskip("jax")

from bench.common import prng_key  # noqa: E402

SPEC = dict(p=48, m=12, r=2, n=24, noise=0.05)


@pytest.mark.parametrize("task,chunks", [("regression", 1),
                                         ("regression", 4),
                                         ("classification", 2)])
def test_generator_copy_matches_program(task, chunks):
    from repro.data.synthetic import SimSpec, generate as program
    from bench.gen.synthetic import generate
    key = prng_key(7)
    Xp, yp, Wp, Sp = program(key, SimSpec(task=task, **SPEC),
                             sample_chunks=chunks)
    Xs, ys, Ws, Ss = generate(key, task=task, sample_chunks=chunks, **SPEC)
    np.testing.assert_array_equal(np.asarray(Xs), np.asarray(Xp))
    np.testing.assert_array_equal(np.asarray(Ss), np.asarray(Sp))
    np.testing.assert_allclose(np.asarray(Ws), np.asarray(Wp), atol=1e-5)
    if task == "regression":
        np.testing.assert_allclose(np.asarray(ys), np.asarray(yp),
                                   atol=1e-4)
    else:   # labels flip only where a margin sits on the draw, to rounding
        assert np.mean(np.asarray(ys) == np.asarray(yp)) > 0.99


def test_large_seed_gives_distinct_data():
    from bench.gen.synthetic import generate
    a = generate(prng_key(2**32 + 5), **SPEC)[0]
    b = generate(prng_key(5), **SPEC)[0]
    assert not np.allclose(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(
        np.asarray(a), np.asarray(generate(prng_key(2**32 + 5), **SPEC)[0]))


def test_serve_factor_copy_matches_serve_bench():
    from benchmarks.serve_bench import _synthetic_model
    from bench.gen.serve_factors import serve_factors
    model = _synthetic_model(64, 10, 3, seed=4)
    U, s, V = serve_factors(jax.random.PRNGKey(4), 64, 10, 3)
    for got, want in ((U, model.U), (s, model.s), (V, model.V)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_schedule_rate_and_zipf():
    from bench.gen.schedule import open_loop, zipf_weights
    rng = np.random.default_rng(11)
    due, ids, rows = open_loop(rng, rate=5000.0, seconds=4.0, m=50,
                               zipf_a=1.1, pool_rows=32)
    n = due.shape[0]
    assert abs(n - 20000) < 5 * np.sqrt(20000)
    assert np.all(np.diff(due) >= 0) and due[-1] < 4.0 and due[0] >= 0
    assert ids.dtype == np.int32 and ids.min() >= 0 and ids.max() < 50
    assert rows.min() >= 0 and rows.max() < 32
    # the most popular task takes its Zipf share of the requests
    top = np.bincount(ids, minlength=50).max() / n
    assert abs(top - zipf_weights(50, 1.1)[0]) < 0.02
    again = open_loop(np.random.default_rng(11), rate=5000.0, seconds=4.0,
                      m=50, zipf_a=1.1, pool_rows=32)
    np.testing.assert_array_equal(again[0], due)
    np.testing.assert_array_equal(again[1], ids)


def _problem(task, loss):
    from repro.core.methods import MTLProblem
    from bench.gen.synthetic import generate
    Xs, ys, _, _ = generate(prng_key(3), task=task, **SPEC)
    return Xs, ys, MTLProblem.make(Xs, ys, loss, gram=False, r=2)


def test_proxgd_reference_matches_program():
    import repro
    from bench.reference.solvers import proxgd
    Xs, ys, prob = _problem("regression", "squared")
    res = repro.solve(prob, method="proxgd", rounds=10, lam=0.02,
                      init="zeros", sv_engine="exact")
    eta = res.extras["eta"]
    W = proxgd(Xs, ys, loss="squared", rounds=10, lam=0.02, eta=eta)
    np.testing.assert_allclose(np.asarray(W), np.asarray(res.W), atol=2e-5)
    W_rule = proxgd(Xs, ys, loss="squared", rounds=10, lam=0.02)
    np.testing.assert_allclose(np.asarray(W_rule), np.asarray(res.W),
                               atol=2e-5)


def test_dgsp_reference_matches_program():
    import repro
    from bench.reference.solvers import dgsp
    Xs, ys, prob = _problem("classification", "logistic")
    res = repro.solve(prob, method="dgsp", rounds=3)
    W = dgsp(Xs, ys, loss="logistic", rounds=3)
    np.testing.assert_allclose(np.asarray(W), np.asarray(res.W), atol=1e-4)


def test_score_reference_matches_server():
    from repro.serve.mtl import FactoredModel, MTLServer
    from bench.gen.serve_factors import serve_factors
    from bench.reference import score as ref
    U, s, V = serve_factors(jax.random.PRNGKey(1), 32, 9, 3)
    server = MTLServer(FactoredModel(U=U, s=s, V=V), batch_size=8)
    rng = np.random.default_rng(0)
    pool = rng.standard_normal((16, 32), dtype=np.float32)
    ids = rng.integers(0, 9, 8).astype(np.int32)
    rows = rng.integers(0, 16, 8)
    got, _ = server.score(ids, pool[rows])
    want = ref.scores(np.asarray(U), np.asarray(s), np.asarray(V), pool,
                      ids, rows)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)
    gaps = ref.gaps(np.asarray(got), np.asarray(U), np.asarray(s),
                    np.asarray(V), pool, ids, rows)
    assert gaps.max() < 1e-6
    # the TPU's default matmul rounds x and U to bfloat16: the reference's
    # second arithmetic, which reads that as no gap at all
    bf = ref.scores(np.asarray(U), np.asarray(s), np.asarray(V), pool, ids,
                    rows, bf16=True)
    assert np.abs(bf - want).max() > 1e-4
    assert ref.gaps(bf, np.asarray(U), np.asarray(s), np.asarray(V), pool,
                    ids, rows).max() == 0.0
