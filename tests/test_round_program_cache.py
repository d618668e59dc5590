"""The process-level cache of compiled round programs (runtime/base.py).

Every ``repro.solve`` builds fresh closures, so ``round_jit`` keys its
cache on the traced program: a later solve that traces to the same
program reuses the compiled one and skips lowering and compiling, and
one whose program differs in anything that decides the executable (a
literal such as ``lam``, the matmul precision, the arguments) stages
anew.  The cached program returns bit for bit what a fresh one does,
and the ledger is recorded by the trace every call still makes.
"""
import gc
import os
import subprocess
import sys
import textwrap
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro.core import solver_names
from repro.core.methods import MTLProblem
from repro.data.synthetic import SimSpec, generate
from repro.obs import default_tracer
from repro.runtime import base

jax.config.update("jax_platform_name", "cpu")

SPEC = SimSpec(p=10, m=8, r=2, n=24)
# a fixed step: the default rule bakes a data-derived float into the
# program, which then differs with every dataset
HP = dict(method="proxgd", rounds=3, lam=0.05, eta=0.5)


@pytest.fixture(autouse=True)
def cleared():
    base.cache_clear()
    yield
    base.cache_clear()


def _prob(seed):
    Xs, ys, _, _ = generate(jax.random.PRNGKey(seed), SPEC)
    return MTLProblem.make(Xs, ys, "squared", A=2.0, r=SPEC.r)


def _ledger(res):
    return [(e.round, e.direction, e.vectors, e.dim, e.note)
            for e in res.comm.events]


def _same(a, b):
    assert np.array_equal(np.asarray(a.W), np.asarray(b.W))
    assert _ledger(a) == _ledger(b)
    assert a.comm.summary() == b.comm.summary()
    for k in ("collective_floats_per_chip",
              "data_collective_floats_per_chip"):
        assert a.extras[k] == b.extras[k], k


@pytest.mark.parametrize("scan", [True, False])
@pytest.mark.parametrize("backend", ["sim", "mesh"])
def test_same_program_on_other_data_hits(backend, scan):
    repro.solve(_prob(0), backend=backend, scan=scan, **HP)
    first = base.cache_info()
    assert first.hits == 0 and first.misses >= 1
    cached = repro.solve(_prob(1), backend=backend, scan=scan, **HP)
    info = base.cache_info()
    assert info.misses == first.misses
    assert info.hits == first.misses
    base.cache_clear()
    fresh = repro.solve(_prob(1), backend=backend, scan=scan, **HP)
    assert base.cache_info().hits == 0
    _same(cached, fresh)


@pytest.mark.parametrize("change", [dict(lam=0.07), dict(eta=0.4),
                                    dict(rounds=4)])
def test_a_changed_program_misses(change):
    prob = _prob(0)
    repro.solve(prob, **HP)
    other = repro.solve(prob, **{**HP, **change})
    assert base.cache_info().hits == 0
    assert base.cache_info().misses == 2
    base.cache_clear()
    _same(other, repro.solve(prob, **{**HP, **change}))


def test_matmul_precision_misses():
    prob = _prob(0)
    repro.solve(prob, **HP)
    with jax.default_matmul_precision("highest"):
        repro.solve(prob, **HP)
    assert base.cache_info().hits == 0
    assert base.cache_info().misses == 2


def test_other_argument_signature_misses():
    # a Python float traces weakly typed, an np.float32 does not: the
    # jaxpr text is the same, the executables differ
    def double(x):
        return x * 2.0

    weak = base.round_jit(double)(2.0)
    strong = base.round_jit(double)(np.float32(2.0))
    assert base.cache_info().hits == 0
    assert base.cache_info().misses == 2
    assert jax.typeof(weak).weak_type and not jax.typeof(strong).weak_type


OTHER_DEVICE = textwrap.dedent("""
    import jax, numpy as np
    import repro
    from repro.core.methods import MTLProblem
    from repro.data.synthetic import SimSpec, generate
    from repro.runtime import base
    from repro.runtime.mesh import auto_mesh
    Xs, ys, _, _ = generate(jax.random.PRNGKey(0), SimSpec(p=10, m=8, r=2, n=24))
    prob = MTLProblem.make(Xs, ys, "squared", A=2.0, r=2)
    devs = jax.devices()
    W = []
    for d in (devs[0], devs[1], devs[1]):
        res = repro.solve(prob, backend="mesh", mesh=auto_mesh((1,), ("tasks",), [d]),
                          method="proxgd", rounds=3, lam=0.05, eta=0.5)
        assert res.W.devices() == {d}, (res.W.devices(), d)
        W.append(np.asarray(res.W))
    assert all(np.array_equal(W[0], w) for w in W)
    info = base.cache_info()
    assert (info.hits, info.misses) == (1, 2), info
    print("DEVICES_OK")
""")


def test_same_program_on_other_devices_misses():
    # a one-chip mesh over another device prints the same jaxpr
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    out = subprocess.run([sys.executable, "-c", OTHER_DEVICE], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "DEVICES_OK" in out.stdout


def test_cached_program_keeps_no_problem_alive():
    prob = _prob(0)
    alive = (weakref.ref(prob), weakref.ref(prob.Xs))
    res = repro.solve(prob, **HP)
    del prob, res
    gc.collect()
    assert base.cache_info().currsize == 1
    assert [r() for r in alive] == [None, None]


def test_eager_driver_misses_once_per_solve():
    for _ in range(2):
        res = repro.solve(_prob(0), scan=False, **{**HP, "rounds": 5})
        assert res.comm.rounds == 5
    info = base.cache_info()
    assert (info.misses, info.hits) == (1, 1)


@pytest.mark.parametrize("scan", [True, False], ids=["scan", "eager"])
def test_plain_and_checkpointed_share_one_program(tmp_path, scan):
    """A checkpointed solve whose one segment spans every round runs the
    program of the same solve without a checkpoint: one round program
    is staged between them, and W comes out bit for bit the same."""
    kw = {**HP, "rounds": 4, "scan": scan}
    plain = repro.solve(_prob(0), backend="sim", **kw)
    ckpt = repro.solve(_prob(0), backend="sim", checkpoint_every=4,
                       ckpt_dir=str(tmp_path / "store"), **kw)
    assert base.cache_info().misses == 1
    assert ckpt.extras["checkpoint"]["segments_run"] == 1
    np.testing.assert_array_equal(np.asarray(plain.W), np.asarray(ckpt.W))


def test_counters_agree_with_the_span_attribute():
    ring = default_tracer()
    before = len(ring.events())
    for seed in (0, 1, 1):
        repro.solve(_prob(seed), **HP)
    repro.solve(_prob(0), **{**HP, "lam": 0.07})
    hits = [e["attrs"]["hit"] for e in ring.events()[before:]
            if e["name"] == "solve.trace"]
    info = base.cache_info()
    assert hits == [False, True, True, False]
    assert (info.hits, info.misses) == (hits.count(True), hits.count(False))
    staged = [e for e in ring.events()[before:] if e["name"] == "solve.compile"]
    assert len(staged) == info.misses


# each registered solver at hyper-parameters it converges with here
SOLVER_HP = {
    "local": {}, "svd_trunc": {}, "bestrep": {},
    "centralize": {"lam": 0.01, "iters": 100},
    "proxgd": {"lam": 0.01, "rounds": 8, "record_every": 3},
    "accproxgd": {"lam": 0.01, "rounds": 8},
    "admm": {"lam": 0.01, "rho": 0.5, "rounds": 6, "record_every": 2},
    "dfw": {"rounds": 6},
    "dgsp": {"rounds": 3},
    "dnsp": {"rounds": 3, "damping": 0.5, "l2": 1e-3},
    "altmin": {"rounds": 3},
}


@pytest.mark.parametrize("method", solver_names())
def test_every_solver_reuses_its_program(method):
    # the same problem twice: a data-derived step is the same literal
    Xs, ys, Wstar, _ = generate(jax.random.PRNGKey(0),
                                SimSpec(p=30, m=8, r=3, n=50))
    prob = MTLProblem.make(Xs, ys, "squared", A=2.0, r=3)
    hp = dict(SOLVER_HP[method])
    if method == "bestrep":
        hp["U_star"] = jnp.linalg.svd(Wstar, full_matrices=False)[0][:, :3]
    first = repro.solve(prob, method=method, **hp)
    misses = base.cache_info().misses
    again = repro.solve(prob, method=method, **hp)
    assert (base.cache_info().hits, base.cache_info().misses) == (
        misses, misses)
    assert np.all(np.isfinite(np.asarray(again.W)))
    _same(first, again)


def test_constants_of_a_nested_jit_are_part_of_the_key():
    # a nested jit keeps the constants it closed over in its own jaxpr,
    # out of the outer program's constants and printed as bare variables
    def program(shift):
        shift = np.asarray(shift, np.float32)
        return lambda x: jax.jit(lambda y: y + jnp.asarray(shift))(x)

    for shift in ([1.0, 2.0, 3.0], [4.0, 5.0, 6.0]):
        out = base.round_jit(program(shift))(jnp.ones(3))
        assert np.array_equal(np.asarray(out), 1.0 + np.float32(shift))
    assert base.cache_info().hits == 0


def test_program_named_by_an_address_is_not_cached():
    def step(x):
        jax.debug.callback(lambda v: None, x)
        return x + 1.0

    for _ in range(2):
        out = base.round_jit(step)(jnp.zeros(3))
        assert np.array_equal(np.asarray(out), np.ones(3))
    info = base.cache_info()
    assert (info.hits, info.misses, info.currsize) == (0, 2, 0)


def test_tracers_pass_through_uncounted():
    closed = jax.make_jaxpr(base.round_jit(lambda x: x * 2.0))(jnp.ones(3))
    assert "mul" in str(closed)
    assert base.cache_info() == (0, 0, base.cache_info().maxsize, 0)


def test_cache_holds_a_bounded_number_of_programs():
    maxsize = base.cache_info().maxsize
    for c in range(maxsize + 2):
        base.round_jit(lambda x, c=c: x + float(c))(jnp.zeros(2))
    info = base.cache_info()
    assert info.currsize == maxsize and info.misses == maxsize + 2
    base.round_jit(lambda x: x + float(maxsize + 1))(jnp.zeros(2))
    base.round_jit(lambda x: x + 0.0)(jnp.zeros(2))     # evicted first
    assert base.cache_info().hits == 1
