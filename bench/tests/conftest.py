"""CPU rehearsals of the benchmark at tiny sizes.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests

Nothing here needs a chip: the harness's look for a TPU is skipped and the
rest of a run is driven on the CPU at sizes a test run holds.
"""
import copy
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

TINY_PROBLEM = {"p": 64, "m": 24, "n": 32, "r": 2, "corr_decay": 1.0,
                "noise": 0.05, "sample_chunks": 2}


def tiny_config(name: str) -> dict:
    """A committed configuration cut to a test's size: every width and
    round count shrunk, the method and the limits kept."""
    from bench.common import load_json
    cfg = copy.deepcopy(load_json(os.path.join(ROOT, "bench", "configs",
                                               name + ".json")))
    cfg["problem"] = dict(TINY_PROBLEM, task=cfg["problem"]["task"])
    rounds = 12 if cfg["method"] == "proxgd" else 4
    cfg["hp"]["rounds"] = rounds
    cfg["reference_hp"]["rounds"] = rounds
    if cfg["method"] == "proxgd":
        cfg["hp"].update(lam=0.02, sv_rank=2)
        cfg["reference_hp"]["lam"] = 0.02
    if "serve" in cfg:
        cfg["serve"].update(m=24, r=2, batch_size=8)
    return cfg


def tiny_ctx(workload: str, seed: int = 3, seconds: float = 0.3,
             trace: bool = False, devices=None):
    import jax
    from bench.common import Ctx
    from bench.run import load_cell
    _, wl, config, traffic = load_cell(workload)
    traffic = dict(traffic)
    if traffic["kind"] == "serve_open_loop":
        traffic.update(rate_per_s=3000.0, pool_rows=64)
    devs = devices or jax.devices()[:wl["chips"]]
    return Ctx(workload=wl, config=tiny_config(wl["config"]), traffic=traffic,
               seed=seed, seconds=seconds, trace=trace, devices=devs)


@pytest.fixture
def ctx_for():
    return tiny_ctx
