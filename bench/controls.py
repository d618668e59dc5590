#!/usr/bin/env python3
"""Readings that set a cell's correctness limits: the program and its control.

    python bench/controls.py --workload sp2048.solve --seeds 1 2 3 \
        --control-seeds 1 2 3 --seconds 3

For each seed it builds the cell as ``run.py`` does, runs its window (a
short one for a serve cell; one whole call for a solve cell) and reads the
numbers the check compares: the program's readings, whose largest is the
limit's lower end.  For each control seed it puts the cell's control in the
program's place and reads the same numbers: their smallest is the upper
end.  The control (``config["control"]``) is one precision below the one
the configuration states: for a solve the plain reference in bfloat16, for
a serve cell the program's own int8 code table.  One JSON line per reading
on stdout.
Needs the cell's chips, like ``run.py``; it never runs within a benchmark
run.
"""
from __future__ import annotations

import argparse
import gc
import importlib
import json
import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(_ROOT, "src"), _ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench.common import ROOT, Ctx  # noqa: E402
from bench.run import load_cell  # noqa: E402


def control_call(driver_kind: str, cell):
    """Put the cell's control in the program's place in ``cell``: for a
    solve, the plain reference in bfloat16, run anew by every call; for a
    serve cell, the program's own lower-precision path, its server with
    the configuration's ``control_kwargs`` (an int8 code table)."""
    import jax
    import jax.numpy as jnp
    if driver_kind == "solve":
        from bench.drivers.solve import reference
        rounds = cell.ctx.config["hp"]["rounds"]

        def call():
            W = reference(cell, dtype=jnp.bfloat16)
            jax.block_until_ready(W)
            return W, rounds, None
        cell.call = call
    else:
        from repro.serve.mtl import FactoredModel, MTLServer
        sv = cell.ctx.config["serve"]
        server = MTLServer(FactoredModel(U=cell.U, s=cell.s, V=cell.V),
                           batch_size=cell.B,
                           **dict(sv["server_kwargs"], **sv["control_kwargs"]))
        cell.server, cell.score = server, server.score
        for n in range(1, cell.B + 1):
            jax.block_until_ready(cell.score(cell.ids[:n], cell.pool[:n])[0])


def reading(ctx: Ctx, control: bool) -> dict:
    driver = importlib.import_module("bench.drivers." + ctx.traffic["kind"])
    cell = driver.setup(ctx)
    if control:
        control_call(ctx.traffic["kind"], cell)
    if ctx.traffic["kind"] == "solve":
        cell.calls = [cell.call()]
        cell.window_s = 1.0
    else:
        driver.window(cell)
    driver.release(cell)
    gc.collect()
    return {"seed": ctx.seed, "control": control,
            "failed": driver.failed(cell), **driver.readings(cell),
            "limits": ctx.limits()}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--any-device", action="store_true",
                    help="run on whatever JAX finds (CPU tests)")
    args = ap.parse_args(argv)
    bench, wl, config, traffic = load_cell(args.workload)
    import jax
    devs = jax.devices()
    if not args.any_device and devs[0].platform != "tpu":
        print("controls: JAX found no TPU", file=sys.stderr)
        return 2
    if not args.any_device:
        from repro.compile_cache import enable_compile_cache
        enable_compile_cache(ROOT)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    for seeds, control in ((args.seeds, False), (args.control_seeds, True)):
        for seed in seeds:
            ctx = Ctx(workload=wl, config=config, traffic=traffic, seed=seed,
                      seconds=args.seconds, trace=False,
                      devices=devs[:wl["chips"]])
            print(json.dumps(reading(ctx, control)), flush=True)
            gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
