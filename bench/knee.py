#!/usr/bin/env python3
"""Sweep the offered rate of a serve cell to find its knee, once, on the chip.

    python bench/knee.py --workload sp2048.serve-overload --seed 5 \
        --seconds 8 --rates 4000 8000 12000 16000 20000 24000

One process, one set-up; each rate gets a fresh open-loop schedule of
``--seconds``.  A rate is sustained when the requests completed in the
window keep up with those offered and the latency of the window's last
quarter is not growing over its first (no backlog building).  The knee, the
highest sustained rate, goes into the traffic file by hand, with the
cell's rate at four fifths of it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(_ROOT, "src"), _ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench.common import ROOT, Ctx  # noqa: E402
from bench.drivers import serve_open_loop as drv  # noqa: E402
from bench.run import load_cell  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    bench, wl, config, traffic = load_cell(args.workload)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("knee: JAX found no TPU", file=sys.stderr)
        return 2
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache(ROOT)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    ctx = Ctx(workload=wl, config=config, traffic=traffic, seed=args.seed,
              seconds=args.seconds, trace=False, devices=jax.devices()[:1])
    cell = drv.setup(ctx)
    for k, rate in enumerate(args.rates):
        cell.reset_schedule(rate, args.seconds, args.seed + k)
        drv.window(cell)
        lat = drv.latency_s(cell)
        q = len(lat) // 4
        e2e = drv.end_to_end(cell)
        print(json.dumps({
            "rate": rate, "offered": len(lat) / args.seconds, **e2e,
            "p50_first_quarter_ms": float(np.median(lat[:q])) * 1e3,
            "p50_last_quarter_ms": float(np.median(lat[-q:])) * 1e3,
            "mean_batch": drv.counters(cell)["mean_batch"],
            "call_ms_p50": float(np.median(cell.call_s)) * 1e3}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
