#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once and print its result line.

    python bench/run.py --workload sp2048.solve --seed 7 --seconds 20 --trace 0

The cell's configuration, traffic mix and per-layer metric readers are
found by name (``bench/configs``, ``bench/traffic``, ``bench/drivers/<kind>``,
``bench/layer_metrics/<metric>.py``).  The run stops at once, with no
result, when JAX's first device is not a TPU or there are fewer chips than
the cell asks for.  Set-up (data from the seed, the system, a warm-up of
every shape the window uses) counts from process start to the window.
``--trace 1`` traces the window with the profiler and reports the cell's
per-layer metrics instead of its end-to-end ones.  After the window the
program's state is freed and the check compares what the window produced
with the plain reference; each number compared is printed beside its limit
as the last lines of stderr and under ``checks``, the last key of the
result line, which is the last line of stdout.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(_ROOT, "src"), _ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench.common import BENCH, ROOT, Ctx, load_json, peak_bytes  # noqa: E402


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_cell(name: str):
    """(BENCHMARK.json, workload entry, configuration, traffic mix)."""
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; have {sorted(cells)}")
    wl = cells[name]
    cfg = {c["name"]: c for c in bench["configs"]}[wl["config"]]
    config = load_json(os.path.join(ROOT, cfg["file"]))
    traffic = load_json(os.path.join(BENCH, "traffic",
                                     wl["traffic"] + ".json"))
    return bench, wl, config, traffic


def cell_metrics(bench, wl, kind: str) -> list:
    """The entries of ``bench[kind]`` that this cell reports."""
    out = []
    e2e = {m["name"] for m in cell_metrics(bench, wl, "end_to_end")} \
        if kind == "per_layer" else None
    for m in bench[kind]:
        if "workloads" in m:
            if wl["name"] in m["workloads"]:
                out.append(m)
        elif kind == "end_to_end" or m["moves"] in e2e:
            out.append(m)
    return out


def reader(name: str):
    path = os.path.join(BENCH, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_layer_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def device_peaks(kind: str) -> dict:
    table = load_json(os.path.join(BENCH, "peaks.json"))
    if kind not in table:
        raise SystemExit(f"no peaks for device kind {kind!r} in "
                         f"bench/peaks.json; have {sorted(table)}")
    return table[kind]


class CompileCounter:
    """Compilations and compile-cache loads, by phase (none belong in a
    window).  JAX reports both as a backend compile; a cache hit inside
    one marks it as a load."""

    def __init__(self):
        import jax.monitoring
        self.phase = "set-up"
        self.events = []                  # (phase, kind, function, seconds)
        self._hit = False
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self._hit = True

    def _duration(self, event, duration, fun_name="?", **_):
        if event == "/jax/core/compile/backend_compile_duration":
            kind = "cache load" if self._hit else "compile"
            self.events.append((self.phase, kind, fun_name, duration))
            self._hit = False

    def count(self, phase: str, kind: str) -> int:
        return sum(1 for p, k, _, _ in self.events if p == phase and k == kind)

    def summary(self) -> str:
        out = []
        for phase in ("set-up", "window"):
            for kind in ("compile", "cache load"):
                evs = [e for e in self.events if e[:2] == (phase, kind)]
                if evs:
                    top = max(evs, key=lambda e: e[3])
                    out.append(f"{phase}: {len(evs)} {kind}s, "
                               f"{sum(e[3] for e in evs):.3f} s, longest "
                               f"{top[2]} {top[3]:.3f} s")
        return "; ".join(out) or "no compilations"


class GcTimer:
    """Python's garbage collections while ``on``: count and pause times."""

    def __init__(self):
        self.on, self.pauses, self._t = False, [], None
        gc.callbacks.append(self._cb)

    def _cb(self, phase, info):
        if not self.on:
            return
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.pauses.append((info["generation"],
                                time.perf_counter() - self._t))
            self._t = None

    def summary(self) -> str:
        if not self.pauses:
            return "no collections"
        by_gen = {}
        for g, s in self.pauses:
            by_gen.setdefault(g, []).append(s)
        return ", ".join(f"gen{g}: {len(v)} taking {sum(v):.4f} s, longest "
                         f"{max(v):.4f} s" for g, v in sorted(by_gen.items()))


def _start_trace(trace_dir: str) -> None:
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0          # host spans only, no per-call cost
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def run_cell(ctx: Ctx, bench: dict, peaks: dict, t0: float) -> dict:
    """Set up, measure, check; returns the result line's object."""
    import jax
    from bench import trace_reduce
    driver = importlib.import_module("bench.drivers." + ctx.traffic["kind"])
    compiles = CompileCounter()
    collections = GcTimer()
    cell = driver.setup(ctx)
    # Every run enters its window with the set-up's garbage collected.
    gc.collect()
    setup_s = time.perf_counter() - t0
    log(f"set-up {setup_s:.3f} s")
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if ctx.trace else None
    try:
        if trace_dir:
            _start_trace(trace_dir)
        compiles.phase = "window"
        collections.on = True
        try:
            driver.window(cell)
        finally:
            compiles.phase = "after"
            collections.on = False
            if trace_dir:
                jax.profiler.stop_trace()
        trace = trace_reduce.load(trace_dir) if trace_dir else None
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    memory = peak_bytes(ctx.devices)
    e2e = dict(driver.end_to_end(cell), setup_s=setup_s)
    counters = driver.counters(cell)
    log("window: " + json.dumps({k: v for k, v in counters.items()
                                 if not isinstance(v, list)}))
    log(f"compilations: {compiles.summary()}")
    log(f"garbage collections in the window: {collections.summary()}")
    driver.release(cell)
    gc.collect()
    checks = driver.check(cell)
    failed = driver.failed(cell)

    device = {"platform": ctx.devices[0].platform,
              "kind": ctx.devices[0].device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": memory}
    if ctx.trace:
        rec = {"trace": trace, "counters": counters, "config": ctx.config,
               "chips": ctx.chips, "peaks": peaks}
        metrics = {}
        for m in cell_metrics(bench, ctx.workload, "per_layer"):
            value = reader(m["name"])(rec)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device.update(busy_s=trace_reduce.busy_s(trace) or 0.0,
                      window_s=trace.window_s)
    else:
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell_metrics(bench, ctx.workload, "end_to_end")}
    result = {"correct": failed == 0 and all(c.ok for c in checks),
              "attempted": driver.attempted(cell), "failed": failed,
              "metrics": metrics, "device": device}
    if ctx.trace:
        result["breakdown"] = {"device_ops": trace_reduce.top_ops(trace),
                               "idle_gaps": trace_reduce.idle_gaps(trace)}
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in checks}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench, wl, config, traffic = load_cell(args.workload)

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        log(f"bench: JAX found no TPU (first device: {devs[0].platform}); "
            f"refusing to run")
        return 2
    if len(devs) < wl["chips"]:
        log(f"bench: {args.workload} needs {wl['chips']} chips, JAX found "
            f"{len(devs)}")
        return 2
    peaks = device_peaks(devs[0].device_kind)
    from repro.compile_cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache(ROOT)}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    ctx = Ctx(workload=wl, config=config, traffic=traffic, seed=args.seed,
              seconds=args.seconds, trace=bool(args.trace),
              devices=devs[:wl["chips"]])
    result = run_cell(ctx, bench, peaks, T_START)
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
