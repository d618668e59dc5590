"""What the drivers, the layer metrics and ``run.py`` share."""
from __future__ import annotations

import contextlib
import dataclasses
import json
import os
from typing import Any, Dict, List, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Ctx:
    """One run of one cell."""
    workload: Dict[str, Any]       # the BENCHMARK.json entry
    config: Dict[str, Any]         # bench/configs/<config>.json
    traffic: Dict[str, Any]        # bench/traffic/<traffic>.json
    seed: int
    seconds: float
    trace: bool
    devices: List[Any]             # the chips this cell uses

    @property
    def chips(self) -> int:
        return len(self.devices)

    def limits(self) -> Dict[str, float]:
        """This cell's limits on the numbers its check compares."""
        return self.config["limits"][self.traffic["kind"]]

    def annotate(self, name: str):
        """A host span in the profiler's trace (traced runs only)."""
        if not self.trace:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)


def prng_key(seed: int):
    """A JAX key from any non-negative seed, also one above 2**32."""
    import jax
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed {seed} must be >= 0")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32) if seed >> 32 else key


@dataclasses.dataclass
class Check:
    """One number the correctness check compares, beside its limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value == self.value and self.value <= self.limit


def peak_bytes(devices) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None
