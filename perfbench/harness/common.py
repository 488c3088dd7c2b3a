"""Shared pieces of the chip benchmark: where its files are, how a cell
is looked up by name, the chip's peaks, percentiles and the compile
clock.  Nothing here imports the program under test."""
from __future__ import annotations

import json
import math
import os
import time
from typing import Any, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
# The persistent compile cache lives at this fixed path inside the
# checkout: the path is part of JAX's cache key, so it never moves.
CACHE_DIR = os.path.join(BENCH_DIR, ".jax_cache")


def configure_cache() -> None:
    """Point JAX's persistent compilation cache at the benchmark's
    directory (and the program's own entry points with it) and cache
    every executable, however quickly it compiled."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def bench_file(*parts: str) -> str:
    return os.path.join(BENCH_DIR, *parts)


class Cell:
    """One entry of ``workloads`` with its configuration, traffic and
    limits, each read from the file named after it."""

    def __init__(self, spec: dict, name: str) -> None:
        by_name = {w["name"]: w for w in spec["workloads"]}
        if name not in by_name:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json")
        self.workload = by_name[name]
        self.name = name
        cfg = {c["name"]: c for c in spec["configs"]}[self.workload["config"]]
        self.config = load_json(os.path.join(ROOT, cfg["file"]))
        self.traffic = load_json(
            bench_file("traffic", self.workload["traffic"] + ".json"))
        self.limits = load_json(bench_file("limits", name + ".json"))
        self.chips = int(self.workload["chips"])
        self.end_to_end = [m for m in spec["end_to_end"]
                           if name in m.get("workloads", [name])]
        reported = {m["name"] for m in self.end_to_end}
        self.per_layer = [m for m in spec["per_layer"]
                          if (name in m["workloads"] if "workloads" in m
                              else m["moves"] in reported)]


def load_spec() -> dict:
    return load_json(os.path.join(ROOT, "BENCHMARK.json"))


def peaks_for(device_kind: str) -> dict:
    """The chip's published peaks; a kind not in the table is an error."""
    table = load_json(bench_file("harness", "peaks.json"))
    if device_kind not in table["devices"]:
        raise KeyError(f"device kind {device_kind!r} is not in the peak "
                       f"table ({sorted(table['devices'])})")
    return table["devices"][device_kind]


def percentile(samples: List[float], q: float) -> float:
    """Nearest-rank percentile (q in [0, 100]) of unsorted samples."""
    if not samples:
        raise ValueError("percentile of no samples")
    s = sorted(samples)
    rank = max(1, math.ceil(q / 100.0 * len(s)))
    return s[min(rank, len(s)) - 1]


def mean(xs: List[float]) -> Optional[float]:
    return sum(xs) / len(xs) if xs else None


def seed32(seed: int, stream: int = 0) -> int:
    """A 31-bit key for JAX's PRNG from any whole-number seed (seeds
    may exceed 32 signed bits)."""
    import numpy as np
    return int(np.random.default_rng([seed, stream]).integers(2 ** 31))


_COMPILE_EVENTS = {
    "/jax/core/compile/backend_compile_duration": "compile",
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "trace",
}
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class CompileClock:
    """Seconds JAX spends tracing/lowering and compiling (backend
    compile, including loads from the persistent cache), the number of
    backend compiles, and persistent-cache hits, summed from JAX's own
    monitoring events."""

    def __init__(self) -> None:
        import jax
        self._jax = jax
        self.totals = {"compile": 0.0, "trace": 0.0, "compiles": 0,
                       "cache_hits": 0}
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        kind = _COMPILE_EVENTS.get(event)
        if kind is not None:
            self.totals[kind] += secs
            if kind == "compile":
                self.totals["compiles"] += 1

    def _event(self, event: str, **_) -> None:
        if event == _CACHE_HIT_EVENT:
            self.totals["cache_hits"] += 1

    def snapshot(self) -> Dict[str, float]:
        return dict(self.totals)

    def since(self, snap: Dict[str, float]) -> Dict[str, float]:
        return {k: self.totals[k] - snap[k] for k in snap}

    def close(self) -> None:
        self._jax.monitoring.unregister_event_duration_listener(
            self._duration)
        self._jax.monitoring.unregister_event_listener(self._event)


def say(msg: str) -> None:
    """A progress line on standard error (stdout ends with the result)."""
    import sys
    print(f"[bench {time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr,
          flush=True)
