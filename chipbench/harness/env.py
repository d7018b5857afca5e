"""Process set-up shared by every cell: where things are, the compile cache,
the device check, seeds, compile counting and the result line."""
from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import sys
import time
from typing import Any, Dict, Optional

import numpy as np

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
# a fixed path inside the checkout: the path is part of the cache's key
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


def use_program() -> None:
    """Make the program under test (``src/repro``) importable."""
    src = os.path.join(ROOT, "src")
    if src not in sys.path:
        sys.path.insert(0, src)


def use_compile_cache() -> str:
    """Point JAX (and the program, which reads the same variable) at the
    checkout's cache, and cache every program however quick its compile.
    Must run before JAX is imported."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"
    return CACHE_DIR


def load_json(*parts: str) -> Any:
    with open(os.path.join(BENCH_DIR, *parts)) as f:
        return json.load(f)


@dataclasses.dataclass
class Spec:
    """One cell as ``BENCHMARK.json`` and its data files describe it."""

    bench: Dict[str, Any]      # the whole BENCHMARK.json
    cell: Dict[str, Any]       # its ``workloads`` entry
    config: Dict[str, Any]     # configs/<config>.json
    workload: Dict[str, Any]   # workloads/<cell>.json
    traffic: Dict[str, Any]    # traffic/<mix>.json

    @property
    def name(self) -> str:
        return self.cell["name"]

    def end_to_end(self):
        """The cell's end-to-end metrics, in ``BENCHMARK.json`` order."""
        return [m for m in self.bench["end_to_end"]
                if self.name in m.get("workloads", [self.name])]

    def per_layer(self):
        """The per-layer metrics whose readers this cell runs."""
        mine = {m["name"] for m in self.end_to_end()}
        return [m for m in self.bench["per_layer"]
                if (self.name in m["workloads"] if "workloads" in m
                    else m["moves"] in mine)]


def load_spec(workload: str) -> Spec:
    """The cell ``workload`` of ``BENCHMARK.json``."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {c["name"]: c for c in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"chipbench: no workload {workload!r} in "
                         f"BENCHMARK.json ({', '.join(cells)})")
    cell = cells[workload]
    files = {c["name"]: c["file"] for c in bench["configs"]}
    path = files.get(cell["config"],
                     os.path.join("chipbench", "configs",
                                  f"{cell['config']}.json"))
    with open(os.path.join(ROOT, path)) as f:
        config = json.load(f)
    return Spec(bench=bench, cell=cell, config=config,
                workload=load_json("workloads", f"{workload}.json"),
                traffic=load_json("traffic", f"{cell['traffic']}.json"))


def derive_seeds(seed: int, n: int = 8) -> list:
    """``n`` independent 31-bit seeds from one ``--seed`` of any size."""
    state = np.random.SeedSequence(int(seed)).generate_state(n)
    return [int(s) & 0x7FFF_FFFF for s in state]


def devices(chips: int, rehearse: bool):
    """The devices of this run. A measured run needs ``chips`` TPU chips and
    fails without them; a rehearsal runs on the CPU and never measures."""
    import jax

    devs = jax.devices()
    if rehearse:
        if devs[0].platform != "cpu":
            raise SystemExit("chipbench: --rehearse runs on the CPU only")
        return devs[:chips] if len(devs) >= chips else devs
    if devs[0].platform != "tpu":
        raise SystemExit(f"chipbench: needs a TPU; JAX found "
                         f"{devs[0].platform} ({devs[0].device_kind})")
    if len(devs) < chips:
        raise SystemExit(f"chipbench: the cell needs {chips} chips; JAX "
                         f"found {len(devs)}")
    return devs[:chips]


class CompileCounter:
    """Backend compiles and persistent-cache hits, from JAX's own events."""

    def __init__(self):
        import jax

        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1
                self.compile_s += duration

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


def peak_bytes(devs) -> int:
    """Peak device memory on the fullest chip (0 where not reported)."""
    peaks = []
    for d in devs:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def device_info(devs, peak: int) -> Dict[str, Any]:
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(peak)}


@contextlib.contextmanager
def annotate(name: str):
    """A host span in the profiler's trace (no cost when not tracing)."""
    import jax

    with jax.profiler.TraceAnnotation(name):
        yield


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


_T0 = time.perf_counter()


def stage(name: str) -> None:
    """A progress line on stderr, with seconds since the process began."""
    log(f"[{time.perf_counter() - _T0:8.2f} s] {name}")


def emit(result: Dict[str, Any], checks: Dict[str, Dict[str, float]]
         ) -> None:
    """Print the compared numbers as the last lines on stderr, then the
    result as the last line on stdout, with ``checks`` as its last key."""
    for name, c in checks.items():
        log(f"check {name} = {c['value']!r} (limit {c['limit']!r})")
    out = dict(result)
    out["checks"] = checks
    print(json.dumps(out), flush=True)


def now() -> float:
    return time.perf_counter()


def first_failure(checks: Dict[str, Dict[str, float]]) -> Optional[str]:
    for name, c in checks.items():
        if not (c["value"] <= c["limit"]):
            return name
    return None
