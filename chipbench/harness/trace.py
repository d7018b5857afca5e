"""Reduction of a profiler trace to the benchmark's device numbers.

A trace is read once into plain events (``Event``: name, start, end in ns,
and the HLO module it ran in); everything below works on those, so a check
can feed a small synthetic trace. On each device plane the ``XLA Ops`` line
holds one event per operation the chip ran (where a plane has no such line,
every event on it counts) and the ``XLA Modules`` line one event per program
run, named after the program; host planes hold the benchmark's own
``TraceAnnotation`` spans, which label what the host was doing.

* busy: the union of the device's operation intervals inside the window;
  idle share = 1 − busy / window, averaged over the chips;
* exposed collective: the part of the window in which a collective runs on
  a chip and no other operation does;
* program time: device time of each program the window ran (its
  operations' time inside its runs);
* breakdown: the operations that took most device time, and the longest
  idle gaps, each labelled by the innermost host span covering it.
"""
from __future__ import annotations

import dataclasses
import glob
import os
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

COLLECTIVE_MARKS = ("all-reduce", "all-gather", "collective-permute",
                    "reduce-scatter", "all-to-all", "ppermute", "psum",
                    "send", "recv")


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float     # ns
    end: float       # ns
    module: str = ""


@dataclasses.dataclass
class Trace:
    devices: List[List[Event]]   # per chip: its operations
    host: List[Event]            # host spans
    # per chip: its program runs (empty where the trace has none: then the
    # operations' own module names group them)
    programs: List[List[Event]] = dataclasses.field(default_factory=list)


def read(trace_dir: str) -> Trace:
    """Events of the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(files[-1])
    devices, programs, host, cpu_ops = [], [], [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") or (
                plane.name.startswith("/device:")
                and "CUSTOM" not in plane.name):
            lines = list(plane.lines)
            ops = [ln for ln in lines if ln.name == "XLA Ops"] or lines
            evs = []
            for ln in ops:
                for e in ln.events:
                    if e.duration_ns <= 0:
                        continue
                    stats = dict(e.stats)
                    evs.append(Event(e.name, e.start_ns, e.end_ns,
                                     str(stats.get("hlo_module", ""))))
            devices.append(evs)
            programs.append([Event(e.name, e.start_ns, e.end_ns, e.name)
                             for ln in lines if ln.name == "XLA Modules"
                             for e in ln.events if e.duration_ns > 0])
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    if e.duration_ns <= 0:
                        continue
                    host.append(Event(e.name, e.start_ns, e.end_ns))
                    stats = dict(e.stats)
                    if "hlo_op" in stats:
                        cpu_ops.append(Event(e.name, e.start_ns, e.end_ns,
                                             str(stats.get("hlo_module", ""))))
    # a CPU run (a rehearsal) has no device plane: its XLA operations run
    # on host threads, and stand in for one device
    return Trace(devices or [cpu_ops], host, programs if devices else [])


def union(intervals: Iterable[Tuple[float, float]], lo: float, hi: float
          ) -> List[Tuple[float, float]]:
    """Sorted disjoint union of intervals, clipped to [lo, hi]."""
    out: List[List[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(intervals: Sequence[Tuple[float, float]]) -> float:
    return float(sum(e - s for s, e in intervals))


def subtract(a: Sequence[Tuple[float, float]],
             b: Sequence[Tuple[float, float]]) -> float:
    """Length of union ``a`` not covered by union ``b`` (both disjoint)."""
    total, j = 0.0, 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                total += b[k][0] - cur
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            total += e - cur
    return total


def is_collective(name: str) -> bool:
    low = name.lower()
    return any(m in low for m in COLLECTIVE_MARKS)


def window_of(trace: Trace, span: str) -> Tuple[float, float]:
    """Bounds of the host span ``span`` (the measured window)."""
    spans = [e for e in trace.host if e.name == span]
    if not spans:
        raise ValueError(f"no host span {span!r} in the trace")
    return min(e.start for e in spans), max(e.end for e in spans)


def label_at(trace: Trace, t: float, prefix: str) -> str:
    """The innermost host span named ``prefix``* that covers time ``t``."""
    best: Optional[Event] = None
    for e in trace.host:
        if e.name.startswith(prefix) and e.start <= t <= e.end:
            if best is None or (e.end - e.start) < (best.end - best.start):
                best = e
    return best.name if best is not None else "no benchmark span"


def reduce(trace: Trace, lo: float, hi: float, label_prefix: str
           ) -> Dict[str, object]:
    """Device numbers of the window [lo, hi] (ns)."""
    window = hi - lo
    if window <= 0 or not trace.devices:
        raise ValueError("empty window or no device plane in the trace")
    busy, exposed = [], []
    op_time: Dict[str, float] = {}
    by_module: Dict[str, float] = {}
    gaps: List[Tuple[float, float]] = []
    for i, evs in enumerate(trace.devices):
        u = union(((e.start, e.end) for e in evs), lo, hi)
        busy.append(length(u))
        coll = union(((e.start, e.end) for e in evs
                      if is_collective(e.name)), lo, hi)
        comp = union(((e.start, e.end) for e in evs
                      if not is_collective(e.name)), lo, hi)
        exposed.append(subtract(coll, comp))
        for e in evs:
            d = min(e.end, hi) - max(e.start, lo)
            if d > 0:
                op_time[e.name] = op_time.get(e.name, 0.0) + d
        runs = trace.programs[i] if i < len(trace.programs) else []
        spans: Dict[str, List[Tuple[float, float]]] = {}
        for e in (runs or evs):
            # "jit_f(1234)", a program run's name, is "jit_f"
            spans.setdefault(e.module.split("(")[0], []).append(
                (e.start, e.end))
        for mod, ivs in spans.items():
            m = union(ivs, lo, hi)
            by_module[mod] = by_module.get(mod, 0.0) + (
                length(m) - subtract(m, u) if runs else length(m))
        edges = [lo] + [x for iv in u for x in iv] + [hi]
        gaps.extend((edges[i], edges[i + 1])
                    for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i])
    n = len(trace.devices)
    busy_s = sum(busy) / n / 1e9
    top_ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:10]
    top_gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:10]
    return {
        "window_s": window / 1e9,
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / (window / 1e9),
        "exposed_collective_share": sum(exposed) / n / window,
        "by_module": {m: t / n / 1e9 for m, t in by_module.items()},
        "breakdown": {
            "device_ops": [[name, t / n / 1e9] for name, t in top_ops],
            "idle_gaps": [[label_at(trace, (s + e) / 2, label_prefix),
                           (e - s) / 1e9] for s, e in top_gaps],
        },
    }
