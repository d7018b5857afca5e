#!/usr/bin/env python3
"""Run one cell of the chip benchmark once.

    python3 chipbench/run.py --workload train-alias-query --seed 7 \
        --seconds 20 --trace 0

The cell, its configuration and its traffic mix are found by name through
``BENCHMARK.json`` (``chipbench/configs``, ``workloads``, ``traffic``). Set-up
(corpus or model from ``--seed``, compiles or compile-cache loads, the
cell's warm-up) is timed as ``setup_s``; the window then runs for
``--seconds``. With ``--trace 0`` the last line on stdout holds the cell's
end-to-end metrics; with ``--trace 1`` the window runs under the profiler
and the line holds its per-layer metrics (each read by
``chipbench/metrics/<name>.py``), the device's busy and window seconds and
a breakdown. Every run compares what the window's path produced with the
plain reference and prints the compared numbers with their limits, last on
stderr and as the ``checks`` key of the result.

Without a TPU, or with fewer chips than the cell asks for, the command
exits non-zero and prints no result. ``--rehearse`` runs the same path on
the CPU at the cell file's tiny sizes and prints a rehearsal line that
holds no metric (``JAX_PLATFORMS=cpu``).
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from harness import env  # noqa: E402


class Tracer:
    """The profiler around the window, into a directory under TMPDIR."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="chipbench-trace-")

    def start(self):
        import jax

        jax.profiler.start_trace(self.dir)

    def stop(self):
        import jax

        jax.profiler.stop_trace()

    def close(self):
        shutil.rmtree(self.dir, ignore_errors=True)


def load_reader(name: str):
    """``chipbench/metrics/<name>.py``'s ``read``."""
    path = os.path.join(env.BENCH_DIR, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on the CPU; prints no metric")
    args = ap.parse_args(argv)

    env.use_program()
    if not args.rehearse:
        env.use_compile_cache()
    spec = env.load_spec(args.workload)
    devs = env.devices(int(spec.cell["chips"]), args.rehearse)
    counter = env.CompileCounter()
    tracer = Tracer() if args.trace else None
    driver = importlib.import_module(f"harness.{spec.workload['driver']}")
    try:
        out = driver.run(spec, args.seed, args.seconds, devs, T_START,
                         counter, tracer, args.rehearse)
        reduced = None
        if tracer is not None:
            from harness import trace as trace_mod

            tr = trace_mod.read(tracer.dir)
            lo, hi = trace_mod.window_of(tr, out["window_span"])
            reduced = trace_mod.reduce(tr, lo, hi, "chipbench.")
    finally:
        if tracer is not None:
            tracer.close()

    checks = out["checks"]
    correct = env.first_failure(checks) is None
    metrics = {}
    if tracer is None:
        for m in spec.end_to_end():
            value, unit = out["e2e"][m["name"]]
            metrics[m["name"]] = {"value": value, "unit": unit}
    else:
        kind = "TPU v5 lite" if args.rehearse else devs[0].device_kind
        ctx = {"counters": out["counters"], "trace": reduced,
               "device_kind": kind}
        for m in spec.per_layer():
            value = load_reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    if args.rehearse:
        # names only: a CPU run gives no device number
        env.emit({"rehearsal": True, "correct": correct,
                  "attempted": out["attempted"], "failed": out["failed"],
                  "platform": devs[0].platform,
                  "would_report": sorted(metrics)}, checks)
        return 0
    result = {"correct": correct, "attempted": int(out["attempted"]),
              "failed": int(out["failed"]), "metrics": metrics,
              "device": env.device_info(devs, out["peak"])}
    if tracer is not None:
        result["device"]["busy_s"] = reduced["busy_s"]
        result["device"]["window_s"] = reduced["window_s"]
        result["breakdown"] = reduced["breakdown"]
    env.log("counters: " + json.dumps(
        {k: v for k, v in out["counters"].items() if not isinstance(v, list)}))
    env.emit(result, checks)
    return 0


if __name__ == "__main__":
    sys.exit(main())
