"""Consolidated benchmark runner — one command, every ``BENCH_*.json``.

    python benchmarks/run.py --quick          # trimmed sweep, all modules
    python benchmarks/run.py --only sampler   # one module
    scripts/bench.sh --quick                  # the shell wrapper

Each module is one paper table/figure (or one perf trajectory line) exposing
``run() -> [(name, us, derived), ...]``; this driver prints the CSV stream,
then writes/updates the module's ``BENCH_<name>.json`` with a SHARED schema:

    {"bench": <name>, "git_sha": ..., "wall_s": ..., "tokens_per_s": ...,
     "quick": ..., "schema": 1, "rows": [[name, us, derived], ...], ...}

Modules that already emit a richer record (sampler, data) keep their fields —
the shared keys are merged on top. ``--quick`` exports ``BENCH_QUICK=1``,
which quick-aware modules honor. Roofline/dry-run tables are separate
(launch/dryrun.py produces them; benchmarks/roofline.py formats them) because
they need the 512-device host platform, which the benches must NOT inherit.

Each module runs in a child process of its own, one at a time, and this
parent never imports JAX: a process that has touched JAX holds the chip, and
a child that needs it would then fail or hang.
"""
from __future__ import annotations

import argparse
import importlib
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (REPO, os.path.join(REPO, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

# (name, module, paper anchor) — the json file is BENCH_<name>.json
MODULES = [
    ("pipeline", "benchmarks.bench_pipeline", "Table 1"),
    ("rtlda", "benchmarks.bench_rtlda", "Fig 5"),
    ("scaling", "benchmarks.bench_scaling", "Fig 6"),
    ("quality", "benchmarks.bench_quality", "Fig 1/7/8"),
    ("train", "benchmarks.bench_train", "Trainer"),
    ("data", "benchmarks.bench_data", "Fig 3/4"),
    ("sampler", "benchmarks.bench_sampler", "§9 alias-MH"),
    ("shard", "benchmarks.bench_shard", "§10 model parallel"),
    ("fleet", "benchmarks.bench_fleet", "§13 serving fleet"),
]


def git_sha():
    try:
        return subprocess.check_output(
            ["git", "rev-parse", "--short", "HEAD"], cwd=REPO,
            text=True).strip()
    except Exception:  # noqa: BLE001 — sha is best-effort metadata
        return None


def run_child(modpath: str) -> None:
    """Child side: run one bench module and hand its rows to the parent as
    the last line of stdout."""
    mod = importlib.import_module(modpath)
    rows = [(n, float(us), str(d)) for n, us, d in mod.run()]
    print(json.dumps(rows), flush=True)


def run_module(name: str, modpath: str, anchor: str, sha) -> bool:
    """Run one bench module in its own process, print its CSV, stamp its
    BENCH json. Returns success."""
    if "jax" in sys.modules:
        raise RuntimeError("benchmarks/run.py imported JAX in its parent; a "
                           "child would then find the chip held")
    json_path = os.path.join(REPO, f"BENCH_{name}.json")
    t0 = time.perf_counter()
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--child", modpath],
        cwd=REPO, stdout=subprocess.PIPE, text=True)
    out = proc.stdout.rstrip("\n").split("\n")
    print("\n".join(out[:-1]), flush=True)
    if proc.returncode != 0:
        print(f"# {name}({anchor}) FAILED (exit {proc.returncode})",
              flush=True)
        return False
    rows = [tuple(r) for r in json.loads(out[-1])]
    wall = time.perf_counter() - t0
    for n, us, derived in rows:
        print(f"{n},{us:.1f},{derived}", flush=True)
    print(f"# {name}({anchor}) done in {wall:.1f}s", flush=True)

    record = {}
    if os.path.exists(json_path):
        try:
            with open(json_path) as f:
                record = json.load(f)
        except Exception:  # noqa: BLE001 — stale/corrupt record: overwrite
            record = {}
    record.update({
        "bench": name,
        "git_sha": sha,
        "wall_s": round(wall, 3),
        "tokens_per_s": record.get("tokens_per_s"),
        "quick": bool(os.environ.get("BENCH_QUICK")),
        "schema": 1,
        "rows": rows,
    })
    with open(json_path, "w") as f:
        json.dump(record, f, indent=2)
    return True


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="trimmed sweeps (exports BENCH_QUICK=1)")
    ap.add_argument("--only", default=None,
                    help="comma-separated module names "
                         f"({', '.join(n for n, _, _ in MODULES)})")
    ap.add_argument("--child", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.child:
        run_child(args.child)
        return
    if args.quick:
        os.environ["BENCH_QUICK"] = "1"
    os.chdir(REPO)    # module-written BENCH_*.json land at the repo root
    enable_compile_cache()     # through the environment, for the children

    work = MODULES
    if args.only:
        names = {s.strip() for s in args.only.split(",")}
        unknown = names - {n for n, _, _ in MODULES}
        if unknown:
            ap.error(f"unknown bench module(s): {sorted(unknown)}")
        work = [m for m in MODULES if m[0] in names]

    sha = git_sha()
    failures = sum(
        0 if run_module(name, modpath, anchor, sha) else 1
        for name, modpath, anchor in work)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
