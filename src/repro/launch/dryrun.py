import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any other import: jax locks the device
# count at first init, and the production dry-run needs 512 host devices.

"""Multi-pod dry-run: lower + compile every (architecture × shape × mesh) cell.

For each cell we record:
  * compile success, memory_analysis (bytes/device proof-of-fit),
  * cost_analysis (with the documented scan-undercount caveat),
  * jaxpr-walker FLOPs/bytes (scan-aware; the roofline source),
  * collective op mix parsed from compiled HLO,
  * the three roofline terms (see benchmarks/roofline.py for the math).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch smollm-135m --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--out FILE]
"""
import argparse
import json
import time
import traceback


# TPU v5e-class constants (given by the assignment)
PEAK_FLOPS = 197e12          # bf16 FLOP/s per chip
HBM_BW = 819e9               # B/s per chip
ICI_BW = 50e9                # B/s per link


def run_cell(spec, shape: str, multi_pod: bool, skip_jaxpr: bool = False) -> dict:
    import jax

    from repro.dist import analysis
    from repro.launch.mesh import make_production_mesh

    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = int(len(jax.devices()) if multi_pod else 256)
    rec: dict = {
        "arch": spec.arch_id, "shape": shape,
        "mesh": "2x16x16" if multi_pod else "16x16", "chips": chips,
    }
    try:
        cell = spec.cell(shape, mesh, multi_pod)
    except Exception as e:  # noqa: BLE001 — a failed build is a recorded bug
        rec["status"] = "fail"
        rec["error"] = f"build: {type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
        return rec
    if cell is None:
        rec["status"] = "skip"
        rec["reason"] = spec.skip.get(shape, "")
        return rec
    rec["note"] = cell.note
    if cell.extra:
        # analytic side-channel (e.g. dense vs alias sampler HBM traffic,
        # dist/analysis.sampler_epoch_bytes) — recorded even when the
        # lower/compile below fails, so --sampler planning never blocks on
        # a compile bug
        rec.update(cell.extra)
    t0 = time.time()
    try:
        lowered = cell.lower()
        rec["lower_s"] = round(time.time() - t0, 1)
        t0 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t0, 1)

        ma = compiled.memory_analysis()
        rec["bytes_per_device"] = {
            "arguments": int(ma.argument_size_in_bytes),
            "outputs": int(ma.output_size_in_bytes),
            "temps": int(ma.temp_size_in_bytes),
            "aliased": int(ma.alias_size_in_bytes),
            "code": int(ma.generated_code_size_in_bytes),
        }
        live = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
        rec["live_bytes_per_device"] = int(live)
        rec["fits_16gb_hbm"] = bool(live < 16e9)

        ca = compiled.cost_analysis()
        rec["xla_cost"] = {"flops": float(ca.get("flops", 0.0)),
                           "bytes": float(ca.get("bytes accessed", 0.0))}

        hlo_text = compiled.as_text()
        rec["collectives_hlo"] = analysis.collective_bytes(hlo_text)

        if not skip_jaxpr:
            t0 = time.time()
            cost = analysis.trace_cost(cell.fn, *cell.args)
            rec["jaxpr_cost"] = {"flops": cost.flops, "bytes": cost.bytes,
                                 "trace_s": round(time.time() - t0, 1)}
            # fold scan trip counts into the HLO while-body accounting —
            # without this, scan-carried ring traffic counts once per loop
            rec["collectives_hlo_folded"] = analysis.collective_bytes(
                hlo_text, while_trips=analysis.hlo_collective_counts(cost))
        rec["model_flops"] = cell.model_flops
        rec["model_coll_bytes"] = cell.model_coll_bytes

        # roofline terms (global work / aggregate machine rate)
        flops = rec.get("jaxpr_cost", {}).get("flops", cell.model_flops)
        mem_bytes = rec.get("jaxpr_cost", {}).get("bytes", 0.0)
        coll_parsed = rec.get("collectives_hlo_folded", rec["collectives_hlo"])
        coll = max(cell.model_coll_bytes,
                   sum(coll_parsed.values()) * chips)
        terms = {
            "compute_s": flops / (chips * PEAK_FLOPS),
            "memory_s": mem_bytes / (chips * HBM_BW),
            "collective_s": coll / (chips * ICI_BW),
        }
        rec["roofline"] = terms
        rec["bottleneck"] = max(terms, key=terms.get)
        rec["useful_flops_ratio"] = (cell.model_flops / flops) if flops else None
        rec["status"] = "ok"
    except Exception as e:  # noqa: BLE001 — a failed cell is a recorded bug
        rec["status"] = "fail"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
    return rec


def print_shard_table(n_topics: int = 100_000, vocab: int = 1_000_000,
                      data_shards: int = 16, out=None,
                      as_json: bool = False) -> list:
    """Replicated-vs-word-sharded per-device HBM table at paper scale
    (10⁵ topics × 10⁶ words; DESIGN.md §10) — the HBM win without hardware.

    Token count is the paper's regime (~10⁹ queries × 4.5 tokens); it only
    enters the rotation-traffic column, never the HBM fit."""
    from repro.dist import analysis

    n_tokens = 4.5e9
    recs = []
    if not as_json:
        print(f"# §10 word-sharded model parallelism @ K={n_topics:,} "
              f"V={vocab:,} (data ring M={data_shards}):", flush=True)
        print("#   P   phi+tables/dev      theta/dev      HBM/dev  <16GB  "
              "rotation/dev/epoch", flush=True)
    for p in (1, 2, 4, 8):
        r = analysis.model_shard_report(
            n_topics, vocab, data_shards, p, n_tokens,
            docs_per_shard=4096, doc_topic_cap=64)
        model = r["phi_bytes_per_device"] + r["tables_bytes_per_device"]
        hbm = r["hbm_bytes_per_device"]
        fits = hbm < 16e9
        r["fits_16gb_hbm"] = bool(fits)
        recs.append(r)
        if not as_json:
            print(f"#  {p:2d}   {model/1e9:10.1f} GB   "
                  f"{r['theta_bytes_per_device']/1e9:8.3f} GB"
                  f"   {hbm/1e9:8.1f} GB   {'yes' if fits else ' no'}  "
                  f"{r['rotation_bytes_per_epoch']/1e9:12.1f} GB",
                  flush=True)
    if as_json:
        # one parseable document: the shard table plus its inputs — CI and
        # the preflight budget derivation consume this instead of scraping
        # the `#` comment lines
        print(json.dumps({"shard_table": {
            "n_topics": n_topics, "vocab": vocab,
            "data_shards": data_shards, "n_tokens": n_tokens,
            "rows": recs,
        }}, indent=2), flush=True)
    if out:
        with open(out, "a") as f:
            for r in recs:
                f.write(json.dumps({"shard_table": r}) + "\n")
    return recs


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default=None, help="append JSONL records here")
    ap.add_argument("--skip-jaxpr", action="store_true")
    ap.add_argument("--shard-table", action="store_true",
                    help="print the replicated-vs-word-sharded per-device "
                         "HBM/rotation table at paper scale (§10) and exit")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable output only (suppresses the "
                         "human `#` tables; with --shard-table emits one "
                         "JSON document, with --verify the preflight "
                         "report)")
    ap.add_argument("--verify", action="store_true",
                    help="run the repro.analysis static contract checks "
                         "(sharding/VMEM/determinism/lint) on the default "
                         "P=2 alias session and exit 0/1")
    args = ap.parse_args()

    if args.verify:
        from repro.analysis import preflight as pf

        report = pf.run_preflight(pf.SessionSpec())
        print(report.to_json(indent=2) if args.json else report.render())
        raise SystemExit(0 if report.ok else 1)

    if args.shard_table:
        print_shard_table(out=args.out, as_json=args.json)
        return

    from repro.configs import all_specs, get_arch

    if args.all:
        work = [(spec, shape) for spec in all_specs().values()
                for shape in spec.shapes]
    else:
        spec = get_arch(args.arch)
        shapes = [args.shape] if args.shape else list(spec.shapes)
        work = [(spec, s) for s in shapes]

    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    for spec, shape in work:
        for mp in meshes:
            rec = run_cell(spec, shape, mp, skip_jaxpr=args.skip_jaxpr)
            line = json.dumps(rec)
            print(line, flush=True)
            if args.out:
                with open(args.out, "a") as f:
                    f.write(line + "\n")
            if rec["status"] == "ok":
                print(
                    f"# {rec['arch']}/{rec['shape']} [{rec['mesh']}] OK "
                    f"compile={rec['compile_s']}s live/dev="
                    f"{rec['live_bytes_per_device']/1e9:.2f}GB "
                    f"bottleneck={rec['bottleneck']}", flush=True)
                st = rec.get("sampler_traffic")
                if st:
                    print(
                        f"#   sampler HBM/epoch: dense="
                        f"{st['dense_bytes_per_epoch']/1e9:.1f}GB alias="
                        f"{st['alias_bytes_per_epoch']/1e9:.1f}GB "
                        f"(x{st['dense_over_alias']:.0f} less with "
                        f"--sampler alias)", flush=True)
            elif rec["status"] == "skip":
                print(f"# {rec['arch']}/{rec['shape']} SKIP: {rec['reason']}",
                      flush=True)
            else:
                print(f"# {rec['arch']}/{rec['shape']} [{rec['mesh']}] FAIL: "
                      f"{rec['error']}", flush=True)


if __name__ == "__main__":
    main()
