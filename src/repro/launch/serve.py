"""Open-loop serving load driver: tail latency vs offered load (§3.2, Fig. 5A).

    PYTHONPATH=src python -m repro.launch.serve --qps 500 --duration 3 \
        --bench-out BENCH_serve.json
    PYTHONPATH=src python -m repro.launch.serve --replicas 4 --cache-mb 64 \
        --shed --zipf-pool 512 --bench-out BENCH_fleet.json

Trains a quick model, stands up a :class:`TopicEngine` — or, with
``--replicas``/``--cache-mb``/``--shed``, a :class:`TopicFleet` front over N
replicas (DESIGN.md §13) — then replays a **Poisson arrival process** against
it at the offered ``--qps``. Open loop means arrivals do not wait for
completions — the honest way to measure a serving system: a closed loop
(submit, wait, repeat) caps the offered load at the system's own speed and
hides queueing collapse, which is exactly the regime a tail-latency story
must expose.

``--zipf-pool N`` switches traffic to a Zipf(1.0) mix over a pool of N
distinct queries — the power-law head the fleet's result cache exists for;
the default mixed-length traffic is all-distinct (every lookup misses).

Mid-run the driver hot-swaps the model (``--swap-mid``, on by default) to
prove the train→aggregate loop can publish fresh Φ without downtime.

``--bench-out`` writes a machine-readable BENCH json record (p50/p99,
achieved QPS, occupancy, deadline-miss rate, per-bucket counts; fleet runs
add hit-rate/shed-rate/per-replica routing) so the bench trajectory tracks
serving, not just training throughput.
"""
import argparse
import json
import time


def build_model(topics: int, vocab: int, train_iters: int = 25):
    """Quick synthetic train → RT-LDA serving model (R cache, Eq. 3)."""
    from repro.core import rtlda
    from repro.data.fixtures import quick_train

    _, state = quick_train(topics, vocab, train_iters)
    return rtlda.build_model(state.phi, state.beta, state.alpha), state


def make_traffic(n: int, vocab: int, buckets, seed: int = 1):
    """Mixed-length queries spanning every shape bucket (plus over-long
    tails that must route to the widest bucket with ``truncated`` set)."""
    import numpy as np

    rng = np.random.default_rng(seed)
    max_b = max(buckets)
    lengths = rng.choice(
        [2, 4, max(1, min(buckets) - 1)] + [b - 1 for b in buckets]
        + [max_b + 4],
        size=n, p=None)
    return [rng.integers(0, vocab, size=int(L)).astype(np.int32)
            for L in lengths]


def make_zipf_traffic(n: int, pool: int, vocab: int, buckets, seed: int = 1,
                      s: float = 1.0):
    """Zipf(s) traffic over a pool of ``pool`` distinct queries: rank-r
    probability ∝ 1/r^s. The power-law head repeats constantly (cacheable),
    the tail is near-unique — the §3.2 serving mix."""
    import numpy as np

    rng = np.random.default_rng(seed)
    max_b = max(buckets)
    queries = [rng.integers(0, vocab,
                            size=int(rng.integers(2, max_b + 1))
                            ).astype(np.int32)
               for _ in range(pool)]
    weights = 1.0 / np.arange(1, pool + 1, dtype=np.float64) ** s
    weights /= weights.sum()
    idx = rng.choice(pool, size=n, p=weights)
    return [queries[i] for i in idx]


def warm_shape_grid(target, buckets, batch: int, vocab: int):
    """Warm the (row-bucket, length-bucket) program grid so runs measure
    serving, not XLA compiles. Rows are DISTINCT random queries — identical
    payloads would short-circuit into a fleet's result cache and leave the
    engine shapes cold."""
    import numpy as np

    rng = np.random.default_rng(0)
    for b in buckets:
        rows = 1
        while rows < batch:
            target.infer([rng.integers(0, vocab, size=b).astype(np.int32)
                          for _ in range(rows)])
            rows *= 2
        # full batches run at rows=batch even when it isn't a power of two
        target.infer([rng.integers(0, vocab, size=b).astype(np.int32)
                      for _ in range(batch)])
    target.reset_stats()


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--qps", type=float, default=500.0,
                    help="offered load (Poisson arrival rate)")
    ap.add_argument("--duration", type=float, default=3.0,
                    help="seconds of open-loop traffic")
    ap.add_argument("--deadline-ms", type=float, default=50.0)
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--buckets", type=str, default="8,16,32,64")
    ap.add_argument("--topics", type=int, default=32)
    ap.add_argument("--vocab", type=int, default=600)
    ap.add_argument("--n-trials", type=int, default=2)
    ap.add_argument("--train-iters", type=int, default=25)
    ap.add_argument("--max-delay-ms", type=float, default=5.0)
    ap.add_argument("--replicas", type=int, default=1,
                    help="serve through a TopicFleet of N engine replicas "
                         "(DESIGN.md §13) instead of one bare engine")
    ap.add_argument("--cache-mb", type=float, default=0.0,
                    help="fleet hot-query result cache budget (0 = off; "
                         "implies fleet mode)")
    ap.add_argument("--shed", action=argparse.BooleanOptionalAction,
                    default=False,
                    help="fleet admission control: reject-fast with a typed "
                         "ShedResponse when p99 slack goes negative")
    ap.add_argument("--zipf-pool", type=int, default=0,
                    help="draw traffic Zipf(1.0) from a pool of N distinct "
                         "queries (0 = all-distinct mixed-length traffic)")
    ap.add_argument("--swap-mid", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="hot-swap the model halfway through the run")
    ap.add_argument("--bench-out", type=str, default=None,
                    help="write a machine-readable JSON record here")
    ap.add_argument("--preflight", action="store_true",
                    help="run the serving-side static contract checks "
                         "(repro.analysis: concurrency thread contracts + "
                         "repo lint) and exit before building any engine — "
                         "pure AST, no model trained, no thread started; "
                         "exit 0 iff every check passes (parity with "
                         "launch/train.py --preflight, which gates the "
                         "jitted side)")
    ap.add_argument("--preflight-json", action="store_true",
                    help="with --preflight: machine-readable report")
    args = ap.parse_args(argv)

    if args.preflight:
        # static serving gate: verify the thread contracts of the engine /
        # watcher / stream / checkpoint classes this driver is about to
        # exercise, then exit — nothing is built, so the gate is safe (and
        # sub-second) in front of every load run
        from repro.analysis import preflight as pf

        report = pf.run_preflight(pf.SessionSpec(),
                                  passes=("concurrency", "lint"))
        # §13 gate extension: the fleet classes must actually be IN the
        # analyzer's inventory — discovery silently skipping fleet.py or
        # cache.py would let this gate certify thread contracts it never
        # looked at
        inventory = next(
            (f for r in report.results for f in r.findings
             if f.check == "concurrency.inventory"), None)
        missing = [cls for cls in ("TopicFleet", "ResultCache",
                                   "TopicEngine", "SnapshotWatcher",
                                   "CircuitBreaker", "FaultPlane")
                   if inventory is None or cls not in inventory.message]
        ok = report.ok and not missing
        print(report.to_json(indent=2) if args.preflight_json
              else report.render())
        if missing:
            print("[preflight] serving classes missing from the concurrency "
                  f"inventory: {', '.join(missing)}")
        raise SystemExit(0 if ok else 1)

    import numpy as np

    from repro.core import rtlda
    from repro.serving import ShedResponse, TopicEngine, TopicFleet

    buckets = tuple(int(b) for b in args.buckets.split(","))
    model, state = build_model(args.topics, args.vocab, args.train_iters)
    # the mid-run swap target: same shapes, rebuilt Φ (a later aggregate)
    model_b = rtlda.build_model(state.phi + 1, state.beta, state.alpha)

    fleet_mode = (args.replicas > 1 or args.cache_mb > 0 or args.shed)
    if fleet_mode:
        target = TopicFleet(model, n_replicas=max(1, args.replicas),
                            buckets=buckets, max_batch=args.batch,
                            n_trials=args.n_trials,
                            max_delay_ms=args.max_delay_ms,
                            cache_mb=args.cache_mb, shed=args.shed,
                            deadline_budget_ms=args.deadline_ms)
    else:
        target = TopicEngine(model, buckets=buckets, max_batch=args.batch,
                             n_trials=args.n_trials,
                             max_delay_ms=args.max_delay_ms)

    warm_shape_grid(target, buckets, args.batch, args.vocab)
    if fleet_mode and target.cache is not None:
        target.cache.clear()     # warmup queries must not seed the run

    n = max(1, int(args.qps * args.duration))
    if args.zipf_pool > 0:
        traffic = make_zipf_traffic(n, args.zipf_pool, args.vocab, buckets)
    else:
        traffic = make_traffic(n, args.vocab, buckets)
    rng = np.random.default_rng(7)
    gaps = rng.exponential(1.0 / args.qps, size=n)
    arrivals = np.cumsum(gaps)

    futs = []
    swapped_at = None
    n_backed_off = 0
    backoff_until = 0.0
    t0 = time.monotonic()
    for i, (req, at) in enumerate(zip(traffic, arrivals)):
        lag = t0 + at - time.monotonic()
        if lag > 0:
            time.sleep(lag)          # open loop: schedule is the clock's, not ours
        if args.swap_mid and swapped_at is None and i >= n // 2:
            target.swap_model(model_b, version=1)
            swapped_at = i
        if time.monotonic() < backoff_until:
            # a well-behaved client honors ShedResponse.retry_after_ms:
            # arrivals inside the back-off window are dropped client-side
            # instead of re-offered into guaranteed rejects (which would
            # make shed-rate numbers measure client rudeness, not capacity)
            n_backed_off += 1
            continue
        fut = target.submit(req, deadline_ms=args.deadline_ms)
        futs.append(fut)
        if fut.done():
            r = fut.result()
            if isinstance(r, ShedResponse) and r.retry_after_ms > 0:
                backoff_until = max(
                    backoff_until,
                    time.monotonic() + r.retry_after_ms / 1e3)
    results = [f.result(timeout=60) for f in futs]
    wall = time.monotonic() - t0
    target.close()

    responses = [r for r in results if not isinstance(r, ShedResponse)]
    n_shed = len(results) - len(responses)
    lat = np.array([r.latency_ms for r in responses])
    assert all(np.isfinite(r.pkd).all() for r in responses)
    n_trunc = sum(r.truncated for r in responses)
    n_missed = sum(r.deadline_missed for r in responses)
    record = {
        "bench": "fleet_open_loop" if fleet_mode else "serve_open_loop",
        "offered_qps": args.qps,
        "achieved_qps": len(responses) / wall,
        "duration_s": wall,
        "n_requests": len(results),
        "p50_ms": float(np.quantile(lat, 0.5)) if len(lat) else 0.0,
        "p99_ms": float(np.quantile(lat, 0.99)) if len(lat) else 0.0,
        "mean_ms": float(lat.mean()) if len(lat) else 0.0,
        "deadline_ms": args.deadline_ms,
        "buckets": list(buckets),
        "truncated": n_trunc,
        "swap_mid": swapped_at is not None,
        "n_trials": args.n_trials,
        "topics": args.topics,
        "zipf_pool": args.zipf_pool,
        "backed_off": n_backed_off,
    }
    if fleet_mode:
        fstats = target.stats()
        occ = [s.mean_batch_occupancy for s in fstats.per_replica]
        record.update({
            "replicas": len(target.engines),
            "cache_mb": args.cache_mb,
            "cache_hit_rate": fstats.hit_rate,
            "shed_enabled": args.shed,
            "shed": n_shed,
            "shed_rate": fstats.shed_rate,
            "routed": list(fstats.routed),
            "deadline_miss_rate": (n_missed / len(responses)
                                   if responses else 0.0),
            "mean_batch_occupancy": float(np.mean(occ)) if occ else 0.0,
            "per_bucket": {},
            "probes": fstats.probes,
            "hedges": fstats.hedges,
            "retries": fstats.retries,
            "failed": fstats.failed,
            "breakers": [b["state"] for b in fstats.breakers],
        })
        print(f"offered {args.qps:,.0f} QPS → achieved "
              f"{record['achieved_qps']:,.0f} QPS over {wall:.1f}s | "
              f"{record['replicas']} replicas routed {record['routed']} | "
              f"p50 {record['p50_ms']:.1f} ms  p99 {record['p99_ms']:.1f} ms"
              f" | miss {record['deadline_miss_rate']:.1%} @ "
              f"{args.deadline_ms:.0f} ms | cache hit "
              f"{record['cache_hit_rate']:.1%} | shed {n_shed}"
              + (f" | hot-swap at req {swapped_at}"
                 if swapped_at is not None else ""))
    else:
        stats = target.stats()
        record.update({
            "deadline_miss_rate": stats.deadline_miss_rate,
            "mean_batch_occupancy": stats.mean_batch_occupancy,
            "per_bucket": {str(k): v for k, v in stats.per_bucket.items()},
        })
        print(f"offered {args.qps:,.0f} QPS → achieved "
              f"{record['achieved_qps']:,.0f} QPS over {wall:.1f}s | "
              f"p50 {record['p50_ms']:.1f} ms  p99 {record['p99_ms']:.1f} ms"
              f" | miss rate {stats.deadline_miss_rate:.1%} @ "
              f"{args.deadline_ms:.0f} ms | occupancy "
              f"{stats.mean_batch_occupancy:.2f} | buckets "
              f"{record['per_bucket']}"
              + (f" | hot-swap at req {swapped_at}"
                 if swapped_at is not None else ""))
    if args.bench_out:
        with open(args.bench_out, "w") as f:
            json.dump(record, f, indent=2)
        print(f"[bench] wrote {args.bench_out}")
    return record


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
