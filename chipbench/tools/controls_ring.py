#!/usr/bin/env python3
"""Readings of the control and of the planted faults of a ring training
cell (``train_ring``), one JSON line per (seed, variant): the numbers the
cell's check would compare, had the variant been the program.

    python3 chipbench/tools/controls_ring.py train-ring-query-4chip 11 12
    python3 chipbench/tools/controls_ring.py train-ring-query-4chip 11 \
        --epochs 1 --variants control,phi_frozen
    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \
        python3 chipbench/tools/controls_ring.py train-ring-query-4chip 11 \
        --rehearse

The program only shards the corpus onto the ring (``Trainer.setup``: the
token layout and the initial assignments); every chain is the round-by-round
reference put in the program's place. Variants: ``sound`` (the float32
reference, which must pass), ``control`` (the reference in bfloat16, the
precision below the float32 the configuration states), and the faults of
``ref_ring.FAULTS``: ``psum_every_round``, ``phi_frozen``,
``z_unforwarded``. ``--epochs n`` checks the first n of the cell's set-up
epochs (each variant's chain and the check replay one reference epoch per
epoch; a fault misdraws in every epoch, so its share is read from fewer).
Limits are set from these readings and the cell's own runs (``PERF.md``);
this tool is not part of a cell's run.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from harness import env  # noqa: E402

VARIANTS = ("sound", "control", "psum_every_round", "phi_frozen",
            "z_unforwarded")


def ring_readings(spec, seed: int, rehearse: bool, devs,
                  variants=VARIANTS, epochs=None):
    from harness import ref_ring, train, train_ring

    cell = spec.workload
    sz = train.sizes(spec, rehearse)
    K, V = sz["K"], sz["V"]
    s_corpus, s_train, s_shard, s_check = env.derive_seeds(seed, 4)
    words, docs = train_ring.corpus(spec, sz, s_corpus)
    tr = train_ring.trainer(spec, sz, words, docs, s_train, s_shard)
    tr.setup()
    lay = ref_ring.Layout.from_stacks(tr.state[2], tr.state[4], sz["tokens"],
                                      int(tr.state[0].shape[1]))
    z0 = train.z_by_uid(tr.state, sz["tokens"])
    seeds = [tr.config.seed * 131 + 7 + e
             for e in range(int(epochs or cell["setup_epochs"]))]
    del tr
    for name in variants:
        zs, alpha = train_ring.chain(
            spec, sz, docs, lay, z0, seeds, devs,
            "bfloat16" if name == "control" else "float32",
            name if name in ref_ring.FAULTS else None)
        z = zs[-1]
        phi = np.zeros((V, K), np.int64)
        np.add.at(phi, (words, z), 1)
        checks = train_ring.check(spec, sz, words, docs, lay, zs,
                                  np.asarray(alpha, np.float64), seeds,
                                  (phi, np.bincount(z, minlength=K), z),
                                  s_check, devs)
        yield name, checks


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("cell")
    ap.add_argument("seeds", type=int, nargs="+")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--epochs", type=int, default=None,
                    help="set-up epochs to check (default: the cell's)")
    ap.add_argument("--variants", default=",".join(VARIANTS),
                    help="comma-separated, of " + ", ".join(VARIANTS))
    args = ap.parse_args()
    variants = tuple(args.variants.split(","))
    unknown = set(variants) - set(VARIANTS)
    if unknown:
        ap.error(f"unknown variants {sorted(unknown)}")
    env.use_program()
    if not args.rehearse:
        env.use_compile_cache()
    spec = env.load_spec(args.cell)
    devs = env.devices(int(spec.cell["chips"]), args.rehearse)
    for seed in args.seeds:
        for name, checks in ring_readings(spec, seed, args.rehearse, devs,
                                          variants, args.epochs):
            print(json.dumps({"cell": spec.name, "seed": seed,
                              "variant": name, "epochs": args.epochs,
                              **{k: v["value"] for k, v in checks.items()}}),
                  flush=True)


if __name__ == "__main__":
    main()
