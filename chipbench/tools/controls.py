#!/usr/bin/env python3
"""Readings of the controls and of the planted faults, at a cell's own
size, one JSON line per (seed, variant): the numbers a cell's check would
compare, had the variant been the program.

    python3 chipbench/tools/controls.py train-alias-query 11 12 13
    JAX_PLATFORMS=cpu python3 chipbench/tools/controls.py \
        train-alias-query 11 --rehearse

Variants: ``control``, the plain reference put in the program's place and
computed in bfloat16, the precision below the float32 the configuration
states; and, planted in the reference put in the program's place: ``unchanged`` (every epoch returns its state),
``half`` (each epoch resamples only the tokens of even uid and keeps the
rest), ``token`` (one token's new topic altered where it is drawn),
``output`` (one token's topic altered in the epoch's output, after the
counts were updated), and
``sound`` (the float32 reference itself, which must read as the program
does). Limits are set from these readings and the cells' own runs
(``PERF.md``); this tool is not part of a cell's run.
"""
from __future__ import annotations

import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from harness import env  # noqa: E402


def train_readings(spec, seed: int, rehearse: bool):
    from harness import gen, ref_lda, train

    cfg, cell = spec.config, spec.workload
    sz = train.sizes(spec, rehearse)
    K, V = sz["K"], sz["V"]
    s_corpus, s_z, s_seed, s_check = env.derive_seeds(seed, 4)
    mix = dict(spec.traffic)
    mix["lengths"] = dict(mix["lengths"], total=sz["tokens"])
    words, docs = gen.corpus(mix, sz["docs"], V, s_corpus)
    z0 = np.random.default_rng(s_z).integers(0, K, sz["tokens"]).astype(
        np.int32)
    beta, n_mh = float(cfg["beta"]), int(cfg["n_mh"])
    n_ep = int(cell["setup_epochs"])
    seeds = [s_seed % (1 << 20) * 131 + 7 + e for e in range(n_ep)]
    alpha0 = np.full(K, np.float32(float(cfg["alpha0"]) / K), np.float32)

    def chain(dtype, plant=None):
        zs, alpha = [z0], alpha0
        for e in range(n_ep):
            z, tables = ref_lda.transition(words, docs, sz["docs"], V, K,
                                           zs[-1], alpha, beta, seeds[e],
                                           n_mh, dtype)
            del tables
            zs.append(plant(zs[-1], z) if plant else z)
            if e >= int(cell["alpha_opt_from"]):
                alpha = ref_lda.minka_alpha(
                    alpha, docs, zs[-1], sz["docs"], K,
                    int(cell["alpha_opt_iters"]), dtype).astype(np.float32)
        return zs, alpha

    even = (np.arange(sz["tokens"]) % 2) == 0

    def token(_, z):
        z = z.copy()
        z[0] = (z[0] + 1) % K
        return z

    def output():
        # one token's topic altered in the epoch's output, after its counts
        zs, alpha = chain("float32")
        counted = zs[-1]
        zs[-1] = token(None, counted)
        return zs, alpha, counted

    variants = {
        "output": output,
        "sound": lambda: chain("float32"),
        "control": lambda: chain("bfloat16"),
        "unchanged": lambda: ([z0] * (n_ep + 1), chain("float32")[1]),
        "half": lambda: chain("float32", lambda old, new: np.where(even, new,
                                                                   old)),
        "token": lambda: chain("float32", token),
    }
    for name, make in variants.items():
        zs, alpha, *counted = make()
        z = counted[0] if counted else zs[-1]
        phi = np.zeros((V, K), np.int64)
        np.add.at(phi, (words, z), 1)
        checks = train.check(spec, sz, words, docs, zs,
                             np.asarray(alpha, np.float64), seeds,
                             (phi, np.bincount(z, minlength=K), zs[-1]),
                             s_check)
        yield name, checks


def main():
    args = [a for a in sys.argv[1:] if a != "--rehearse"]
    rehearse = "--rehearse" in sys.argv
    env.use_program()
    if not rehearse:
        env.use_compile_cache()
    spec = env.load_spec(args[0])
    env.devices(int(spec.cell["chips"]), rehearse)
    for seed in (int(s) for s in args[1:]):
        for name, checks in train_readings(spec, seed, rehearse):
            print(json.dumps({"cell": spec.name, "seed": seed,
                              "variant": name,
                              **{k: v["value"] for k, v in checks.items()}}),
                  flush=True)


if __name__ == "__main__":
    main()
