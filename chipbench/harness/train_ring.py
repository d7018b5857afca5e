"""Training cells on the M-ring of several chips: the program's ``Trainer``
with the alias sampler on a data × 1 mesh, every chip a data server and a
sampling server (Peacock §3.1), driven epoch by epoch through
``Trainer.fit`` as ``train.run`` drives one chip.

Set-up makes the corpus from the seed, builds the Trainer, reads the token
layout from its stacks (the data shard, vocabulary shard and row of each
token uid) and drives it through its first epochs, keeping the chain's
assignments after each. The window drives the same Trainer on and stops at
the first epoch end after ``--seconds`` (a traced run's window is one
epoch). After it: the device peak of the fullest chip, the program's final
state, and the check, which replays the set-up's transitions round by round
through ``ref_ring``, then the α step and the counts of the final state.

``counters`` carry one chip's work: ``epoch_flops``/``epoch_bytes`` are
``work.alias_epoch_work`` of one chip's tokens, documents and rows, since
the trace's busy time is averaged over the chips. ``ring`` is the program's
record of the ring's geometry (``Trainer.bench_record()["ring"]``), absent
where the program keeps none.

Before anything else the run asks whether the program builds the word
tables per shard (``builds_per_shard``). A build that gathers the ring's Φ
onto every chip holds the whole [3284, 10⁵] Φ and its build temporaries
on each chip and builds every row there: the cell's configuration is out
of its reach, so the run exits non-zero at once rather than run for as
long as such a build takes.
"""
from __future__ import annotations

import gc
from typing import Dict, List, Optional

import numpy as np

from . import env, gen, ref_lda, ref_ring, train, work


def run(spec: env.Spec, seed: int, seconds: float, devs, t_start: float,
        counter: env.CompileCounter, tracer, rehearse: bool):
    import jax

    from repro.training import AlphaOptimizer

    cfg, cell = spec.config, spec.workload
    chips = int(spec.cell["chips"])
    sz = train.sizes(spec, rehearse)
    K = sz["K"]
    env.stage("word-table layout")
    if not builds_per_shard(devs, cell):
        raise SystemExit(
            "chipbench: the program's word-table build gathers a ring-"
            "sharded Phi onto every chip (its tables come out replicated); "
            f"{spec.name} needs them built per shard, in Phi's layout")
    s_corpus, s_train, s_shard, s_check = env.derive_seeds(seed, 4)
    env.stage("corpus")
    with env.annotate("chipbench.setup.corpus"):
        words, docs = corpus(spec, sz, s_corpus)
    tr = trainer(spec, sz, words, docs, s_train, s_shard, [AlphaOptimizer()])

    def one_epoch():
        tr.config = tr.config.replace(n_epochs=tr.epoch + 1)
        tr.fit()

    env.stage("trainer set-up")
    with env.annotate("chipbench.setup.trainer"):
        tr.setup()
    lay = ref_ring.Layout.from_stacks(tr.state[2], tr.state[4], sz["tokens"],
                                      int(tr.state[0].shape[1]))
    zs = [train.z_by_uid(tr.state, sz["tokens"])]
    for e in range(int(cell["setup_epochs"])):
        env.stage(f"set-up epoch {e}")
        with env.annotate("chipbench.setup.epoch"):
            one_epoch()
        zs.append(train.z_by_uid(tr.state, sz["tokens"]))
    alpha_after = np.asarray(tr.alpha, np.float64)
    jax.block_until_ready((tr.state, tr.alpha))
    n_setup_epochs = len(tr.metrics["epoch_s"])

    # ------------------------------------------------------------ window --
    env.stage("window")
    compiles0 = counter.compiles
    setup_s = env.now() - t_start
    if tracer is not None:
        tracer.start()
    t0 = env.now()
    epochs = 0
    with env.annotate("chipbench.window"):
        while True:
            with env.annotate("chipbench.epoch"):
                one_epoch()
            epochs += 1
            if tracer is not None or env.now() - t0 >= seconds:
                break
        jax.block_until_ready((tr.state, tr.alpha))
    window_s = env.now() - t0
    if tracer is not None:
        tracer.stop()
    compiles_in_window = counter.compiles - compiles0
    peak = env.peak_bytes(devs)
    epoch_s = list(tr.metrics["epoch_s"][n_setup_epochs:])
    ring = tr.bench_record().get("ring")

    # ----------------------------------------- the program's final state --
    env.stage(f"window done: {epochs} epochs in {window_s:.3f} s")
    phi_prog = tr.gather_phi()
    psi_prog = np.asarray(tr.local_model()[1])
    z_final = train.z_by_uid(tr.state, sz["tokens"])
    seeds = [tr.config.seed * 131 + 7 + e for e in range(len(zs) - 1)]
    del tr
    gc.collect()

    checks = check(spec, sz, words, docs, lay, zs, alpha_after, seeds,
                   (phi_prog, psi_prog, z_final), s_check, devs)
    flops, nbytes = work.alias_epoch_work(
        sz["tokens"] / chips, sz["docs"] / chips, K, lay.rows,
        int(cfg["n_mh"]))
    counters = {
        "window_s": window_s, "epochs": epochs,
        "tokens": sz["tokens"] * epochs, "epoch_s": epoch_s,
        "epoch_flops": flops, "epoch_bytes": nbytes,
        "compiles_in_window": compiles_in_window, "ring": ring,
    }
    e2e = {"train_tokens_per_s": (sz["tokens"] * epochs / window_s, "tokens/s"),
           "setup_s": (setup_s, "s")}
    return {"attempted": sz["tokens"] * epochs, "failed": 0, "peak": peak,
            "e2e": e2e, "counters": counters, "checks": checks,
            "window_span": "chipbench.window"}


def builds_per_shard(devs, cell, make_word_tables=None) -> bool:
    """Whether ``make_word_tables`` (the program's, by default) builds the
    tables of a Φ laid out on the cell's ring chip by chip: over a small Φ
    in the ring's row layout, the tables come out in Φ's layout."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    if make_word_tables is None:
        from repro.core.sparse import make_word_tables
    d, m = int(cell["data_shards"]), int(cell["model_shards"])
    mesh = jax.sharding.Mesh(np.asarray(devs[:d * m]).reshape(d, m),
                             ("data", "model"))
    # the program's layouts: the M-ring stacks d·m row shards on dim 0,
    # the word-sharded ring d coarse shards on dim 0, m slices on dim 1
    spec = P(("data", "model")) if m == 1 else P("data", "model")
    shards, rows = (d * m, 8) if m == 1 else (d, 8 * m)
    counts = np.arange(shards * rows * 128, dtype=np.int32) % 5
    phi = jax.device_put(jnp.asarray(counts.reshape(shards, rows, 128)),
                         NamedSharding(mesh, spec))
    psi = jax.device_put(jnp.sum(phi, axis=1), NamedSharding(mesh, P(spec[0])))
    tables = make_word_tables(phi, psi, 0.01, 1000)
    return all(t.sharding.is_equivalent_to(phi.sharding, phi.ndim)
               for t in tables)


def corpus(spec: env.Spec, sz, seed: int):
    """(words, docs) of the cell's traffic at sizes ``sz`` from ``seed``."""
    mix = dict(spec.traffic)
    mix["lengths"] = dict(mix["lengths"], total=sz["tokens"])
    return gen.corpus(mix, sz["docs"], sz["V"], seed)


def trainer(spec: env.Spec, sz, words, docs, train_seed: int,
            shard_seed: int, callbacks=()):
    """The program's Trainer for the cell on the ring its file states."""
    from repro.data.corpus import Corpus
    from repro.training import Trainer, TrainerConfig

    cfg, cell = spec.config, spec.workload
    tcfg = TrainerConfig(
        n_docs=sz["docs"], vocab_size=sz["V"], n_topics=sz["K"],
        sampler="alias", n_mh=int(cfg["n_mh"]), n_epochs=1,
        agg_every=int(cell["agg_every"]),
        alpha_opt_from=int(cell["alpha_opt_from"]),
        alpha_opt_iters=int(cell["alpha_opt_iters"]),
        alpha0=float(cfg["alpha0"]), beta=float(cfg["beta"]),
        data_shards=int(cell["data_shards"]),
        model_shards=int(cell["model_shards"]),
        n_model_shards=int(cell["model_shards"]),
        seed=train_seed % (1 << 20), shard_seed=shard_seed, bench_out=None)
    return Trainer(tcfg, corpus=Corpus(words, docs, sz["docs"], sz["V"]),
                   callbacks=list(callbacks))


def chain(spec: env.Spec, sz, docs, lay: ref_ring.Layout, z0, seeds, devs,
          dtype: str = "float32", fault: Optional[str] = None):
    """The reference's own chain from ``z0``: its assignments after each
    epoch and its α after them, the α step as the cell schedules it."""
    cfg, cell = spec.config, spec.workload
    K = sz["K"]
    alpha = np.full(K, np.float32(float(cfg["alpha0"]) / K), np.float32)
    blocks = lay.blocks()
    zs = [z0]
    for e, s in enumerate(seeds):
        z, _ = ref_ring.epoch(lay, docs, sz["docs"], sz["V"], K, zs[-1],
                              alpha, float(cfg["beta"]), s, int(cfg["n_mh"]),
                              devs, dtype, fault, blocks)
        zs.append(z)
        if e >= int(cell["alpha_opt_from"]):
            alpha = ref_lda.minka_alpha(
                alpha, docs, z, sz["docs"], K, int(cell["alpha_opt_iters"]),
                dtype).astype(np.float32)
    return zs, alpha


def check(spec: env.Spec, sz, words, docs, lay: ref_ring.Layout,
          zs: List[np.ndarray], alpha_after, seeds, final, check_seed: int,
          devs, dtype: str = "float32") -> Dict[str, Dict[str, float]]:
    """The compared numbers of a ring training cell, each beside its limit
    (``train.check``'s numbers, with the transitions replayed round by
    round)."""
    cfg, cell = spec.config, spec.workload
    K, V = sz["K"], sz["V"]
    beta, n_mh = float(cfg["beta"]), int(cfg["n_mh"])
    limits = cell["limits"]
    rng = np.random.default_rng(check_seed)
    alpha = np.full(K, np.float32(float(cfg["alpha0"]) / K), np.float32)
    order = np.argsort(docs, kind="stable")
    doc_tokens = np.split(order, np.cumsum(
        np.bincount(docs, minlength=sz["docs"]))[:-1])
    blocks = lay.blocks()
    mismatched = unexplained = 0.0
    for e in range(len(zs) - 1):
        env.stage(f"reference epoch {e}")
        z_ref, rec = ref_ring.epoch(lay, docs, sz["docs"], V, K, zs[e],
                                    alpha, beta, seeds[e], n_mh, devs, dtype,
                                    None, blocks)
        bad = np.nonzero(z_ref != zs[e + 1])[0]
        mismatched += len(bad)
        if len(bad):
            # a token that differs counts unless some rounding of a tie
            # in its chain leads to the program's topic
            look = bad if len(bad) <= 200 else rng.choice(bad, 200,
                                                           replace=False)
            outcomes = ref_ring.replay(lay, rec, docs, doc_tokens, V, K,
                                       beta, n_mh, look)
            odd = sum(int(zs[e + 1][t]) not in outcomes[int(t)]
                      for t in look)
            unexplained += odd * len(bad) / len(look)
        del rec
        if e >= int(cell["alpha_opt_from"]):
            alpha = ref_lda.minka_alpha(
                alpha, docs, zs[e + 1], sz["docs"], K,
                int(cell["alpha_opt_iters"]), dtype).astype(np.float32)
    n_checked = (len(zs) - 1) * sz["tokens"]
    env.log(f"train check: {int(mismatched)} of {n_checked} token draws "
            f"differ from the ring reference, {unexplained:.1f} beyond a tie")
    a = alpha.astype(np.float64)
    alpha_gap = float(np.max(np.abs(alpha_after - a) / a))
    env.stage("reference counts")
    phi, psi, z = final
    hist = np.zeros((V, K), np.int64)
    np.add.at(hist, (words, z), 1)
    count_gap = float(np.abs(phi.astype(np.int64) - hist).sum()
                      + np.abs(psi.astype(np.int64)
                               - np.bincount(z, minlength=K)).sum())
    return {
        "z_unexplained_share": {"value": float(unexplained / n_checked),
                                "limit": float(limits["z_unexplained_share"])},
        "alpha_rel_gap": {"value": alpha_gap,
                          "limit": float(limits["alpha_rel_gap"])},
        "count_gap": {"value": count_gap,
                      "limit": float(limits["count_gap"])},
    }
