"""Training cells: the program's ``Trainer`` with the alias sampler, driven
epoch by epoch through ``Trainer.fit``.

Set-up makes the corpus from the seed, builds the Trainer and drives it
through its first epochs (compiling or loading every program the window
uses: the ring epoch, the table builds, the α step), keeping the chain's
assignments after each. The window then drives the same Trainer on, one
``fit`` per epoch, and stops at the first epoch end after ``--seconds``
(a traced run's window is one epoch). After the window: the device peak,
then the program's final state is read and freed, and the reference checks
the set-up's transitions, the α step and the counts of the final state.
"""
from __future__ import annotations

import gc
from typing import Any, Dict, List

import numpy as np

from . import env, gen, ref_lda, work


def sizes(spec: env.Spec, rehearse: bool) -> Dict[str, Any]:
    cfg, cell = spec.config, spec.workload
    chips = int(spec.cell["chips"])
    s = {"K": int(cfg["n_topics"]),
         "V": int(cfg["vocab_rows_trained"]) * chips,
         "docs": int(cfg["corpus_queries"]) * chips,
         "tokens": int(cfg["corpus_tokens"]) * chips}
    if rehearse:
        s.update(cell["rehearse"])
    return s


def z_by_uid(state, n_tokens: int) -> np.ndarray:
    """The chain's assignments indexed by token uid (host)."""
    wl, uid, z = (np.asarray(state[i]) for i in (2, 4, 5))
    valid = wl >= 0
    out = np.full(n_tokens, -1, np.int32)
    out[uid[valid]] = z[valid]
    return out


def run(spec: env.Spec, seed: int, seconds: float, devs, t_start: float,
        counter: env.CompileCounter, tracer, rehearse: bool):
    import jax

    from repro.data.corpus import Corpus
    from repro.training import AlphaOptimizer, Trainer, TrainerConfig

    cfg, cell = spec.config, spec.workload
    sz = sizes(spec, rehearse)
    K, V = sz["K"], sz["V"]
    s_corpus, s_train, s_shard, s_check = env.derive_seeds(seed, 4)
    mix = dict(spec.traffic)
    mix["lengths"] = dict(mix["lengths"], total=sz["tokens"])
    env.stage("corpus")
    with env.annotate("chipbench.setup.corpus"):
        words, docs = gen.corpus(mix, sz["docs"], V, s_corpus)
    corpus = Corpus(words, docs, sz["docs"], V)
    train_seed = s_train % (1 << 20)
    tcfg = TrainerConfig(
        n_docs=sz["docs"], vocab_size=V, n_topics=K, sampler="alias",
        n_mh=int(cfg["n_mh"]), n_epochs=1, agg_every=int(cell["agg_every"]),
        alpha_opt_from=int(cell["alpha_opt_from"]),
        alpha_opt_iters=int(cell["alpha_opt_iters"]),
        alpha0=float(cfg["alpha0"]), beta=float(cfg["beta"]),
        data_shards=int(cell["data_shards"]),
        model_shards=int(cell["model_shards"]),
        n_model_shards=int(cell["model_shards"]),
        seed=train_seed, shard_seed=s_shard, bench_out=None)
    tr = Trainer(tcfg, corpus=corpus, callbacks=[AlphaOptimizer()])

    def one_epoch():
        tr.config = tr.config.replace(n_epochs=tr.epoch + 1)
        tr.fit()

    env.stage("trainer set-up")
    with env.annotate("chipbench.setup.trainer"):
        tr.setup()
    zs = [z_by_uid(tr.state, sz["tokens"])]
    for e in range(int(cell["setup_epochs"])):
        env.stage(f"set-up epoch {e}")
        with env.annotate("chipbench.setup.epoch"):
            one_epoch()
        zs.append(z_by_uid(tr.state, sz["tokens"]))
    alpha_after = np.asarray(tr.alpha, np.float64)
    jax.block_until_ready((tr.state, tr.alpha))
    n_setup_epochs = len(tr.metrics["epoch_s"])

    # ------------------------------------------------------------ window --
    env.stage("window")
    compiles0 = counter.compiles
    setup_s = env.now() - t_start
    # a traced run's window is one epoch: the table build's 10⁵-step sweep
    # makes about a million device events per epoch, and collecting a whole
    # window's would outlast the run's time limit
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

    # ----------------------------------------- the program's final state --
    env.stage(f"window done: {epochs} epochs in {window_s:.3f} s")
    phi_prog = tr.gather_phi()
    psi_prog = np.asarray(tr.local_model()[1])
    z_final = z_by_uid(tr.state, sz["tokens"])
    seeds = [tcfg.seed * 131 + 7 + e for e in range(len(zs) - 1)]
    del tr
    gc.collect()

    checks = check(spec, sz, words, docs, zs, alpha_after, seeds,
                   (phi_prog, psi_prog, z_final), s_check)
    flops, nbytes = work.alias_epoch_work(sz["tokens"], sz["docs"], K, V,
                                          int(cfg["n_mh"]))
    counters = {
        "window_s": window_s, "epochs": epochs,
        "tokens": sz["tokens"] * epochs, "epoch_s": epoch_s,
        "epoch_flops": flops, "epoch_bytes": nbytes,
        "compiles_in_window": compiles_in_window,
    }
    e2e = {"train_tokens_per_s": (sz["tokens"] * epochs / window_s, "tokens/s"),
           "setup_s": (setup_s, "s")}
    return {"attempted": sz["tokens"] * epochs, "failed": 0, "peak": peak,
            "e2e": e2e, "counters": counters, "checks": checks,
            "window_span": "chipbench.window"}


def check(spec: env.Spec, sz, words, docs, zs: List[np.ndarray],
          alpha_after, seeds, final, check_seed: int,
          dtype: str = "float32") -> Dict[str, Dict[str, float]]:
    """The compared numbers of a training cell, each beside its limit.

    ``zs`` are the chain's assignments at the start and after each checked
    epoch (epochs at the initial α), ``alpha_after`` the α the program
    holds after them, ``final`` its (Φ, Ψ, z) at the end of the run."""
    cfg, cell = spec.config, spec.workload
    K, V = sz["K"], sz["V"]
    beta, n_mh = float(cfg["beta"]), int(cfg["n_mh"])
    limits = cell["limits"]
    rng = np.random.default_rng(check_seed)
    alpha = np.full(K, np.float32(float(cfg["alpha0"]) / K), np.float32)
    mismatched = unexplained = 0.0
    for e in range(len(zs) - 1):
        env.stage(f"reference epoch {e}")
        z_ref, tables = ref_lda.transition(
            words, docs, sz["docs"], V, K, zs[e], alpha, beta, seeds[e],
            n_mh, dtype)
        bad = np.nonzero(z_ref != zs[e + 1])[0]
        mismatched += len(bad)
        if len(bad):
            # a token that differs counts unless some rounding of a tie
            # in its chain leads to the program's topic
            look = bad if len(bad) <= 200 else rng.choice(bad, 200,
                                                           replace=False)
            data = ref_lda.ReplayData(words, docs, sz["docs"], V, K, zs[e],
                                      alpha, beta, seeds[e], n_mh, tables,
                                      look)
            odd = sum(int(zs[e + 1][t]) not in
                      ref_lda.replay_outcomes(data, t) for t in look)
            unexplained += odd * len(bad) / len(look)
            del data
        del tables
        if e >= int(cell["alpha_opt_from"]):
            # the α step after epoch e, from the assignments it ended on
            alpha = ref_lda.minka_alpha(
                alpha, docs, zs[e + 1], sz["docs"], K,
                int(cell["alpha_opt_iters"]), dtype).astype(np.float32)
    n_checked = (len(zs) - 1) * sz["tokens"]
    env.log(f"train check: {int(mismatched)} of {n_checked} token draws "
            f"differ from the reference, {unexplained:.1f} beyond a tie")
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
