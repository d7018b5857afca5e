#!/usr/bin/env python3
"""Chip smoke: Peacock's train → publish → serve path on a TPU at K = 10⁵.

    python chip_smoke.py              # one chip, every phase below
    python chip_smoke.py --chips 4    # four chips: the word-sharded ring and
                                      # the replicated ring, nothing else

Deployment (``src/repro/configs/peacock_lda.py``): V = 2.1×10⁵, K = 10⁵,
4.5 tokens per query, 4096 documents per data shard on a 256-chip ring. One
chip of that ring holds ⌈V/256⌉ = 821 vocabulary rows at the full K; that is
the vocabulary trained and served here, and queries draw their ids from it.
K is never cut. Corpora are made from ``--seed`` in bulk.

One chip, through the entry points a user calls (``Trainer``,
``ModelPublisher``, ``load_snapshot``, ``TopicEngine``):

1. alias: ``sampler="alias"`` for 3 epochs on ~10⁶ tokens (≥ 10 per topic,
   so dedup does not fold empty topics together); the word tables rebuild
   before every epoch after the first and α moves after epochs 2 and 3; the
   run publishes a snapshot. (With tables two epochs stale, the
   log-likelihood falls at this width, on the CPU as on the chip.)
2. dense: ``sampler="dense"`` for 2 epochs on one 4096-document shard, which
   runs the Gibbs Pallas kernel; then one package's kernel draws against
   ``force="ref"`` on the same inputs (differences only at near-ties).
3. serve: the snapshot in a ``TopicEngine``; batches from four length
   buckets; every P(k|d) finite and summing to 1.

Four chips: one alias epoch on a (data 2 × model 2) word-sharded mesh and the
same epoch on the replicated (data 2) ring, same corpus and seeds, K = 10⁵;
Φ, Ψ and z must agree bitwise.

Every phase prints its compile and steady-state times and the device's peak
memory. The last line is one JSON object naming the device; any failure
exits non-zero before it, and without a TPU the script fails at once.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import math
import os
import shutil
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

K_FULL = 100_000          # peacock_lda.K_TOPICS
RING = 256                # chips in the deployment's ring
PACKAGE_LEN = 1024        # dense [L, K] planes: 0.4 GB each at K = 10⁵
NEAR_TIE = 1e-4           # log-score gap below which two draws are a tie
N_QUERIES = 222_222       # ≈ 10⁶ tokens at 4.5 per query: ≥ 10 per topic


def log(msg: str) -> None:
    print(msg, flush=True)


# ----------------------------------------------------------------- corpora --


def query_corpus(n_docs: int, vocab: int, seed: int, multiple: int = 1,
                 n_true: int = 2000, words_per_topic: int = 20):
    """Search-query-like corpus made in bulk: lengths Poisson(4.5), at least
    2; each query mixes two of ``n_true`` Zipf word bumps. ``multiple``
    pads the token count to a multiple (one token more for the first few
    queries), so a single shard's capacity divides into dense packages."""
    from repro.data.corpus import Corpus

    rng = np.random.default_rng(seed)
    lengths = np.maximum(2, rng.poisson(4.5, n_docs))
    lengths[:(-lengths.sum()) % multiple] += 1
    words = np.argsort(rng.random((n_true, vocab)), axis=1)[:, :words_per_topic]
    cum = np.cumsum(np.arange(1, words_per_topic + 1) ** -1.1)
    cum /= cum[-1]
    doc_ids = np.repeat(np.arange(n_docs, dtype=np.int32), lengths)
    pair = rng.integers(0, n_true, (n_docs, 2))[doc_ids]
    topic = np.where(rng.random(len(doc_ids)) < 0.7, pair[:, 0], pair[:, 1])
    rank = np.minimum(np.searchsorted(cum, rng.random(len(doc_ids))),
                      words_per_topic - 1)
    word_ids = words[topic, rank].astype(np.int32)
    return Corpus(word_ids, doc_ids, n_docs, vocab)


# --------------------------------------------------------------- accounting --


class Phases:
    """Per-phase wall time, compiles (count, seconds, persistent-cache hits)
    and device peak memory, from JAX's own monitoring events."""

    def __init__(self, device):
        import jax

        self.device = device
        self.compiles = 0
        self.compile_s = 0.0
        self.hits = 0

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1
                self.compile_s += duration

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def peak_gb(self) -> float:
        return self.device.memory_stats()["peak_bytes_in_use"] / 1e9

    @contextlib.contextmanager
    def phase(self, name: str):
        c0, s0, h0 = self.compiles, self.compile_s, self.hits
        t0 = time.perf_counter()
        yield
        log(f"[{name}] wall {time.perf_counter() - t0:.2f} s; "
            f"{self.compiles - c0} programs compiled or loaded in "
            f"{self.compile_s - s0:.2f} s ({self.hits - h0} of them from the "
            f"compile cache); device peak {self.peak_gb():.2f} GB")


def epoch_line(name: str, trainer, n_tokens: int) -> None:
    ep = trainer.metrics["epoch_s"]
    ll = trainer.metrics["ll"]
    steady = ep[1:] or ep
    log(f"[{name}] epoch s (first includes compile): "
        + ", ".join(f"{t:.3f}" for t in ep)
        + f"; steady {np.mean(steady):.3f} s = "
        f"{n_tokens / np.mean(steady):,.0f} tokens/s")
    log(f"[{name}] log-likelihood per epoch: "
        + ", ".join(f"{x:,.1f}" for x in ll))
    if not all(b > a for a, b in zip(ll, ll[1:])):
        raise RuntimeError(f"{name}: log-likelihood did not rise: {ll}")


# ------------------------------------------------------------------- phases --


def train_alias(corpus, n_topics: int, snap_dir: str, seed: int):
    """Phase 1: alias sampler, rebuild + α update, publish at the end."""
    from repro.checkpoint import snapshots
    from repro.training import (AlphaOptimizer, Metrics, ModelPublisher,
                                Trainer, TrainerConfig)

    cfg = TrainerConfig(
        n_docs=corpus.n_docs, vocab_size=corpus.vocab_size,
        n_topics=n_topics, sampler="alias", n_epochs=3, agg_every=1,
        alpha_opt_from=1, seed=seed, shard_seed=seed + 1, bench_out=None)
    publisher = ModelPublisher(snap_dir, every=cfg.n_epochs)
    tr = Trainer(cfg, corpus=corpus,
                 callbacks=[AlphaOptimizer(), publisher, Metrics()])
    alpha0 = cfg.alpha0 / n_topics
    tr.fit()
    epoch_line("alias", tr, corpus.n_tokens)
    if tr._tables_built_at != cfg.n_epochs - 1:
        raise RuntimeError("alias: the word tables were not rebuilt")
    alpha = np.asarray(tr.alpha)
    if np.all(alpha == np.float32(alpha0)):
        raise RuntimeError("alias: α never moved")
    log(f"[alias] word tables rebuilt before epochs 2..{cfg.n_epochs}; α ∈ "
        f"[{alpha.min():.3e}, {alpha.max():.3e}] (init {alpha0:.3e})")
    meta = snapshots.read_meta(snap_dir, publisher.last_version)
    log(f"[alias] published v{publisher.last_version} in "
        f"{tr.metrics['publish_s'][-1]:.2f} s: K {meta['n_topics_raw']} "
        f"before dedup, {meta['n_topics']} after; duplicate fraction "
        f"{meta['duplicate_fraction']:.4f}")
    return tr


def train_dense(corpus, n_topics: int, seed: int):
    """Phase 2: dense sampler (the Gibbs Pallas kernel), two epochs."""
    from repro.training import Metrics, Trainer, TrainerConfig

    cfg = TrainerConfig(
        n_docs=corpus.n_docs, vocab_size=corpus.vocab_size,
        n_topics=n_topics, sampler="dense", n_epochs=2,
        package_len=PACKAGE_LEN, alpha_opt_from=10 ** 6, seed=seed,
        shard_seed=seed + 1, bench_out=None)
    tr = Trainer(cfg, corpus=corpus, callbacks=[Metrics()])
    tr.fit()
    epoch_line("dense", tr, corpus.n_tokens)
    return tr


def kernel_agreement(tr, seed: int) -> float:
    """One package of the dense trainer's final state: the compiled Pallas
    kernel's draws against ``force="ref"`` on the same inputs. Returns the
    share that agree; raises if the trainer's epoch program holds no
    compiled kernel, or if a disagreement is not a near-tie."""
    import jax
    import jax.numpy as jnp

    from repro.core import prng
    from repro.kernels.gibbs import ops as gibbs_ops

    rc = tr.ring_cfg
    L, K = rc.package_len, rc.n_topics
    epoch = tr._epoch_fn.lower(*jax.device_put(
        (*tr.state, tr.alpha, tr.beta, jnp.uint32(seed)),
        tr._epoch_in)).compile()
    has_kernel = "tpu_custom_call" in epoch.as_text()
    log(f"[dense] epoch program contains the compiled Pallas kernel "
        f"(tpu_custom_call): {has_kernel}")
    if not has_kernel:
        raise RuntimeError("dense: the epoch ran no compiled Gibbs kernel")

    phi, psi = tr.state[0][0], tr.state[1]            # [rows, K], [K]
    wl, dl, uid, z = (tr.state[i][0, 0] for i in (2, 3, 4, 5))
    theta = jnp.zeros((rc.docs_per_shard, K), jnp.float32).at[dl, z].add(
        (wl >= 0).astype(jnp.float32))
    wl, dl, uid, z = wl[:L], dl[:L], uid[:L], z[:L]
    valid = np.asarray(wl >= 0)
    w, d = jnp.where(wl >= 0, wl, 0), jnp.where(wl >= 0, dl, 0)
    onehot = jax.nn.one_hot(z, K, dtype=jnp.float32)
    args = (phi[w].astype(jnp.float32) - onehot,
            psi.astype(jnp.float32)[None, :] - onehot,
            theta[d] - onehot, tr.alpha, tr.beta, uid.astype(jnp.uint32),
            jnp.uint32(seed))
    got = np.asarray(gibbs_ops.gibbs_argmax(*args, rc.vocab_size,
                                            force="pallas"))
    ref = np.asarray(gibbs_ops.gibbs_argmax(*args, rc.vocab_size,
                                            force="ref"))
    vb = rc.vocab_size * tr.beta
    score = np.asarray(
        jnp.log(args[0] + tr.beta) - jnp.log(args[1] + vb)
        + jnp.log(args[2] + tr.alpha[None, :])
        + prng.gumbel(args[6], args[5][:, None],
                      jnp.arange(K, dtype=jnp.uint32)[None, :]))
    rows = np.arange(L)
    gap = score[rows, ref] - score[rows, got]
    differ = valid & (got != ref)
    share = 1.0 - differ.sum() / valid.sum()
    worst = float(gap[differ].max()) if differ.any() else 0.0
    log(f"[dense] one package ({int(valid.sum())} draws): Pallas kernel "
        f"agrees with ref on {share:.6f} of draws; {int(differ.sum())} "
        f"differ, largest log-score gap {worst:.2e} (near-tie bound "
        f"{NEAR_TIE:g})")
    if worst > NEAR_TIE:
        raise RuntimeError("dense: kernel and ref differ beyond a near-tie")
    return share


def serve(snap_dir: str, vocab: int, seed: int, batches: int = 3,
          rows: int = 64) -> None:
    """Phase 3: the published snapshot behind a TopicEngine."""
    from repro.checkpoint import snapshots
    from repro.serving import TopicEngine

    model, meta = snapshots.load_snapshot(snap_dir)
    K = model.pvk.shape[1]
    log(f"[serve] loaded v{meta['version']}: V={model.pvk.shape[0]} K={K}")
    rng = np.random.default_rng(seed)
    engine = TopicEngine(model, max_batch=rows, start=False)
    try:
        for lo, hi in ((2, 8), (9, 16), (17, 32), (33, 64)):
            times = []
            for _ in range(batches):
                qs = [rng.integers(0, vocab, rng.integers(lo, hi + 1))
                      for _ in range(rows)]
                t0 = time.perf_counter()
                out = engine.infer(qs)
                times.append(time.perf_counter() - t0)
                for r in out:
                    pkd = np.asarray(r.pkd, np.float64)
                    if pkd.shape != (K,) or not np.isfinite(pkd).all():
                        raise RuntimeError("serve: P(k|d) not finite")
                    if abs(pkd.sum() - 1.0) > 1e-3:
                        raise RuntimeError(
                            f"serve: P(k|d) sums to {pkd.sum()}")
                    ids = np.asarray(r.feature_ids)
                    if not ((ids >= 0) & (ids < vocab)).all():
                        raise RuntimeError("serve: feature id out of range")
            log(f"[serve] {hi:2d}-token bucket, {batches} x {rows} queries: "
                f"first batch {times[0]:.3f} s (includes compile), then "
                + ", ".join(f"{t * 1e3:.1f}" for t in times[1:])
                + " ms per batch; every P(k|d) finite, sums to 1")
    finally:
        engine.close()


def ring_parity(corpus, n_topics: int, seed: int) -> None:
    """Four chips: one alias epoch word-sharded (data 2 × model 2) vs the
    replicated data-2 ring; Φ, Ψ and z must agree bitwise."""
    from repro.training import Trainer, TrainerConfig

    def run(n_model: int):
        cfg = TrainerConfig(
            n_docs=corpus.n_docs, vocab_size=corpus.vocab_size,
            n_topics=n_topics, sampler="alias", n_epochs=1,
            data_shards=2, model_shards=n_model, n_model_shards=n_model,
            alpha_opt_from=10 ** 6, seed=seed, shard_seed=seed + 1,
            bench_out=None)
        tr = Trainer(cfg, corpus=corpus)
        tr.fit()
        wl, uid, z = (np.asarray(tr.state[i]) for i in (2, 4, 5))
        z_by_uid = np.zeros(corpus.n_tokens, np.int32)
        z_by_uid[uid[wl >= 0]] = z[wl >= 0]
        log(f"[ring] n_model_shards={n_model} on {tr.mesh.devices.size} "
            f"chips: epoch {tr.metrics['epoch_s'][0]:.2f} s (includes "
            f"compile); Φ rows per chip {tr.ring_cfg.rows_per_shard // n_model}")
        return tr.gather_phi(), np.asarray(tr.state[1]), z_by_uid

    sharded = run(2)
    replicated = run(1)
    for name, a, b in zip(("phi", "psi", "z"), sharded, replicated):
        if not np.array_equal(a, b):
            raise RuntimeError(f"ring: word-sharded {name} differs from the "
                               "replicated ring")
    log("[ring] word-sharded (data 2 x model 2) == replicated (data 2): "
        "phi, psi and z bitwise equal")


# --------------------------------------------------------------------- main --


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cache = enable_compile_cache()
    import jax

    from repro import kernels
    from repro.configs import peacock_lda as pl

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, JAX found "
                         f"{dev.platform} ({dev.device_kind})")
    if len(devices) < args.chips:
        raise SystemExit(f"chip_smoke: --chips {args.chips} but JAX found "
                         f"{len(devices)} devices")
    rows = math.ceil(pl.VOCAB / RING)
    log(f"deployment: V={pl.VOCAB} K={pl.K_TOPICS}, {pl.TOKENS_PER_DOC} "
        f"tokens/query, {pl.DOCS_PER_SHARD} docs/shard on a {RING}-chip "
        f"ring (configs/peacock_lda.py)")
    log(f"cut: one chip's share, {rows} vocabulary rows at the full "
        f"K={K_FULL}; --chips {args.chips} trains "
        f"{rows * args.chips} rows; K is not cut")
    log(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
        f"compile cache {cache}")
    log(f"kernels: gibbs_argmax -> {kernels.kernel_mode()} (compiled "
        f"Pallas), alias build_alias/mh_resample -> XLA programs")
    if kernels.kernel_mode() != "pallas":
        raise RuntimeError("the Gibbs op does not dispatch to its kernel")

    phases = Phases(dev)
    work = os.path.join(ROOT, ".smoke")
    shutil.rmtree(work, ignore_errors=True)
    try:
        if args.chips == 4:
            with phases.phase("ring"):
                corpus = query_corpus(N_QUERIES, rows * args.chips,
                                      args.seed)
                ring_parity(corpus, K_FULL, args.seed)
        else:
            snap = os.path.join(work, "snapshots")
            with phases.phase("alias"):
                corpus = query_corpus(N_QUERIES, rows, args.seed)
                log(f"[alias] corpus: {corpus.n_docs} queries, "
                    f"{corpus.n_tokens} tokens "
                    f"({corpus.n_tokens / K_FULL:.1f} per topic)")
                train_alias(corpus, K_FULL, snap, args.seed)
            with phases.phase("dense"):
                corpus = query_corpus(pl.DOCS_PER_SHARD, rows, args.seed + 1,
                                      multiple=PACKAGE_LEN)
                tr = train_dense(corpus, K_FULL, args.seed)
                kernel_agreement(tr, args.seed)
                del tr
            with phases.phase("serve"):
                serve(snap, rows, args.seed)
    finally:
        shutil.rmtree(work, ignore_errors=True)
    log(f"device peak memory: {phases.peak_gb():.2f} GB "
        f"(peak_bytes_in_use)")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
