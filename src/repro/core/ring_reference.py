"""Plain round-by-round reference of one alias-MH epoch of the M-ring.

Peacock (arXiv:1405.4402) §3.1: every device is a data server (one data
shard's token stack) and a sampling server (one vocabulary shard of Φ).
Round r of M has device v sample the sub-block B_{(v−r) mod M, v} — the
tokens of data shard i = (v − r) mod M whose words live in vocabulary shard
v — against its own Φ_v; then the visiting stacks move one hop around the
ring. Ψ is summed over the ring once per epoch.

This module replays that schedule one round and one shard at a time in
NumPy float32, with none of the ring's collectives, stacks or scans, to be
compared draw for draw with ``core/distributed.py``'s ring epoch:

* Φ_v at the start of each round is the histogram of the current z over
  shard v's rows;
* Ψ_v is the epoch-start Ψ plus shard v's own deltas so far (the others'
  arrive with the epoch-end sum);
* each document's (topic, count) pairs are built from its data shard's
  current z, in ascending topic order;
* the stale proposal tables are built once per epoch, per shard, from the
  epoch-start [1, rows, K] weights (wq = (Φ_v + β)/(Ψ + Vβ)), and the α
  table from α;
* each token makes n_mh MH steps (doc, word, doc, … proposals) from the
  uid-keyed counter hash, accepted against the collapsed posterior with
  the token itself excluded;
* after each round every shard's draws are written into z; at the end of
  the epoch Ψ is the histogram of z.

Departures from the paper: the paper samples a sub-block token by token
with live counts (Gibbs), where this replays the program's alias-MH
sampler, which draws every token of a round against the round-start
snapshot (one package per round); and it replays the replicated layout
(model_shards = 1) only. The Walker tables come from the kernel layer's own
``alias_tables`` and the uniforms from ``core/prng.py``: this reference
checks the ring's schedule, not the table build or the hash, which are
checked on their own (the build against a sequential NumPy Walker loop in
``tests/test_kernels_alias.py``).

Besides each draw it returns the token's margin: the smallest relative gap
between two numbers any of its MH steps compared. A draw that differs from
the program's where the margin is below ``TIE`` is a tie that float32
rounding may decide either way.
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import numpy as np

TIE = 1e-5


@dataclasses.dataclass(frozen=True)
class RingLayout:
    """Where each valid token of the ring's stacks lives, one entry per token.

    ``data``: its data shard (stack), ``vocab``: its vocabulary shard
    (sub-block), ``row``: its word's row in that shard, ``doc``: its
    document, numbered over the ring (data shard · docs_per_shard + the
    stack's shard-local id), ``uid``: its global uid."""

    n_shards: int
    rows: int
    docs_per_shard: int
    data: np.ndarray
    vocab: np.ndarray
    row: np.ndarray
    doc: np.ndarray
    uid: np.ndarray

    @classmethod
    def from_stacks(cls, word_local, doc_local, uid, rows: int,
                    docs_per_shard: int) -> "RingLayout":
        """From the [S, M, cap] stacks (−1 word = sentinel)."""
        wl = np.asarray(word_local)
        valid = wl >= 0
        data, vocab, _ = np.nonzero(valid)
        doc = data.astype(np.int64) * docs_per_shard + np.asarray(
            doc_local)[valid]
        return cls(wl.shape[1], rows, docs_per_shard, data.astype(np.int32),
                   vocab.astype(np.int32), wl[valid].astype(np.int32), doc,
                   np.asarray(uid)[valid].astype(np.uint32))


def _uniform(seed2, uid, counter) -> np.ndarray:
    from repro.core import prng

    return np.asarray(prng.uniform01(np.uint32(seed2), uid,
                                     np.uint32(counter)))


def _gap(a, b) -> np.ndarray:
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return np.abs(a - b) / np.maximum(np.maximum(np.abs(a), np.abs(b)),
                                      1e-30)


def doc_pairs(doc: np.ndarray, z: np.ndarray, n_docs: int
              ) -> Tuple[np.ndarray, np.ndarray]:
    """(topic, count) pairs [n_docs, cap] of documents ``doc`` with topics
    ``z``, ascending topics in each row, −1/0 padded to the most distinct
    topics of any document."""
    key, counts = np.unique(doc.astype(np.int64) * (1 << 31) + z,
                            return_counts=True)
    d = (key >> 31).astype(np.int64)
    k = (key & ((1 << 31) - 1)).astype(np.int32)
    first = np.searchsorted(d, d, side="left")
    col = np.arange(len(d)) - first
    cap = int(col.max()) + 1 if len(col) else 1
    topic = np.full((n_docs, cap), -1, np.int32)
    count = np.zeros((n_docs, cap), np.int32)
    topic[d, col] = k
    count[d, col] = counts
    return topic, count


def mh_draws(phi, psi, topic, count, wq, wp, wa, alpha, ap, aa, alpha_sum,
             w, d, z, uid, seed2, beta, vocab_size: int, n_mh: int):
    """n_mh MH steps for each token (w row, d doc, z topic, uid); float32
    throughout. Returns (z_new, margin)."""
    f32 = np.float32
    K = psi.shape[0]
    beta = f32(beta)
    vb = f32(vocab_size) * beta
    rows_t = topic[d]
    rows_c = count[d].astype(f32)
    total = rows_c.sum(axis=1, dtype=f32)
    margin = np.full(len(w), np.inf)

    def lookup(k):
        return np.where(rows_t == k[:, None], rows_c, f32(0)).sum(
            axis=1, dtype=f32)

    def post(k):
        ex = (k == z).astype(f32)
        ph = phi[w, k].astype(f32) - ex
        ps = psi[k].astype(f32) - ex
        th = lookup(k) - ex
        return (ph + beta) * (th + alpha[k]) / (ps + vb)

    s = z.copy()
    p_s = post(s)
    for step in range(n_mh):
        b0 = 4 * step
        u_draw = _uniform(seed2, uid, b0 + 1)
        u_coin = _uniform(seed2, uid, b0 + 2)
        jk = np.minimum((u_draw * f32(K)).astype(np.int32), K - 1)
        if step % 2 == 0:
            u_mix = _uniform(seed2, uid, b0)
            r = u_draw * total
            cum = np.cumsum(rows_c, axis=1, dtype=f32)
            prev = cum - rows_c
            hit = (cum > r[:, None]) & (prev <= r[:, None]) & (rows_c > 0)
            t_cnt = np.where(hit.any(axis=1),
                             np.where(hit, rows_t, 0).sum(axis=1), s)
            t_al = np.where(u_coin < ap[jk], jk, aa[jk])
            cut = u_mix * (total + alpha_sum)
            t = np.where(cut < total, t_cnt, t_al).astype(np.int32)
            q_s = lookup(s) + alpha[s]
            q_t = lookup(t) + alpha[t]
            edge = np.min(np.abs(cum - r[:, None]), axis=1) / np.maximum(
                r, 1e-30)
            margin = np.minimum(margin, np.minimum.reduce(
                [_gap(u_coin, ap[jk]), _gap(cut, total), edge]))
        else:
            t = np.where(u_coin < wp[w, jk], jk, wa[w, jk]).astype(np.int32)
            q_s = wq[w, s]
            q_t = wq[w, t]
            margin = np.minimum(margin, _gap(u_coin, wp[w, jk]))
        x = u_draw * f32(K)
        margin = np.minimum(margin, _gap(x, np.round(x)))
        u_acc = _uniform(seed2, uid, b0 + 3)
        p_t = post(t)
        ratio = (p_t * q_s) / (p_s * q_t)
        margin = np.minimum(margin, _gap(u_acc, ratio))
        acc = u_acc < ratio
        s = np.where(acc, t, s)
        p_s = np.where(acc, p_t, p_s)
    return s.astype(np.int32), margin


def word_tables(phi_v, psi, beta, vocab_size: int):
    """(wq, wp, wa) [rows, K] of one shard from its epoch-start counts,
    built from [1, rows, K] weights as the ring holds them."""
    import jax.numpy as jnp

    from repro.kernels.alias import ops as alias_ops

    beta = np.float32(beta)
    wq = (phi_v.astype(np.float32) + beta) / (
        psi.astype(np.float32)[None, :] + np.float32(vocab_size) * beta)
    wp, wa = alias_ops.build_alias(jnp.asarray(wq[None]))
    return wq, np.asarray(wp[0]), np.asarray(wa[0])


def ring_epoch(layout: RingLayout, z, alpha, beta, seed: int,
               vocab_size: int, n_mh: int):
    """One alias epoch of the ring, replayed round by round.

    ``z`` [n_tokens] int32 by layout position at epoch start, ``alpha``
    [K] f32, ``seed`` the epoch's sampler seed. Returns (z_next, margin,
    psi_next) by layout position."""
    import jax
    import jax.numpy as jnp

    from repro.core import prng
    from repro.kernels.alias import ops as alias_ops

    M, R = layout.n_shards, layout.rows
    K = int(np.asarray(alpha).shape[0])
    alpha = np.asarray(alpha, np.float32)
    z = np.asarray(z, np.int32).copy()
    margin = np.full(len(z), np.inf)
    seed2 = int(prng.fmix32(jnp.uint32(seed) ^ jnp.uint32(alias_ops.MH_SALT)))
    with jax.default_matmul_precision("highest"):
        alpha_sum = np.float32(jnp.sum(jnp.asarray(alpha)))
        ap, aa = (np.asarray(a[0]) for a in alias_ops.build_alias(
            jnp.asarray(alpha[None])))
        mine = [np.nonzero(layout.vocab == v)[0] for v in range(M)]

        def phi_of(v):
            phi = np.zeros((R, K), np.int32)
            np.add.at(phi, (layout.row[mine[v]], z[mine[v]]), 1)
            return phi

        psi0 = np.bincount(z, minlength=K).astype(np.int64)
        tables = [word_tables(phi_of(v), psi0, beta, vocab_size)
                  for v in range(M)]
        psi = [psi0.copy() for _ in range(M)]
        for r in range(M):
            topic, count = doc_pairs(layout.doc, z, M * layout.docs_per_shard)
            drawn = []
            for v in range(M):
                i = (v - r) % M
                sel = mine[v][layout.data[mine[v]] == i]
                z_new, m = mh_draws(
                    phi_of(v), psi[v], topic, count, *tables[v], alpha, ap,
                    aa, alpha_sum, layout.row[sel], layout.doc[sel], z[sel],
                    layout.uid[sel], seed2, beta, vocab_size, n_mh)
                psi[v] += (np.bincount(z_new, minlength=K)
                           - np.bincount(z[sel], minlength=K))
                drawn.append((sel, z_new, m))
            for sel, z_new, m in drawn:
                z[sel] = z_new
                margin[sel] = m
    return z, margin, np.bincount(z, minlength=K).astype(np.int32)
