"""Topic de-duplication (paper §3.3) + hyperparameter optimization.

Two mechanisms, exactly as in the paper:

1. **Asymmetric Dirichlet prior** α_k over document-topic distributions,
   optimized with the Wallach/Mimno/McCallum histogram fixed point
   ("Rethinking LDA: why priors matter" [23], Minka's fixed-point update on
   count histograms). The coordinator keeps only
     * ``doc_len_hist``  — histogram of document lengths l_d,
     * ``omega``         — Ω_kn = #documents in which topic k occurs n times,
   never per-document state — which is what makes the update cheap to
   aggregate across data servers (one psum of two small histograms).

       α_k ← α_k · Σ_n Ω_kn [ψ(n + α_k) − ψ(α_k)]
                   ─────────────────────────────────
                   Σ_l H_l [ψ(l + Σα) − ψ(Σα)]

   Topics that are duplicates absorb shrinking α_k mass (the prior
   concentrates on one of them), so duplicated topics decay to near-zero prior
   weight and RT-LDA automatically ignores them at serving time.

2. **L1 clustering**: topics whose column distributions are closer than a
   threshold in L1 are merged (union-find over the pairwise L1 graph, count
   columns summed into the cluster representative).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.scipy.special import digamma

from repro.core import lda, sparse


# ---------------------------------------------------------------------------
# Coordinator statistics (paper Fig. 3: CountNtn, doc lengths)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("n_docs", "n_topics", "max_count"))
def topic_count_histogram(doc_ids, z, valid, n_docs: int, n_topics: int,
                          max_count: int = 64):
    """Ω_kn for n in [1, max_count) — counts above the cap are clipped into the
    last bin (their digamma increments are nearly identical there).

    Built from the sparse (doc, topic, count) pairs of one sort pass, never
    from a dense [n_docs, K] Θ: O(T log T) work and O(T + K) memory."""
    d = jnp.where(valid, doc_ids, n_docs)
    _, topic, count, active = sparse.segment_totals(
        d, z, valid.astype(jnp.int32), n_docs)
    n = jnp.minimum(count, max_count - 1)
    return jnp.zeros((n_topics, max_count), jnp.int32).at[topic, n].add(
        active.astype(jnp.int32))


@functools.partial(jax.jit, static_argnames=("max_len",))
def doc_length_histogram(doc_lengths, max_len: int = 512):
    clipped = jnp.minimum(doc_lengths, max_len - 1)
    return jnp.zeros((max_len,), jnp.int32).at[clipped].add(1)


# ---------------------------------------------------------------------------
# OPTIMIZEHYPERPARAMS (paper Fig. 3 line 4; [23])
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("n_iters",))
def optimize_alpha(alpha, omega, doc_len_hist, n_iters: int = 20,
                   floor: float = 1e-7):
    """Minka fixed point on histograms. omega [K, Nmax], doc_len_hist [Lmax]."""
    K, n_max = omega.shape
    ns = jnp.arange(n_max, dtype=jnp.float32)
    ls = jnp.arange(doc_len_hist.shape[0], dtype=jnp.float32)
    omega_f = omega.astype(jnp.float32)
    hist_f = doc_len_hist.astype(jnp.float32)

    def body(alpha, _):
        a0 = alpha.sum()
        num = (omega_f * (digamma(ns[None, :] + alpha[:, None]) -
                          digamma(alpha)[:, None])).sum(axis=1)
        den = (hist_f * (digamma(ls + a0) - digamma(a0))).sum()
        alpha = alpha * num / jnp.maximum(den, 1e-30)
        return jnp.maximum(alpha, floor), None

    alpha, _ = jax.lax.scan(body, alpha, None, length=n_iters)
    return alpha


# ---------------------------------------------------------------------------
# L1 topic clustering
# ---------------------------------------------------------------------------

class L1Scan(NamedTuple):
    """One blocked pairwise-L1 pass over the normalized topic columns,
    reduced to what dedup consumes — never the K×K matrix itself."""

    nearest: np.ndarray   # [K] f32 — each topic's smallest L1 to another topic
    pairs: np.ndarray     # [E, 2] int32 — (i, j), i < j, with L1 < threshold
    threshold: float


@functools.partial(jax.jit, static_argnames=("block", "n_topics"))
def _l1_row_block(pvk, start, threshold, block: int, n_topics: int):
    """L1 distances from topic columns [start, start + block) of ``pvk``
    [V, Kp] to every column, reduced on the device: (nearest [block],
    number of below-threshold pairs (i < j), the bit-packed pair mask)."""
    rows = jax.lax.dynamic_slice_in_dim(pvk, start, block, axis=1)  # [V, B]
    # reduced over the leading (vocab) axis: no [V, B, Kp] intermediate
    d = jnp.sum(jnp.abs(rows[:, :, None] - pvk[:, None, :]), axis=0)
    row = start + jnp.arange(block)[:, None]
    col = jnp.arange(pvk.shape[1])[None, :]
    d = jnp.where((col == row) | (col >= n_topics), jnp.inf, d)
    below = (d < threshold) & (col > row) & (row < n_topics)
    return (jnp.min(d, axis=1), jnp.sum(below, dtype=jnp.int32),
            jnp.packbits(below, axis=1))


def l1_scan(phi, beta, threshold: float, block: int = 512) -> L1Scan:
    """Pairwise L1 between normalized topic columns, in blocks of ``block``
    topics on the device: each topic's nearest distance plus the pairs below
    ``threshold``. Device memory is O(V·K + block·K); a block's pair mask
    leaves the device only when it holds a pair."""
    pvk = lda.phi_hat(jnp.asarray(phi), jnp.float32(beta))      # [V, K]
    K = pvk.shape[1]
    block = min(block, K)
    pvk = jnp.pad(pvk, ((0, 0), (0, (-K) % block)))
    nearest = np.empty(pvk.shape[1], np.float32)
    pairs = []
    for start in range(0, K, block):
        near, n_below, packed = _l1_row_block(
            pvk, start, jnp.float32(threshold), block, K)
        nearest[start:start + block] = np.asarray(near)
        if int(n_below):
            below = np.unpackbits(np.asarray(packed), axis=1,
                                  count=pvk.shape[1]).astype(bool)
            i, j = np.nonzero(below)
            pairs.append(np.stack([start + i, j], axis=1))
    pairs = (np.concatenate(pairs).astype(np.int32) if pairs
             else np.zeros((0, 2), np.int32))
    return L1Scan(nearest[:K], pairs, float(threshold))


class _UnionFind:
    def __init__(self, n):
        self.p = list(range(n))

    def find(self, x):
        while self.p[x] != x:
            self.p[x] = self.p[self.p[x]]
            x = self.p[x]
        return x

    def union(self, a, b):
        ra, rb = self.find(a), self.find(b)
        if ra != rb:
            self.p[max(ra, rb)] = min(ra, rb)


def cluster_topics(phi, beta, l1_threshold: float,
                   scan: L1Scan | None = None) -> Tuple[np.ndarray, int]:
    """Merge topics with L1 distance below threshold.

    Returns (cluster_of_topic [K], n_clusters). Lower threshold ⇒ fewer merges;
    the paper prunes 10⁶ → ~10⁵ topics this way (Fig. 7B).

    ``scan`` may carry an :func:`l1_scan` at this threshold so callers that
    also need ``duplicate_fraction`` pay the O(K²V) distance pass once.
    """
    if scan is None:
        scan = l1_scan(phi, beta, l1_threshold)
    elif scan.threshold != l1_threshold:
        raise ValueError(f"scan was taken at L1 < {scan.threshold}, "
                         f"not the merge threshold {l1_threshold}")
    K = scan.nearest.shape[0]
    uf = _UnionFind(K)
    for a, b in scan.pairs:
        uf.union(int(a), int(b))
    roots = np.array([uf.find(k) for k in range(K)])
    _, cluster_of = np.unique(roots, return_inverse=True)
    return cluster_of.astype(np.int32), int(cluster_of.max()) + 1


def merge_topics(phi, psi, alpha, cluster_of: np.ndarray, n_clusters: int):
    """Sum counts (and prior mass) of merged topics into cluster representatives."""
    phi = np.asarray(phi)
    V = phi.shape[0]
    phi_new = np.zeros((V, n_clusters), phi.dtype)
    np.add.at(phi_new.T, cluster_of, np.asarray(phi).T)
    psi_new = np.zeros((n_clusters,), np.asarray(psi).dtype)
    np.add.at(psi_new, cluster_of, np.asarray(psi))
    alpha_new = np.zeros((n_clusters,), np.float32)
    np.add.at(alpha_new, cluster_of, np.asarray(alpha))
    return jnp.asarray(phi_new), jnp.asarray(psi_new), jnp.asarray(alpha_new)


def duplicate_fraction(phi, beta, l1_threshold: float = 0.5,
                       scan: L1Scan | None = None) -> float:
    """Fraction of topics that have at least one duplicate (paper: 20–40% at 10⁵).

    Accepts an :func:`l1_scan` via ``scan`` (any threshold: it reads only
    the nearest distances).
    """
    if scan is None:
        scan = l1_scan(phi, beta, l1_threshold)
    return float((scan.nearest < l1_threshold).mean())
