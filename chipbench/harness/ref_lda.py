"""Plain reference of what the timed path computes, written from the
published algorithms (Peacock, arXiv:1405.4402; LightLDA's alias MH) and
the program's documented random streams. It imports nothing of the program
and takes none of its tables: it gets the corpus (made by the benchmark
from the seed) and the assignments the program's chain moved through.

One transition z_e → z_{e+1} of the alias sampler on one chip, one package
per round, so every token sees the epoch-start counts:

* counts Φ = hist(w, z_e), Ψ = hist(z_e) and each document's topics;
* the stale word-proposal tables q_w(k) = (Φ_wk + β)/(Ψ_k + Vβ), made into
  Walker alias tables (the two-stack sweep: smalls in index order, larges in
  index order, a demoted large finalised next), and the α alias table;
* n_mh Metropolis–Hastings steps per token, alternating doc proposal
  (n_dk + α_k: the doc's topics in ascending order, or the α table) and
  word proposal, accepted against the exact collapsed posterior with the
  token itself excluded; uniforms from the murmur3-finalizer counter hash
  of (seed, token uid, counter);
* α by Minka's fixed point on the (topic, count) and length histograms.

``dtype`` selects the precision: float32 is the reference, bfloat16 the
control that must come out as not correct.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, List, Tuple

import numpy as np

_C1 = 0x85EB_CA6B
_C2 = 0xC2B2_AE35
_GOLDEN = 0x9E37_79B9
MH_SALT = 0x5EED_A11A
TIE = 1e-5          # relative gap under which two readings are a tie
FLOOR_TIE = 2.4e-7  # four float32 roundings (2⁻²⁴ each), relative


# ------------------------------------------------------- counter hash ------


def _fmix(h, xp):
    h = h ^ (h >> xp.uint32(16))
    h = h * xp.uint32(_C1)
    h = h ^ (h >> xp.uint32(13))
    h = h * xp.uint32(_C2)
    h = h ^ (h >> xp.uint32(16))
    return h


def uniform(seed, a, b, xp=np):
    """U(0, 1) from the 24 top bits of hash(seed, a, b), below 1."""
    u32 = lambda x: xp.asarray(x).astype(xp.uint32)
    with np.errstate(over="ignore"):
        h = _fmix(u32(seed) ^ xp.uint32(_GOLDEN), xp)
        h = _fmix(h ^ (u32(a) * xp.uint32(_C1) + xp.uint32(_GOLDEN)), xp)
        h = _fmix(h ^ (u32(b) * xp.uint32(_C2) + xp.uint32(_GOLDEN)), xp)
    bits = (h >> xp.uint32(8)).astype(xp.int32).astype(xp.float32)
    u = (bits + xp.float32(0.5)) * xp.float32(1.0 / (1 << 24))
    return xp.minimum(u, xp.float32(1.0 - 2.0 ** -24))


def mh_seed(seed: int) -> int:
    """The MH stream's seed: the sweep seed mixed with the sampler salt."""
    with np.errstate(over="ignore"):
        return int(_fmix(np.uint32(seed) ^ np.uint32(MH_SALT), np))


# ------------------------------------------------------------ Walker ------


def _sweep(wn, order, n_small, jnp, lax):
    """The Walker sweep of one normalised row (mean 1), given its slots in
    small-then-large order: each of the K steps finalises one slot and
    emits (slot, prob, alias); -1 once nothing remains."""
    K = wn.shape[0]
    first = order[jnp.minimum(n_small, K - 1)]
    big0 = jnp.where(n_small < K, first, -1)
    bigw0 = jnp.where(n_small < K, wn[first], 0.0)

    def step(c, _):
        i, j, big, bigw, pend, pendw = c
        nxt = order[jnp.minimum(i, K - 1)]
        sm = jnp.where(pend >= 0, pend, jnp.where(i < n_small, nxt, -1))
        smw = jnp.where(pend >= 0, pendw,
                        jnp.where(i < n_small, wn[nxt], 0.0))
        i = jnp.where((pend < 0) & (i < n_small), i + 1, i)
        pair = (sm >= 0) & (big >= 0)
        slot = jnp.where(sm >= 0, sm, big)
        prob = jnp.where(pair, jnp.clip(smw, 0.0, 1.0), 1.0)
        alias = jnp.where(pair, big, slot)
        bigw = jnp.where(pair, bigw - (1.0 - smw), bigw)
        demote = pair & (bigw < 1.0)
        move = demote | ((sm < 0) & (big >= 0))
        pend = jnp.where(demote, big, -1)
        pendw = jnp.where(demote, bigw, 0.0)
        k = n_small + j
        nb = order[jnp.minimum(k, K - 1)]
        big = jnp.where(move, jnp.where(k < K, nb, -1), big)
        bigw = jnp.where(move, jnp.where(k < K, wn[nb], 0.0), bigw)
        j = jnp.where(move, j + 1, j)
        return (i, j, big, bigw, pend, pendw), (slot, prob, alias)

    c0 = (jnp.int32(0), jnp.int32(1), big0, bigw0, jnp.int32(-1),
          jnp.asarray(0.0, wn.dtype))
    _, out = lax.scan(step, c0, None, length=K)
    return out


def walker(weights):
    """Alias tables (prob, alias) of each row of ``weights`` [R, K]: the
    sweeps run side by side across rows; the ordering and the table writes
    go row by row (one [K] scatter each)."""
    import jax
    import jax.numpy as jnp

    R, K = weights.shape
    total = jnp.maximum(jnp.sum(weights, axis=-1, keepdims=True),
                        jnp.asarray(1e-30, weights.dtype))
    wn = (weights * (jnp.asarray(K, weights.dtype) / total)).astype(
        weights.dtype)
    small = wn < 1.0
    n_small = jnp.sum(small.astype(jnp.int32), axis=-1)
    pos = jnp.where(small, jnp.cumsum(small.astype(jnp.int32), axis=-1),
                    n_small[:, None] + jnp.cumsum((~small).astype(jnp.int32),
                                                  axis=-1)) - 1
    ks = jnp.arange(K, dtype=jnp.int32)
    order = jax.lax.map(lambda p: jnp.zeros((K,), jnp.int32).at[p].set(ks),
                        pos)
    slots, probs, aliases = jax.vmap(
        lambda w, o, n: _sweep(w, o, n, jnp, jax.lax))(wn, order, n_small)

    def write(row):
        slot, prob, alias = row
        where = jnp.where(slot >= 0, slot, K)
        return (jnp.ones((K,), weights.dtype).at[where].set(prob, mode="drop"),
                ks.at[where].set(alias.astype(jnp.int32), mode="drop"))

    return jax.lax.map(write, (slots, probs, aliases))


# ----------------------------------------------------------- sampler ------


@functools.lru_cache(maxsize=None)
def _walker_fn():
    """``walker`` as a program of its own, whose input is the finished
    weights: fused into a larger program, the row totals were reduced in
    another order, and a total that differs in its last bit moves a Walker
    sweep's residual across 1 somewhere in a row of 10⁵ slots and changes
    the row's layout from there on."""
    import jax

    return jax.jit(walker)


@functools.lru_cache(maxsize=None)
def _counts_fn(V: int, K: int, dtype: str):
    """Φ, Ψ and the stale word-proposal weights wq = (Φ + β)/(Ψ + Vβ)."""
    import jax
    import jax.numpy as jnp

    dt = jnp.dtype(dtype)

    def run(w, z, beta):
        phi = jnp.zeros((V, K), jnp.int32).at[w, z].add(1)
        psi = jnp.zeros((K,), jnp.int32).at[z].add(1)
        beta_ = beta.astype(dt)
        wq = (phi.astype(dt) + beta_) / (psi.astype(dt)[None, :]
                                         + jnp.asarray(V, dt) * beta_)
        return phi, psi, wq

    return jax.jit(run)


@functools.lru_cache(maxsize=None)
def _transition_fn(V: int, K: int, n_mh: int, dtype: str):
    """jitted one-epoch MH transition of every token, given the counts and
    the proposal tables."""
    import jax
    import jax.numpy as jnp

    dt = jnp.dtype(dtype)

    def run(phi, psi, wq, wp, wa, ap, aa, w, z, uid, doc_topics, total,
            alpha, beta, seed2):
        vb = jnp.asarray(V, dt) * beta.astype(dt)
        beta_ = beta.astype(dt)
        alpha_ = alpha.astype(dt)
        alpha_sum = jnp.sum(alpha_).astype(dt)
        tot = total.astype(dt)

        def lookup(k):
            return jnp.sum(jnp.where(doc_topics == k[:, None],
                                     jnp.asarray(1, dt), jnp.asarray(0, dt)),
                           axis=1)

        def posterior(k):
            ex = (k == z).astype(dt)
            ph = phi[w, k].astype(dt) - ex
            ps = psi[k].astype(dt) - ex
            th = lookup(k) - ex
            return (ph + beta_) * (th + alpha_[k]) / (ps + vb)

        s = z
        p_s = posterior(s)
        for step in range(n_mh):
            b0 = 4 * step
            u_draw = uniform(seed2, uid, b0 + 1, jnp).astype(dt)
            u_coin = uniform(seed2, uid, b0 + 2, jnp).astype(dt)
            jk = jnp.minimum((u_draw * K).astype(jnp.int32), K - 1)
            if step % 2 == 0:
                u_mix = uniform(seed2, uid, b0, jnp).astype(dt)
                pick = jnp.floor(u_draw * tot).astype(jnp.int32)
                inside = pick < total
                t_cnt = jnp.take_along_axis(
                    doc_topics, jnp.minimum(pick, doc_topics.shape[1] - 1)
                    [:, None], axis=1)[:, 0]
                t_cnt = jnp.where(inside, t_cnt, s)
                t_al = jnp.where(u_coin < ap[jk], jk, aa[jk])
                t = jnp.where(u_mix * (tot + alpha_sum) < tot, t_cnt, t_al)
                q_s = lookup(s) + alpha_[s]
                q_t = lookup(t) + alpha_[t]
            else:
                t = jnp.where(u_coin < wp[w, jk], jk, wa[w, jk])
                q_s = wq[w, s]
                q_t = wq[w, t]
            u_acc = uniform(seed2, uid, b0 + 3, jnp).astype(dt)
            p_t = posterior(t)
            acc = u_acc < (p_t * q_s) / (p_s * q_t)
            s = jnp.where(acc, t, s)
            p_s = jnp.where(acc, p_t, p_s)
        return s.astype(jnp.int32)

    return jax.jit(run)


def doc_topic_rows(doc_ids: np.ndarray, z: np.ndarray, n_docs: int,
                   K: int) -> Tuple[np.ndarray, np.ndarray]:
    """Each document's topics (one per token) in ascending order, padded
    with ``K``: [n_docs, longest], and the lengths [n_docs]."""
    lengths = np.bincount(doc_ids, minlength=n_docs)
    longest = int(lengths.max())
    order = np.lexsort((z, doc_ids))
    d_sorted = doc_ids[order]
    starts = np.concatenate([[0], np.cumsum(lengths)[:-1]])
    col = np.arange(len(order)) - starts[d_sorted]
    rows = np.full((n_docs, longest), K, np.int32)
    rows[d_sorted, col] = z[order]
    return rows, lengths


def transition(w, doc_ids, n_docs, V, K, z_start, alpha, beta, seed, n_mh,
               dtype="float32"):
    """z after one reference epoch from ``z_start`` (all host int arrays,
    indexed by token uid) at sweep seed ``seed``. Returns (z_next, tables)
    with tables = (wp, wa, ap, aa) on the device."""
    import jax.numpy as jnp

    rows, lengths = doc_topic_rows(doc_ids, z_start, n_docs, K)
    uid = np.arange(len(w), dtype=np.uint32)
    w_, z_ = jnp.asarray(w), jnp.asarray(z_start)
    phi, psi, wq = _counts_fn(V, K, dtype)(w_, z_, jnp.float32(beta))
    alpha_ = jnp.asarray(alpha, jnp.float32)
    wp, wa = _walker_fn()(wq)
    ap, aa = _walker_fn()(alpha_.astype(dtype)[None, :])
    ap, aa = ap[0], aa[0]
    fn = _transition_fn(V, K, n_mh, dtype)
    z_next = fn(phi, psi, wq, wp, wa, ap, aa, w_, z_, jnp.asarray(uid),
                jnp.asarray(rows[doc_ids]), jnp.asarray(lengths[doc_ids]),
                alpha_, jnp.float32(beta), jnp.uint32(mh_seed(seed)))
    return np.asarray(z_next), (wp, wa, ap, aa)


def minka_alpha(alpha, doc_ids, z, n_docs, K, n_iters, dtype="float32",
                max_count=64, max_len=512):
    """Minka's fixed point for the asymmetric prior from the (topic, count)
    histogram Ω_kn and the document-length histogram, ``n_iters`` steps."""
    import jax.numpy as jnp
    from jax.scipy.special import digamma

    key = doc_ids.astype(np.int64) * K + z
    uniq, counts = np.unique(key, return_counts=True)
    omega = np.zeros((K, max_count), np.float64)
    np.add.at(omega, (uniq % K, np.minimum(counts, max_count - 1)), 1)
    hist = np.bincount(np.minimum(np.bincount(doc_ids, minlength=n_docs),
                                  max_len - 1), minlength=max_len)
    dt = jnp.dtype(dtype)
    om = jnp.asarray(omega, dt)
    hs = jnp.asarray(hist, dt)
    ns = jnp.arange(max_count, dtype=dt)
    ls = jnp.arange(max_len, dtype=dt)
    a = jnp.asarray(alpha, dt)
    for _ in range(n_iters):
        a0 = a.sum()
        num = (om * (digamma(ns[None, :] + a[:, None])
                     - digamma(a)[:, None])).sum(axis=1)
        den = (hs * (digamma(ls + a0) - digamma(a0))).sum()
        a = jnp.maximum(a * num / jnp.maximum(den, 1e-30),
                        jnp.asarray(1e-7, dt))
    return np.asarray(a, np.float64)


# ------------------------------------------- tolerant host-side replay ----


class ReplayData:
    """What the host replay of single tokens needs, gathered once."""

    def __init__(self, w, doc_ids, n_docs, V, K, z_start, alpha, beta,
                 seed, n_mh, tables, tokens: np.ndarray):
        import jax.numpy as jnp

        self.V, self.K, self.n_mh = V, K, n_mh
        self.beta = float(beta)
        self.alpha = np.asarray(alpha, np.float64)
        self.alpha_sum = float(np.float32(np.sum(np.asarray(alpha,
                                                            np.float32))))
        self.seed2 = mh_seed(seed)
        self.z = z_start
        self.w = w
        self.doc_ids = doc_ids
        self.psi = np.bincount(z_start, minlength=K).astype(np.float64)
        words = np.unique(w[tokens])
        self.row = {int(v): i for i, v in enumerate(words)}
        mask = np.isin(w, words)
        phi = np.zeros((len(words), K), np.float64)
        np.add.at(phi, (np.searchsorted(words, w[mask]), z_start[mask]), 1)
        self.phi = phi
        order = np.argsort(doc_ids, kind="stable")
        self.doc_tokens = np.split(order, np.cumsum(
            np.bincount(doc_ids, minlength=n_docs))[:-1])
        # table entries at every jk a token's draws can land on
        wp, wa, ap, aa = tables
        self.jk: Dict[int, List[List[int]]] = {}
        pairs_w, pairs_k = [], []
        for t in tokens:
            per_step = []
            for step in range(n_mh):
                u = float(uniform(self.seed2, np.uint32(t), 4 * step + 1))
                ks = _floor_candidates(u * K, K - 1)
                per_step.append(ks)
                pairs_w.extend([w[t]] * len(ks))
                pairs_k.extend(ks)
            self.jk[int(t)] = per_step
        pw = np.asarray(pairs_w, np.int32)
        pk = np.asarray(pairs_k, np.int32)
        wp_v = np.asarray(wp[jnp.asarray(pw), jnp.asarray(pk)], np.float64)
        wa_v = np.asarray(wa[jnp.asarray(pw), jnp.asarray(pk)])
        self.word_table = {(int(a), int(b)): (p, int(q)) for a, b, p, q
                           in zip(pw, pk, wp_v, wa_v)}
        self.ap = np.asarray(ap, np.float64)
        self.aa = np.asarray(aa)


def _floor_candidates(x: float, top: int) -> List[int]:
    """floor(x), and its neighbour where x lies within a few float32
    roundings of an integer (the product is taken in float32)."""
    k = int(math.floor(x))
    out = {min(k, top)}
    near = round(x)
    if abs(x - near) <= FLOOR_TIE * max(1.0, abs(x)):
        out |= {min(max(near - 1, 0), top), min(near, top)}
    return sorted(out)


def _ties(a: float, b: float) -> bool:
    return abs(a - b) <= TIE * max(abs(a), abs(b), 1e-30)


def replay_outcomes(data: ReplayData, t: int, limit: int = 64) -> set:
    """Every topic token ``t``'s MH chain can end on when each comparison
    that lies within a tie may go either way (float64 arithmetic)."""
    K, beta = data.K, data.beta
    w, z0 = int(data.w[t]), int(data.z[t])
    vb = data.V * beta
    phi_row = data.phi[data.row[w]]
    doc = data.doc_tokens[data.doc_ids[t]]
    doc_z = np.sort(data.z[doc])
    total = float(len(doc))

    def n_dk(k):
        return float(np.sum(doc_z == k))

    def post(k):
        ex = 1.0 if k == z0 else 0.0
        return ((phi_row[k] - ex + beta) * (n_dk(k) - ex + data.alpha[k])
                / (data.psi[k] - ex + vb))

    def wq(k):
        return (phi_row[k] + beta) / (data.psi[k] + vb)

    states = {z0}
    for step in range(data.n_mh):
        b0 = 4 * step
        u = lambda c: float(uniform(data.seed2, np.uint32(t), b0 + c))
        u_draw, u_coin, u_acc = u(1), u(2), u(3)
        props = set()
        if step % 2 == 0:
            from_alpha = set()
            for jk in data.jk[t][step]:
                p = data.ap[jk]
                if _ties(u_coin, p) or u_coin < p:
                    from_alpha.add(jk)
                if _ties(u_coin, p) or u_coin >= p:
                    from_alpha.add(int(data.aa[jk]))
            from_doc = {int(doc_z[pick]) for pick in
                        _floor_candidates(u_draw * total, len(doc) - 1)}
            cut = u(0) * (total + data.alpha_sum)
            if _ties(cut, total):
                props = from_doc | from_alpha
            else:
                props = from_doc if cut < total else from_alpha
        else:
            for jk in data.jk[t][step]:
                p, a = data.word_table[(w, jk)]
                if _ties(u_coin, p) or u_coin < p:
                    props.add(jk)
                if _ties(u_coin, p) or u_coin >= p:
                    props.add(a)
        nxt = set()
        for s in states:
            for tp in props:
                if step % 2:
                    q_s, q_t = wq(s), wq(tp)
                else:
                    q_s = n_dk(s) + data.alpha[s]
                    q_t = n_dk(tp) + data.alpha[tp]
                ratio = (post(tp) * q_s) / (post(s) * q_t)
                if _ties(u_acc, ratio) or u_acc < ratio:
                    nxt.add(tp)
                if _ties(u_acc, ratio) or u_acc >= ratio:
                    nxt.add(s)
        states = nxt
        if len(states) > limit:
            break
    return states
