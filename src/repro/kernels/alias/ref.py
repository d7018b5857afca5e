"""Pure-jnp oracle for the alias-table build / Metropolis–Hastings probe kernels.

Both ops evaluate exactly the same integer/float formulas as ``kernel.py`` —
including the counter-based uniforms and the branch-free Walker sweep — so
kernel vs ref agreement is required to be bitwise (identical float ops in
identical order on both paths).

The build implements Walker/Vose alias construction as a K-step sweep with a
scalar carry (vmapped across rows): each step finalizes exactly one slot, so K
steps construct the whole table. Smalls pair with the active large; a large
whose residual drops below 1 is demoted and finalized as the very next small
(the classic two-stack schedule with a stack depth of one). The normalization
and small/large partition order are computed ONCE in ``ops._prepare`` and
shared verbatim with the Pallas kernel.

The MH probe implements the LightLDA proposal cycle (doc, word, doc, ...):

  doc proposal   q_d(k) ∝ n_dk + α_k   — mixture of the doc's sparse
                 (topic, count) pairs (O(k_d) cumulative walk) and the α
                 alias table;
  word proposal  q_w(k) ∝ (ñ_wk + β)/(ñ_k + Vβ) — a STALE per-word alias
                 table (rebuilt at aggregation boundaries), O(1) probes;

each followed by a Metropolis–Hastings accept against the TRUE collapsed
posterior ratio (live counts, exact ¬ivd self-exclusion), which is what keeps
the stale proposals exact rather than approximate.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import prng


# --------------------------------------------------------------- build ------


def _sweep_step(carry, _, wn, order, ns, n_topics):
    """One branch-free Walker-sweep step (shared slot/value algebra with the
    Pallas kernel — keep any edit mirrored in ``kernel._alias_build_kernel``).

    The step only carries six scalars and EMITS its finalized
    (slot, prob, alias) triple as a scan output — the [K] tables materialize
    in one scatter after the scan, so the sweep is O(K) per row, not the
    O(K²) a carried-array copy per step would cost."""
    i, j, cur, curw, pend, pendw = carry
    K = n_topics
    has_pend = pend >= 0
    has_small = i < ns
    oi = order[jnp.minimum(i, K - 1)]
    s_slot = jnp.where(has_pend, pend, jnp.where(has_small, oi, -1))
    sw = jnp.where(has_pend, pendw, jnp.where(has_small, wn[oi], 0.0))
    i2 = jnp.where(jnp.logical_and(~has_pend, has_small), i + 1, i)

    use_small = jnp.logical_and(s_slot >= 0, cur >= 0)
    slot = jnp.where(s_slot >= 0, s_slot, cur)    # -1 when nothing remains
    val = jnp.where(use_small, jnp.clip(sw, 0.0, 1.0), 1.0)
    ali = jnp.where(use_small, cur, slot)

    curw2 = jnp.where(use_small, curw - (1.0 - sw), curw)
    demote = jnp.logical_and(use_small, curw2 < 1.0)
    advance = jnp.logical_or(demote,
                             jnp.logical_and(s_slot < 0, cur >= 0))
    pend2 = jnp.where(demote, cur, -1)
    pendw2 = jnp.where(demote, curw2, 0.0)
    nl = ns + j
    has_next = nl < K
    onl = order[jnp.minimum(nl, K - 1)]
    cur2 = jnp.where(advance, jnp.where(has_next, onl, -1), cur)
    curw3 = jnp.where(advance,
                      jnp.where(has_next, wn[onl], 0.0), curw2)
    j2 = jnp.where(advance, j + 1, j)
    return (i2, j2, cur2, curw3, pend2, pendw2), (slot, val, ali)


def _sweep_row(wn, order, ns):
    """Alias sweep of ONE normalized row. wn [K] f32, order [K] int32 (smalls
    in index order, then larges), ns [] int32 (small count). Returns the
    per-step (slot, prob, alias) triples, each [K]."""
    K = wn.shape[0]
    has_l = ns < K
    first = order[jnp.minimum(ns, K - 1)]
    cur0 = jnp.where(has_l, first, -1)
    curw0 = jnp.where(has_l, wn[first], 0.0)
    carry0 = (jnp.int32(0), jnp.int32(1), cur0, curw0, jnp.int32(-1),
              jnp.float32(0.0))
    step = functools.partial(_sweep_step, wn=wn, order=order, ns=ns,
                             n_topics=K)
    _, out = jax.lax.scan(step, carry0, None, length=K)
    return out


def _write_row(slots, vals, alis):
    """Scatter one row's finalized triples into its (prob, alias) tables."""
    K = slots.shape[0]
    # every live step finalizes exactly one slot; idle tail steps emit
    # slot = -1 → redirected out of bounds and dropped (defaults: prob 1,
    # alias self — the same values a live finalize would have written)
    slot_w = jnp.where(slots >= 0, slots, K)
    prob = jnp.ones((K,), jnp.float32).at[slot_w].set(vals, mode="drop")
    alias = jnp.arange(K, dtype=jnp.int32).at[slot_w].set(
        alis.astype(jnp.int32), mode="drop")
    return prob, alias


def build_alias_ref(wn, order, ns):
    """Batched alias construction. wn [R, K] normalized (mean 1) weights,
    order [R, K] small/large partition order, ns [R] small counts — all from
    ``ops._prepare``. Returns (prob [R, K] f32, alias [R, K] int32).

    The sweeps run side by side across rows; the table writes run row by
    row, since one [R, K] scatter costs the TPU compiler ~25 s at
    R·K ≈ 10⁸ and a loop of [K] scatters about 2 s."""
    steps = jax.vmap(_sweep_row)(wn, order, ns)
    return jax.lax.map(lambda t: _write_row(*t), steps)


# --------------------------------------------------------------- probe ------


def mh_resample_ref(
    phi,         # [rows, K] int32 — LIVE word-topic counts (vocab shard)
    psi,         # [K] int32       — LIVE topic totals
    doc_topic,   # [D, cap] int32  — sparse Θ pairs (-1 = empty slot)
    doc_count,   # [D, cap] int32
    wq,          # [rows, K] f32   — stale word-proposal weights (ñ+β)/(ψ̃+Vβ)
    wp,          # [rows, K] f32   — word alias probs
    wa,          # [rows, K] int32 — word alias indices
    alpha,       # [K] f32
    ap,          # [K] f32         — α alias probs
    aa,          # [K] int32       — α alias indices
    w,           # [T] int32 — word ids (rows-local)
    d,           # [T] int32 — doc ids (local to doc_topic)
    z,           # [T] int32 — current assignments
    uid,         # [T] uint32 — global token uids (RNG counters)
    seed2,       # [] uint32 — pre-salted sampler seed (ops mixes the salt)
    beta,        # [] f32
    alpha_sum,   # [] f32
    vocab_size: int,
    n_mh: int,
):
    """n_mh MH steps per token against the true collapsed posterior ratio.

    Per-token cost: O(k_d) for each doc proposal (the pair-row walk) plus
    O(1) gathers per probe — never O(K).
    """
    K = psi.shape[0]
    vb = jnp.float32(vocab_size) * beta
    rows_t = doc_topic[d]                                # [T, cap]
    rows_c = doc_count[d].astype(jnp.float32)            # [T, cap]
    total = jnp.sum(rows_c, axis=1)                      # [T]
    z0 = z

    def lookup(k):
        """n_dk INCLUDING the token itself (the raw stored pairs)."""
        return jnp.sum(jnp.where(rows_t == k[:, None], rows_c, 0.0), axis=1)

    def p_of(k):
        """True collapsed posterior at k, self-excluded wrt z0 (¬ivd)."""
        ex = (k == z0).astype(jnp.float32)
        ph = phi[w, k].astype(jnp.float32) - ex
        ps = psi[k].astype(jnp.float32) - ex
        th = lookup(k) - ex
        return (ph + beta) * (th + alpha[k]) / (ps + vb)

    s = z0
    p_s = p_of(s)
    for step in range(n_mh):
        b0 = jnp.uint32(4 * step)
        u_draw = prng.uniform01(seed2, uid, b0 + jnp.uint32(1))
        u_coin = prng.uniform01(seed2, uid, b0 + jnp.uint32(2))
        if step % 2 == 0:
            # ----- doc proposal: q_d(k) ∝ n_dk + α_k ------------------------
            u_mix = prng.uniform01(seed2, uid, b0)
            r = u_draw * total
            cum = jnp.cumsum(rows_c, axis=1)
            prev = cum - rows_c
            mask = ((cum > r[:, None]) & (prev <= r[:, None])
                    & (rows_c > 0.0))
            t_cnt = jnp.sum(jnp.where(mask, rows_t, 0), axis=1)
            t_cnt = jnp.where(jnp.any(mask, axis=1), t_cnt, s)
            jk = jnp.minimum((u_draw * K).astype(jnp.int32), K - 1)
            t_al = jnp.where(u_coin < ap[jk], jk, aa[jk])
            use_counts = u_mix * (total + alpha_sum) < total
            t_prop = jnp.where(use_counts, t_cnt, t_al).astype(jnp.int32)
            q_s = lookup(s) + alpha[s]
            q_t = lookup(t_prop) + alpha[t_prop]
        else:
            # ----- word proposal: stale alias table, O(1) probes ------------
            jk = jnp.minimum((u_draw * K).astype(jnp.int32), K - 1)
            t_prop = jnp.where(u_coin < wp[w, jk], jk, wa[w, jk])
            q_s = wq[w, s]
            q_t = wq[w, t_prop]
        u_acc = prng.uniform01(seed2, uid, b0 + jnp.uint32(3))
        p_t = p_of(t_prop)
        ratio = (p_t * q_s) / (p_s * q_t)
        acc = u_acc < ratio
        s = jnp.where(acc, t_prop, s)
        p_s = jnp.where(acc, p_t, p_s)
    return s.astype(jnp.int32)
