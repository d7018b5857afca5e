"""Alias-table build and Metropolis–Hastings probe for the alias sampler
(DESIGN.md §9): jnp programs, compiled by XLA on every backend.

The build implements Walker/Vose alias construction as a K-step sweep with a
carry of six values per row (all rows side by side): each step finalizes
exactly one slot, so K steps construct the whole table. Smalls pair with the
active large; a large whose residual drops below 1 is demoted and finalized
as the very next small (the classic two-stack schedule with a stack depth of
one). ``prepare`` normalizes the rows and computes their small/large
partition order once: two prefix sums give each slot's place in the partition
stream, and one row-batched sort along K turns those places into the stream,
with no scatter.

Each row's sweep and table sort are exact per row: no value of a row depends
on which other rows share the batch. Only ``prepare``'s row total does, in
how the program around it reduces it. So rows prepared in a program of their
own can join another build's sweep (``alias_tables_with``) and keep the
tables their own build would give.

The sweep reads its operands from the partition stream (``order``, and the
weights in stream order, ``wn_ord``) through two heads, the next small and the
next large, each of which advances at most one position per step. So once
every C = ``WINDOW`` steps it takes, per row and head, a window of two aligned
C-wide blocks of the stream, and picks each step's four operands out of the
windows by an exact lane select. Gathering them from the whole [R, K] rows on
every step, four [821]-element gathers from [821, 10⁵], took ~44 µs of a
47 µs step on a TPU v5e (95% of the sweep). The windows change where the
operands are read from, not their values or the order of the arithmetic, so
the tables are bit for bit those of the gather-per-step sweep.

The MH probe implements the LightLDA proposal cycle (doc, word, doc, ...):

  doc proposal   q_d(k) ∝ n_dk + α_k   — mixture of the doc's sparse
                 (topic, count) pairs (O(k_d) cumulative walk) and the α
                 alias table;
  word proposal  q_w(k) ∝ (ñ_wk + β)/(ñ_k + Vβ) — a STALE per-word alias
                 table (rebuilt at aggregation boundaries), O(1) probes;

each followed by a Metropolis–Hastings accept against the TRUE collapsed
posterior ratio (live counts, exact ¬ivd self-exclusion), which is what keeps
the stale proposals exact rather than approximate. ``mh_resample`` mixes the
sampler seed with a sampler-family salt first (the MH uniform stream must not
collide with the dense path's Gumbel stream at equal (seed, uid) counters).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import prng

# decorrelates the MH uniform stream from the dense sampler's Gumbel stream
MH_SALT = 0x5EED_A11A


# --------------------------------------------------------------- build ------

# Sweep steps per window refresh, and the width of the aligned blocks the
# windows are made of: the lane width of a TPU vector register (K when K is
# smaller).
WINDOW = 128
# Sweep steps per loop iteration: on a TPU v5e, unrolling by 4 cut the
# 821-row sweep from 0.18 to 0.15 s and the one-row α sweep from 0.35 to
# 0.32 s at K = 10⁵.
UNROLL = 4


def prepare(weights):
    """Mean-1 normalization + stable small/large partition of [R, K] rows.

    Returns (order, wn_ord, ns): ``order`` lists small slots (w < 1) in
    index order, then large slots; ``wn_ord`` holds the normalized weights
    in that stream order; ``ns`` is the per-row small count. The sweep takes
    each step's operands from C-wide windows of (order, wn_ord) instead of
    gathering from the [R, K] rows on every step.
    """
    K = weights.shape[-1]
    total = jnp.maximum(jnp.sum(weights, axis=-1, keepdims=True),
                        jnp.float32(1e-30))
    wn = (weights * (jnp.float32(K) / total)).astype(jnp.float32)
    is_large = wn >= 1.0
    small = ~is_large
    ns = jnp.sum(small, axis=-1, dtype=jnp.int32)
    # each slot's place in the stable argsort of is_large, from two prefix
    # sums: a permutation of [0, K) in every row
    pos = jnp.where(small, jnp.cumsum(small, axis=-1, dtype=jnp.int32),
                    ns[:, None] + jnp.cumsum(is_large, axis=-1,
                                             dtype=jnp.int32)) - 1
    # inverted by one row-batched sort along K keyed by pos, which carries
    # the slot ids and their weights in the same pass; pos is unique, so the
    # sort need not be stable (a stable one takes an iota operand more). A
    # sort, not a [K] scatter per row: on a TPU v5e a scatter writes one
    # index at a time, ~5.9 ns each, where this sort takes ~1.5 ns per
    # element (0.119 s at [821, 10⁵]).
    slots = jnp.broadcast_to(jnp.arange(K, dtype=jnp.int32), pos.shape)
    _, order, wn_ord = jax.lax.sort((pos, slots, wn), dimension=1,
                                    is_stable=False, num_keys=1)
    return order, wn_ord, ns


def _sweep_step(carry, _, small_at, large_at, ns, n_topics):
    """One branch-free Walker-sweep step over every row at once.

    ``small_at(p)`` / ``large_at(p)`` return (order[p], wn_ord[p]) per row at
    stream positions p (clamped to K − 1 here) for the small and the large
    head: the step never indexes the [R, K] rows itself, so its caller
    decides where the operands are read from (C-wide windows,
    :func:`_sweep`).

    The step only carries six values per row and EMITS its finalized
    (slot, prob, alias) triple as a scan output — the [K] tables materialize
    in one sort along K after the scan, so the sweep is O(K) per row, not
    the O(K²) a carried-array copy per step would cost."""
    i, j, cur, curw, pend, pendw = carry
    K = n_topics
    has_pend = pend >= 0
    has_small = i < ns
    oi, wi = small_at(jnp.minimum(i, K - 1))
    s_slot = jnp.where(has_pend, pend, jnp.where(has_small, oi, -1))
    sw = jnp.where(has_pend, pendw, jnp.where(has_small, wi, 0.0))
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
    onl, wnl = large_at(jnp.minimum(nl, K - 1))
    cur2 = jnp.where(advance, jnp.where(has_next, onl, -1), cur)
    curw3 = jnp.where(advance, jnp.where(has_next, wnl, 0.0), curw2)
    j2 = jnp.where(advance, j + 1, j)
    return (i2, j2, cur2, curw3, pend2, pendw2), (slot, val, ali)


def _blocks(a, width):
    """[R, K] → [R, nb, width]: each row cut into aligned width-wide blocks,
    zero-padded, with one spare block so that block b + 1 always exists."""
    R, K = a.shape
    nb = -(-K // width) + 1
    return jnp.pad(a, ((0, 0), (0, nb * width - K))).reshape(R, nb, width)


def _take_pair(blocks, b):
    """Row r's blocks b[r] and b[r] + 1 of [R, nb, width] blocks, as
    [2·width, R]: rows on lanes, so a select reduces across sublanes. The
    row is a batch dimension of the gather, so a build whose rows are split
    across chips reads only its own chip's blocks."""
    pair = jax.vmap(lambda bl, i: jax.lax.dynamic_slice_in_dim(bl, i, 2))(
        blocks, b)
    return pair.reshape(pair.shape[0], -1).T


def _window(blocks_o, blocks_w, lane, head, n_topics):
    """A fetch function for :func:`_sweep_step` that reads every row's stream
    positions [min(head, K − 1), min(head + width − 1, K − 1)] from a window
    of two aligned blocks: the one that holds min(head, K − 1) and the next.
    ``lane`` is arange(2·width), made once outside the sweep's loops (on a
    TPU v5e the one-row α sweep ran 20% slower with an iota in each step).

    Each select matches exactly one window position and takes its value
    unchanged. Whole aligned blocks are a row gather the TPU does natively:
    a per-row window at an unaligned offset compiles to a loop of R slices,
    and at 821 × 10⁵ on a TPU v5e a word-table build took 5.5 s with it
    against 2.2 s with the blocks."""
    width = blocks_o.shape[2]
    b = jnp.minimum(head, n_topics - 1) // width
    win_o, win_w = _take_pair(blocks_o, b), _take_pair(blocks_w, b)
    off = b * width

    def at(p):
        hit = lane[:, None] == (p - off)[None, :]
        return (jnp.max(jnp.where(hit, win_o, jnp.iinfo(jnp.int32).min),
                        axis=0),
                jnp.max(jnp.where(hit, win_w, -jnp.inf), axis=0))
    return at


def _sweep(order, wn_ord, ns):
    """Alias sweeps of all rows side by side. order [R, K] int32 (smalls in
    index order, then larges), wn_ord [R, K] f32 (the normalized weights in
    that order), ns [R] int32 (small counts). Returns the per-step
    (slot, prob, alias) triples, each [R, K].

    ⌈K/C⌉ chunks of C = min(``WINDOW``, K) steps: each chunk takes one
    window per row at the small head i and one at the large head ns + j, and
    its steps read only those. Each head moves at most one position per step,
    so C steps never leave their windows; the steps past K in the last chunk
    are idle and trimmed. The chunk's steps are unrolled by ``UNROLL``."""
    R, K = order.shape
    C = min(WINDOW, K)
    blocks_o, blocks_w = _blocks(order, C), _blocks(wn_ord, C)
    lane = jnp.arange(2 * C, dtype=jnp.int32)
    has_l = ns < K
    first = jnp.minimum(ns, K - 1)[:, None]
    cur0 = jnp.where(has_l, jnp.take_along_axis(order, first, 1)[:, 0], -1)
    curw0 = jnp.where(has_l, jnp.take_along_axis(wn_ord, first, 1)[:, 0],
                      0.0)
    zero = jnp.zeros((R,), jnp.int32)
    carry0 = (zero, zero + 1, cur0, curw0, zero - 1,
              jnp.zeros((R,), jnp.float32))

    def chunk(carry, _):
        i, j = carry[0], carry[1]
        step = functools.partial(
            _sweep_step,
            small_at=_window(blocks_o, blocks_w, lane, i, K),
            large_at=_window(blocks_o, blocks_w, lane, ns + j, K),
            ns=ns, n_topics=K)
        return jax.lax.scan(step, carry, None, length=C, unroll=UNROLL)

    _, out = jax.lax.scan(chunk, carry0, None, length=-(-K // C))
    return tuple(o.reshape(-1, R)[:K].T for o in out)


def _tables(order, wn_ord, ns):
    """(prob, alias) [R, K] of ``prepare``'d rows: their sweep, then the
    tables written by one sort along K.

    The K kept steps of a row's sweep finalize each of its slots exactly once
    (every step finalizes one slot while any remains, and no slot twice), so
    their ``slot`` outputs are a permutation of [0, K). The tables are
    therefore one row-batched sort along K keyed by ``slot`` (unique, so not
    stable) that carries (prob, alias): the same arrays, bit for bit, as
    writing each step's values at its slot, without the per-index writes of
    a [K] scatter (~5.9 ns per index on a TPU v5e).
    """
    slot, val, ali = _sweep(order, wn_ord, ns)
    _, prob, alias = jax.lax.sort((slot, val, ali), dimension=1,
                                  is_stable=False, num_keys=1)
    return prob, alias


def _prepare_rows(weights):
    """``prepare`` of weights [..., K] as f32 rows [R, K]."""
    return prepare(weights.reshape(-1, weights.shape[-1])
                   .astype(jnp.float32))


def alias_tables(weights):
    """Batched Walker alias tables over the trailing axis (traceable; the
    program is :func:`build_alias`).

    weights [..., K] nonneg f32 → (prob [..., K] f32, alias [..., K] int32)
    with the table identity  q(k) = (prob_k + Σ_j (1−prob_j)·1[alias_j = k])/K
    = weights_k / Σ weights  (exactly, up to f32 rounding).
    """
    prob, alias = _tables(*_prepare_rows(weights))
    return prob.reshape(weights.shape), alias.reshape(weights.shape)


def alias_tables_with(weights, row):
    """:func:`alias_tables` of weights [..., K], with one more row swept in
    the same sweep and sorted in the same sort.

    ``row`` is that row already prepared, ``prepare``'s (order [1, K],
    wn_ord [1, K], ns [1]), in a program of its own so that its total rounds
    as in a build of that row alone. It rides as row R of the [R + 1, K]
    sweep: on a TPU v5e the sweep puts rows on lanes, and a one-row sweep
    costs 2.3× that of 821 rows, whose 7 × 128 lanes leave 75 idle. Returns
    (prob [..., K], alias [..., K], its prob [K], its alias [K]), each table
    bit for bit that of the row's own build.
    """
    rows = [jnp.concatenate(pair) for pair in zip(_prepare_rows(weights), row)]
    prob, alias = _tables(*rows)
    return (prob[:-1].reshape(weights.shape),
            alias[:-1].reshape(weights.shape), prob[-1], alias[-1])


@jax.jit
def build_alias(weights):
    """:func:`alias_tables` as a program of its own."""
    return alias_tables(weights)


# --------------------------------------------------------------- probe ------


def mh_resample(
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
    seed,        # [] uint32 — the raw sweep seed (the MH salt is mixed here)
    beta,        # [] f32
    vocab_size: int,
    n_mh: int,
):
    """n_mh MH steps per token against the true collapsed posterior ratio;
    returns z_new [T] int32.

    Per-token cost: O(k_d) for each doc proposal (the pair-row walk) plus
    O(1) gathers per probe — never O(K). Every token samples against the
    same snapshots with its own uid-keyed counter stream, so its draw does
    not depend on where it sits in the token arrays.
    """
    seed2 = prng.fmix32(jnp.asarray(seed, jnp.uint32)
                        ^ jnp.uint32(MH_SALT))
    alpha_sum = jnp.sum(alpha).astype(jnp.float32)
    beta = jnp.float32(beta)
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
