"""Alias-table build / MH probe against plain references, plus the sampler's
statistical-equivalence contract (DESIGN.md §9).

The build must give the tables of a sequential NumPy Walker loop bit for bit;
the MH probe must draw what ``core/ring_reference.mh_draws`` (NumPy float32,
written apart from the program) draws, except at float32 near-ties. The
statistical tests then anchor the whole alias path to the exact Gumbel-max
categorical: MH topic-assignment marginals must match the true collapsed
posterior within total-variation tolerance.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import prng, ring_reference, sparse
from repro.kernels.alias import ops as alias_ops
from repro.kernels.gibbs import ops as gibbs_ops

pytestmark = pytest.mark.kernels

RNG = np.random.default_rng(11)


# ------------------------------------------------------------- build --------


def _prepared(w):
    """``_prepare``'s (order, wn_ord, ns) as NumPy arrays, with the
    normalized weights by slot, ``wn``, rebuilt exactly by inverting
    ``order`` over ``wn_ord``."""
    order, wn_ord, ns = (np.asarray(a) for a in alias_ops._prepare(w))
    wn = np.empty_like(wn_ord)
    np.put_along_axis(wn, order, wn_ord, axis=-1)
    return wn, order, wn_ord, ns


def _walker_oracle(wn, order, ns):
    """The Walker sweep as a plain sequential loop (NumPy, float32, rows side
    by side), gathering its four operands from the whole rows on every step:
    the schedule ``ops._sweep_step`` follows, written out independently."""
    R, K = wn.shape
    rows = np.arange(R)
    one, zero = np.float32(1.0), np.float32(0.0)
    prob = np.ones((R, K), np.float32)
    alias = np.tile(np.arange(K, dtype=np.int32), (R, 1))
    has_l = ns < K
    first = order[rows, np.minimum(ns, K - 1)]
    cur = np.where(has_l, first, -1)
    curw = np.where(has_l, wn[rows, first], zero).astype(np.float32)
    i, j = np.zeros(R, np.int64), np.ones(R, np.int64)
    pend, pendw = np.full(R, -1), np.zeros(R, np.float32)
    for _ in range(K):
        has_pend, has_small = pend >= 0, i < ns
        oi = order[rows, np.minimum(i, K - 1)]
        s_slot = np.where(has_pend, pend, np.where(has_small, oi, -1))
        sw = np.where(has_pend, pendw,
                      np.where(has_small, wn[rows, oi], zero))
        i = np.where(~has_pend & has_small, i + 1, i)
        use_small = (s_slot >= 0) & (cur >= 0)
        slot = np.where(s_slot >= 0, s_slot, cur)
        live = slot >= 0
        prob[rows[live], slot[live]] = np.where(
            use_small, np.clip(sw, zero, one), one)[live]
        alias[rows[live], slot[live]] = np.where(use_small, cur, slot)[live]
        curw2 = np.where(use_small, curw - (one - sw), curw)
        demote = use_small & (curw2 < one)
        advance = demote | ((s_slot < 0) & (cur >= 0))
        pend = np.where(demote, cur, -1)
        pendw = np.where(demote, curw2, zero)
        nl = ns + j
        onl = order[rows, np.minimum(nl, K - 1)]
        cur2 = np.where(advance, np.where(nl < K, onl, -1), cur)
        curw = np.where(advance, np.where(nl < K, wn[rows, onl], zero),
                        curw2).astype(np.float32)
        cur = cur2
        j = np.where(advance, j + 1, j)
    return prob, alias


def _rows(kind, R, K, rng):
    """[R, K] nonnegative weights of one family."""
    if kind == "gamma":
        return rng.gamma(0.3, 1.0, (R, K))
    if kind == "onehot":                   # one large absorbs every small
        return np.eye(K)[rng.integers(0, K, R)]
    if kind == "cascade":       # larges just above the mean, demoted one
        w = rng.choice([0.2, 1.05], (R, K))          # after another
        w[:, rng.integers(0, K)] = 3.0
        return w
    if kind == "equal":                    # every wn exactly 1
        return np.ones((R, K))
    if kind == "zipf":
        return rng.permuted(np.tile(1.0 / np.arange(1, K + 1), (R, 1)),
                            axis=1)
    if kind == "zero":
        return np.zeros((R, K))
    if kind == "one_large":                # all small but one
        w = np.full((R, K), 0.5)
        w[np.arange(R), rng.integers(0, K, R)] = 0.5 * K
        return w
    raise ValueError(kind)


@pytest.mark.parametrize("R,K", [(1, 8), (5, 37), (16, 128), (3, 513)])
def test_alias_build_matches_sequential_oracle(R, K):
    """``build_alias`` on gamma(0.3) + 10⁻³ rows gives the tables of the
    sequential Walker loop bit for bit."""
    w = jnp.asarray(RNG.gamma(0.3, 1.0, (R, K)).astype(np.float32)) + 1e-3
    wn, order, _, ns = _prepared(w)
    prob, alias = alias_ops.build_alias(w)
    p_want, a_want = _walker_oracle(wn, order, ns)
    np.testing.assert_array_equal(np.asarray(prob), p_want)
    np.testing.assert_array_equal(np.asarray(alias), a_want)


@pytest.mark.parametrize("kind", ["gamma", "onehot", "cascade", "equal",
                                  "zipf", "zero", "one_large"])
@pytest.mark.parametrize("R,K", [(1, 37), (64, 37), (3, 128), (64, 129),
                                 (1, 5000), (4, 1000)])
def test_windowed_sweep_matches_sequential_oracle(R, K, kind):
    """The windowed sweep of ``build_alias`` gives the tables of the
    gather-per-step Walker loop bit for bit: K below, at and just above the
    window width, K ≫ the width, one row and many."""
    rng = np.random.default_rng(R * 7919 + K)
    w = jnp.asarray(_rows(kind, R, K, rng).astype(np.float32))
    wn, order, _, ns = _prepared(w)
    prob, alias = alias_ops.build_alias(w)
    p_want, a_want = _walker_oracle(wn, order, ns)
    np.testing.assert_array_equal(np.asarray(prob), p_want)
    np.testing.assert_array_equal(np.asarray(alias), a_want)


# the grid of test_windowed_sweep_matches_sequential_oracle
GRID_KINDS = ["gamma", "onehot", "cascade", "equal", "zipf", "zero",
              "one_large"]
GRID_SHAPES = [(1, 37), (64, 37), (3, 128), (64, 129), (1, 5000), (4, 1000)]


def _swept(R, K, kind):
    """Weights of one family and the trimmed sweep's per-step (slot, prob,
    alias) outputs for them, each [R, K]."""
    rng = np.random.default_rng(R * 7919 + K)
    w = jnp.asarray(_rows(kind, R, K, rng).astype(np.float32))
    order, wn_ord, ns = alias_ops._prepare(w)
    return w, tuple(np.asarray(a) for a in alias_ops._sweep(order, wn_ord,
                                                            ns))


@pytest.mark.parametrize("kind", GRID_KINDS)
@pytest.mark.parametrize("R,K", GRID_SHAPES)
def test_sweep_finalizes_each_slot_once(R, K, kind):
    """The K kept steps of every row finalize each slot exactly once: their
    ``slot`` outputs are a permutation of [0, K), the premise of writing the
    tables by a sort keyed by ``slot``."""
    _, (slot, _, _) = _swept(R, K, kind)
    np.testing.assert_array_equal(
        np.sort(slot, axis=-1), np.broadcast_to(np.arange(K), (R, K)))


@pytest.mark.parametrize("kind", GRID_KINDS)
@pytest.mark.parametrize("R,K", GRID_SHAPES)
def test_sorted_table_write_matches_scatter_oracle(R, K, kind):
    """``build_alias`` == each step's (prob, alias) written at its slot, as
    a NumPy scatter, bit for bit; a slot no step wrote stays NaN / −1."""
    w, (slot, val, ali) = _swept(R, K, kind)
    rows = np.arange(R)[:, None]
    p_want = np.full((R, K), np.nan, np.float32)
    a_want = np.full((R, K), -1, np.int32)
    p_want[rows, slot] = val
    a_want[rows, slot] = ali
    prob, alias = alias_ops.build_alias(w)
    np.testing.assert_array_equal(np.asarray(prob), p_want)
    np.testing.assert_array_equal(np.asarray(alias), a_want)


@pytest.mark.parametrize("R,K", [(1, 1), (3, 40), (6, 1000)])
def test_prepare_partition_is_the_stable_argsort(R, K):
    """The prefix-sum partition in ``_prepare`` == a stable argsort of the
    small/large flags (smalls in index order, then larges)."""
    w = jnp.asarray(RNG.gamma(0.3, 1.0, (R, K)).astype(np.float32))
    w = w.at[0, :K // 2].set(0.0)          # zero-weight slots are smalls
    wn, order, _, ns = _prepared(w)
    large = wn >= 1.0
    np.testing.assert_array_equal(
        np.asarray(order), np.argsort(large, axis=-1, kind="stable"))
    np.testing.assert_array_equal(np.asarray(ns), (~large).sum(axis=-1))


@pytest.mark.parametrize("R,K", [(1, 1), (3, 40), (6, 1000)])
def test_prepare_stream_weights_follow_order(R, K):
    """``wn_ord`` from the ordering sort == the mean-1 weights gathered by
    ``order``."""
    w = jnp.asarray(RNG.gamma(0.3, 1.0, (R, K)).astype(np.float32))
    order, wn_ord, _ = (np.asarray(a) for a in alias_ops._prepare(w))
    wn = np.asarray(w * (jnp.float32(K) / jnp.maximum(
        jnp.sum(w, axis=-1, keepdims=True), jnp.float32(1e-30))))
    np.testing.assert_array_equal(
        wn_ord, np.take_along_axis(wn, order, axis=-1))


@pytest.mark.parametrize("shape", [(4, 64), (2, 3, 32)])
def test_alias_invariant_reconstructs_distribution(shape):
    """prob/alias must reconstruct the normalized input exactly:
    q(k) = (prob_k + Σ_j (1−prob_j)·1[alias_j = k]) / K = w_k / Σw."""
    w = jnp.asarray(RNG.gamma(0.5, 1.0, shape).astype(np.float32)) + 1e-3
    prob, alias = alias_ops.build_alias(w)
    K = shape[-1]
    wn = np.asarray(w).reshape(-1, K)
    wn = wn * (K / wn.sum(1, keepdims=True))
    p = np.asarray(prob).reshape(-1, K)
    a = np.asarray(alias).reshape(-1, K)
    rec = p.copy()
    for r in range(p.shape[0]):
        np.add.at(rec[r], a[r], 1.0 - p[r])
    np.testing.assert_allclose(rec, wn, atol=2e-5, rtol=1e-5)
    assert (p >= 0).all() and (p <= 1).all()
    assert ((a >= 0) & (a < K)).all()


def test_alias_build_degenerate_rows():
    """Uniform rows (all slots exactly at the mean) and one-hot rows."""
    K = 16
    uni = jnp.ones((1, K), jnp.float32)
    p, a = alias_ops.build_alias(uni)
    np.testing.assert_allclose(np.asarray(p)[0], np.ones(K), atol=1e-6)
    onehot = jnp.zeros((1, K), jnp.float32).at[0, 3].set(5.0)
    p, a = alias_ops.build_alias(onehot)
    # every draw must land on topic 3: zero-prob slots all alias to 3
    rec = np.asarray(p)[0].copy()
    np.add.at(rec, np.asarray(a)[0], 1.0 - np.asarray(p)[0])
    np.testing.assert_allclose(rec[3], float(K), atol=1e-4)


# ------------------------------------------------------------- probe --------


def _consistent_counts(V, K, D, T, seed=3):
    rng = np.random.default_rng(seed)
    w = rng.integers(0, V, T).astype(np.int32)
    # round-robin docs: exactly ⌈T/D⌉ tokens per doc, so cap = ⌈T/D⌉
    # suffices even at cap ≪ K (the suggest_cap contract)
    d = (np.arange(T) % D).astype(np.int32)
    z = rng.integers(0, K, T).astype(np.int32)
    phi = np.zeros((V, K), np.int32)
    np.add.at(phi, (w, z), 1)
    psi = np.bincount(z, minlength=K).astype(np.int32)
    return w, d, z, phi, psi


def _mh_args(V, K, D, T, cap, seed=3):
    rng = np.random.default_rng(seed + 100)
    w, d, z, phi, psi = _consistent_counts(V, K, D, T, seed)
    tp, ct = sparse.pairs_from_assignments(
        jnp.asarray(d), jnp.asarray(z), jnp.ones(T, bool), D, cap)
    alpha = jnp.asarray(rng.uniform(0.05, 0.8, K).astype(np.float32))
    beta = jnp.float32(0.01)
    tabs = sparse.make_tables(jnp.asarray(phi), jnp.asarray(psi), alpha,
                              beta, V)
    uid = jnp.arange(T, dtype=jnp.uint32) + 7
    return ((jnp.asarray(phi), jnp.asarray(psi), tp, ct,
             tabs.wq, tabs.wp, tabs.wa, alpha, tabs.ap, tabs.aa,
             jnp.asarray(w), jnp.asarray(d), jnp.asarray(z), uid,
             jnp.uint32(42), beta),
            (w, d, z, phi, psi, alpha, beta, tabs))


@pytest.mark.parametrize("T,K,n_mh", [(37, 16, 1), (300, 16, 5), (64, 130, 4)])
def test_mh_matches_plain_reference(T, K, n_mh):
    """``mh_resample`` draws what the NumPy float32 reference
    ``ring_reference.mh_draws`` draws from the same state at the seed salted
    with ``MH_SALT``; a draw may differ only where its margin is below
    ``ring_reference.TIE`` (a float32 near-tie)."""
    args, _ = _mh_args(V=20, K=K, D=8, T=T, cap=K)
    got = np.asarray(alias_ops.mh_resample(*args, vocab_size=20, n_mh=n_mh))
    (phi, psi, tp, ct, wq, wp, wa, alpha, ap, aa, w, d, z, uid, seed,
     beta) = (np.asarray(a) for a in args)
    seed2 = int(prng.fmix32(jnp.uint32(seed) ^ jnp.uint32(alias_ops.MH_SALT)))
    alpha_sum = np.float32(jnp.sum(jnp.asarray(alpha)))
    want, margin = ring_reference.mh_draws(
        phi, psi, tp, ct, wq, wp, wa, alpha, ap, aa, alpha_sum, w, d, z, uid,
        seed2, beta, 20, n_mh)
    differ = got != want
    assert (want != z).any()                 # the draws move some tokens
    assert not (differ & (margin >= ring_reference.TIE)).any(), (
        np.nonzero(differ)[0], margin[differ])


def test_mh_draws_follow_their_tokens():
    """Permuting the token arrays (w, d, z, uid) permutes the draws and
    changes none of them: every token samples against the same snapshots
    with its own uid-keyed counters, which the ring's stack layout rests
    on."""
    args, _ = _mh_args(V=20, K=16, D=8, T=300, cap=16)
    base = np.asarray(alias_ops.mh_resample(*args, vocab_size=20, n_mh=4))
    perm = np.random.default_rng(4).permutation(300)
    alt = list(args)
    for i in (10, 11, 12, 13):               # w, d, z, uid
        alt[i] = args[i][perm]
    moved = np.asarray(alias_ops.mh_resample(*alt, vocab_size=20, n_mh=4))
    assert (base != np.asarray(args[12])).any()
    np.testing.assert_array_equal(moved, base[perm])


def test_mh_seed_and_uid_decorrelate():
    args, _ = _mh_args(V=20, K=16, D=8, T=128, cap=16)
    base = alias_ops.mh_resample(*args, vocab_size=20, n_mh=4)
    alt = list(args)
    alt[14] = jnp.uint32(43)
    other_seed = alias_ops.mh_resample(*alt, vocab_size=20, n_mh=4)
    alt = list(args)
    alt[13] = args[13] + jnp.uint32(1000)
    other_uid = alias_ops.mh_resample(*alt, vocab_size=20, n_mh=4)
    assert (np.asarray(base) != np.asarray(other_seed)).any()
    assert (np.asarray(base) != np.asarray(other_uid)).any()


def _tv(a, b):
    return 0.5 * np.abs(a - b).sum()


def test_mh_marginals_match_exact_categorical():
    """Statistical equivalence (small K, many draws): the alias-MH chain's
    topic marginals must match the exact collapsed posterior — and the exact
    Gumbel-max categorical draw — within total-variation tolerance."""
    rng = np.random.default_rng(5)
    V, K, D, T = 6, 12, 1, 40000
    w = np.zeros(T, np.int32)
    d = np.zeros(T, np.int32)
    z0 = np.full(T, 3, np.int32)
    doc_dense = np.zeros((D, K), np.int32)
    doc_dense[0, [1, 3, 5, 8, 9]] = [12, 7, 3, 20, 1]     # sparse skewed Θ
    phi = rng.integers(0, 30, (V, K)).astype(np.int32)
    phi[0, 3] = max(phi[0, 3], 8)
    psi = phi.sum(0).astype(np.int32) + rng.integers(0, 40, K).astype(np.int32)
    cap = K
    tp = np.full((D, cap), -1, np.int32)
    ct = np.zeros((D, cap), np.int32)
    nz = np.nonzero(doc_dense[0])[0]
    tp[0, :len(nz)] = nz
    ct[0, :len(nz)] = doc_dense[0, nz]
    alpha = jnp.asarray(rng.uniform(0.1, 0.6, K).astype(np.float32))
    beta = jnp.float32(0.05)
    tabs = sparse.make_tables(jnp.asarray(phi), jnp.asarray(psi), alpha,
                              beta, V)
    uid = jnp.arange(T, dtype=jnp.uint32)

    ex = np.zeros(K)
    ex[3] = 1.0      # ¬ivd self-exclusion of the shared z0
    p_true = ((phi[0] - ex + 0.05) / (psi - ex + V * 0.05)
              * (doc_dense[0] - ex + np.asarray(alpha)))
    p_true = p_true / p_true.sum()

    zs = alias_ops.mh_resample(
        jnp.asarray(phi), jnp.asarray(psi), jnp.asarray(tp), jnp.asarray(ct),
        tabs.wq, tabs.wp, tabs.wa, alpha, tabs.ap, tabs.aa,
        jnp.asarray(w), jnp.asarray(d), jnp.asarray(z0), uid,
        jnp.uint32(9), beta, vocab_size=V, n_mh=8)
    emp_mh = np.bincount(np.asarray(zs), minlength=K) / T

    g = gibbs_ops.gibbs_argmax(
        jnp.broadcast_to(jnp.asarray((phi[0] - ex).astype(np.float32)), (T, K)),
        jnp.broadcast_to(jnp.asarray((psi - ex).astype(np.float32)), (T, K)),
        jnp.broadcast_to(jnp.asarray((doc_dense[0] - ex).astype(np.float32)),
                         (T, K)),
        alpha, beta, uid, jnp.uint32(4), V, 1.0, force="ref")
    emp_gumbel = np.bincount(np.asarray(g), minlength=K) / T

    assert _tv(emp_mh, p_true) < 0.02, _tv(emp_mh, p_true)
    assert _tv(emp_mh, emp_gumbel) < 0.02, _tv(emp_mh, emp_gumbel)


# ------------------------------------------------- sparse Θ bookkeeping -----


def test_pairs_round_trip_and_lookup():
    rng = np.random.default_rng(1)
    D, K, T = 13, 24, 400
    d = jnp.asarray(rng.integers(0, D, T).astype(np.int32))
    z = jnp.asarray(rng.integers(0, K, T).astype(np.int32))
    valid = jnp.asarray(rng.random(T) > 0.1)
    tp, ct = sparse.pairs_from_assignments(d, z, valid, D, K)
    dense = np.zeros((D, K), np.int32)
    np.add.at(dense, (np.asarray(d)[np.asarray(valid)],
                      np.asarray(z)[np.asarray(valid)]), 1)
    np.testing.assert_array_equal(
        np.asarray(sparse.pairs_to_dense(tp, ct, K)), dense)
    look = sparse.pairs_lookup(tp, ct, d, z)
    np.testing.assert_array_equal(np.asarray(look),
                                  dense[np.asarray(d), np.asarray(z)])


def test_apply_deltas_full_row_free_then_alloc():
    """cap < K, doc row at FULL capacity: a flip from a count-1 topic to a
    fresh topic must free the old slot and land the new one in the same
    block (the single-pass regression: the +1 saw the pre-free row and was
    silently dropped — total 3 → 2)."""
    K, D, cap = 10, 1, 3
    d = jnp.zeros(3, jnp.int32)
    z = jnp.array([1, 4, 7], jnp.int32)
    tp, ct = sparse.pairs_from_assignments(d, z, jnp.ones(3, bool), D, cap)
    z_new = jnp.array([1, 4, 9], jnp.int32)
    tp2, ct2 = sparse.apply_deltas(tp, ct, d, z, z_new, jnp.ones(3, bool))
    dense = np.asarray(sparse.pairs_to_dense(tp2, ct2, K))[0]
    assert dense[7] == 0 and dense[9] == 1
    assert int(np.asarray(ct2).sum()) == 3


@pytest.mark.parametrize("cap_mode", ["cap_eq_K", "cap_lt_K"])
def test_apply_deltas_matches_dense_scatter(cap_mode):
    """The incremental z-flip update stays exact across repeated blocks,
    including slot frees (count→0) and fresh-topic allocations — in BOTH
    regimes: cap == K and the production cap = max doc length ≪ K (rows run
    at full capacity, so every fresh topic needs a same-block free)."""
    rng = np.random.default_rng(2)
    if cap_mode == "cap_lt_K":
        D, K, T = 20, 64, 160          # 8 tokens/doc → cap 8 ≪ K
        d = jnp.asarray((np.arange(T) % D).astype(np.int32))
        cap = 8
        valid = jnp.ones(T, bool)
    else:
        D, K, T = 9, 20, 300
        d = jnp.asarray(rng.integers(0, D, T).astype(np.int32))
        cap = K
        valid = jnp.asarray(rng.random(T) > 0.15)
    z = jnp.asarray(rng.integers(0, K, T).astype(np.int32))
    tp, ct = sparse.pairs_from_assignments(d, z, valid, D, cap)
    dense = np.asarray(sparse.pairs_to_dense(tp, ct, K)).copy()
    ch = np.asarray(valid)
    cur = z
    for it in range(5):
        nxt = jnp.where(jnp.asarray(rng.random(T) > 0.4),
                        jnp.asarray(rng.integers(0, K, T).astype(np.int32)),
                        cur)
        tp, ct = sparse.apply_deltas(tp, ct, d, cur, nxt, valid)
        np.add.at(dense, (np.asarray(d)[ch], np.asarray(cur)[ch]), -1)
        np.add.at(dense, (np.asarray(d)[ch], np.asarray(nxt)[ch]), 1)
        cur = nxt
        np.testing.assert_array_equal(
            np.asarray(sparse.pairs_to_dense(tp, ct, K)), dense)
    assert (np.asarray(ct) >= 0).all()
    # freed slots are truly free: count==0 ⇒ topic==-1
    tpn, ctn = np.asarray(tp), np.asarray(ct)
    assert ((ctn > 0) | (tpn == -1)).all()


@pytest.mark.parametrize("K,cap", [(16, 16), (128, 12)])
def test_sample_block_mh_counts_consistent(K, cap):
    """sample_block_mh keeps (phi, psi, pairs) exactly consistent with the
    resampled z — the mirror of sample_block's scatter bookkeeping. The
    (128, 12) case runs pair rows near capacity (cap ≪ K, ~37 tokens per
    doc would overflow — so D is sized for ≤ cap tokens/doc)."""
    V, D, T = 20, 32, 300     # round-robin docs: ≤ ⌈300/32⌉ = 10 < cap
    args, (w, d, z, phi, psi, alpha, beta, tabs) = _mh_args(
        V=V, K=K, D=D, T=T, cap=cap)
    tp, ct = args[2], args[3]
    uid = args[13]
    z2, phi2, psi2, tp2, ct2 = sparse.sample_block_mh(
        jnp.asarray(phi), jnp.asarray(psi), tp, ct, jnp.asarray(z),
        jnp.asarray(w), jnp.asarray(d), uid, alpha, beta, 11, V, tabs,
        n_mh=4)
    z2n = np.asarray(z2)
    phi_re = np.zeros((V, K), np.int32)
    np.add.at(phi_re, (w, z2n), 1)
    np.testing.assert_array_equal(np.asarray(phi2), phi_re)
    np.testing.assert_array_equal(np.asarray(psi2),
                                  np.bincount(z2n, minlength=K))
    dn = np.zeros((D, K), np.int32)
    np.add.at(dn, (d, z2n), 1)
    np.testing.assert_array_equal(
        np.asarray(sparse.pairs_to_dense(tp2, ct2, K)), dn)


def test_suggest_cap_bounds():
    assert sparse.suggest_cap([3, 9, 4], 100) == 9
    assert sparse.suggest_cap([3, 9, 4], 5) == 5
    assert sparse.suggest_cap([], 5) == 1
