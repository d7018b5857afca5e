"""Topic de-duplication: asymmetric prior fixed point + L1 clustering."""
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import dedup


def test_omega_histogram_counts():
    doc_ids = jnp.array([0, 0, 0, 1, 1, 2], jnp.int32)
    z = jnp.array([1, 1, 0, 1, 2, 2], jnp.int32)
    valid = jnp.ones(6, bool)
    omega = dedup.topic_count_histogram(doc_ids, z, valid, 3, 4, max_count=8)
    # topic 1: appears 2x in doc0, 1x in doc1 → omega[1,2]=1, omega[1,1]=1
    assert int(omega[1, 2]) == 1 and int(omega[1, 1]) == 1
    # topic 0: once in doc0
    assert int(omega[0, 1]) == 1
    # topic 2: once in doc1, once in doc2
    assert int(omega[2, 1]) == 2
    assert int(omega[:, 0].sum()) == 0


def test_alpha_fixed_point_matches_direct_minka():
    """Histogram-based update == direct per-document Minka update."""
    from jax.scipy.special import digamma

    rng = np.random.default_rng(0)
    D, K = 60, 5
    theta = rng.integers(0, 6, (D, K))
    lengths = theta.sum(axis=1)
    alpha0 = np.full(K, 0.7, np.float32)

    # direct Minka fixed point (one iteration, per-document sums)
    a = jnp.array(alpha0)
    num = np.zeros(K)
    for d in range(D):
        # zero-count topics contribute ψ(α)−ψ(α) = 0, consistent with Ω_k0 = 0
        num += np.asarray(digamma(theta[d] + a) - digamma(a))
    den = float(sum(np.asarray(digamma(l + a.sum()) - digamma(a.sum()))
                    for l in lengths))
    direct = alpha0 * num / den

    # histogram-based
    doc_ids = np.repeat(np.arange(D), lengths)
    z = np.concatenate([np.repeat(np.arange(K), theta[d]) for d in range(D)])
    omega = dedup.topic_count_histogram(
        jnp.array(doc_ids, jnp.int32), jnp.array(z, jnp.int32),
        jnp.ones(len(z), bool), D, K, max_count=16)
    dl = dedup.doc_length_histogram(jnp.array(lengths, jnp.int32))
    ours = dedup.optimize_alpha(jnp.array(alpha0), omega, dl, n_iters=1)
    np.testing.assert_allclose(np.asarray(ours), direct, rtol=1e-4)


def test_alpha_optimization_concentrates_on_used_topics():
    rng = np.random.default_rng(1)
    D, K = 200, 8
    # docs use topics 0-3 heavily, 4-7 almost never
    theta = np.concatenate([rng.integers(2, 10, (D, 4)),
                            rng.integers(0, 2, (D, 4))], axis=1)
    doc_ids = np.repeat(np.arange(D), theta.sum(axis=1))
    z = np.concatenate([np.repeat(np.arange(K), theta[d]) for d in range(D)])
    omega = dedup.topic_count_histogram(
        jnp.array(doc_ids, jnp.int32), jnp.array(z, jnp.int32),
        jnp.ones(len(z), bool), D, K)
    dl = dedup.doc_length_histogram(jnp.array(theta.sum(axis=1), jnp.int32))
    alpha = dedup.optimize_alpha(jnp.full((K,), 1.0), omega, dl, n_iters=30)
    a = np.asarray(alpha)
    assert a[:4].mean() > 3 * a[4:].mean()   # prior mass follows usage
    assert (a > 0).all()


@given(k=st.integers(2, 10), dup=st.integers(1, 3), seed=st.integers(0, 50))
@settings(max_examples=10, deadline=None)
def test_l1_merge_properties(k, dup, seed):
    rng = np.random.default_rng(seed)
    V = 40
    base = rng.integers(0, 60, (V, k)).astype(np.int32)
    # append `dup` exact duplicates of column 0
    phi = np.concatenate([base] + [base[:, :1]] * dup, axis=1)
    psi = phi.sum(axis=0)
    alpha = np.full(phi.shape[1], 0.5, np.float32)
    cl, ncl = dedup.cluster_topics(jnp.array(phi), jnp.float32(0.01),
                                   l1_threshold=1e-6)
    assert ncl <= k   # duplicates merged (maybe more if random cols collide)
    phi_m, psi_m, alpha_m = dedup.merge_topics(phi, psi, alpha, cl, ncl)
    assert int(np.asarray(phi_m).sum()) == int(phi.sum())       # mass conserved
    assert int(np.asarray(psi_m).sum()) == int(psi.sum())
    np.testing.assert_allclose(float(np.asarray(alpha_m).sum()),
                               float(alpha.sum()), rtol=1e-5)
    # merged phi columns still consistent with merged psi
    assert (np.asarray(phi_m).sum(axis=0) == np.asarray(psi_m)).all()


def test_duplicate_fraction_detects_duplicates():
    rng = np.random.default_rng(2)
    phi = rng.integers(0, 50, (60, 10)).astype(np.int32)
    phi_dup = np.concatenate([phi, phi[:, :5]], axis=1)
    f_clean = dedup.duplicate_fraction(jnp.array(phi), jnp.float32(0.01), 0.05)
    f_dup = dedup.duplicate_fraction(jnp.array(phi_dup), jnp.float32(0.01), 0.05)
    assert f_dup > f_clean
    assert f_dup >= 10 / 15 - 1e-6   # at least the 10 involved columns


def test_precomputed_distance_matches_and_conserves_counts():
    """cluster_topics/duplicate_fraction accept one shared l1_scan pass."""
    rng = np.random.default_rng(3)
    phi = rng.integers(0, 30, (40, 9)).astype(np.int32)
    phi[:, 5] = phi[:, 2]
    phi[:, 7] = phi[:, 0]
    scan = dedup.l1_scan(phi, 0.01, 1e-6)

    cl_pre, n_pre = dedup.cluster_topics(phi, 0.01, 1e-6, scan=scan)
    cl, n = dedup.cluster_topics(phi, 0.01, 1e-6)
    np.testing.assert_array_equal(cl_pre, cl)
    assert n_pre == n and n <= 7

    f_pre = dedup.duplicate_fraction(phi, 0.01, 1e-6, scan=scan)
    assert f_pre == dedup.duplicate_fraction(phi, 0.01, 1e-6)
    # a scan taken at another threshold cannot stand in for the merge's
    with pytest.raises(ValueError):
        dedup.cluster_topics(phi, 0.01, 0.5, scan=scan)

    psi = phi.sum(axis=0)
    alpha = np.full(phi.shape[1], 0.4, np.float32)
    phi_m, psi_m, alpha_m = dedup.merge_topics(phi, psi, alpha, cl_pre, n_pre)
    assert int(np.asarray(phi_m).sum()) == int(phi.sum())
    assert int(np.asarray(psi_m).sum()) == int(psi.sum())
    np.testing.assert_allclose(float(np.asarray(alpha_m).sum()),
                               float(alpha.sum()), rtol=1e-6)


def _numpy_pairwise_l1(phi, beta):
    """The dense host pass the blocked device scan replaced: the full K×K
    float64 L1 matrix between normalized topic columns."""
    pvk = np.asarray(phi, np.float64) + float(beta)
    pvk = pvk / pvk.sum(axis=0, keepdims=True)
    return np.abs(pvk[:, :, None] - pvk[:, None, :]).sum(axis=0)


def _numpy_clusters(d, thr):
    K = d.shape[0]
    parent = list(range(K))

    def find(x):
        while parent[x] != x:
            x = parent[x]
        return x

    for a, b in zip(*np.where((d < thr) & (np.triu(np.ones_like(d), 1) > 0))):
        ra, rb = find(int(a)), find(int(b))
        if ra != rb:
            parent[max(ra, rb)] = min(ra, rb)
    _, cluster_of = np.unique([find(k) for k in range(K)], return_inverse=True)
    return cluster_of, int(cluster_of.max()) + 1


@pytest.mark.parametrize("K,block,n_dup", [(9, 4, 2), (37, 8, 5), (64, 64, 0),
                                          (130, 32, 12)])
def test_blocked_scan_matches_numpy_pass(K, block, n_dup):
    """Blocked on-device scan == the old dense numpy pass: same clusters and
    duplicate fraction, at thresholds that cut through the distance spread."""
    rng = np.random.default_rng(K)
    V = 50
    phi = rng.integers(0, 8, (V, K)).astype(np.int32)
    src = rng.choice(K, n_dup)
    for j, s in zip(rng.choice(K, n_dup, replace=False), src):
        phi[:, j] = phi[:, s]          # exact duplicates (distance 0)
    d = _numpy_pairwise_l1(phi, 0.01)
    u = np.unique(d[~np.eye(K, dtype=bool)])

    def gap_threshold(q):
        # midway across a clear gap in the sorted distances, so float32 vs
        # float64 rounding cannot move any pair across the threshold
        i = int(q * (len(u) - 1))
        while u[i + 1] - u[i] < 1e-4:
            i += 1
        return float(u[i] + u[i + 1]) / 2

    for thr in (1e-6, gap_threshold(0.02), gap_threshold(0.3)):
        scan = dedup.l1_scan(phi, 0.01, thr, block=block)
        cl, ncl = dedup.cluster_topics(phi, 0.01, thr, scan=scan)
        ref_cl, ref_n = _numpy_clusters(d, thr)
        np.testing.assert_array_equal(cl, ref_cl)
        assert ncl == ref_n
        dd = d.copy()
        np.fill_diagonal(dd, np.inf)
        ref_frac = float((dd.min(axis=0) < thr).mean())
        assert dedup.duplicate_fraction(phi, 0.01, thr, scan=scan) == ref_frac
        np.testing.assert_allclose(scan.nearest, dd.min(axis=0), rtol=1e-5,
                                   atol=1e-6)


@pytest.mark.parametrize("D,K,T", [(3, 4, 6), (50, 7, 400), (200, 33, 1500)])
def test_omega_from_pairs_matches_dense_theta(D, K, T):
    """Ω from the sparse (doc, topic, count) pairs == Ω from a dense Θ."""
    rng = np.random.default_rng(D)
    doc_ids = rng.integers(0, D, T).astype(np.int32)
    z = rng.integers(0, K, T).astype(np.int32)
    valid = rng.uniform(size=T) < 0.9
    max_count = 8
    theta = np.zeros((D, K), np.int64)
    np.add.at(theta, (doc_ids[valid], z[valid]), 1)
    ref = np.zeros((K, max_count), np.int64)
    for k in range(K):
        np.add.at(ref[k], np.minimum(theta[:, k], max_count - 1), 1)
    ref[:, 0] = 0
    omega = dedup.topic_count_histogram(
        jnp.asarray(doc_ids), jnp.asarray(z), jnp.asarray(valid), D, K,
        max_count=max_count)
    np.testing.assert_array_equal(np.asarray(omega), ref)


def test_alpha_step_and_export_at_paper_widths():
    """The α statistics compile at the from_peacock_lda widths with
    temporaries far below a dense [n_docs, K] Θ, and the α step and the
    export's device programs trace there on abstract shapes (their TPU
    compiles, with memory, are in test_tpu_compile.py)."""
    import jax

    from repro.core import rtlda
    from repro.training import TrainerConfig

    cfg = TrainerConfig.from_peacock_lda()
    K, V = cfg.n_topics, cfg.vocab_size
    n_docs = cfg.n_docs
    T = int(n_docs * 4.5)
    sds = jax.ShapeDtypeStruct
    hist = jax.jit(dedup.topic_count_histogram,
                   static_argnames=("n_docs", "n_topics", "max_count"))
    c = hist.lower(sds((T,), jnp.int32), sds((T,), jnp.int32),
                   sds((T,), jnp.bool_), n_docs=n_docs,
                   n_topics=K).compile()
    assert c.memory_analysis().temp_size_in_bytes < 1e9     # Θ: 4.3e11
    alpha = jax.eval_shape(dedup.optimize_alpha, sds((K,), jnp.float32),
                           sds((K, 64), jnp.int32), sds((512,), jnp.int32))
    assert alpha.shape == (K,)
    block = 512
    kp = K + (-K) % block
    near, n_below, packed = jax.eval_shape(
        dedup._l1_row_block, sds((V, kp), jnp.float32), 0, jnp.float32(0.3),
        block, K)
    assert near.shape == (block,) and n_below.shape == ()
    assert packed.shape == (block, kp // 8)
    model = jax.eval_shape(rtlda.build_model, sds((V, K), jnp.int32),
                           jnp.float32(0.01), sds((K,), jnp.float32))
    assert model.pvk.shape == (V, K) and model.r_topic.shape == (V,)
