"""Word-sharded model parallelism conformance suite (DESIGN.md §10).

The replicated ring (``n_model_shards=1``) is the bitwise oracle: a P-way
word-sharded session must produce exactly the replicated (phi, psi, z) for
both sampler families — one package per round, round-start snapshots and
uid-keyed counter RNG make every per-token draw independent of which device
executed it. The suite covers:

  * epoch-level parity P=2 / P=4 vs replicated, dense and alias samplers;
  * Trainer kill→resume bitwise with SHARDED checkpoints;
  * resharding-loader round-trips (replicated ckpt → P=2 resume and back);
  * the pure row-permutation algebra of ``training.reshard``;
  * ``collective_bytes`` recognizing the rotation's collective-permutes in
    compiled HLO (regression: rotation traffic must not be invisible to the
    cost model), with trip-folded totals matching the §10 analytic model.

Multi-device cases run in subprocesses (``conftest.run_with_devices``); the
mesh is (data=4, model=P), so P=2 needs 8 host devices and P=4 needs 16.
"""
import numpy as np
import pytest

pytestmark = pytest.mark.shard


# Builds one small corpus, runs `run(n_model, sampler)` through the raw ring
# epoch (3 epochs), prints PARITY_OK per case. The replicated baseline runs
# in the SAME process on the first D devices of the same host platform.
PARITY_CODE = """
import numpy as np, jax, jax.numpy as jnp
from repro.data import synthetic, corpus as corpus_mod
from repro.core import distributed as dist, sparse

corpus, _ = synthetic.lda_corpus(seed=0, n_docs=240, n_topics=10,
                                 vocab_size=180, doc_len_mean=11)
D, K = 4, 12

def run(n_model, sampler, n_epochs=3):
    sc = corpus_mod.shard_corpus(corpus, D, D, K, seed=1,
                                 n_model_shards=n_model)
    if n_model > 1:
        mesh = jax.make_mesh((D, n_model), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
    else:
        mesh = jax.make_mesh((D, 1), ("data", "model"),
                             devices=jax.devices()[:D],
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
    phi, psi, wl, dl, uid, z = dist.device_arrays(sc, K)
    cap = sc.word_local.shape[2]
    doc_cap = sparse.suggest_cap(corpus.doc_lengths(), K)
    cfg = dist.RingConfig(
        n_topics=K, vocab_size=corpus.vocab_size,
        rows_per_shard=sc.rows_per_shard, docs_per_shard=sc.docs_per_shard,
        cap=cap, package_len=cap, n_rounds=D, model_shards=n_model,
        sampler=sampler, n_mh=4, doc_topic_cap=doc_cap)
    epoch = dist.make_ring_epoch(mesh, cfg)
    alpha = jnp.full((K,), 50.0 / K, jnp.float32)
    beta = jnp.float32(0.01)
    args = ()
    if sampler == "alias":
        wq, wp, wa = sparse.make_word_tables(phi, psi, beta,
                                             corpus.vocab_size)
        ap, aa = sparse.make_alpha_table(alpha)
        args = (wq, wp, wa, ap, aa)
    for ep in range(n_epochs):
        phi, psi, wl, dl, uid, z = epoch(phi, psi, wl, dl, uid, z, alpha,
                                         beta, jnp.uint32(ep * 977 + 3),
                                         *args)
    phi_full = dist.gather_phi(phi, sc, K)
    wl_h, u_h, z_h = np.asarray(wl), np.asarray(uid), np.asarray(z)
    valid = wl_h >= 0
    z_by_uid = np.zeros(corpus.n_tokens, np.int32)
    z_by_uid[u_h[valid]] = z_h[valid]
    return np.asarray(phi_full), np.asarray(psi), z_by_uid

P = {P}
for sampler in ("dense", "alias"):
    ref = run(1, sampler)
    got = run(P, sampler)
    assert (ref[0] == got[0]).all(), f"{{sampler}} P={{P}}: phi mismatch"
    assert (ref[1] == got[1]).all(), f"{{sampler}} P={{P}}: psi mismatch"
    assert (ref[2] == got[2]).all(), f"{{sampler}} P={{P}}: z mismatch"
    print(f"{{sampler}}:PARITY_OK")
"""


@pytest.mark.parametrize("p,n_dev", [(2, 8), (4, 16)])
def test_epoch_parity_vs_replicated(subproc, p, n_dev):
    out = subproc(PARITY_CODE.format(P=p), n_devices=n_dev, timeout=900)
    assert out.count("PARITY_OK") == 2, out


# Trainer-level: sharded checkpoints kill→resume + reshard round-trips.
TRAINER_CODE = """
import shutil
import numpy as np
from repro.training import Trainer, TrainerConfig, Checkpointing, KillSwitch

def run(n_model, ckpt_dir=None, kill_at=None, resume=False):
    cfg = TrainerConfig(
        n_docs=240, vocab_size=180, n_topics=12, true_topics=10,
        doc_len_mean=11, data_shards=4, model_shards=max(1, n_model),
        n_model_shards=n_model, n_epochs=6, agg_every=3,
        alpha_opt_from=100, sampler="alias",
        ckpt_dir=ckpt_dir, ckpt_every=2, resume=resume, bench_out=None)
    cbs = [Checkpointing()] if ckpt_dir else []
    if kill_at:
        cbs.append(KillSwitch(kill_at))
    tr = Trainer(cfg, callbacks=cbs)
    try:
        tr.fit()
    except SystemExit as e:
        return ("killed", e.code)
    phi = tr.gather_phi()
    psi = np.asarray(tr.state[1])
    wl, uid, z = (np.asarray(tr.state[2]), np.asarray(tr.state[4]),
                  np.asarray(tr.state[5]))
    valid = wl >= 0
    zg = np.zeros(tr.source.n_tokens, np.int32)
    zg[uid[valid]] = z[valid]
    return phi, psi, zg, np.asarray(tr.alpha)

names = ("phi", "psi", "z", "alpha")

# kill -> resume with SHARDED (P=2) checkpoints
d = "/tmp/shard_suite_ck"
shutil.rmtree(d, ignore_errors=True)
assert run(2, ckpt_dir=d, kill_at=4) == ("killed", 17)
got = run(2, ckpt_dir=d, resume=True)
ref2 = run(2)
for a, b, n in zip(ref2, got, names):
    assert (a == b).all(), f"resume P=2: {n} mismatch"
print("RESUME_OK")

# reshard round trip: replicated ckpt -> P=2 resume == uninterrupted P=2
# (== uninterrupted replicated, by the parity above)
d = "/tmp/shard_suite_re1"
shutil.rmtree(d, ignore_errors=True)
assert run(1, ckpt_dir=d, kill_at=4) == ("killed", 17)
got = run(2, ckpt_dir=d, resume=True)
for a, b, n in zip(ref2, got, names):
    assert (a == b).all(), f"reshard 1->2: {n} mismatch"
print("RESHARD_UP_OK")

# and back: P=2 ckpt -> replicated resume
d = "/tmp/shard_suite_re2"
shutil.rmtree(d, ignore_errors=True)
assert run(2, ckpt_dir=d, kill_at=4) == ("killed", 17)
got = run(1, ckpt_dir=d, resume=True)
for a, b, n in zip(ref2, got, names):
    assert (a == b).all(), f"reshard 2->1: {n} mismatch"
print("RESHARD_DOWN_OK")
"""


def test_trainer_resume_and_reshard_roundtrip(subproc):
    out = subproc(TRAINER_CODE, n_devices=8, timeout=900)
    assert "RESUME_OK" in out, out
    assert "RESHARD_UP_OK" in out, out
    assert "RESHARD_DOWN_OK" in out, out


def test_reshard_row_permutation_roundtrip():
    """The slice-major row permutation composes to identity through any
    p_old → p_new → p_old chain, pads excluded."""
    from repro.training import reshard

    rng = np.random.default_rng(0)
    rows_coarse = 23
    for p_a, p_b in [(1, 2), (2, 4), (4, 3), (1, 8)]:
        rows_a = p_a * (-(-rows_coarse // p_a))
        rows_b = p_b * (-(-rows_coarse // p_b))
        arr = rng.integers(0, 100, (4, rows_a, 6)).astype(np.int32)
        # zero the pad rows of layout a (they are never populated)
        ga, gb = reshard.row_permutation(rows_coarse, p_a, rows_a, p_b, rows_b)
        mask = np.zeros(rows_a, bool)
        mask[ga] = True
        arr[:, ~mask, :] = 0
        fwd = reshard.permute_rows(arr, ga, gb, rows_b)
        ga2, gb2 = reshard.row_permutation(rows_coarse, p_b, rows_b,
                                           p_a, rows_a)
        back = reshard.permute_rows(fwd, ga2, gb2, rows_a)
        assert (back == arr).all(), (p_a, p_b)


def test_identity_layout_at_p1():
    """n_model_shards=1 must reproduce the historical replicated layout
    bit-for-bit (the conformance baseline is the existing oracle suite)."""
    from repro.data import corpus as corpus_mod, synthetic

    corpus, _ = synthetic.lda_corpus(seed=3, n_docs=60, n_topics=6,
                                     vocab_size=90, doc_len_mean=7)
    a = corpus_mod.shard_corpus(corpus, 2, 2, 8, seed=5)
    b = corpus_mod.shard_corpus(corpus, 2, 2, 8, seed=5, n_model_shards=1)
    for name in ("word_local", "doc_local", "uid", "z0", "shard_of_word",
                 "local_of_word"):
        assert (getattr(a, name) == getattr(b, name)).all(), name
    assert a.rows_per_shard == b.rows_per_shard
    assert a.word_local.shape == b.word_local.shape


def test_bucket_layout_partitions_tokens_by_slice():
    """P>1 stacks are bucket-major: positions [j·capb, (j+1)·capb) of every
    (s, m) sub-block hold exactly the tokens whose word row lives in model
    slice j (word_local // rpm == j)."""
    from repro.data import corpus as corpus_mod, synthetic

    corpus, _ = synthetic.lda_corpus(seed=3, n_docs=120, n_topics=6,
                                     vocab_size=90, doc_len_mean=9)
    P = 3
    sc = corpus_mod.shard_corpus(corpus, 2, 2, 8, seed=5, n_model_shards=P)
    assert sc.n_model_shards == P
    assert sc.rows_per_shard % P == 0
    rpm = sc.rows_per_shard // P
    cap = sc.word_local.shape[-1]
    assert cap % P == 0
    capb = cap // P
    wl = np.asarray(sc.word_local)
    for j in range(P):
        bucket = wl[:, :, j * capb:(j + 1) * capb]
        real = bucket[bucket >= 0]
        assert (real // rpm == j).all(), j
    # every real token present exactly once, by uid
    uid = np.asarray(sc.uid)[wl >= 0]
    assert len(np.unique(uid)) == corpus.n_tokens


# collective_bytes regression: a compiled rotation round's ppermutes must be
# visible to the cost model, and trip-folding must match the §10 analytics.
COLLECTIVE_CODE = """
import numpy as np, jax, jax.numpy as jnp
from repro.data import synthetic, corpus as corpus_mod
from repro.core import distributed as dist
from repro.dist import analysis

corpus, _ = synthetic.lda_corpus(seed=0, n_docs=240, n_topics=10,
                                 vocab_size=180, doc_len_mean=11)
D, K, P = 4, 12, 2
sc = corpus_mod.shard_corpus(corpus, D, D, K, seed=1, n_model_shards=P)
mesh = jax.make_mesh((D, P), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
phi, psi, wl, dl, uid, z = dist.device_arrays(sc, K)
cap = sc.word_local.shape[2]
cfg = dist.RingConfig(n_topics=K, vocab_size=corpus.vocab_size,
                      rows_per_shard=sc.rows_per_shard,
                      docs_per_shard=sc.docs_per_shard,
                      cap=cap, package_len=cap, n_rounds=D, model_shards=P)
epoch = dist.make_ring_epoch(mesh, cfg)
alpha = jnp.full((K,), 50.0 / K, jnp.float32)
args = (phi, psi, wl, dl, uid, z, alpha, jnp.float32(0.01), jnp.uint32(3))
hlo = jax.jit(epoch).lower(*args).compile().as_text()

got = analysis.collective_bytes(hlo)
assert got.get("collective-permute", 0) > 0, got

cost = analysis.trace_cost(epoch, *args)
# per epoch: D rounds x (3 stack planes + z re-ship) data hops
#          + D rounds x (P-1) model hops x 2 gathered planes
expect_n = D * 4 + D * (P - 1) * 2
assert cost.collectives.get("ppermute") == expect_n, cost.collectives
counts = analysis.hlo_collective_counts(cost)
assert counts.get("collective-permute") == expect_n, counts
folded = analysis.collective_bytes(hlo, while_trips=counts)
capb = cap // P
per_hop = D * capb * 4              # one [1, D, capb] int32/u32 plane
assert folded["collective-permute"] == expect_n * per_hop, (
    folded, expect_n * per_hop)
assert folded["collective-permute"] > got["collective-permute"]
print("COLLECTIVE_OK", folded["collective-permute"])
"""


def test_collective_bytes_sees_rotation_permutes(subproc):
    out = subproc(COLLECTIVE_CODE, n_devices=8, timeout=900)
    assert "COLLECTIVE_OK" in out, out


def test_model_shard_report_paper_scale():
    """The §10 analytic model: per-device Φ+tables shrink ~P×; the paper's
    10⁵×10⁶ regime fits 16 GB HBM at P=8 on a 16-ring."""
    from repro.dist import analysis

    base = analysis.model_shard_report(100_000, 1_000_000, 16, 1, 4.5e9,
                                       docs_per_shard=4096, doc_topic_cap=64)
    p8 = analysis.model_shard_report(100_000, 1_000_000, 16, 8, 4.5e9,
                                     docs_per_shard=4096, doc_topic_cap=64)
    model_b = lambda r: (r["phi_bytes_per_device"]
                         + r["tables_bytes_per_device"])
    assert model_b(base) / model_b(p8) == pytest.approx(8.0, rel=1e-3)
    assert base["hbm_bytes_per_device"] > 16e9
    assert p8["hbm_bytes_per_device"] < 16e9
    assert base["theta_bytes_per_device"] == p8["theta_bytes_per_device"]
    # rotation traffic stays bounded (never worse than replicated here)
    assert (p8["rotation_bytes_per_epoch"]
            <= 1.5 * base["rotation_bytes_per_epoch"])


def test_config_validation():
    """n_model_shards wiring: geometry rules + ring_size semantics."""
    from repro.training import TrainerConfig

    cfg = TrainerConfig(data_shards=4, model_shards=2, n_model_shards=2)
    assert cfg.ring_size == 4              # rotation over "data" only
    assert cfg.n_devices == 8
    rep = TrainerConfig(data_shards=4, model_shards=2)
    assert rep.ring_size == 8              # flattened ring, historical
    with pytest.raises(ValueError, match="model_shards"):
        TrainerConfig(data_shards=4, model_shards=4, n_model_shards=2)
    with pytest.raises(ValueError, match="package_len"):
        TrainerConfig(data_shards=4, model_shards=2, n_model_shards=2,
                      package_len=16)
