"""Alias-MH sampler through the ring / Trainer layers (DESIGN.md §9).

The kernel-level contracts live in test_kernels_alias.py; here the sparse
sampling path runs through ``build_epoch_body`` (multi-device subprocess) and
the Trainer (table rebuild cadence, determinism, checkpoint-derived tables).
"""
import numpy as np
import pytest

pytestmark = pytest.mark.kernels


ALIAS_RING_CODE = r"""
import numpy as np, jax, jax.numpy as jnp
from repro.data import synthetic, corpus as corpus_mod
from repro.core import distributed as dist, lda, sparse

corpus, truth = synthetic.lda_corpus(seed=0, n_docs=400, n_topics=12, vocab_size=300, doc_len_mean=6)
mesh = jax.make_mesh((4, 2), ("data", "model"), axis_types=(jax.sharding.AxisType.Auto,)*2)
M, K = 8, 32
sc = corpus_mod.shard_corpus(corpus, M, M, K, seed=1)
phi, psi, wl, dl, uid, z = dist.device_arrays(sc, K)
cap_p = sparse.suggest_cap(corpus.doc_lengths(), K)
assert cap_p < K, (cap_p, K)   # the production pair-row regime (cap < K)
cfg = dist.RingConfig(n_topics=K, vocab_size=corpus.vocab_size, rows_per_shard=sc.rows_per_shard,
                      docs_per_shard=sc.docs_per_shard, cap=sc.word_local.shape[2],
                      package_len=sc.word_local.shape[2]//2, n_rounds=M,
                      sampler="alias", n_mh=4, doc_topic_cap=cap_p)
epoch = dist.make_ring_epoch(mesh, cfg)
alpha = jnp.full((K,), 50.0/K, jnp.float32); beta = jnp.float32(0.01)
ll0 = float(lda.word_log_likelihood(jnp.asarray(dist.gather_phi(phi, sc, K)), psi, beta))
tabs = None
for ep in range(9):
    if ep % 3 == 0:    # the aggregation-boundary rebuild cadence
        tabs = sparse.make_tables(phi, psi, alpha, beta, corpus.vocab_size)
    phi, psi, wl, dl, uid, z = epoch(phi, psi, wl, dl, uid, z, alpha, beta, jnp.uint32(ep*977+3), *tabs)
phi_full = dist.gather_phi(phi, sc, K)
ll1 = float(lda.word_log_likelihood(jnp.asarray(phi_full), psi, beta))
assert ll1 > ll0, (ll0, ll1)
assert int(np.asarray(psi).sum()) == corpus.n_tokens
assert int(phi_full.sum()) == corpus.n_tokens
wl_h, z_h = np.asarray(wl), np.asarray(z)
valid = wl_h >= 0
phi_chk = np.zeros((M, sc.rows_per_shard, K), np.int32)
for m in range(M):
    np.add.at(phi_chk[m], (wl_h[:, m][valid[:, m]], z_h[:, m][valid[:, m]]), 1)
assert (phi_chk == np.asarray(phi)).all(), "phi inconsistent with traveling z"
assert (np.asarray(phi).sum(axis=(0, 1)) == np.asarray(psi)).all()
print("ALIAS_RING_OK", ll0, ll1)
"""


def test_alias_ring_epoch_multidevice(subproc):
    out = subproc(ALIAS_RING_CODE, n_devices=8)
    assert "ALIAS_RING_OK" in out


def _fit(seed=0, **kw):
    from repro.training import AlphaOptimizer, Trainer, TrainerConfig

    # n_topics > max doc length ⇒ suggest_cap yields cap < K: the trainer
    # tests run the production pair-row regime, not the cap == K easy case
    cfg = TrainerConfig(n_docs=300, vocab_size=150, n_topics=32,
                        true_topics=8, doc_len_mean=6, n_epochs=7,
                        agg_every=3, alpha_opt_from=3, seed=seed,
                        sampler="alias", n_mh=4, **kw)
    tr = Trainer(cfg, callbacks=[AlphaOptimizer()])
    tr.log = lambda m: None
    tr.fit()
    return tr


def test_trainer_alias_counts_and_progress():
    tr = _fit()
    assert tr.ring_cfg.doc_topic_cap < tr.config.n_topics  # cap < K regime
    phi = np.asarray(tr.state[0])
    psi = np.asarray(tr.state[1])
    wl, z = np.asarray(tr.state[2]), np.asarray(tr.state[5])
    valid = wl >= 0
    assert int(psi.sum()) == int(valid.sum())
    assert (phi.sum(axis=(0, 1)) == psi).all()
    assert np.isfinite(tr.log_likelihood())
    # the sampler must actually have moved assignments
    assert (np.asarray(tr.state[5]) != 0).any()


def test_trainer_alias_deterministic():
    a = _fit(seed=3)
    b = _fit(seed=3)
    np.testing.assert_array_equal(np.asarray(a.state[5]),
                                  np.asarray(b.state[5]))
    np.testing.assert_array_equal(np.asarray(a.state[0]),
                                  np.asarray(b.state[0]))


def test_trainer_alias_streaming_runs():
    tr = _fit(n_segments=3)
    assert np.isfinite(tr.log_likelihood())
    psi = np.asarray(tr.state[1])
    assert int(psi.sum()) == int(tr.source.n_tokens)


@pytest.mark.parametrize("ckpt_every", [2, 3])
def test_trainer_alias_kill_resume_bitwise(tmp_path, ckpt_every):
    """Kill → resume must replay bit-for-bit. ckpt_every=2 lands MID table-
    staleness window (rebuilds at epoch starts 3 and 6 under agg_every=3):
    the proposal tables must ride in the checkpoint — rebuilding from the
    restored Φ would hand the resumed run fresher proposals than the
    uninterrupted one sampled with. ckpt_every=3 ALIGNS the save with a
    rebuild boundary: the resumed run must re-derive the due rebuild from
    the restored state (= the uninterrupted run's epoch-start state)."""
    from repro.training import (Checkpointing, KillSwitch, Metrics, Trainer,
                                TrainerConfig)

    def build(ck, resume=False, kill=None):
        cfg = TrainerConfig(n_docs=240, vocab_size=150, n_topics=32,
                            true_topics=8, doc_len_mean=6, n_epochs=7,
                            agg_every=3, alpha_opt_from=3, ckpt_dir=str(ck),
                            ckpt_every=ckpt_every, resume=resume,
                            sampler="alias", n_mh=4)
        cbs = [Checkpointing()]
        if kill:
            cbs.append(KillSwitch(kill))
        cbs.append(Metrics(printer=lambda m: None))
        tr = Trainer(cfg, callbacks=cbs)
        tr.log = lambda m: None
        return tr

    gold_tr = build(tmp_path / "gold")
    gold_tr.fit()
    gold = [np.asarray(x) for x in gold_tr.state]

    ck = tmp_path / "ck"
    with pytest.raises(SystemExit):
        build(ck, kill=5).fit()
    res_tr = build(ck, resume=True)
    res_tr.fit()
    for i, (a, b) in enumerate(zip(gold, [np.asarray(x)
                                          for x in res_tr.state])):
        np.testing.assert_array_equal(a, b,
                                      err_msg=f"state leaf {i} diverged")
    np.testing.assert_array_equal(np.asarray(gold_tr.alpha),
                                  np.asarray(res_tr.alpha))


def test_config_validates_sampler_fields():
    from repro.training import TrainerConfig

    with pytest.raises(ValueError):
        TrainerConfig(sampler="fancy")
    with pytest.raises(ValueError):
        TrainerConfig(sampler="alias", n_mh=0)
