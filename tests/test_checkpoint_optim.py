"""Checkpoint/fault-recovery + optimizer tests."""
import os

import jax
import jax.numpy as jnp
import numpy as np

from hypothesis import given, settings, strategies as st

from repro.checkpoint import io
from repro.checkpoint.manager import CheckpointManager
from repro.optim import l1_loglinear, schedules
from repro.optim.adamw import AdamW


# ------------------------------ checkpoint ---------------------------------

def test_save_load_roundtrip(tmp_path):
    tree = {"a": jnp.arange(10), "b": {"c": jnp.ones((3, 4)),
                                       "d": jnp.uint32(7)}}
    p = str(tmp_path / "ckpt")
    io.save(p, tree, meta={"step": 3})
    restored, meta = io.load(p, tree)
    assert meta["step"] == 3
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_incomplete_checkpoint_invisible(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    tree = {"x": jnp.ones(4)}
    mgr.save(1, tree)
    # simulate a crash mid-write: step dir without manifest
    broken = str(tmp_path / "step_00000002")
    os.makedirs(broken)
    with open(os.path.join(broken, io.PAYLOAD), "wb") as f:
        f.write(b"partial garbage")
    assert mgr.steps() == [1]
    restored, meta = mgr.restore_latest(tree)
    assert meta["step"] == 1


def test_rotation_keeps_latest(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    tree = {"x": jnp.ones(2)}
    for s in range(5):
        mgr.save(s, jax.tree.map(lambda v: v * s, tree))
    assert mgr.steps() == [3, 4]


def test_per_pod_fault_recovery_replay(tmp_path):
    """Peacock §3.1.4: a failed pod restores ITS checkpoint and deterministic
    replay reproduces the lost epochs bit-for-bit (counter-based RNG)."""
    from repro.core import gibbs, lda
    from repro.data import corpus as corpus_mod, synthetic

    corpus, _ = synthetic.lda_corpus(seed=0, n_docs=200, n_topics=8,
                                     vocab_size=120, doc_len_mean=8)
    wi, di = corpus_mod.pad_corpus(corpus.word_ids, corpus.doc_ids, 256)
    valid = wi >= 0
    V, K = corpus.vocab_size, 8
    state = lda.init_state(jax.random.key(0), jnp.array(wi[valid]), K, V)
    z = np.zeros(len(wi), np.int32)
    z[valid] = np.asarray(state.z)
    state = lda.LDAState(state.phi, state.psi, jnp.array(z), state.alpha,
                         state.beta)

    mgr = CheckpointManager(str(tmp_path))
    step = lambda s, it: gibbs.gibbs_epoch(
        s, jnp.array(wi), jnp.array(di), corpus.n_docs, V, seed=it * 17 + 5,
        block_size=256)

    # run 4 epochs, checkpoint pod 0 at epoch 2, keep going to epoch 4
    s = state
    for it in range(2):
        s = step(s, it)
    mgr.save(2, s, pod=0)
    for it in range(2, 4):
        s = step(s, it)
    gold = np.asarray(s.z)

    # "pod fails" — restore from its own checkpoint, replay epochs 2..4
    restored, meta = mgr.restart_pod(0, s)
    assert meta["step"] == 2
    r = jax.tree.map(jnp.asarray, restored)
    r = lda.LDAState(*[jnp.asarray(x) for x in
                       (restored.phi, restored.psi, restored.z,
                        restored.alpha, restored.beta)])
    for it in range(2, 4):
        r = step(r, it)
    np.testing.assert_array_equal(np.asarray(r.z), gold)
    np.testing.assert_array_equal(np.asarray(r.phi), np.asarray(s.phi))


def test_async_save(tmp_path):
    mgr = CheckpointManager(str(tmp_path), async_save=True)
    mgr.save(7, {"x": jnp.arange(5)})
    mgr.wait()
    assert mgr.steps() == [7]


def test_async_save_snapshots_before_mutation(tmp_path):
    """``async_save`` must snapshot to host BEFORE returning: a caller
    mutating (or donating) its buffers right after ``save`` returns races
    the writer thread otherwise. The snapshot happens synchronously in
    ``save``, so the checkpoint holds the at-save values."""
    mgr = CheckpointManager(str(tmp_path), async_save=True)
    tree = {"x": np.arange(8, dtype=np.int32)}
    mgr.save(1, tree)
    tree["x"][:] = -1            # epoch loop reuses the buffer immediately
    mgr.wait()
    restored, meta = mgr.restore_latest({"x": np.zeros(8, np.int32)})
    assert meta["step"] == 1
    np.testing.assert_array_equal(restored["x"], np.arange(8))


def test_async_save_wait_serializes_back_to_back(tmp_path):
    """A second ``save`` waits out the first (one writer thread at a time);
    ``wait()`` is idempotent and both checkpoints land complete."""
    mgr = CheckpointManager(str(tmp_path), async_save=True, keep=5)
    mgr.save(1, {"x": jnp.zeros(4)})
    mgr.save(2, {"x": jnp.ones(4)})      # internally waits for step 1
    mgr.wait()
    mgr.wait()                            # second wait is a no-op
    assert mgr.steps() == [1, 2]
    restored, _ = mgr.restore_latest({"x": jnp.zeros(4)})
    np.testing.assert_array_equal(np.asarray(restored["x"]), np.ones(4))


def test_restart_pod_restores_single_configuration(tmp_path):
    """§3.1.4: ``restart_pod`` restores ONE failed configuration from ITS
    latest checkpoint — other pods' and the global checkpoint streams are
    independent namespaces and stay untouched."""
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(2, {"x": jnp.full(3, 10)}, pod=0)
    mgr.save(4, {"x": jnp.full(3, 11)}, pod=0)
    mgr.save(3, {"x": jnp.full(3, 20)}, pod=1)
    mgr.save(5, {"x": jnp.full(3, 99)})           # global stream
    like = {"x": jnp.zeros(3)}

    restored, meta = mgr.restart_pod(1, like)
    assert meta["step"] == 3
    np.testing.assert_array_equal(np.asarray(restored["x"]), np.full(3, 20))

    restored0, meta0 = mgr.restart_pod(0, like)   # pod 0: its own latest
    assert meta0["step"] == 4
    np.testing.assert_array_equal(np.asarray(restored0["x"]), np.full(3, 11))

    assert mgr.steps() == [5]                     # global stream unaffected
    assert mgr.steps(pod=0) == [2, 4]
    assert mgr.restart_pod(7, like) is None       # never-checkpointed pod


# ------------------------------- optimizers --------------------------------

def test_adamw_matches_reference_math():
    opt = AdamW(lr=0.1, b1=0.9, b2=0.99, eps=1e-8, weight_decay=0.0,
                clip_norm=None)
    p = {"w": jnp.array([1.0, -2.0])}
    g = {"w": jnp.array([0.5, 0.5])}
    st = opt.init(p)
    newp, st = opt.update(g, st, p)
    m = 0.1 * 0.5
    v = 0.01 * 0.25
    mhat = m / (1 - 0.9)
    vhat = v / (1 - 0.99)
    expect = 1.0 - 0.1 * mhat / (np.sqrt(vhat) + 1e-8)
    np.testing.assert_allclose(float(newp["w"][0]), expect, rtol=1e-6)


def test_adamw_clip():
    opt = AdamW(lr=0.1, clip_norm=1.0, weight_decay=0.0)
    p = {"w": jnp.zeros(3)}
    g = {"w": jnp.full(3, 100.0)}
    st = opt.init(p)
    newp, _ = opt.update(g, st, p)
    assert np.abs(np.asarray(newp["w"])).max() < 0.2


@given(peak=st.floats(1e-5, 1e-2), warm=st.integers(1, 100),
       stable=st.integers(1, 100), decay=st.integers(1, 100))
@settings(max_examples=20, deadline=None)
def test_wsd_schedule_properties(peak, warm, stable, decay):
    lr_w = float(schedules.wsd(warm // 2, peak, warm, stable, decay))
    lr_s = float(schedules.wsd(warm + stable // 2, peak, warm, stable, decay))
    lr_e = float(schedules.wsd(warm + stable + decay + 10, peak, warm, stable,
                               decay))
    assert lr_w <= peak + 1e-12
    assert abs(lr_s - peak) < 1e-9          # plateau == peak
    assert lr_e <= peak * 0.1 + 1e-9        # decays to final_ratio
    assert lr_e > 0


def test_l1_loglinear_sparsifies_and_learns():
    rng = np.random.default_rng(0)
    n, n_sparse = 2000, 50
    ids = rng.integers(0, n_sparse, (n, 3)).astype(np.int32)
    w_true = np.zeros(n_sparse)
    w_true[:5] = 2.0
    logits = w_true[ids].sum(1) - 1.0
    y = (rng.uniform(size=n) < 1 / (1 + np.exp(-logits))).astype(np.float32)
    st = l1_loglinear.init_state(n_sparse, 1)
    dx = jnp.zeros((n, 1))
    for _ in range(200):
        st, loss = l1_loglinear.train_step(st, jnp.array(ids), dx,
                                           jnp.array(y), 0.3, 3e-3)
    w = np.asarray(st.w_sparse)
    assert (np.abs(w) < 1e-6).mean() > 0.3          # L1 sparsity
    assert w[:5].mean() > np.abs(w[5:]).mean()      # signal recovered
    scores = l1_loglinear.predict(st, jnp.array(ids), dx)
    assert l1_loglinear.auc(np.asarray(scores), y) > 0.65


def test_auc_known_values():
    assert l1_loglinear.auc(np.array([0.9, 0.8, 0.1]), np.array([1, 1, 0])) == 1.0
    assert abs(l1_loglinear.auc(np.array([0.1, 0.8, 0.9]),
                                np.array([1, 0, 0]))) < 1e-9
    assert l1_loglinear.auc(np.array([0.5, 0.5]), np.array([1, 0])) == 0.5
