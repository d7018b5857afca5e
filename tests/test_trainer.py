"""repro.training: TrainerConfig validation + the training-entrypoint
integration tests (ROADMAP open item — the multi-host launch path had none).

The integration tests drive the REAL entrypoint (``repro.launch.train.main``,
now a thin adapter over Trainer) on fake host devices in subprocesses,
including the §3.1.4 recovery demo: kill mid-run, resume, and assert the
resumed run reproduces the uninterrupted run bit-for-bit.
"""
import pytest

from repro.training.config import TrainerConfig

pytestmark = pytest.mark.trainer


# ------------------------------ config ------------------------------------

def test_config_defaults_valid():
    cfg = TrainerConfig()
    assert cfg.ring_size == 1 and cfg.n_devices == 1 and not cfg.multi_pod


@pytest.mark.parametrize("bad", [
    dict(n_docs=0), dict(n_topics=1), dict(n_pods=0), dict(agg_every=0),
    dict(beta=0.0), dict(alpha0=-1.0), dict(package_len=-1),
    dict(ckpt_every=-2),
])
def test_config_rejects_bad_values(bad):
    with pytest.raises(ValueError):
        TrainerConfig(**bad)


def test_config_resume_requires_ckpt_dir():
    with pytest.raises(ValueError):
        TrainerConfig(resume=True)
    TrainerConfig(resume=True, ckpt_dir="/tmp/x")   # fine


def test_config_derived_geometry():
    cfg = TrainerConfig(n_pods=2, data_shards=4, model_shards=2)
    assert cfg.ring_size == 8
    assert cfg.n_devices == 16
    assert cfg.multi_pod
    assert cfg.replace(n_pods=1).n_devices == 8


def test_single_pod_rejects_elastic_liveness():
    """A liveness probe on a session with no aggregation boundaries would
    silently never fire — setup must refuse it loudly."""
    import numpy as np

    from repro.training import ElasticLiveness, Trainer

    cfg = TrainerConfig(n_docs=50, vocab_size=30, n_topics=4, true_topics=3,
                        n_epochs=1)
    tr = Trainer(cfg, callbacks=[ElasticLiveness(lambda ep: np.array([1]))])
    with pytest.raises(ValueError, match="ElasticLiveness"):
        tr.setup()


def test_config_from_peacock_lda():
    from repro.configs import peacock_lda as pl

    cfg = TrainerConfig.from_peacock_lda(n_epochs=3, ckpt_dir="/tmp/ck")
    assert cfg.n_topics == pl.K_TOPICS
    assert cfg.vocab_size == pl.VOCAB
    assert cfg.ring_size == 256
    assert cfg.n_docs == 256 * pl.DOCS_PER_SHARD
    assert cfg.agg_every == pl.TRAIN_DEFAULTS["agg_every"]
    assert cfg.n_epochs == 3                      # override wins


# ----------------------- entrypoint integration ---------------------------

TRAIN_E2E_CODE = r"""
import json, os, tempfile
import numpy as np
from repro.launch import train

ck = tempfile.mkdtemp()
bench = os.path.join(tempfile.mkdtemp(), "BENCH_train.json")
argv = ["--docs","240","--vocab","120","--topics","8","--true-topics","6",
        "--epochs","6","--data-shards","2","--model-shards","2",
        "--agg-every","2","--alpha-opt-from","3","--ckpt-dir",ck,
        "--ckpt-every","2","--bench-out",bench]
tr = train.main(argv)
assert tr.epoch == 6
rec = json.load(open(bench))
assert rec["bench"] == "train" and rec["epochs_timed"] == 6
assert rec["tokens_per_s"] > 0 and rec["epoch_s_mean"] > 0
assert rec["ll_final"] is not None
lls = tr.metrics["ll"]
assert lls[-1] > lls[0], "LL did not improve"
print("TRAIN_E2E_OK")
"""


RESUME_CODE = r"""
import tempfile
import numpy as np
from repro.launch import train

def argv(ck, extra=()):
    return ["--docs","240","--vocab","120","--topics","8","--true-topics","6",
            "--epochs","6","--data-shards","2","--model-shards","2",
            "--alpha-opt-from","3","--ckpt-dir",ck,"--ckpt-every","2",
            "--bench-out",""] + list(extra)

# uninterrupted run = gold
tr_gold = train.main(argv(tempfile.mkdtemp()))
gold = [np.asarray(x) for x in tr_gold.state]

# killed run + resume must reproduce it bit-for-bit (§3.1.4 deterministic
# replay: counter-based seeds make the replayed epochs identical)
ck = tempfile.mkdtemp()
try:
    train.main(argv(ck, ["--kill-at","4"]))
    raise AssertionError("kill-at did not exit")
except SystemExit as e:
    assert e.code == 17, e.code
tr_res = train.main(argv(ck, ["--resume"]))
assert tr_res.epoch == 6
for i, (a, b) in enumerate(zip(gold, [np.asarray(x) for x in tr_res.state])):
    assert a.dtype == b.dtype and (a == b).all(), f"state leaf {i} diverged"
np.testing.assert_array_equal(np.asarray(tr_gold.alpha),
                              np.asarray(tr_res.alpha))
print("RESUME_BITWISE_OK")
"""


MULTIPOD_TRAINER_CODE = r"""
import numpy as np, tempfile
from repro.training import (ElasticLiveness, Metrics, ModelPublisher,
                            Trainer, TrainerConfig)

snap = tempfile.mkdtemp()
cfg = TrainerConfig(n_docs=300, vocab_size=200, n_topics=12, true_topics=10,
                    n_pods=2, data_shards=2, model_shards=2,
                    n_epochs=4, agg_every=2, alpha_opt_from=99)
# pod 1 dead at the first boundary, back for the second (elastic §3.1.4)
sched = {1: np.array([1, 0]), 3: np.array([1, 1])}
live = ElasticLiveness(lambda ep: sched[ep])
pub = ModelPublisher(snap, every=1)
tr = Trainer(cfg, callbacks=[live, pub, Metrics(printer=lambda m: None)])
res = tr.fit()
phi = np.asarray(tr.state[0])
assert (phi[0] == phi[1]).all(), "pods disagree after aggregation"
assert live.last_n_live == 2, live.last_n_live
assert len(res.metrics["agg_s"]) == 2          # two boundaries timed
assert pub.last_version == 1                   # one publish per boundary
from repro.training import spans
rec = spans.recorder()
assert res.metrics["agg_s"] == [
    s.duration for s in rec.recent("peacock.train.aggregate")]
assert res.metrics["publish_s"] == [
    s.duration for s in rec.recent("peacock.publish")]
print("MULTIPOD_TRAINER_OK")
"""


MULTIPOD_RESUME_CODE = r"""
import numpy as np, tempfile
from repro.training import (Checkpointing, KillSwitch, Metrics, Trainer,
                            TrainerConfig)

# ckpt_every=3 lands BETWEEN aggregation boundaries (agg_every=2: boundaries
# at epochs 2 and 4): the resume must replay against the epoch-2 refs, which
# ride in the checkpoint — re-deriving refs from the restored per-pod states
# would break the pods-agree invariant at the epoch-4 merge.
def build(ck, resume=False, kill=None):
    cfg = TrainerConfig(n_docs=240, vocab_size=150, n_topics=10,
                        true_topics=8, n_pods=2, data_shards=2,
                        model_shards=2, n_epochs=4, agg_every=2,
                        alpha_opt_from=99, ckpt_dir=ck, ckpt_every=3,
                        resume=resume)
    cbs = [Checkpointing()]
    if kill:
        cbs.append(KillSwitch(kill))
    cbs.append(Metrics(printer=lambda m: None))
    tr = Trainer(cfg, callbacks=cbs)
    tr.log = lambda m: None
    return tr

gold_tr = build(tempfile.mkdtemp())
gold_tr.fit()
gold = [np.asarray(x) for x in gold_tr.state]
assert (gold[0][0] == gold[0][1]).all()      # boundary merged: pods agree

ck = tempfile.mkdtemp()
try:
    build(ck, kill=3).fit()
    raise AssertionError("kill did not fire")
except SystemExit:
    pass
res_tr = build(ck, resume=True)
res_tr.fit()
res = [np.asarray(x) for x in res_tr.state]
assert (res[0][0] == res[0][1]).all(), "pods disagree after resumed merge"
for i, (a, b) in enumerate(zip(gold, res)):
    assert (a == b).all(), f"state leaf {i} diverged after mid-window resume"
print("MULTIPOD_RESUME_OK")
"""


BOUNDARY_CKPT_CODE = r"""
import numpy as np, tempfile
from repro.checkpoint.manager import CheckpointManager
from repro.training import Checkpointing, Metrics, Trainer, TrainerConfig

# agg_every=2, 6 epochs → boundaries at epochs 2, 4, 6. A pure boundary
# cadence must checkpoint exactly there — never mid-window — even though
# ckpt_every (the epoch cadence default) is 1.
ck = tempfile.mkdtemp()
cfg = TrainerConfig(n_docs=200, vocab_size=120, n_topics=8, true_topics=6,
                    n_pods=2, data_shards=2, model_shards=1,
                    n_epochs=6, agg_every=2, alpha_opt_from=99,
                    ckpt_dir=ck, ckpt_every=1)
tr = Trainer(cfg, callbacks=[Checkpointing(every_boundaries=1),
                             Metrics(printer=lambda m: None)])
tr.log = lambda m: None
tr.fit()
steps = CheckpointManager(ck, keep=99).steps()
assert steps == [2, 4, 6], steps
# every_boundaries=2 → every other boundary
ck2 = tempfile.mkdtemp()
tr2 = Trainer(cfg.replace(ckpt_dir=ck2),
              callbacks=[Checkpointing(every_boundaries=2),
                         Metrics(printer=lambda m: None)])
tr2.log = lambda m: None
tr2.fit()
steps2 = CheckpointManager(ck2, keep=99).steps()
assert steps2 == [4], steps2
print("BOUNDARY_CKPT_OK")
"""


CORPUS_DIR_E2E_CODE = r"""
import os, tempfile
import numpy as np
from repro.data import open_segments, save_segments
from repro.launch import train
from repro.training import Trainer, TrainerConfig

def argv(ck, extra=()):
    return ["--docs","200","--vocab","120","--topics","8","--true-topics","6",
            "--epochs","4","--data-shards","2","--model-shards","2",
            "--alpha-opt-from","2","--ckpt-dir",ck,"--ckpt-every","2",
            "--bench-out",""] + list(extra)

# resident reference: the same synthetic corpus streamed from memory
tr_mem = train.main(argv(tempfile.mkdtemp(), ["--n-segments","4"]))
assert tr_mem.source.n_segments == 4

# save that segmentation, retrain out-of-core through the DiskSource
d = tempfile.mkdtemp()
save_segments(tr_mem.source, d)
tr_disk = train.main(argv(tempfile.mkdtemp(), ["--corpus-dir",d]))
assert type(tr_disk.source).__name__ == "DiskSource"
assert tr_disk.config.prefetch
assert (np.asarray(tr_mem.state[0]) == np.asarray(tr_disk.state[0])).all()
assert (np.asarray(tr_mem.state[1]) == np.asarray(tr_disk.state[1])).all()
assert (tr_mem._z == tr_disk._z).all()
assert (np.asarray(tr_mem.alpha) == np.asarray(tr_disk.alpha)).all()

# kill at an intra-epoch segment boundary → resume lands bitwise on it
ck = tempfile.mkdtemp()
try:
    train.main(argv(ck, ["--corpus-dir",d,"--ckpt-segments","1",
                         "--kill-at","3","--kill-at-segment","2"]))
    raise AssertionError("kill-at-segment did not exit")
except SystemExit as e:
    assert e.code == 17, e.code
tr_res = train.main(argv(ck, ["--corpus-dir",d,"--resume"]))
assert tr_res.epoch == 4
for i in (0, 1):
    assert (np.asarray(tr_disk.state[i]) == np.asarray(tr_res.state[i])).all(), i
assert (tr_disk._z == tr_res._z).all()
assert (np.asarray(tr_disk.alpha) == np.asarray(tr_res.alpha)).all()
print("CORPUS_DIR_E2E_OK")
"""


def test_train_entrypoint_e2e(subproc):
    out = subproc(TRAIN_E2E_CODE, n_devices=4)
    assert "TRAIN_E2E_OK" in out
    assert "[ckpt] epoch 6 saved" in out


def test_checkpoint_every_aggregation_boundary(subproc):
    out = subproc(BOUNDARY_CKPT_CODE, n_devices=4)
    assert "BOUNDARY_CKPT_OK" in out


def test_segment_cadence_covers_every_boundary(tmp_path):
    """every_segments=1 must persist EVERY segment boundary — the last one
    of each epoch lands via the epoch-end save (post-α), even when the
    epoch cadence itself is not due (regression: it was silently dropped
    whenever ckpt_every didn't happen to align)."""
    import numpy as np

    from repro.checkpoint.manager import CheckpointManager
    from repro.training import Checkpointing, Trainer, TrainerConfig

    ck = str(tmp_path)
    cfg = TrainerConfig(n_docs=80, vocab_size=50, n_topics=4, true_topics=3,
                        n_epochs=2, n_segments=2, alpha_opt_from=99,
                        ckpt_dir=ck, ckpt_every=99, ckpt_keep=99)
    tr = Trainer(cfg, callbacks=[Checkpointing(every_segments=1)])
    tr.log = lambda m: None
    tr.fit()
    # global step = epoch * 2 + segments_done: (0,1)=1, (1,0)=2, (1,1)=3,
    # (2,0)=4 — every boundary present, none skipped
    steps = CheckpointManager(ck, keep=99).steps()
    assert steps == [1, 2, 3, 4], steps


@pytest.mark.parametrize("sampler", ["dense", "alias"])
def test_session_compiles_its_epoch_once(sampler):
    """Host-built first-epoch state, later epochs' sharded outputs and an
    optimized α reach the epoch in one placement: one compile per session
    (each extra one cost ~50 s at K = 10⁵ on a TPU v5e)."""
    import jax

    from repro.training import AlphaOptimizer, Trainer, TrainerConfig

    compiles = []

    def on_duration(event, duration, **kw):
        if (event == "/jax/core/compile/backend_compile_duration"
                and kw.get("fun_name") == "jit(epoch)"):
            compiles.append(duration)

    cfg = TrainerConfig(n_docs=80, vocab_size=50, n_topics=6, true_topics=3,
                        n_epochs=3, agg_every=1, alpha_opt_from=1,
                        sampler=sampler, seed=len(sampler))
    tr = Trainer(cfg, callbacks=[AlphaOptimizer()])
    tr.log = lambda m: None
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        tr.fit()
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
    assert len(compiles) == 1, compiles


def test_checkpoint_cadences_refuse_sessions_they_cannot_fire_in(tmp_path):
    """every_boundaries on a never-aggregating session (and every_segments
    on a 1-segment one) would silently write zero checkpoints — data loss
    discovered only at restore time. Both must refuse at train start."""
    from repro.training import Checkpointing, Trainer, TrainerConfig

    base = dict(n_docs=60, vocab_size=40, n_topics=4, true_topics=3,
                n_epochs=1, ckpt_dir=str(tmp_path))
    for cfg, cb in [
        # single-pod: no aggregation boundaries at all
        (TrainerConfig(**base), Checkpointing(every_boundaries=1)),
        # resident session: no segment boundaries
        (TrainerConfig(**base), Checkpointing(every_segments=1)),
        # streamed, but the cadence skips past every boundary in the epoch
        (TrainerConfig(**{**base, "n_segments": 2}),
         Checkpointing(every_segments=3)),
    ]:
        tr = Trainer(cfg, callbacks=[cb])
        tr.log = lambda m: None
        with pytest.raises(ValueError, match="can never fire"):
            tr.fit()


def test_kill_at_segment_refuses_sessions_it_cannot_fire_in():
    """A segment kill on a non-streamed session (or beyond the segment
    count) would silently never fire — the failure-sim must refuse loudly,
    like ElasticLiveness on a single-pod session."""
    import pytest as _pytest

    from repro.training import KillSwitch, Trainer, TrainerConfig

    base = dict(n_docs=60, vocab_size=40, n_topics=4, true_topics=3,
                n_epochs=1)
    tr = Trainer(TrainerConfig(**base),
                 callbacks=[KillSwitch(1, at_segment=1)])
    tr.log = lambda m: None
    with _pytest.raises(ValueError, match="streamed session"):
        tr.fit()
    tr2 = Trainer(TrainerConfig(n_segments=2, **base),
                  callbacks=[KillSwitch(1, at_segment=5)])
    tr2.log = lambda m: None
    with _pytest.raises(ValueError, match="never fire"):
        tr2.fit()


def test_train_corpus_dir_streams_and_resumes_bitwise(subproc):
    """Acceptance: --corpus-dir + --n-segments trains out-of-core through
    DiskSource with prefetch, matches the resident run bitwise, and
    kill-at→resume restores the exact (epoch, segment) boundary."""
    out = subproc(CORPUS_DIR_E2E_CODE, n_devices=4)
    assert "CORPUS_DIR_E2E_OK" in out
    assert "DiskSource" in out
    assert "[recovery] resumed from epoch 2 (+2 segments)" in out


def test_train_resume_bitwise_roundtrip(subproc):
    out = subproc(RESUME_CODE, n_devices=4)
    assert "RESUME_BITWISE_OK" in out
    assert "[recovery] resumed from epoch 4" in out


def test_trainer_multipod_elastic_publish(subproc):
    out = subproc(MULTIPOD_TRAINER_CODE, n_devices=8)
    assert "MULTIPOD_TRAINER_OK" in out


def test_trainer_multipod_resume_mid_window(subproc):
    out = subproc(MULTIPOD_RESUME_CODE, n_devices=8)
    assert "MULTIPOD_RESUME_OK" in out
