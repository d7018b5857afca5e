"""repro.data streaming pipeline: CorpusSource / DiskSource / SegmentStream.

Covers the ISSUE-4 satellite contract: vocab placement identical across all
segments and across a save→load round trip; streamed training bitwise equal
between the resident (in-memory) and out-of-core (DiskSource, mmap,
prefetch) paths; the explicit SyntheticSource fallback; and the
(epoch, segment) resume boundary.
"""
import tempfile

import numpy as np
import pytest

from repro.data import corpus as corpus_mod, synthetic
from repro.data import (DiskSource, InMemorySource, SegmentStream,
                        SyntheticSource, initial_z, open_segments,
                        save_segments, segment_order)

pytestmark = pytest.mark.data


def _corpus(n_docs=140, vocab=90, seed=1):
    c, _ = synthetic.lda_corpus(seed=seed, n_docs=n_docs, n_topics=6,
                                vocab_size=vocab, doc_len_mean=9)
    return c


# ------------------------------ segmentation --------------------------------

def test_assign_segments_balanced_and_deterministic():
    a = corpus_mod.assign_segments(103, 4, seed=7)
    b = corpus_mod.assign_segments(103, 4, seed=7)
    np.testing.assert_array_equal(a, b)
    counts = np.bincount(a, minlength=4)
    assert counts.max() - counts.min() <= 1
    # a different seed moves documents (it is a permutation, not modulo)
    c = corpus_mod.assign_segments(103, 4, seed=8)
    assert (a != c).any()


def test_segment_corpus_common_static_shapes_and_global_uids():
    corpus = _corpus()
    segs = corpus_mod.segment_corpus(corpus, 3, 2, 2, 8, seed=0).segments
    shapes = {sc.word_local.shape for sc in segs}
    assert len(shapes) == 1, "segments must share one static cap"
    assert len({sc.docs_per_shard for sc in segs}) == 1
    # uids are GLOBAL token ids: disjoint across segments, covering the corpus
    uids = [np.asarray(sc.uid)[np.asarray(sc.word_local) >= 0] for sc in segs]
    allu = np.concatenate(uids)
    assert len(allu) == corpus.n_tokens
    assert len(np.unique(allu)) == corpus.n_tokens
    # every token's word survives the round trip through its segment layout
    for sc in segs:
        valid = np.asarray(sc.word_local) >= 0
        words = corpus.word_ids[np.asarray(sc.uid)[valid]]
        assert (np.asarray(sc.shard_of_word)[words]
                == np.where(valid)[1]).all()


def _flat_corpus(seed, n_tokens, n_docs, vocab_size=400):
    rng = np.random.default_rng(seed)
    docs = np.sort(rng.integers(0, n_docs, n_tokens)).astype(np.int32)
    words = rng.integers(0, vocab_size, n_tokens).astype(np.int32)
    return corpus_mod.Corpus(words, docs, n_docs, vocab_size)


@pytest.mark.parametrize("n_segments", [1, 2])
def test_ring_cap_rounds_up_to_a_stable_multiple(n_segments):
    corpus = _flat_corpus(5, 20_000, 4_000)
    segs = corpus_mod.segment_corpus(corpus, n_segments, 4, 2, 8,
                                     seed=1).segments
    mult = corpus_mod.stable_cap_multiple(
        corpus.n_tokens / (n_segments * 8), 8)
    assert mult == (64 if n_segments == 1 else 32)
    fullest = max(int((np.asarray(sc.word_local)[s, m] >= 0).sum())
                  for sc in segs for s in range(4) for m in range(2))
    cap = segs[0].word_local.shape[-1]
    assert cap % mult == 0 and fullest <= cap < fullest + mult


def test_ring_cap_is_one_shape_across_seeds_of_one_size():
    caps = {corpus_mod.segment_corpus(_flat_corpus(seed, 1 << 18, 40_000),
                                      1, 4, 2, 8, seed=seed)
            .segments[0].word_local.shape[-1] for seed in range(3)}
    assert caps == {33 * 1024}, caps
    # one block holds the whole corpus: its cap is the corpus, as before
    one = corpus_mod.segment_corpus(_flat_corpus(0, 1001, 300), 1, 1, 1, 8)
    assert corpus_mod.stable_cap_multiple(1001, 1) == 8
    assert one.segments[0].word_local.shape[-1] == 1008


def test_segment_order_is_a_seeded_permutation():
    o1 = segment_order(5, epoch=3, seed=11)
    o2 = segment_order(5, epoch=3, seed=11)
    np.testing.assert_array_equal(o1, o2)
    assert sorted(o1.tolist()) == list(range(5))
    orders = {tuple(segment_order(5, epoch=e, seed=11)) for e in range(8)}
    assert len(orders) > 1, "visit order should vary across epochs"


# ------------------------------ sources -------------------------------------

def test_in_memory_source_stable_placement():
    src = InMemorySource(_corpus(), 3, 2, 2, 8, seed=2)
    s0 = src.segment(0)
    for g in range(1, src.n_segments):
        sg = src.segment(g)
        np.testing.assert_array_equal(np.asarray(s0.shard_of_word),
                                      np.asarray(sg.shard_of_word))
        np.testing.assert_array_equal(np.asarray(s0.local_of_word),
                                      np.asarray(sg.local_of_word))
    assert src.word_freq().sum() == src.n_tokens
    assert src.doc_lengths().sum() == src.n_tokens


def test_disk_roundtrip_bitwise_and_memory_mapped():
    src = InMemorySource(_corpus(), 3, 2, 2, 8, seed=2)
    d = tempfile.mkdtemp()
    save_segments(src, d)
    disk = open_segments(d)
    assert (disk.n_docs, disk.n_tokens, disk.vocab_size, disk.n_segments) == \
           (src.n_docs, src.n_tokens, src.vocab_size, src.n_segments)
    for g in range(src.n_segments):
        a, b = src.segment(g), disk.segment(g)
        for name in ("word_local", "doc_local", "uid", "z0"):
            np.testing.assert_array_equal(np.asarray(getattr(a, name)),
                                          np.asarray(getattr(b, name)))
            assert isinstance(getattr(b, name), np.memmap), \
                "disk stacks must be memory-mapped (out-of-core residency)"
        np.testing.assert_array_equal(np.asarray(a.shard_of_word),
                                      np.asarray(b.shard_of_word))
        assert a.n_real_tokens == b.n_real_tokens
    np.testing.assert_array_equal(src.word_freq(), disk.word_freq())
    np.testing.assert_array_equal(src.doc_lengths(), disk.doc_lengths())


def test_open_segments_rejects_non_corpus_dir():
    with pytest.raises(FileNotFoundError, match="save_segments"):
        open_segments(tempfile.mkdtemp())


def test_interrupted_resave_is_not_openable():
    """Re-saving over an existing corpus dir drops the old completeness
    marker FIRST — a crash mid-rewrite must not leave a directory that
    opens as the (stale) previous corpus with mixed contents."""
    d = tempfile.mkdtemp()
    save_segments(InMemorySource(_corpus(), 2, 1, 1, 8, seed=0), d)
    assert open_segments(d).n_segments == 2

    class Boom(RuntimeError):
        pass

    class FailingSource(InMemorySource):
        def segment(self, g):
            if g == 1:
                raise Boom("disk died mid-save")
            return super().segment(g)

    bad = FailingSource(_corpus(n_docs=80, seed=2), 2, 1, 1, 8, seed=1)
    with pytest.raises(Boom):
        save_segments(bad, d)
    with pytest.raises(FileNotFoundError):
        open_segments(d)


def test_initial_z_covers_every_token():
    src = InMemorySource(_corpus(), 2, 2, 2, 8, seed=3)
    z = initial_z(src)
    assert z.shape == (src.n_tokens,)
    for g in range(src.n_segments):
        sc = src.segment(g)
        valid = np.asarray(sc.word_local) >= 0
        np.testing.assert_array_equal(z[np.asarray(sc.uid)[valid]],
                                      np.asarray(sc.z0)[valid])


# ------------------------------ stream --------------------------------------

def test_segment_stream_prefetch_bitwise_invisible():
    src = InMemorySource(_corpus(), 3, 2, 2, 8, seed=4)
    for epoch in (0, 1):
        z_a, z_b = initial_z(src), initial_z(src)
        sync = SegmentStream(src, z_a, prefetch=False)
        pref = SegmentStream(src, z_b, prefetch=True)
        got_a = [(s.gid, np.asarray(s.wl), np.asarray(s.z))
                 for s in sync.epoch(epoch)]
        got_b = [(s.gid, np.asarray(s.wl), np.asarray(s.z))
                 for s in pref.epoch(epoch)]
        assert [g for g, *_ in got_a] == [g for g, *_ in got_b]
        for (_, wa, za), (_, wb, zb) in zip(got_a, got_b):
            np.testing.assert_array_equal(wa, wb)
            np.testing.assert_array_equal(za, zb)


def test_segment_stream_commit_scatters_by_uid():
    src = InMemorySource(_corpus(), 2, 2, 2, 8, seed=5)
    z = initial_z(src)
    stream = SegmentStream(src, z, prefetch=False)
    segs = list(stream.epoch(0))
    seg = segs[0]
    marked = np.full(np.asarray(seg.z).shape, 7, np.int32)
    stream.commit(seg, marked)
    # every valid token of THIS segment now reads 7; the other segment's
    # tokens are untouched (disjoint documents → disjoint uids)
    assert (z[seg.host_uid[seg.host_valid]] == 7).all()
    other = segs[1]
    np.testing.assert_array_equal(
        z[other.host_uid[other.host_valid]],
        np.asarray(src.segment(other.gid).z0)[other.host_valid])


# ------------------------- trainer integration ------------------------------

def test_trainer_routes_corpus_none_through_synthetic_source():
    from repro.training import Trainer, TrainerConfig

    logs = []
    tr = Trainer(TrainerConfig(n_docs=60, vocab_size=40, n_topics=4,
                               true_topics=3, n_epochs=1))
    tr.log = logs.append
    tr.setup()
    assert isinstance(tr.source, SyntheticSource)
    data_lines = [m for m in logs if m.startswith("[data]")]
    assert len(data_lines) == 1
    assert "SyntheticSource" in data_lines[0]
    assert f"{tr.source.n_tokens} tokens" in data_lines[0]


def test_trainer_rejects_mismatched_disk_geometry():
    from repro.training import Trainer, TrainerConfig

    src = InMemorySource(_corpus(), 2, 1, 1, 8, seed=0)   # 1x1 ring, K=8
    d = tempfile.mkdtemp()
    save_segments(src, d)
    with pytest.raises(ValueError, match="n_topics"):
        Trainer(TrainerConfig(n_topics=16, corpus_dir=d)).setup()
    with pytest.raises(ValueError, match="ring geometry"):
        Trainer(TrainerConfig(n_topics=8, corpus_dir=d,
                              data_shards=2, model_shards=2)).setup()


STREAM_EQUIV_CODE = r"""
import tempfile
import numpy as np
from repro.data import save_segments
from repro.training import Trainer, TrainerConfig

def run(**kw):
    cfg = TrainerConfig(n_docs=200, vocab_size=120, n_topics=8,
                        true_topics=6, n_epochs=4, alpha_opt_from=2,
                        data_shards=2, model_shards=2, **kw)
    tr = Trainer(cfg)
    tr.log = lambda m: None
    tr.fit()
    return tr

# the resident reference: in-memory stream, 2 segments, no prefetch
mem = run(n_segments=2, prefetch=False)
d = tempfile.mkdtemp()
save_segments(mem.source, d)
# out-of-core: DiskSource (mmap) with double-buffered prefetch
disk = run(corpus_dir=d, prefetch=True)
assert (np.asarray(mem.state[0]) == np.asarray(disk.state[0])).all(), "phi"
assert (np.asarray(mem.state[1]) == np.asarray(disk.state[1])).all(), "psi"
assert (mem._z == disk._z).all(), "z"
assert (np.asarray(mem.alpha) == np.asarray(disk.alpha)).all(), "alpha"

# the streaming path degenerates to the legacy resident path at 1 segment:
# same phi/psi/z trajectory, just with device-resident stacks
gold = run()                              # legacy (6-tuple state)
d1 = tempfile.mkdtemp()
save_segments(gold.source, d1)
one = run(corpus_dir=d1)                  # streamed, 1 mmap'd segment
assert (gold.gather_phi() == one.gather_phi()).all()
assert (np.asarray(gold.state[1]) == np.asarray(one.state[1])).all()
assert (np.asarray(gold.alpha) == np.asarray(one.alpha)).all()
sc = gold.sc0
valid = np.asarray(sc.word_local) >= 0
z_legacy = np.zeros(gold.source.n_tokens, np.int32)
z_legacy[np.asarray(sc.uid)[valid]] = np.asarray(gold.state[5])[valid]
assert (z_legacy == one._z).all()
print("STREAM_EQUIV_OK")
"""


def test_streamed_training_matches_resident_bitwise(subproc):
    """Memory↔disk, prefetch↔sync, and streamed↔legacy-resident all produce
    bitwise-identical models for the same seed (acceptance criterion)."""
    out = subproc(STREAM_EQUIV_CODE, n_devices=4)
    assert "STREAM_EQUIV_OK" in out
