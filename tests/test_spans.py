"""The training path's span recorder (``repro.training.spans``) and the
names it puts on host and device work: recorder nesting, self time, epoch
tags, a session's own totals and the recorder's bound; a tiny alias
``Trainer`` session's spans; the program names of the two table builds and
the named scopes of the ring epoch.

Single-device, so everything runs in the main pytest process.
"""
import re

import jax
import numpy as np
import pytest

from repro.training import (AlphaOptimizer, ModelPublisher, Trainer,
                            TrainerConfig, spans)

pytestmark = pytest.mark.trainer


@pytest.fixture
def clock(monkeypatch):
    """A recorder clock that advances only when told to."""
    t = [0.0]
    monkeypatch.setattr(spans, "now", lambda: t[0])
    return t


# ------------------------------ recorder -----------------------------------

def test_recorder_nesting_and_self_time(clock):
    rec = spans.Recorder()
    with rec.span("outer") as outer:
        clock[0] += 1.0
        with rec.span("inner") as a:
            clock[0] += 3.0
        with rec.span("inner"):
            clock[0] += 2.0
        clock[0] += 0.5
    assert outer.duration == 6.5 and outer.self_s == 1.5
    assert a.parent == "outer" and outer.parent is None
    assert [s.name for s in rec.recent()] == ["inner", "inner", "outer"]
    tot = rec.totals()
    assert tot["inner"] == {"n": 2, "total_s": 5.0, "self_s": 5.0}
    assert tot["outer"] == {"n": 1, "total_s": 6.5, "self_s": 1.5}


def test_recorder_epoch_tags_and_session_totals(clock):
    rec = spans.Recorder()
    with rec.span("setup"):
        clock[0] += 1.0
    mark = rec.totals()
    rec.at_epoch(3, 1)
    with rec.span("epoch", kind="ring") as sp:
        rec.at_epoch(4)             # a span keeps the tag it opened under
        clock[0] += 2.0
    with rec.span("epoch"):
        clock[0] += 3.0
    assert rec.recent("setup")[0].epoch is None
    assert (sp.epoch, sp.segment, sp.attrs) == (3, 1, {"kind": "ring"})
    # only what was recorded after the mark: "setup" had no call since
    assert rec.totals(since=mark) == {
        "epoch": {"n": 2, "total_s": 5.0, "self_s": 5.0}}
    assert rec.totals()["setup"] == {"n": 1, "total_s": 1.0, "self_s": 1.0}


def test_recorder_keeps_a_bounded_window_and_exact_totals(clock):
    rec = spans.Recorder(max_spans=5)
    for i in range(20):
        with rec.span("s", i=i):
            clock[0] += 1.0
    kept = rec.recent()
    assert len(kept) == 5 and [s.attrs["i"] for s in kept] == list(range(15, 20))
    assert rec.totals()["s"] == {"n": 20, "total_s": 20.0,
                                          "self_s": 20.0}


def test_recorder_closes_a_span_the_block_raises_out_of(clock):
    rec = spans.Recorder()
    with pytest.raises(KeyError):
        with rec.span("outer"):
            with rec.span("inner"):
                clock[0] += 2.0
                raise KeyError("x")
    with rec.span("after") as sp:
        pass
    assert sp.parent is None            # the stack unwound
    assert rec.totals()["outer"]["self_s"] == 0.0


# ------------------------- a tiny alias session ----------------------------

def _session(n_epochs=4, agg_every=2, **kw):
    cfg = TrainerConfig(n_docs=80, vocab_size=40, n_topics=8, true_topics=3,
                        n_epochs=n_epochs, sampler="alias",
                        agg_every=agg_every, alpha_opt_from=1, **kw)
    return Trainer(cfg, callbacks=[AlphaOptimizer()])


def _since(t0, name=None):
    return [s for s in spans.recorder().recent(name) if s.start >= t0]


def test_alias_session_records_its_spans():
    t0 = spans.now()
    tr = _session()
    res = tr.fit()
    # word tables at the session's start and at epoch 2 (agg_every = 2); the
    # α table with each of them and again at epoch 3, after the α step of
    # epoch 2 (α steps after epochs 1, 2 and 3)
    own = tr.bench_record()["spans"]
    assert own["peacock.train.tables.word"]["n"] == 2
    assert own["peacock.train.tables.alpha"]["n"] == 3
    assert own["peacock.train.alpha_step"]["n"] == 3
    assert own["peacock.train.ring_epoch"]["n"] == 4
    assert own["peacock.train.setup"]["n"] == 1

    names = {s.name for s in _since(t0)}
    assert {"peacock.train.setup", "peacock.train.setup.source",
            "peacock.train.setup.state", "peacock.train.setup.programs",
            "peacock.train.tables.word", "peacock.train.tables.alpha",
            "peacock.train.ring_epoch", "peacock.train.ring_epoch.put",
            "peacock.train.ring_epoch.dispatch",
            "peacock.train.ring_epoch.wait", "peacock.train.alpha_step",
            "peacock.train.callbacks"} <= names
    by_epoch = {}
    for s in _since(t0):
        by_epoch.setdefault(s.epoch, []).append(s.name)
    for ep in range(4):
        assert by_epoch[ep].count("peacock.train.ring_epoch") == 1
        assert by_epoch[ep].count("peacock.train.ring_epoch.wait") == 1
    assert [e for e in by_epoch
            if "peacock.train.tables.word" in by_epoch[e]] == [0, 2]
    assert [e for e in by_epoch
            if "peacock.train.alpha_step" in by_epoch[e]] == [1, 2, 3]
    for s in _since(t0, "peacock.train.alpha_step"):
        assert s.parent == "peacock.train.callbacks"

    # the epoch timer IS the ring_epoch span
    ring = _since(t0, "peacock.train.ring_epoch")
    assert res.metrics["epoch_s"] == [s.duration for s in ring]
    assert "ll_epoch" not in res.metrics
    assert own["peacock.train.ring_epoch"]["total_s"] == pytest.approx(
        sum(res.metrics["epoch_s"]))


def test_bench_record_holds_only_its_sessions_spans():
    first = _session(n_epochs=2, agg_every=1)
    first.fit()
    assert first.bench_record()["spans"]["peacock.train.ring_epoch"]["n"] == 2
    second = _session(n_epochs=1, agg_every=1)
    second.fit()
    own = second.bench_record()["spans"]
    assert own["peacock.train.ring_epoch"]["n"] == 1
    assert own["peacock.train.setup"]["n"] == 1


def test_streamed_segments_are_timed_by_their_spans():
    t0 = spans.now()
    tr = _session(n_epochs=2, agg_every=1, n_segments=2)
    res = tr.fit()
    ring = [s.duration for s in _since(t0, "peacock.train.ring_epoch")]
    assert res.metrics["segment_s"] == ring and len(ring) == 4
    assert res.metrics["epoch_s"] == pytest.approx(
        [ring[0] + ring[1], ring[2] + ring[3]])
    assert [(s.epoch, s.segment)
            for s in _since(t0, "peacock.train.ring_epoch")] == [
        (0, 0), (0, 1), (1, 0), (1, 1)]


def test_publish_is_timed_by_its_span(tmp_path):
    t0 = spans.now()
    cfg = TrainerConfig(n_docs=60, vocab_size=40, n_topics=6, true_topics=3,
                        n_epochs=2)
    tr = Trainer(cfg, callbacks=[ModelPublisher(str(tmp_path), every=1,
                                                at_end=False)])
    res = tr.fit()
    pub = [s.duration for s in _since(t0, "peacock.publish")]
    assert res.metrics["publish_s"] == pub and len(pub) == 2


# ------------------------------ device names -------------------------------

def _strip(hlo: str) -> str:
    """An HLO text without its module name, op metadata and the source
    locations the metadata points into."""
    hlo = re.sub(r"\n(FileNames|FunctionNames|FileLocations|StackFrames)\n"
                 r".*?(?=\n\n|$)", "", hlo, flags=re.S)
    hlo = re.sub(r",? ?metadata=\{[^}]*\}", "", hlo)
    return re.sub(r"HloModule \S+,", "HloModule m,", hlo)


@pytest.mark.parametrize("name", ["build_alias_word", "build_alias_alpha"])
def test_named_table_builds_compile_to_build_alias(name):
    from repro.core import sparse
    from repro.kernels.alias import ops

    x = jax.ShapeDtypeStruct((3, 64), np.float32)
    prog = getattr(sparse, name).lower(x).compile().as_text()
    assert f"HloModule jit_{name}," in prog
    assert _strip(prog) == _strip(ops.build_alias.lower(x).compile().as_text())


def test_ring_epoch_carries_the_sampler_scopes():
    tr = _session(n_epochs=1, package_len=8)
    tr.fit()
    from repro.core import sparse

    args = jax.device_put(tuple(tr.state) + (tr.alpha, tr.beta,
                                             np.uint32(1)) + tuple(tr._tables),
                          tr._epoch_in)
    assert isinstance(tr._tables, sparse.AliasTables)
    hlo = tr._epoch_fn.lower(*args).compile().as_text()
    ops = re.findall(r'op_name="([^"]*)"', hlo)
    for scope in ("mh_resample", "apply_deltas", "doc_pairs",
                  "ring_exchange"):
        assert any(f"/{scope}/" in op for op in ops), scope
