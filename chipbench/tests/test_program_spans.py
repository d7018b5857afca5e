"""The readers of the program's own spans, on a synthetic trace and a
recorder filled by hand."""
import pytest

from harness import program_spans, trace
from harness.trace import Event, Trace

MS = 1e6


@pytest.fixture
def recorder(monkeypatch):
    """A fresh program recorder on a clock that moves only when told to."""
    from repro.training import spans

    t = [0.0]
    monkeypatch.setattr(spans, "now", lambda: t[0])
    rec = spans.Recorder()
    monkeypatch.setattr(program_spans, "recorder", lambda: rec)

    def record(name, epoch, seconds=0.0):
        rec.at_epoch(epoch)
        with rec.span(name):
            t[0] += seconds

    return record


def window(word_builds=1):
    """One traced epoch: ``word_builds`` word-table builds of 3 ms, an α
    build of 1 ms and the epoch program, each a program run."""
    ops, runs, t = [], [], 0.0
    for i in range(word_builds):
        ops.append(Event("while.4", t, t + 3 * MS))
        runs.append(Event(f"jit_build_alias_word({i})", t, t + 3.5 * MS,
                          f"jit_build_alias_word({i})"))
        t += 4 * MS
    ops.append(Event("while.7", t, t + 1 * MS))
    runs.append(Event("jit_build_alias_alpha(9)", t, t + 1 * MS,
                      "jit_build_alias_alpha(9)"))
    ops.append(Event("fusion.2", t + 1 * MS, t + 3 * MS))
    runs.append(Event("jit_epoch(5)", t + 1 * MS, t + 3 * MS, "jit_epoch(5)"))
    tr = Trace([ops], [Event("chipbench.window", 0, t + 4 * MS)], [runs])
    return trace.reduce(tr, 0, t + 4 * MS, "chipbench.")


def ctx(red, epochs=1):
    return {"counters": {"epochs": epochs, "epoch_s": [0.003],
                         "window_s": 0.004, "epoch_flops": 0.0,
                         "epoch_bytes": 0.0},
            "trace": red, "device_kind": "TPU v5 lite"}


def test_table_readers_divide_device_time_by_the_window_builds(recorder):
    import run

    # set-up epochs 0-2 and the window's epoch 3, each with its builds
    for ep in range(4):
        recorder("peacock.train.tables.word", ep)
        recorder("peacock.train.tables.alpha", ep)
        recorder("peacock.train.ring_epoch", ep)
    c = ctx(window())
    read = lambda name: run.load_reader(name)(c)
    assert read("train_word_table_s") == pytest.approx(0.003)
    assert read("train_alpha_table_s") == pytest.approx(0.001)
    # the two builds make up the whole table-build share
    busy = c["trace"]["busy_s"]
    assert 100 * (read("train_word_table_s") + read("train_alpha_table_s")) \
        / busy == pytest.approx(read("train_table_build_share"))


def test_table_readers_average_over_the_builds_of_the_window(recorder):
    import run

    for ep in range(3):
        recorder("peacock.train.ring_epoch", ep)
    for ep in (1, 2):                       # the window: epochs 1 and 2
        recorder("peacock.train.tables.word", ep)
        recorder("peacock.train.tables.alpha", ep)
    recorder("peacock.train.tables.word", 0)        # set-up, not counted
    c = ctx(window(word_builds=2), epochs=2)
    assert run.load_reader("train_word_table_s")(c) == pytest.approx(0.003)
    assert run.load_reader("train_alpha_table_s")(c) == pytest.approx(0.0005)


def test_setup_shard_reads_the_source_span(recorder):
    import run

    assert run.load_reader("train_setup_shard_s")(ctx(None)) is None
    recorder("peacock.train.setup.source", 0, seconds=4.0)
    recorder("peacock.train.setup.source", 0, seconds=2.5)
    assert run.load_reader("train_setup_shard_s")(ctx(None)) == 2.5


def test_readers_report_nothing_without_the_program_recorder(monkeypatch):
    import run

    monkeypatch.setattr(program_spans, "recorder", lambda: None)
    c = ctx(window())
    for name in ("train_word_table_s", "train_alpha_table_s",
                 "train_setup_shard_s"):
        assert run.load_reader(name)(c) is None
    assert run.load_reader("train_table_build_share")(c) is not None


def test_table_readers_need_a_trace(recorder):
    import run

    recorder("peacock.train.ring_epoch", 0)
    recorder("peacock.train.tables.word", 0)
    assert run.load_reader("train_word_table_s")(ctx(None)) is None
