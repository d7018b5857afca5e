"""The trace reduction on a small synthetic trace of two chips."""
import pytest

from harness import trace
from harness.trace import Event, Trace


def synthetic():
    ms = 1e6
    chip0 = [Event("fusion.1", 0 * ms, 4 * ms, "jit_fn"),
             Event("fusion.2", 3 * ms, 6 * ms, "jit_fn"),       # overlaps
             Event("collective-permute.3", 5 * ms, 8 * ms),     # 2 ms alone
             Event("fusion.4", 12 * ms, 30 * ms, "jit_other")]  # past hi
    chip1 = [Event("fusion.1", 1 * ms, 9 * ms, "jit_fn")]
    host = [Event("chipbench.window", 0, 20 * ms),
            Event("chipbench.epoch", 0, 10 * ms),
            Event("chipbench.gap", 9 * ms, 11.5 * ms)]
    return Trace([chip0, chip1], host)


def test_busy_idle_and_exposed_collectives():
    tr = synthetic()
    lo, hi = trace.window_of(tr, "chipbench.window")
    out = trace.reduce(tr, lo, hi, "chipbench.")
    # chip 0 busy [0, 8] + [12, 20] = 16 ms; chip 1 busy [1, 9] = 8 ms
    assert out["window_s"] == pytest.approx(0.020)
    assert out["busy_s"] == pytest.approx(0.012)
    assert out["idle_share"] == pytest.approx(0.4)
    # the permute runs alone on chip 0 for [6, 8]: 2 ms of 20, over 2 chips
    assert out["exposed_collective_share"] == pytest.approx(0.05)
    # jit_fn ops: chip 0 [0, 6], chip 1 [1, 9] → 7 ms on average
    assert out["by_module"]["jit_fn"] == pytest.approx(0.007)
    # jit_other: chip 0 [12, 20] (clipped at hi) → 4 ms on average
    assert out["by_module"]["jit_other"] == pytest.approx(0.004)


def test_breakdown_labels_gaps_by_the_innermost_host_span():
    tr = synthetic()
    out = trace.reduce(tr, 0, 20e6, "chipbench.")
    ops = dict(out["breakdown"]["device_ops"])
    assert ops["fusion.1"] == pytest.approx((0.004 + 0.008) / 2)
    gaps = out["breakdown"]["idle_gaps"]
    # longest gaps: chip 1 [9, 20] = 11 ms, chip 0 [8, 12] = 4 ms, chip 1 [0, 1]
    assert gaps[0][1] == pytest.approx(0.011)
    assert gaps[1] == ["chipbench.gap", pytest.approx(0.004)]
    assert gaps[2] == ["chipbench.epoch", pytest.approx(0.001)]


def test_union_and_subtract():
    u = trace.union([(0, 2), (1, 3), (5, 6)], 0, 10)
    assert u == [(0, 3), (5, 6)]
    assert trace.subtract(u, [(1, 2), (5, 10)]) == pytest.approx(2.0)


def test_program_time_is_its_operations_inside_its_runs():
    ms = 1e6
    ops = [Event("while.4", 0, 5 * ms), Event("fusion.9", 7 * ms, 9 * ms)]
    runs = [Event("jit_build_alias(42)", 0, 6 * ms, "jit_build_alias(42)"),
            Event("jit_epoch(7)", 6 * ms, 10 * ms, "jit_epoch(7)")]
    tr = Trace([ops], [], [runs])
    out = trace.reduce(tr, 0, 10 * ms, "chipbench.")
    # the build's run holds 5 ms of operations and 1 ms idle
    assert out["by_module"]["jit_build_alias"] == pytest.approx(0.005)
    assert out["by_module"]["jit_epoch"] == pytest.approx(0.002)


def test_train_readers_take_device_time_from_the_trace():
    import run

    ms = 1e6
    tr = Trace([[Event("while.4", 0, 6 * ms, "jit_build_alias(3)"),
                 Event("fusion.9", 6 * ms, 8 * ms, "jit_epoch(5)")]],
               [Event("chipbench.window", 0, 10 * ms)])
    red = trace.reduce(tr, 0, 10 * ms, "chipbench.")
    # 4 ms of required bytes at the v5e's 819 GB/s, against 8 ms busy
    ctx = {"counters": {"epochs": 1, "epoch_flops": 0.0,
                        "epoch_bytes": 819e9 * 0.004, "epoch_s": [0.009],
                        "window_s": 0.010},
           "trace": red, "device_kind": "TPU v5 lite"}
    read = lambda name: run.load_reader(name)(ctx)
    assert read("train_step_mfu") == pytest.approx(50.0)
    assert read("train_table_build_share") == pytest.approx(75.0)
    assert read("device_idle_share.train") == pytest.approx(20.0)
    assert read("train_session_share") == pytest.approx(10.0)
    ctx["trace"] = None
    assert read("train_step_mfu") is None
    assert read("train_table_build_share") is None
