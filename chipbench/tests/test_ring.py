"""The four-chip ring cell ``train-ring-query-4chip`` at its rehearsal sizes,
on four host CPU devices (each case in a process of its own, started with
``XLA_FLAGS``; this process keeps its one device):

* a traced rehearsal comes out correct and would report the cell's
  per-layer metrics, the ring's two among them;
* the control and each planted fault of the round-by-round reference fail
  one of the cell's compared numbers, and the sound reference passes;
* the one-chip cell's traced rehearsal still reports its seven metrics;
* over a program whose word build gathers Φ onto every chip, the ring cell
  exits non-zero before its set-up;
* the ring reader and the fill-share reader on hand-made inputs.
"""
import json
import os
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RING = "train-ring-query-4chip"


def run_cell(workload, devices, trace=1, seed=3_000_000_019):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         workload, "--seed", str(seed), "--seconds", "1", "--trace",
         str(trace), "--rehearse"],
        capture_output=True, text=True, env=env, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_ring_traced_rehearsal_is_correct_and_reports_its_metrics():
    out = run_cell(RING, 4)
    assert out["correct"], out["checks"]
    assert set(out["would_report"]) == {
        "train_session_share", "train_step_mfu", "train_table_build_share",
        "device_idle_share.train", "train_word_table_s",
        "train_alpha_table_s", "train_setup_shard_s",
        "ring_exposed_collective_share", "train_ring_fill_share"}


def test_one_chip_cell_reports_its_seven_metrics():
    out = run_cell("train-alias-query", 1)
    assert out["correct"], out["checks"]
    assert set(out["would_report"]) == {
        "train_session_share", "train_step_mfu", "train_table_build_share",
        "device_idle_share.train", "train_word_table_s",
        "train_alpha_table_s", "train_setup_shard_s"}


CONTROLS = """
import json, sys
sys.path.insert(0, {bench!r})
from harness import env
env.use_program()
from tools import controls_ring
spec = env.load_spec({cell!r})
devs = env.devices(4, True)
limits = spec.workload["limits"]
for name, checks in controls_ring.ring_readings(spec, {seed}, True, devs):
    failed = [k for k, c in checks.items() if not c["value"] <= limits[k]]
    print(json.dumps({{"variant": name, "failed": failed}}))
"""


@pytest.mark.parametrize("seed", [101, 2 ** 31 + 7])
def test_ring_control_and_faults_fail_and_the_reference_passes(seed):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run(
        [sys.executable, "-c", CONTROLS.format(bench=BENCH, cell=RING,
                                               seed=seed)],
        capture_output=True, text=True, env=env, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    failed = {r["variant"]: r["failed"] for r in
              map(json.loads, proc.stdout.strip().splitlines()[-5:])}
    assert failed.pop("sound") == []
    assert set(failed) == {"control", "psum_every_round", "phi_frozen",
                           "z_unforwarded"}
    for variant, which in failed.items():
        assert which, variant


LAYOUT = """
import json, sys
sys.path.insert(0, {bench!r})
import run
from harness import env, train_ring
env.use_program()
import jax
from repro.core import sparse
from repro.kernels.alias import ops


def gathering(phi, psi, beta, vocab_size):
    # one program over the whole Φ, as the build was before it ran per shard
    wq = (phi + beta) / (psi[..., None, :] + vocab_size * beta)
    return (wq,) + tuple(jax.jit(ops.alias_tables)(wq))


devs = env.devices(4, True)
for d, m in ((4, 1), (2, 2)):
    cell = {{"data_shards": d, "model_shards": m}}
    print(json.dumps({{"layout": [d, m],
                      "program": train_ring.builds_per_shard(devs, cell),
                      "gathering": train_ring.builds_per_shard(
                          devs, cell, gathering)}}))
sparse.make_word_tables = gathering
try:
    run.main(["--workload", {cell!r}, "--seed", "5", "--seconds", "1",
              "--trace", "0", "--rehearse"])
except SystemExit as e:
    print(json.dumps({{"exit": str(e.code)}}))
"""


def test_ring_cell_refuses_a_word_build_that_gathers_phi():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run(
        [sys.executable, "-c", LAYOUT.format(bench=BENCH, cell=RING)],
        capture_output=True, text=True, env=env, timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [json.loads(x) for x in proc.stdout.strip().splitlines()]
    for layout in lines[:2]:
        assert layout["program"] and not layout["gathering"], layout
    assert "gathers" in lines[2]["exit"]
    # it stops before the corpus is made
    assert "stage corpus" not in proc.stderr and "] corpus" not in proc.stderr


def test_ring_readers_on_hand_made_inputs():
    import run

    trace = {"exposed_collective_share": 0.025, "busy_s": 1.0}
    ring = {"rounds": 4, "cap": 100, "slots": 1600, "tokens": 1200}
    assert run.load_reader("ring_exposed_collective_share")(
        {"trace": trace, "counters": {}}) == pytest.approx(2.5)
    assert run.load_reader("ring_exposed_collective_share")(
        {"trace": None, "counters": {}}) is None
    fill = run.load_reader("train_ring_fill_share")
    assert fill({"counters": {"ring": ring}}) == pytest.approx(75.0)
    # a program that keeps no record of its ring: nothing to report
    assert fill({"counters": {"ring": None}}) is None
    assert fill({"counters": {}}) is None
