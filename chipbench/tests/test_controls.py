"""The control and the planted faults, at the rehearsal sizes: each must
fail one of the cell's compared numbers, and the float32 reference put in
the program's place must pass them all. ``tools/controls.py`` reads the
same variants on the chip at the cell's own size."""
import pytest

from harness import env
from tools import controls


def fails(readings, spec):
    limits = spec.workload["limits"]
    return [k for k, v in readings.items() if k in limits and v > limits[k]]


def run(cell, seed):
    spec = env.load_spec(cell)
    return spec, {name: {k: c["value"] for k, c in checks.items()}
                  for name, checks in controls.train_readings(
                      spec, seed, rehearse=True)}


@pytest.mark.parametrize("seed", [101, 2 ** 31 + 7])
def test_train_control_and_faults_fail_and_the_reference_passes(seed):
    spec, out = run("train-alias-query", seed)
    assert fails(out["sound"], spec) == []
    for variant in ("control", "unchanged", "half", "output"):
        assert fails(out[variant], spec), variant
