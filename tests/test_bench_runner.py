"""benchmarks/run.py keeps JAX out of its parent: one child per module."""
import importlib.util
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RUNNER = os.path.join(REPO, "benchmarks", "run.py")


def test_runner_parent_never_imports_jax():
    code = ("import runpy, sys; sys.argv = ['run.py', '--help']\n"
            "try:\n"
            f"    runpy.run_path({RUNNER!r}, run_name='__main__')\n"
            "except SystemExit:\n"
            "    pass\n"
            "print('jax' in sys.modules)")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120, check=True).stdout
    assert out.strip().splitlines()[-1] == "False"


def test_runner_refuses_to_spawn_after_jax_import():
    import jax  # noqa: F401 — the state the runner must refuse

    spec = importlib.util.spec_from_file_location("bench_run", RUNNER)
    runner = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(runner)
    with pytest.raises(RuntimeError, match="JAX in its parent"):
        runner.run_module("rtlda", "benchmarks.bench_rtlda", "Fig 5", None)
