import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")


def run_with_devices(code: str, n_devices: int = 8, timeout: int = 600) -> str:
    """Run python code in a subprocess with N fake host devices.

    Keeps the main pytest process at 1 device (per the assignment: only the
    dry-run force-sets device counts globally).
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=timeout, env=env,
    )
    if proc.returncode != 0:
        raise AssertionError(
            f"subprocess failed (rc={proc.returncode}):\n--- stdout ---\n"
            f"{proc.stdout}\n--- stderr ---\n{proc.stderr[-3000:]}")
    return proc.stdout


@pytest.fixture
def subproc():
    return run_with_devices
