"""Entry-point contracts: the compile-cache helper and chip_smoke.py's
refusal to run anywhere but on a TPU. Each case runs in a fresh process, so
this process's JAX config is never touched."""
import json
import os
import shutil
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")

CACHE_CODE = """
import json
from repro.launch.compile_cache import enable_compile_cache
path = enable_compile_cache()
import jax
print(json.dumps([path, jax.config.jax_compilation_cache_dir,
                  jax.config.jax_persistent_cache_min_compile_time_secs]))
"""


def _run(args, env_extra=None, cwd=REPO, drop=()):
    env = {k: v for k, v in os.environ.items() if k not in drop}
    env.update(env_extra or {})
    return subprocess.run([sys.executable, *args], capture_output=True,
                          text=True, timeout=300, env=env, cwd=cwd)


def test_compile_cache_uses_the_env_dir_when_set(tmp_path):
    d = str(tmp_path / "cache")
    out = _run(["-c", CACHE_CODE], {"PYTHONPATH": SRC,
                                    "JAX_COMPILATION_CACHE_DIR": d})
    assert out.returncode == 0, out.stderr
    path, live, min_s = json.loads(out.stdout.strip().splitlines()[-1])
    assert path == live == d and min_s == 0.0


def test_compile_cache_defaults_to_a_fixed_checkout_path():
    out = _run(["-c", CACHE_CODE], {"PYTHONPATH": SRC},
               drop=("JAX_COMPILATION_CACHE_DIR",))
    assert out.returncode == 0, out.stderr
    path, live, _ = json.loads(out.stdout.strip().splitlines()[-1])
    assert path == live == os.path.join(REPO, ".jax_cache")


def test_chip_smoke_refuses_a_cpu_backend():
    out = _run(["chip_smoke.py"], {"JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "needs a TPU" in out.stderr


def test_chip_smoke_fails_without_the_repo(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    out = _run(["chip_smoke.py"], {"JAX_PLATFORMS": "cpu"}, cwd=tmp_path,
               drop=("PYTHONPATH",))
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
