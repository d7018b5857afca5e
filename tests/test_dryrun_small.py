"""Small-mesh dry-run sanity: lower+compile representative cells in a
subprocess (8 fake devices, 4×2 and 2×2×2 meshes). The production 512-device
sweep is launch/dryrun.py; this guards the plumbing in CI time."""
import json


CODE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, json
from repro.configs import get_arch
from repro.launch.mesh import make_test_mesh

mesh = make_test_mesh(4, 2)
mesh3 = make_test_mesh(2, 2, n_pod=2)
results = {}
cells = [
    ("smollm-135m", "train_4k", False),
    ("smollm-135m", "decode_32k", False),
    ("qwen2-moe-a2.7b", "prefill_32k", False),
    ("graphsage-reddit", "molecule", False),
    ("autoint", "serve_p99", False),
    ("peacock-lda", "train_segment", False),
    ("smollm-135m", "train_4k", True),
    ("peacock-lda", "train_segment", True),
]
for arch, shape, mp in cells:
    spec = get_arch(arch)
    cell = spec.cell(shape, mesh3 if mp else mesh, mp)
    compiled = cell.lower().compile()
    ca = compiled.cost_analysis()
    results[f"{arch}/{shape}/{'mp' if mp else 'sp'}"] = float(ca.get("flops", 0))
print("DRYRUN_SMALL_OK", json.dumps(list(results)))
"""


def test_small_mesh_dryrun(subproc):
    out = subproc(CODE, n_devices=8, timeout=900)
    assert "DRYRUN_SMALL_OK" in out
