"""repro.analysis preflight verifier: clean-repo passes + seeded violations.

Two halves, per the static-analysis contract:

* the UNMODIFIED repo passes all four passes cleanly (the launch gate must
  not cry wolf), and
* each pass catches a deliberately seeded violation — a float-ified ψ
  scatter, a VMEM-overflowing BlockSpec geometry, a Φ all-gather under
  P>1, a kernel without a registered oracle — with an actionable message
  (mutation-style tests: if a pass stops detecting its violation, the pass
  is broken, not the repo).

Sharding-pass tests need a multi-device mesh and therefore run through the
``subproc`` fixture (fresh XLA_FLAGS); everything else runs in-process.
"""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest

from repro.analysis import determinism, repolint, report, vmem

pytestmark = pytest.mark.preflight

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


# ------------------------------------------------------------- report -------


def test_finding_severity_validated():
    with pytest.raises(ValueError):
        report.Finding("x", "fatal", "nope")


def test_report_aggregation_and_json():
    r = report.PreflightReport()
    r.add(report.PassResult("a", [report.info("a.ok", "fine")], 0.1))
    assert r.ok
    r.add(report.PassResult("b", [report.error("b.bad", "broken")], 0.2))
    assert not r.ok
    doc = json.loads(r.to_json())
    assert doc["ok"] is False
    assert [p["pass"] for p in doc["passes"]] == ["a", "b"]
    assert doc["passes"][1]["n_errors"] == 1
    rendered = r.render()
    assert "[preflight] FAILED" in rendered and "b.bad" in rendered
    # warnings are advisory: they render but never flip the verdict
    r2 = report.PreflightReport()
    r2.add(report.PassResult("c", [report.warning("c.meh", "hmm")], 0.0))
    assert r2.ok


# -------------------------------------------------------- determinism -------


def test_determinism_clean_int_scatter():
    def upd(psi, z):
        return psi.at[z].add(1)

    psi = jax.ShapeDtypeStruct((8,), jnp.int32)
    z = jax.ShapeDtypeStruct((16,), jnp.int32)
    assert determinism.audit(upd, psi, z) == []


def test_determinism_catches_float_scatter():
    """Seeded violation: the ψ accumulator float-ified (the silent bitwise
    kill→resume breaker)."""
    def upd(psi, z):
        return psi.at[z].add(1.0)

    psi = jax.ShapeDtypeStruct((8,), jnp.float32)
    z = jax.ShapeDtypeStruct((16,), jnp.int32)
    found = determinism.audit(upd, psi, z)
    assert [f.check for f in found] == ["determinism.float-scatter-add"]
    assert found[0].severity == report.ERROR
    assert "int32" in found[0].message       # actionable: what to do instead


def test_determinism_catches_float_scatter_inside_scan():
    def epoch(psi, zs):
        def body(p, z):
            return p.at[z].add(1.0), ()
        return jax.lax.scan(body, psi, zs)[0]

    psi = jax.ShapeDtypeStruct((8,), jnp.float32)
    zs = jax.ShapeDtypeStruct((5, 3), jnp.int32)
    found = determinism.audit(epoch, psi, zs)
    assert len(found) == 1 and "scan" in found[0].location


def test_determinism_catches_jax_random_and_callbacks():
    def draw(key):
        return jax.random.uniform(key, (4,))

    found = determinism.audit(draw, jax.ShapeDtypeStruct((2,), jnp.uint32))
    assert any(f.check == "determinism.jax-random" for f in found)
    assert all("core/prng" in f.message for f in found
               if f.check == "determinism.jax-random")

    def chatty(x):
        jax.debug.callback(lambda v: None, x)
        return x

    found = determinism.audit(chatty, jax.ShapeDtypeStruct((), jnp.float32))
    assert any(f.check == "determinism.host-callback" for f in found)


# --------------------------------------------------------------- vmem -------


def _gibbs_plans(T, K, block_t, block_k):
    from repro.kernels.gibbs import kernel as gk

    sds = jax.ShapeDtypeStruct
    return vmem.plan_fn(
        lambda *a: vmem.unjitted(gk.gibbs_argmax_pallas)(
            *a, vocab_size=K, block_t=block_t, block_k=block_k),
        sds((T, K), jnp.float32), sds((T, K), jnp.float32),
        sds((T, K), jnp.float32), sds((K,), jnp.float32),
        sds((), jnp.float32), sds((T,), jnp.uint32), sds((), jnp.uint32))


def test_vmem_capture_sees_real_blockspecs():
    plans = _gibbs_plans(512, 1024, 256, 512)
    assert len(plans) == 1
    plan = plans[0]
    assert plan.grid == (2, 2)
    kinds = [b.kind for b in plan.buffers]
    assert "in" in kinds and "out" in kinds and "scratch" in kinds
    # three [256, 512] f32 planes double-buffered dominate; well under 16 MB
    assert 0 < plan.vmem_bytes < vmem.VMEM_BUDGET_BYTES
    assert all(f.severity == report.INFO
               for f in vmem.check_vmem(plans))


def test_vmem_catches_overflowing_blockspec():
    """Seeded violation: an inflated (1024, 8192) tile — 3 double-buffered
    f32 planes = 192 MB, an order past the ~16 MB/core budget."""
    plans = _gibbs_plans(1024, 8192, 1024, 8192)
    findings = vmem.check_vmem(plans)
    errs = [f for f in findings if f.severity == report.ERROR]
    assert len(errs) == 1
    msg = errs[0].message
    assert "MB VMEM" in msg and "shrink the tile" in msg
    assert "phi_ref" in msg            # the per-buffer table names operands
    assert errs[0].data["vmem_bytes"] > vmem.VMEM_BUDGET_BYTES


def test_vmem_hbm_resident_table_is_free():
    """The embedding-bag table rides MemorySpace.ANY — it must contribute
    zero VMEM no matter how big the table is."""
    from repro.kernels.embedding_bag import kernel as ek

    sds = jax.ShapeDtypeStruct
    plans = vmem.plan_fn(
        lambda t, i: vmem.unjitted(ek.embedding_bag_pallas)(t, i),
        sds((1_000_000, 64), jnp.float32), sds((32, 8), jnp.int32))
    (plan,) = plans
    table = next(b for b in plan.buffers if b.kind == "any(HBM)")
    assert table.vmem_bytes == 0
    assert plan.vmem_bytes < vmem.VMEM_BUDGET_BYTES


# --------------------------------------------------------------- lint -------


def test_lint_clean_repo_passes():
    findings = repolint.lint_repo(REPO)
    errs = [f for f in findings if f.severity == report.ERROR]
    assert errs == [], [f.message for f in errs]


def _fake_repo(tmp_path, kernel_named="foo", with_ref=False,
               extra_src=""):
    (tmp_path / "pyproject.toml").write_text("[project]\nname='x'\n")
    pkg = tmp_path / "src" / "repro" / "kernels" / kernel_named
    pkg.mkdir(parents=True)
    (pkg / "kernel.py").write_text("def k():\n    pass\n")
    if with_ref:
        (pkg / "ref.py").write_text("def k_ref():\n    pass\n")
    (tmp_path / "tests").mkdir()
    if extra_src:
        (tmp_path / "src" / "repro" / "extra.py").write_text(extra_src)
    return str(tmp_path)


def test_lint_catches_kernel_without_oracle(tmp_path):
    """Seeded violation: kernels/foo/kernel.py with no ref.py and no
    registered `-m kernels` test."""
    root = _fake_repo(tmp_path)
    findings = repolint.check_kernel_oracles(root)
    checks = {f.check for f in findings if f.severity == report.ERROR}
    assert checks == {"lint.kernel-oracle", "lint.kernel-test"}
    oracle = next(f for f in findings if f.check == "lint.kernel-oracle")
    assert "ref.py" in oracle.message and "bitwise" in oracle.message


def test_lint_catches_unmarked_kernel_test(tmp_path):
    root = _fake_repo(tmp_path, with_ref=True)
    (tmp_path / "tests" / "test_kernels_foo.py").write_text(
        "def test_k():\n    pass\n")          # exists, but no marker
    findings = repolint.check_kernel_oracles(root)
    errs = [f for f in findings if f.severity == report.ERROR]
    assert [f.check for f in errs] == ["lint.kernel-test"]
    assert "marker" in errs[0].message


def test_lint_catches_unfrozen_config(tmp_path):
    root = _fake_repo(tmp_path, with_ref=True, extra_src=textwrap.dedent("""
        import dataclasses

        @dataclasses.dataclass
        class SloppyConfig:
            x: int = 1
    """))
    findings = repolint.check_frozen_configs(root)
    errs = [f for f in findings if f.severity == report.ERROR]
    assert len(errs) == 1 and errs[0].data["cls"] == "SloppyConfig"
    assert "frozen=True" in errs[0].message


def test_lint_catches_stray_backend_probe(tmp_path):
    root = _fake_repo(tmp_path, with_ref=True, extra_src=textwrap.dedent("""
        import jax

        def pick():
            return jax.default_backend() == "tpu"
    """))
    findings = repolint.check_backend_probes(root)
    errs = [f for f in findings if f.severity == report.ERROR]
    assert len(errs) == 1 and "kernel_mode" in errs[0].message
    assert errs[0].location.endswith(":5")   # the default_backend() line


def test_lint_advisories_are_warnings(tmp_path):
    root = _fake_repo(tmp_path, with_ref=True, extra_src=textwrap.dedent("""
        import os

        def f():
            try:
                return 1
            except:
                return 0
    """))
    findings = repolint.check_advisories(root, subdirs=("src",))
    assert {f.check for f in findings} == {"lint.unused-import",
                                           "lint.bare-except"}
    assert all(f.severity == report.WARNING for f in findings)


# ----------------------------------------------------------- sharding -------


SHARDING_CLEAN_CODE = """
from repro.analysis import preflight as pf, shardcheck

session = pf.build_session(pf.SessionSpec())   # D=2, P=2, alias
audit = shardcheck.check_epoch(
    session.epoch_sm, session.abstract_args,
    n_topics=session.ring_cfg.n_topics,
    rows_per_shard=session.ring_cfg.rows_per_shard,
    n_rounds=session.ring_cfg.n_rounds,
    model_shards=session.ring_cfg.model_shards,
    padded_tokens=session.padded_tokens, hlo_text=None)
assert audit.ppermute_traced == audit.ppermute_expected, audit.to_dict()
assert not any(f.severity == "error" for f in audit.findings), \\
    [f.message for f in audit.findings]

# mutation 1: a wrong declared schedule must be flagged with the formula
bad = shardcheck.check_epoch(
    session.epoch_sm, session.abstract_args,
    n_topics=session.ring_cfg.n_topics,
    rows_per_shard=session.ring_cfg.rows_per_shard,
    n_rounds=3,                                  # session really has M=2
    model_shards=session.ring_cfg.model_shards,
    padded_tokens=session.padded_tokens, hlo_text=None)
errs = [f for f in bad.findings if f.severity == "error"]
assert [f.check for f in errs] == ["sharding.ppermute-count"], errs
assert "M\\u00b74 + M\\u00b7(P\\u22121)\\u00b72" in errs[0].message

# mutation 2: an epoch wrapper that all-gathers the resident slice
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P

def leaky(*args):
    phi = args[0]
    gathered = jax.lax.all_gather(phi, "model")  # Phi replication!
    phi = gathered.reshape(-1, *phi.shape[1:])[:phi.shape[0]]
    return session.ring_cfg and args             # keep args alive

leaky_sm = jax.shard_map(
    leaky, mesh=session.mesh,
    in_specs=tuple(P() for _ in session.abstract_args),
    out_specs=tuple(P() for _ in session.abstract_args),
    check_vma=False)
found = shardcheck.find_phi_allgathers(
    jax.make_jaxpr(leaky_sm)(*session.abstract_args),
    n_topics=session.ring_cfg.n_topics,
    min_rows=session.ring_cfg.rows_per_shard
        // session.ring_cfg.model_shards)
assert found and found[0].check == "sharding.phi-all-gather", found
assert "HBM" in found[0].message
print("SHARDCHECK_OK")
"""


def test_sharding_contract_clean_and_mutations(subproc):
    out = subproc(SHARDING_CLEAN_CODE, n_devices=4, timeout=600)
    assert "SHARDCHECK_OK" in out, out


FULL_PREFLIGHT_CODE = """
import json
from repro.analysis import preflight as pf

report = pf.run_preflight(pf.SessionSpec(), compile_hlo=True)
assert report.ok, report.render()
doc = json.loads(report.to_json())
assert [p["pass"] for p in doc["passes"]] == \\
    ["sharding", "vmem", "determinism", "concurrency", "lint"]
sharding = doc["session"]["sharding"]
assert sharding["ppermute_traced"] == sharding["ppermute_expected"] == 12
assert sharding["folded_bytes"]["collective-permute"] > 0
assert sharding["folded_bytes"]["collective-permute"] <= \\
    sharding["budget_bytes"]["collective-permute"]
print("PREFLIGHT_OK")
"""


def test_full_preflight_clean_repo(subproc):
    """The unmodified repo passes all five passes, budgets included."""
    out = subproc(FULL_PREFLIGHT_CODE, n_devices=4, timeout=600)
    assert "PREFLIGHT_OK" in out, out


# ------------------------------------------------------- CLI entrypoints ----


def _run_cli(argv, timeout=600):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)         # the CLIs set their own device count
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    return subprocess.run([sys.executable, *argv], capture_output=True,
                          text=True, timeout=timeout, env=env)


def test_preflight_cli_json():
    proc = _run_cli(["-m", "repro.analysis.preflight", "--json"])
    assert proc.returncode == 0, proc.stderr[-2000:]
    doc = json.loads(proc.stdout)
    assert doc["ok"] is True
    assert {p["pass"] for p in doc["passes"]} == \
        {"sharding", "vmem", "determinism", "concurrency", "lint"}


def test_preflight_cli_rejects_unknown_pass():
    proc = _run_cli(["-m", "repro.analysis.preflight", "--passes", "nope"])
    assert proc.returncode == 2


def test_train_preflight_gate():
    """Acceptance: launch/train.py --preflight verifies a P=2 alias session
    end-to-end without allocating training state."""
    proc = _run_cli(["-m", "repro.launch.train", "--data-shards", "2",
                     "--model-shards", "2", "--sharded-model",
                     "--sampler", "alias", "--topics", "16",
                     "--vocab", "128", "--docs", "200", "--preflight"])
    assert proc.returncode == 0, proc.stdout + proc.stderr[-2000:]
    assert "[preflight] OK" in proc.stdout
    assert "[export]" not in proc.stdout       # no training ran


def test_dryrun_verify_and_json():
    proc = _run_cli(["-m", "repro.launch.dryrun", "--shard-table", "--json"])
    assert proc.returncode == 0, proc.stderr[-2000:]
    doc = json.loads(proc.stdout)
    rows = doc["shard_table"]["rows"]
    assert [int(r["model_shards"]) for r in rows] == [1, 2, 4, 8]
    assert rows[3]["fits_16gb_hbm"] is True
