"""Launch-gate preflight: run every static contract check before the mesh.

``python -m repro.analysis.preflight`` (or ``launch/train.py --preflight``)
builds an *abstract* session — the same synthetic corpus → ``shard_corpus``
→ ``ring_epoch_parts`` pipeline a real run would take, but traced and
compiled on ``ShapeDtypeStruct``s so no training state is ever allocated —
then runs four passes:

  ``sharding``     §10 layout contract (repro.analysis.shardcheck)
  ``vmem``         static per-kernel VMEM plans (repro.analysis.vmem)
  ``determinism``  bitwise kill→resume jaxpr audit (repro.analysis.determinism)
  ``concurrency``  §12 thread contracts (repro.analysis.concurrency): lock
                   discipline, lock-order graph, thread lifecycle,
                   wait/notify protocol — AST only, zero threads started
  ``lint``         AST repo invariants (repro.analysis.repolint)

``concurrency`` and ``lint`` need no abstract session (pure source
analysis), so ``--passes concurrency`` gates the serving layer in well
under a second.

Exit code 0 iff no pass produced an ``error`` finding; ``--json`` emits the
machine-readable report CI consumes. A P=2 alias session verifies end-to-end
in a few seconds on the host mesh — the check belongs *before* every
multi-hour session, which is why ``launch/train.py`` grew the flag.

Import discipline: this module must stay importable before jax — it sets
``XLA_FLAGS`` host device counts itself, so every jax-touching import
happens inside functions, after :func:`ensure_host_devices`.
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import sys
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

from repro.analysis import repolint
from repro.analysis.report import (PassResult, PreflightReport, error,
                                   info)

PASSES = ("sharding", "vmem", "determinism", "concurrency", "lint")


@dataclasses.dataclass(frozen=True)
class SessionSpec:
    """The geometry preflight verifies (a TrainerConfig's static shadow)."""

    n_topics: int = 12
    vocab_size: int = 96
    data_shards: int = 2
    model_shards: int = 2      # P — word-sharded slices (1 = replicated ring)
    sampler: str = "alias"
    n_mh: int = 4
    n_docs: int = 120
    doc_len_mean: float = 7.0
    seed: int = 0

    @property
    def n_devices(self) -> int:
        return self.data_shards * max(1, self.model_shards)


def spec_from_trainer_config(cfg: Any) -> SessionSpec:
    """Derive the preflight geometry from a :class:`TrainerConfig` — same
    corpus knobs, same mesh, same sampler family the session would run."""
    P = int(getattr(cfg, "n_model_shards", 1))
    return SessionSpec(
        n_topics=cfg.n_topics, vocab_size=cfg.vocab_size,
        data_shards=cfg.ring_size if P == 1 else cfg.data_shards,
        model_shards=P, sampler=cfg.sampler, n_mh=cfg.n_mh,
        n_docs=cfg.n_docs, doc_len_mean=float(cfg.doc_len_mean),
        seed=cfg.seed)


def ensure_host_devices(n: int) -> None:
    """Make ``n`` host devices available — MUST run before the XLA backend
    initializes (importing jax is fine; creating arrays is not).

    Mirrors launch/train.py: on a CPU container device counts come from
    XLA host devices; on a real cluster XLA_FLAGS is already set by the
    launcher and is left alone.
    """
    if n <= 1:
        return
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={n}".strip())
    import jax

    # reads the flag at first backend creation; too late only if some
    # earlier code already materialized device buffers
    if jax.device_count() < n:
        raise RuntimeError(
            f"preflight needs {n} devices but the XLA backend is already "
            f"initialized with {jax.device_count()} — set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n} before first "
            "device use (or run `python -m repro.analysis.preflight` "
            "standalone)")


# ------------------------------------------------------------- the session --


@dataclasses.dataclass
class AbstractSession:
    """Everything the passes need, with only abstract (shape-only) args."""

    spec: SessionSpec
    mesh: Any
    ring_cfg: Any
    epoch_sm: Any              # shard_map'd, unjitted epoch
    abstract_args: Tuple[Any, ...]
    padded_tokens: int
    meta: Dict[str, Any]


def build_session(spec: SessionSpec) -> AbstractSession:
    """Synthetic corpus → shard_corpus → ring_epoch_parts, args as
    ShapeDtypeStructs. The only concrete work is the (host, numpy) corpus
    shuffle — no device buffers are created."""
    ensure_host_devices(spec.n_devices)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import distributed as dist, sparse
    from repro.data import corpus as corpus_mod, synthetic

    K, V = spec.n_topics, spec.vocab_size
    D, P = spec.data_shards, max(1, spec.model_shards)
    corpus, _ = synthetic.lda_corpus(
        seed=spec.seed, n_docs=spec.n_docs, n_topics=max(2, min(K, 20)),
        vocab_size=V, doc_len_mean=spec.doc_len_mean)
    sc = corpus_mod.shard_corpus(corpus, D, D, K, seed=spec.seed + 1,
                                 n_model_shards=P)
    S, M, cap = sc.word_local.shape
    doc_cap = 0
    if spec.sampler == "alias":
        lengths = np.bincount(corpus.doc_ids, minlength=corpus.n_docs)
        doc_cap = sparse.suggest_cap(lengths, K)
    ring_cfg = dist.RingConfig(
        n_topics=K, vocab_size=corpus.vocab_size,
        rows_per_shard=sc.rows_per_shard, docs_per_shard=sc.docs_per_shard,
        cap=cap, package_len=cap, n_rounds=M,
        sampler=spec.sampler, n_mh=spec.n_mh, doc_topic_cap=doc_cap,
        model_shards=P)
    mesh = jax.make_mesh((D, P), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    epoch_sm, _, _ = dist.ring_epoch_parts(mesh, ring_cfg)

    sds = jax.ShapeDtypeStruct
    rows = sc.rows_per_shard
    args: List[Any] = [
        sds((M, rows, K), jnp.int32),          # phi
        sds((K,), jnp.int32),                  # psi
        sds((S, M, cap), jnp.int32),           # word_local
        sds((S, M, cap), jnp.int32),           # doc_local
        sds((S, M, cap), jnp.uint32),          # uid
        sds((S, M, cap), jnp.int32),           # z
        sds((K,), jnp.float32),                # alpha
        sds((), jnp.float32),                  # beta
        sds((), jnp.uint32),                   # seed
    ]
    if spec.sampler == "alias":
        args += [
            sds((M, rows, K), jnp.float32),    # wq
            sds((M, rows, K), jnp.float32),    # wp
            sds((M, rows, K), jnp.int32),      # wa
            sds((K,), jnp.float32),            # ap
            sds((K,), jnp.int32),              # aa
        ]
    meta = {
        "n_topics": K, "vocab_size": V, "data_shards": D,
        "model_shards": P, "sampler": spec.sampler, "ring_size": M,
        "rows_per_shard": rows, "docs_per_shard": sc.docs_per_shard,
        "cap": cap, "doc_topic_cap": doc_cap,
        "padded_tokens": S * M * cap, "n_tokens": int(corpus.n_tokens),
    }
    return AbstractSession(spec=spec, mesh=mesh, ring_cfg=ring_cfg,
                           epoch_sm=epoch_sm, abstract_args=tuple(args),
                           padded_tokens=S * M * cap, meta=meta)


# ----------------------------------------------------------------- passes ---


def run_sharding_pass(session: AbstractSession,
                      compile_hlo: bool = True) -> PassResult:
    from repro.analysis import shardcheck

    t0 = time.monotonic()
    cfg = session.ring_cfg
    hlo = None
    if compile_hlo:
        import jax

        hlo = (jax.jit(session.epoch_sm)
               .lower(*session.abstract_args).compile().as_text())
    audit = shardcheck.check_epoch(
        session.epoch_sm, session.abstract_args,
        n_topics=cfg.n_topics, rows_per_shard=cfg.rows_per_shard,
        n_rounds=cfg.n_rounds, model_shards=cfg.model_shards,
        padded_tokens=session.padded_tokens, hlo_text=hlo)
    result = PassResult("sharding", audit.findings,
                        time.monotonic() - t0)
    session.meta["sharding"] = audit.to_dict()
    return result


def run_vmem_pass(session: AbstractSession) -> PassResult:
    from repro.analysis import vmem

    t0 = time.monotonic()
    cfg = session.ring_cfg
    P = max(1, cfg.model_shards)
    plans = vmem.repo_kernel_plans(
        n_topics=cfg.n_topics, rows_per_device=cfg.rows_per_shard // P,
        package_len=min(cfg.package_len, 256) or 256, sampler=cfg.sampler)
    findings = vmem.check_vmem(plans)
    return PassResult("vmem", findings, time.monotonic() - t0)


def run_determinism_pass(session: AbstractSession) -> PassResult:
    from repro.analysis import determinism

    t0 = time.monotonic()
    findings = determinism.audit(session.epoch_sm,
                                 *session.abstract_args)
    if not findings:
        findings = [info(
            "determinism.clean",
            "epoch jaxpr is replay-safe: no float scatter-adds, no "
            "jax.random primitives, no host callbacks",
            location="epoch")]
    return PassResult("determinism", findings, time.monotonic() - t0)


def run_lint_pass(root: Optional[str] = None) -> PassResult:
    t0 = time.monotonic()
    findings = repolint.lint_repo(root)
    return PassResult("lint", findings, time.monotonic() - t0)


def run_concurrency_pass(root: Optional[str] = None) -> PassResult:
    from repro.analysis import concurrency

    t0 = time.monotonic()
    findings = concurrency.run(root)
    return PassResult("concurrency", findings, time.monotonic() - t0)


def run_preflight(spec: SessionSpec,
                  passes: Sequence[str] = PASSES,
                  compile_hlo: bool = True,
                  root: Optional[str] = None) -> PreflightReport:
    """Build the abstract session and run the selected passes."""
    report = PreflightReport()
    needs_session = any(p in passes
                        for p in ("sharding", "vmem", "determinism"))
    session: Optional[AbstractSession] = None
    if needs_session:
        t0 = time.monotonic()
        try:
            session = build_session(spec)
        except Exception as e:                 # noqa: BLE001 — gate verdict
            report.add(PassResult("session", [error(
                "session.build",
                f"abstract session failed to build: {e!r} — the geometry "
                "itself is invalid (this is the failure preflight exists "
                "to move to launch time)", location="build_session")],
                time.monotonic() - t0))
            report.session = dataclasses.asdict(spec)
            return report
        report.session = dict(session.meta)
    for name in passes:
        if name == "sharding" and session is not None:
            report.add(run_sharding_pass(session, compile_hlo=compile_hlo))
        elif name == "vmem" and session is not None:
            report.add(run_vmem_pass(session))
        elif name == "determinism" and session is not None:
            report.add(run_determinism_pass(session))
        elif name == "concurrency":
            report.add(run_concurrency_pass(root))
        elif name == "lint":
            report.add(run_lint_pass(root))
    if session is not None:
        report.session["sharding"] = session.meta.get("sharding", {})
    return report


def verify_trainer_config(cfg: Any, compile_hlo: bool = True,
                          passes: Sequence[str] = PASSES
                          ) -> PreflightReport:
    """The ``launch/train.py --preflight`` entry: verify the session a
    TrainerConfig describes, without constructing a Trainer."""
    return run_preflight(spec_from_trainer_config(cfg),
                         passes=passes, compile_hlo=compile_hlo)


# -------------------------------------------------------------------- CLI ---


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis.preflight",
        description="static sharding/VMEM/determinism/concurrency/lint "
                    "contract checks")
    ap.add_argument("--topics", type=int, default=12)
    ap.add_argument("--vocab", type=int, default=96)
    ap.add_argument("--docs", type=int, default=120)
    ap.add_argument("--data-shards", type=int, default=2)
    ap.add_argument("--model-shards", type=int, default=2,
                    help="P — word-sharded model slices (1 = replicated)")
    ap.add_argument("--sampler", choices=("dense", "alias"), default="alias")
    ap.add_argument("--n-mh", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--passes", default=",".join(PASSES),
                    help=f"comma-separated subset of {','.join(PASSES)}; "
                         "`--passes concurrency` runs only the §12 thread "
                         "contracts (lock discipline / lock order / "
                         "lifecycle / wait-notify) — pure AST, no session "
                         "build, no threads started, sub-second")
    ap.add_argument("--no-compile", action="store_true",
                    help="skip HLO compilation (drops the collective-byte "
                         "budget check; jaxpr-level checks still run)")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable report on stdout")
    return ap


def main(argv: Optional[Sequence[str]] = None) -> int:
    args = build_parser().parse_args(argv)
    passes = tuple(p.strip() for p in args.passes.split(",") if p.strip())
    unknown = [p for p in passes if p not in PASSES]
    if unknown:
        print(f"unknown pass(es): {', '.join(unknown)} "
              f"(valid: {', '.join(PASSES)})", file=sys.stderr)
        return 2
    spec = SessionSpec(
        n_topics=args.topics, vocab_size=args.vocab, n_docs=args.docs,
        data_shards=args.data_shards, model_shards=args.model_shards,
        sampler=args.sampler, n_mh=args.n_mh, seed=args.seed)
    report = run_preflight(spec, passes=passes,
                           compile_hlo=not args.no_compile)
    if args.json:
        print(report.to_json(indent=2))
    else:
        print(report.render())
    return 0 if report.ok else 1


if __name__ == "__main__":
    sys.exit(main())
