#!/usr/bin/env python3
"""Compare the optimized HLO of a training cell's programs between this
checkout and another, compiled for a described TPU v5e at the cell's real
sizes, without a chip: the ring epoch, the word-table build and the α-table
build. Module names, op metadata (named scopes, source lines) and the
source-location tables are stripped first, so a change that only names
work compares equal.

    JAX_PLATFORMS=cpu python3 chipbench/tools/hlo_compare.py \
        train-alias-query --base <other checkout>

Each checkout compiles in a child process of its own, with that
checkout's ``src`` on the path. Prints one line per program with both
sides' sha256 prefixes; exits 1 if any program differs. The ring epoch
takes about a minute to compile on each side.
"""
from __future__ import annotations

import argparse
import hashlib
import json
import os
import re
import subprocess
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
TOOLS = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(TOOLS))

from harness import env  # noqa: E402

PROGRAMS = ("ring_epoch", "word_tables", "alpha_table")


def strip(hlo: str) -> str:
    """An HLO text without its module name, op metadata and the source
    locations the metadata points into."""
    hlo = re.sub(r"\n(FileNames|FunctionNames|FileLocations|StackFrames)\n"
                 r".*?(?=\n\n|$)", "", hlo, flags=re.S)
    hlo = re.sub(r",? ?metadata=\{[^}]*\}", "", hlo)
    return re.sub(r"HloModule \S+,", "HloModule m,", hlo)


def lowered(spec):
    """The cell's three programs, lowered for a described v5e, by name."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, SingleDeviceSharding

    from repro.core import distributed as dist, sparse
    from repro.kernels.alias import ops

    cfg, cell = spec.config, spec.workload
    chips = int(spec.cell["chips"])
    K = int(cfg["n_topics"])
    V = int(cfg["vocab_rows_trained"]) * chips
    T = int(cfg["corpus_tokens"]) * chips
    D = int(cfg["corpus_queries"]) * chips
    M, P = int(cell["data_shards"]), int(cell["model_shards"])
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    mesh = jax.sharding.Mesh(
        np.array(topo.devices[:M * P]).reshape(M, P), ("data", "model"))
    rows = -(-V // M)
    cap = -(-T // (M * M * 8)) * 8 + 64
    rc = dist.RingConfig(n_topics=K, vocab_size=V, rows_per_shard=rows,
                         docs_per_shard=-(-D // M), cap=cap, package_len=cap,
                         n_rounds=M, sampler="alias", n_mh=int(cfg["n_mh"]),
                         doc_topic_cap=16, model_shards=P)
    fn, in_specs, _ = dist.ring_epoch_parts(mesh, rc)
    S = jax.ShapeDtypeStruct
    sh = lambda i: NamedSharding(mesh, in_specs[i])
    stack = lambda i, dt: S((M, M, cap), dt, sharding=sh(i))
    plane = lambda i, dt: S((M, rows, K), dt, sharding=sh(i))
    topics = lambda i, dt: S((K,), dt, sharding=sh(i))
    args = (plane(0, jnp.int32), topics(1, jnp.int32),
            stack(2, jnp.int32), stack(3, jnp.int32), stack(4, jnp.uint32),
            stack(5, jnp.int32), topics(6, jnp.float32),
            S((), jnp.float32, sharding=sh(7)),
            S((), jnp.uint32, sharding=sh(8)),
            plane(9, jnp.float32), plane(10, jnp.float32),
            plane(11, jnp.int32), topics(12, jnp.float32),
            topics(13, jnp.int32))
    # a checkout from before the two builds had names of their own ran
    # both through ops.build_alias
    word = getattr(sparse, "build_alias_word", ops.build_alias)
    alpha = getattr(sparse, "build_alias_alpha", ops.build_alias)
    return {
        "ring_epoch": lambda: jax.jit(
            fn, donate_argnums=(0, 2, 3, 4, 5)).lower(*args),
        "word_tables": lambda: word.lower(S((M, rows, K), jnp.float32,
                                            sharding=one)),
        "alpha_table": lambda: alpha.lower(S((1, K), jnp.float32,
                                             sharding=one)),
    }


def emit(workload: str, src: str) -> None:
    """Child: one JSON line of sha256 of each stripped program."""
    sys.path.insert(0, src)
    spec = env.load_spec(workload)
    out = {}
    for name, lower in lowered(spec).items():
        text = strip(lower().compile().as_text())
        out[name] = hashlib.sha256(text.encode()).hexdigest()
    print(json.dumps(out), flush=True)


def digests(workload: str, root: str) -> dict:
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), workload,
         "--emit", os.path.join(os.path.abspath(root), "src")],
        capture_output=True, text=True)
    if proc.returncode != 0:
        sys.stderr.write(proc.stderr)
        raise SystemExit(f"compile of {root} failed")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("workload")
    ap.add_argument("--base", help="the checkout to compare against")
    ap.add_argument("--emit", metavar="SRC", help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.emit:
        emit(args.workload, args.emit)
        return 0
    if not args.base:
        ap.error("--base is required")
    here = digests(args.workload, env.ROOT)
    base = digests(args.workload, args.base)
    same = True
    for name in PROGRAMS:
        eq = here[name] == base[name]
        same &= eq
        print(f"{name}: {'same' if eq else 'DIFFERENT'} "
              f"(this {here[name][:16]}, base {base[name][:16]})")
    return 0 if same else 1


if __name__ == "__main__":
    sys.exit(main())
