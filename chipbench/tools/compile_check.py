#!/usr/bin/env python3
"""Compile a training cell's programs for a described TPU v5e chip at the
cell's real sizes, without a chip, and print each program's compile
seconds and ``memory_analysis()``: what the chip's compiler refuses here,
or a program that does not fit 16 GB, costs no chip time.

    JAX_PLATFORMS=cpu python3 chipbench/tools/compile_check.py \
        train-alias-query

Nothing runs and nothing is timed on a device: a compile that passes is not
a chip run.
"""
from __future__ import annotations

import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from harness import env  # noqa: E402

env.use_program()


def report(name, fn, *args):
    t0 = time.perf_counter()
    compiled = fn.lower(*args).compile()
    dt = time.perf_counter() - t0
    m = compiled.memory_analysis()
    gb = lambda x: x / 1e9
    print(f"{name}: compile {dt:.1f} s; arguments {gb(m.argument_size_in_bytes):.3f} GB, "
          f"outputs {gb(m.output_size_in_bytes):.3f} GB, temporaries "
          f"{gb(m.temp_size_in_bytes):.3f} GB, aliased "
          f"{gb(m.alias_size_in_bytes):.3f} GB", flush=True)
    return compiled


def train_programs(spec, topo, one):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding

    from repro.core import distributed as dist, sparse

    from harness import ref_lda

    cfg, cell = spec.config, spec.workload
    chips = int(spec.cell["chips"])
    K = int(cfg["n_topics"])
    V = int(cfg["vocab_rows_trained"]) * chips
    T = int(cfg["corpus_tokens"]) * chips
    D = int(cfg["corpus_queries"]) * chips
    M = int(cell["data_shards"])
    P = int(cell["model_shards"])
    devs = np.array(topo.devices[:M * P]).reshape(M, P)
    mesh = jax.sharding.Mesh(devs, ("data", "model"))
    rows = -(-V // M)
    cap = -(-T // (M * M * 8)) * 8 + 64
    rc = dist.RingConfig(n_topics=K, vocab_size=V, rows_per_shard=rows,
                         docs_per_shard=-(-D // M), cap=cap, package_len=cap,
                         n_rounds=M, sampler="alias", n_mh=int(cfg["n_mh"]),
                         doc_topic_cap=16, model_shards=P)
    fn, in_specs, _ = dist.ring_epoch_parts(mesh, rc)
    sh = lambda s: NamedSharding(mesh, s)
    stack = lambda dt: jax.ShapeDtypeStruct((M, M, cap), dt, sharding=sh(in_specs[2]))
    args = (jax.ShapeDtypeStruct((M, rows, K), jnp.int32, sharding=sh(in_specs[0])),
            jax.ShapeDtypeStruct((K,), jnp.int32, sharding=sh(in_specs[1])),
            stack(jnp.int32), stack(jnp.int32), stack(jnp.uint32), stack(jnp.int32),
            jax.ShapeDtypeStruct((K,), jnp.float32, sharding=sh(in_specs[6])),
            jax.ShapeDtypeStruct((), jnp.float32, sharding=sh(in_specs[7])),
            jax.ShapeDtypeStruct((), jnp.uint32, sharding=sh(in_specs[8])),
            jax.ShapeDtypeStruct((M, rows, K), jnp.float32, sharding=sh(in_specs[9])),
            jax.ShapeDtypeStruct((M, rows, K), jnp.float32, sharding=sh(in_specs[10])),
            jax.ShapeDtypeStruct((M, rows, K), jnp.int32, sharding=sh(in_specs[11])),
            jax.ShapeDtypeStruct((K,), jnp.float32, sharding=sh(in_specs[12])),
            jax.ShapeDtypeStruct((K,), jnp.int32, sharding=sh(in_specs[13])))
    report(f"ring epoch (alias, {M}x{P})", jax.jit(fn, donate_argnums=(0, 2, 3, 4, 5)), *args)
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)
    report("word tables (program)", jax.jit(
        lambda phi, psi: sparse.make_word_tables(phi, psi, 0.01, V)),
        sds((M, rows, K), jnp.int32), sds((K,), jnp.int32))
    report("reference counts", ref_lda._counts_fn(V, K, "float32"),
           sds((T,), jnp.int32), sds((T,), jnp.int32), sds((), jnp.float32))
    report("reference word tables", ref_lda._walker_fn(), sds((V, K), jnp.float32))
    report("reference transition", ref_lda._transition_fn(V, K, int(cfg["n_mh"]), "float32"),
           sds((V, K), jnp.int32), sds((K,), jnp.int32), sds((V, K), jnp.float32),
           sds((V, K), jnp.float32), sds((V, K), jnp.int32), sds((K,), jnp.float32),
           sds((K,), jnp.int32), sds((T,), jnp.int32), sds((T,), jnp.int32),
           sds((T,), jnp.uint32), sds((T, 16), jnp.int32), sds((T,), jnp.int32),
           sds((K,), jnp.float32), sds((), jnp.float32), sds((), jnp.uint32))


def main():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    spec = env.load_spec(sys.argv[1])
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    print(f"described devices: {topo.devices[0].device_kind} x{len(topo.devices)}")
    train_programs(spec, topo, one)


if __name__ == "__main__":
    main()
