"""The main path's device programs compile for a TPU v5e at real widths.

Interpret mode cannot see what the chip's compiler refuses (unaligned
slices, unsupported casts, VMEM overuse) or a program that does not fit the
device. These tests compile, without a chip, for a described ``v5e:2x2``
topology at one chip's share of the paper deployment (821 vocabulary rows,
K = 10⁵): the Gibbs kernel, the alias ops as they run on TPU, the serving
step and the blocked dedup pass. Nothing runs, so they say nothing about
results or times.

The topology is described inside a module fixture (never at import): only
one process at a time may load the TPU library, and every test worker
imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import dedup, features, rtlda
from repro.kernels.alias import ops as alias_ops
from repro.kernels.gibbs.kernel import gibbs_argmax_pallas

K = 100_000          # configs/peacock_lda.py K_TOPICS
V = 821              # ⌈2.1e5 / 256⌉ vocabulary rows per chip of the ring
HBM = 16e9           # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def sds(topo):
    """Shape factory on one described chip, with the persistent compilation
    cache off (described-chip programs can be written to it but never read
    back without a chip)."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    one_chip = SingleDeviceSharding(topo.devices[0])
    yield lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=one_chip)
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _fits(compiled) -> float:
    ma = compiled.memory_analysis()
    live = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    assert live < HBM, f"{live / 1e9:.1f} GB does not fit one chip"
    return ma.temp_size_in_bytes


def test_gibbs_kernel_compiles_at_full_width(sds):
    T = 512
    c = gibbs_argmax_pallas.lower(
        sds((T, K), jnp.float32), sds((T, K), jnp.float32),
        sds((T, K), jnp.float32), sds((K,), jnp.float32),
        sds((), jnp.float32), sds((T,), jnp.uint32), sds((), jnp.uint32),
        vocab_size=V).compile()
    assert "tpu_custom_call" in c.as_text()
    _fits(c)


def test_alias_build_compiles_at_chip_share(sds):
    c = alias_ops.build_alias.lower(sds((V, K), jnp.float32),
                                   force=alias_ops.TPU_MODE).compile()
    _fits(c)


def test_alias_mh_resample_compiles_at_chip_share(sds):
    T, D, cap = 4096, 1024, 16
    f = jax.jit(alias_ops.mh_resample,
                static_argnames=("vocab_size", "n_mh", "force"))
    c = f.lower(
        sds((V, K), jnp.int32), sds((K,), jnp.int32),
        sds((D, cap), jnp.int32), sds((D, cap), jnp.int32),
        sds((V, K), jnp.float32), sds((V, K), jnp.float32),
        sds((V, K), jnp.int32), sds((K,), jnp.float32),
        sds((K,), jnp.float32), sds((K,), jnp.int32),
        sds((T,), jnp.int32), sds((T,), jnp.int32), sds((T,), jnp.int32),
        sds((T,), jnp.uint32), sds((), jnp.uint32), sds((), jnp.float32),
        vocab_size=V, n_mh=4, force=alias_ops.TPU_MODE).compile()
    _fits(c)


def test_serving_step_compiles_at_full_width(sds):
    model = rtlda.RTLDAModel(
        pvk=sds((V, K), jnp.float32), alpha=sds((K,), jnp.float32),
        r_topic=sds((V,), jnp.int32), r_value=sds((V,), jnp.float32))
    c = features.make_serving_fn().lower(
        model, sds((16, 8), jnp.int32), sds((), jnp.int32)).compile()
    _fits(c)


def test_dedup_row_block_compiles_without_pair_matrix(sds):
    block = 512
    kp = K + (-K) % block
    c = dedup._l1_row_block.lower(
        sds((V, kp), jnp.float32), sds((), jnp.int32),
        sds((), jnp.float32), block, K).compile()
    # the [V, block, K] difference tensor stays fused: temporaries hold a
    # few [block, K] planes, never V times that
    assert _fits(c) < 4 * block * kp * 4
