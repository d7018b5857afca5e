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


def _loop_computations(hlo: str) -> dict:
    """The computations of an HLO text that run inside a while loop: every
    loop's body and what it calls, by name."""
    import re

    comps = dict(re.findall(r"^(?:ENTRY )?%([\w.\-]+) [^\n]*\{\n(.*?)^\}$",
                            hlo, re.M | re.S))
    todo, seen = re.findall(r"\bbody=%([\w.\-]+)", hlo), set()
    while todo:
        name = todo.pop()
        if name in comps and name not in seen:
            seen.add(name)
            todo += re.findall(r"%([\w.\-]+)", comps[name])
    return {name: comps[name] for name in seen}


def test_alias_build_compiles_at_chip_share(sds):
    """The build writes its permutations by row-batched sorts along K: no
    scatter, no sort inside a loop over rows, and temporaries within 5% of
    1.738 GB, those of a build by row loops of [K] scatters."""
    c = alias_ops.build_alias.lower(sds((V, K), jnp.float32)).compile()
    temp = _fits(c)
    hlo = c.as_text()
    assert "scatter(" not in hlo
    assert hlo.count(" sort(") == 2
    assert not [name for name, body in _loop_computations(hlo).items()
                if " sort(" in body]
    assert abs(temp - 1.738e9) <= 0.05 * 1.738e9, temp


@pytest.mark.parametrize("layout", ["ring", "word_sharded"])
def test_word_tables_over_sharded_phi_sweep_each_chips_rows(topo, layout):
    """A Φ split across the 2×2 chips by rows (the 4-chip ring, or the
    word-sharded data 2 × model 2 layout) keeps the Walker sweep's windows
    per chip: each chip gathers its windows from its own rows' blocks,
    never from blocks of all the rows."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding

    from repro.core import sparse
    from repro.dist import sharding as shd

    k = 1000                               # nb = ⌈k/128⌉ + 1 = 9 blocks
    nb = -(-k // 128) + 1
    if layout == "ring":
        grid, shape, spec = (4, 1), (4, V, k), shd.ring_spec()
    else:
        grid, shape, spec = (2, 2), (2, 2 * V, k), shd.wshard_spec()
    mesh = Mesh(np.array(topo.devices).reshape(grid), shd.RING_AXES)
    phi = jax.ShapeDtypeStruct(shape, jnp.float32,
                               sharding=NamedSharding(mesh, spec))
    hlo = sparse.build_alias_word.lower(phi).compile().as_text()
    assert f"[{V},{nb},128]" in hlo
    assert f"[{4 * V},{nb},128]" not in hlo
    assert f"[{4 * V * nb},128]" not in hlo


@pytest.fixture(scope="module")
def one_chip_word_build(sds):
    """The compiled one-chip word-table build at [1, 821, K]: the ring
    state's shape on one chip of the paper's 256-chip ring."""
    from repro.core import sparse

    return sparse.build_alias_word.lower(sds((1, V, K), jnp.float32)).compile()


def _strip(hlo: str) -> str:
    """An HLO text without module name, metadata and frontend attributes
    (which name the mesh)."""
    import re

    hlo = re.sub(r"\n(FileNames|FunctionNames|FileLocations|StackFrames)\n"
                 r".*?(?=\n\n|$)", "", hlo, flags=re.S)
    hlo = re.sub(r",? ?metadata=\{[^}]*\}", "", hlo)
    hlo = re.sub(r",? ?frontend_attributes=\{[^{}]*(\{[^{}]*\}[^{}]*)*\}",
                 "", hlo)
    return re.sub(r"HloModule \S+,", "HloModule m,", hlo)


@pytest.mark.parametrize("layout", ["one_chip", "ring", "word_sharded"])
def test_word_tables_built_per_shard_hold_no_gathered_phi(
        topo, one_chip_word_build, layout):
    """The word-table build in Φ's own layout (``shard_map``): on a 1×1 mesh
    it is the one-chip program; over the 2×2 chips at 4 × 821 rows, in the
    4-chip ring and in the word-sharded layout, no chip gathers Φ or any
    [3284, ·] plane, and each chip's temporaries are those of the one-chip
    build within 10%."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding

    from repro.core import sparse
    from repro.dist import sharding as shd

    grid, shape, spec = {
        "one_chip": ((1, 1), (1, V, K), shd.ring_spec()),
        "ring": ((4, 1), (4, V, K), shd.ring_spec()),
        "word_sharded": ((2, 2), (2, 2 * V, K), shd.wshard_spec()),
    }[layout]
    n = grid[0] * grid[1]
    mesh = Mesh(np.array(topo.devices[:n]).reshape(grid), shd.RING_AXES)
    phi = jax.ShapeDtypeStruct(shape, jnp.float32,
                               sharding=NamedSharding(mesh, spec))
    c = sparse.build_alias_word.lower(phi, mesh=mesh, spec=spec).compile()
    hlo = c.as_text()
    if layout == "one_chip":
        assert _strip(hlo) == _strip(one_chip_word_build.as_text())
        return
    assert "all-gather" not in hlo
    assert f"[{4 * V}," not in hlo
    base = one_chip_word_build.memory_analysis().temp_size_in_bytes
    temp = _fits(c)
    assert abs(temp - base) <= 0.1 * base, (temp, base)


def test_alias_mh_resample_compiles_at_chip_share(sds):
    T, D, cap = 4096, 1024, 16
    f = jax.jit(alias_ops.mh_resample,
                static_argnames=("vocab_size", "n_mh"))
    c = f.lower(
        sds((V, K), jnp.int32), sds((K,), jnp.int32),
        sds((D, cap), jnp.int32), sds((D, cap), jnp.int32),
        sds((V, K), jnp.float32), sds((V, K), jnp.float32),
        sds((V, K), jnp.int32), sds((K,), jnp.float32),
        sds((K,), jnp.float32), sds((K,), jnp.int32),
        sds((T,), jnp.int32), sds((T,), jnp.int32), sds((T,), jnp.int32),
        sds((T,), jnp.uint32), sds((), jnp.uint32), sds((), jnp.float32),
        vocab_size=V, n_mh=4).compile()
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
