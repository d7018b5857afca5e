"""The M-ring on several devices: word tables built per shard, and the ring
epoch against the plain round-by-round reference (``core/ring_reference``).

* ``build_alias_word`` under ``shard_map`` over a Φ in the 4×1 ring layout
  and in the 2×2 word-sharded layout gives the tables of one build over the
  whole Φ, bit for bit, in Φ's layout; with α's row swept beside each
  chip's rows, α's table on every chip is the one-row build's, and the
  Trainer counts which α builds rode a word build and which ran alone;
* the Trainer's ring epoch at M = 4 draws what the reference draws, token
  for token, over epochs with the tables rebuilt between them; a draw that
  differs counts only where the reference shows it to be a tie;
* the α optimizer's (topic, count) histogram Ω counts each document of
  each data shard apart (the stacks' doc ids are local to their shard);
* the Trainer records the ring's geometry.

Multi-device cases run in subprocesses with 4 host devices
(``conftest.run_with_devices``).
"""
import pytest

pytestmark = pytest.mark.shard

TABLES_CODE = """
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding
from repro.core import sparse
from repro.dist import sharding as shd

rng = np.random.default_rng(3)
K, R, V = 300, 7, 500
for name, grid, shape, spec in [
        ("ring", (4, 1), (4, R, K), shd.ring_spec()),
        ("word_sharded", (2, 2), (2, 2 * R, K), shd.wshard_spec())]:
    mesh = Mesh(np.array(jax.devices()).reshape(grid), shd.RING_AXES)
    phi = (rng.integers(0, 6, shape) * (rng.random(shape) < 0.2)).astype(
        np.int32)
    psi = jnp.asarray(phi.reshape(-1, K).sum(0).astype(np.int32))
    whole = sparse.make_word_tables(jnp.asarray(phi), psi, 0.01, V)
    sharding = NamedSharding(mesh, spec)
    # the layout is Φ's own: no argument names the mesh
    per_shard = sparse.make_word_tables(jax.device_put(phi, sharding), psi,
                                        0.01, V)
    for a, b in zip(whole, per_shard):
        assert b.sharding.is_equivalent_to(sharding, b.ndim), name
        assert np.array_equal(np.asarray(a), np.asarray(b)), name
    hlo = sparse.build_alias_word.lower(
        jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding),
        mesh=mesh, spec=spec).compile().as_text()
    assert "all-gather" not in hlo, name
    print(name + ":TABLES_OK")
"""


def test_word_tables_built_per_shard_equal_one_build(subproc):
    out = subproc(TABLES_CODE, n_devices=4, timeout=600)
    assert out.count("TABLES_OK") == 2, out


ALPHA_CODE = """
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding
from repro.core import sparse
from repro.dist import sharding as shd
from repro.training import Trainer, TrainerConfig

rng = np.random.default_rng(4)
K, R, V = 300, 7, 500
alpha = jnp.asarray(rng.gamma(0.3, 1.0, K).astype(np.float32))
alone = [np.asarray(t) for t in sparse.make_alpha_table(alpha)]
for name, grid, shape, spec in [
        ("ring", (4, 1), (4, R, K), shd.ring_spec()),
        ("word_sharded", (2, 2), (2, 2 * R, K), shd.wshard_spec())]:
    mesh = Mesh(np.array(jax.devices()).reshape(grid), shd.RING_AXES)
    phi = (rng.integers(0, 6, shape) * (rng.random(shape) < 0.2)).astype(
        np.int32)
    psi = jnp.asarray(phi.reshape(-1, K).sum(0).astype(np.int32))
    sharding = NamedSharding(mesh, spec)
    phi = jax.device_put(phi, sharding)
    words = sparse.make_word_tables(phi, psi, 0.01, V)
    both = sparse.make_word_tables(phi, psi, 0.01, V,
                                   sparse.prepare_alpha_table(alpha))
    assert len(both) == 5, name
    for a, b in zip(words, both):
        assert b.sharding.is_equivalent_to(sharding, b.ndim), name
        assert np.array_equal(np.asarray(a), np.asarray(b)), name
    for want, got in zip(alone, both[3:]):
        assert got.sharding.is_fully_replicated, name
        assert len(got.addressable_shards) == 4, name
        for sh in got.addressable_shards:
            assert np.array_equal(np.asarray(sh.data), want), name
    print(name + ":ALPHA_OK")

cfg = TrainerConfig(
    n_docs=400, vocab_size=120, n_topics=32, true_topics=8, doc_len_mean=6,
    data_shards=4, model_shards=1, n_epochs=1, agg_every=1,
    alpha_opt_from=100, sampler="alias", n_mh=4, seed=5, bench_out=None)
tr = Trainer(cfg).setup()

def separate():
    phi, psi = tr.state[:2]
    return (sparse.make_word_tables(phi, psi, tr.beta, cfg.vocab_size)
            + sparse.make_alpha_table(tr.alpha))

def same(tables, want):
    return all(np.array_equal(np.asarray(a), np.asarray(b))
               for a, b in zip(tables, want))

tr._rebuild_tables()
assert tr.bench_record()["alpha_tables"] == {"with_word": 1, "alone": 0}
assert same(tr._tables, separate())
words = tuple(tr._tables[:3])
tr.alpha = tr.alpha * jnp.linspace(0.5, 2.0, cfg.n_topics)
tr._rebuild_tables(word=False)
assert tr.bench_record()["alpha_tables"] == {"with_word": 1, "alone": 1}
assert tr._tables[:3] == words
assert same(tr._tables, separate())
tr._rebuild_tables()
assert tr.bench_record()["alpha_tables"] == {"with_word": 2, "alone": 1}
assert same(tr._tables, separate())
print("TRAINER_OK")
"""


def test_alpha_table_rides_the_per_shard_word_build(subproc):
    out = subproc(ALPHA_CODE, n_devices=4, timeout=600)
    assert out.count("ALPHA_OK") == 2 and "TRAINER_OK" in out, out


RING_CODE = """
import numpy as np
from repro.core import ring_reference
from repro.training import Trainer, TrainerConfig

cfg = TrainerConfig(
    n_docs=400, vocab_size=120, n_topics=32, true_topics=8, doc_len_mean=6,
    data_shards=4, model_shards=1, n_epochs=1, agg_every=1,
    alpha_opt_from=100, sampler="alias", n_mh=4, seed=5, bench_out=None)
tr = Trainer(cfg).setup()
wl, dl, uid = (np.asarray(tr.state[i]) for i in (2, 3, 4))
lay = ring_reference.RingLayout.from_stacks(
    wl, dl, uid, tr.ring_cfg.rows_per_shard, tr.ring_cfg.docs_per_shard)

def z_of(state):
    z = np.asarray(state[5])
    return z[np.asarray(state[2]) >= 0]

zs = [z_of(tr.state)]
for e in range(3):
    tr.config = tr.config.replace(n_epochs=e + 1)
    tr.fit()
    zs.append(z_of(tr.state))
assert tr._tables_built_at == 2
alpha = np.asarray(tr.alpha)
moved = differ = ties = 0
for e in range(3):
    z_ref, margin, psi_ref = ring_reference.ring_epoch(
        lay, zs[e], alpha, float(cfg.beta), cfg.seed * 131 + 7 + e,
        cfg.vocab_size, cfg.n_mh)
    bad = z_ref != zs[e + 1]
    differ += int(bad.sum())
    ties += int((bad & (margin < ring_reference.TIE)).sum())
    moved += int((zs[e + 1] != zs[e]).sum())
assert moved > len(zs[0]), moved
assert differ == ties, (differ, ties)
assert np.array_equal(np.asarray(tr.state[1]), psi_ref)
# Ω over the corpus's own documents
omega, _ = tr.alpha_statistics()
z_uid = np.zeros(tr.corpus.n_tokens, np.int32)
z_uid[lay.uid] = zs[-1]
key, n = np.unique(tr.corpus.doc_ids.astype(np.int64) * cfg.n_topics + z_uid,
                   return_counts=True)
want = np.zeros_like(np.asarray(omega))
np.add.at(want, (key % cfg.n_topics, np.minimum(n, want.shape[1] - 1)), 1)
assert np.array_equal(np.asarray(omega), want)
ring = tr.bench_record()["ring"]
assert ring["rounds"] == 4 and ring["slots"] == 16 * ring["cap"], ring
assert ring["tokens"] == len(zs[0]), ring
print("RING_OK", len(zs[0]), moved, differ)
"""


PLACED_CODE = """
import gc
import jax
from repro.training import Trainer, TrainerConfig

cfg = TrainerConfig(
    n_docs=400, vocab_size=120, n_topics=32, true_topics=8, doc_len_mean=6,
    data_shards=4, model_shards=1, n_epochs=1, agg_every=1,
    alpha_opt_from=100, sampler="alias", n_mh=4, seed=5, bench_out=None)
tr = Trainer(cfg).setup()
shape = tr.state[0].shape
assert len(tr.state[0].sharding.device_set) == 1   # built on the host
tr._rebuild_tables()
gc.collect()
# after the first build Φ is held in the epoch's layout only: no copy of the
# whole host-built Φ stays on one chip
for a in jax.live_arrays():
    if a.shape == shape:
        assert a.sharding.is_equivalent_to(tr._epoch_in[0], a.ndim), a.sharding
assert tr.state[0].sharding.is_equivalent_to(tr._epoch_in[0], 3)
print("PLACED_OK")
"""


def test_first_build_keeps_no_host_built_phi_on_one_chip(subproc):
    out = subproc(PLACED_CODE, n_devices=4, timeout=600)
    assert "PLACED_OK" in out, out


def test_ring_epoch_draws_what_the_round_by_round_reference_draws(subproc):
    out = subproc(RING_CODE, n_devices=4, timeout=600)
    assert "RING_OK" in out, out
