"""Per-kernel validation: fused Gibbs/RT-LDA kernel vs the pure-jnp oracle.

The kernel and oracle share the counter-based RNG, so agreement is required to
be EXACT (argmax over identical floats with identical tie-breaking).
"""
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import prng
from repro.kernels.gibbs import ops

pytestmark = pytest.mark.kernels

RNG = np.random.default_rng(7)


def _inputs(T, K, psi_scale=500):
    phi = jnp.array(RNG.integers(0, 50, (T, K)).astype(np.float32))
    psi = jnp.array(RNG.integers(1, psi_scale, (T, K)).astype(np.float32))
    theta = jnp.array(RNG.integers(0, 10, (T, K)).astype(np.float32))
    alpha = jnp.array(RNG.uniform(0.01, 1.0, K).astype(np.float32))
    uid = jnp.arange(T, dtype=jnp.uint32) + 31
    return phi, psi, theta, alpha, uid


@pytest.mark.parametrize("T,K", [(8, 64), (16, 100), (256, 512), (100, 700),
                                 (257, 513), (64, 1024), (31, 1000)])
@pytest.mark.parametrize("temperature", [1.0, 0.0])
def test_kernel_matches_ref(T, K, temperature):
    phi, psi, theta, alpha, uid = _inputs(T, K)
    kw = dict(vocab_size=5000, temperature=temperature)
    a = ops.gibbs_argmax(phi, psi, theta, alpha, jnp.float32(0.01), uid,
                         jnp.uint32(42), force="ref", **kw)
    b = ops.gibbs_argmax(phi, psi, theta, alpha, jnp.float32(0.01), uid,
                         jnp.uint32(42), force="interpret", **kw)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("block_t,block_k", [(8, 128), (64, 256), (256, 512)])
def test_kernel_block_shapes(block_t, block_k):
    from repro.kernels.gibbs.kernel import gibbs_argmax_pallas
    from repro.kernels.gibbs.ref import gibbs_argmax_ref

    T, K = 96, 384
    phi, psi, theta, alpha, uid = _inputs(T, K)
    a = gibbs_argmax_ref(phi, psi, theta, alpha, jnp.float32(0.05), uid,
                         jnp.uint32(3), 1000, 1.0)
    b = gibbs_argmax_pallas(phi, psi, theta, alpha, jnp.float32(0.05), uid,
                            jnp.uint32(3), 1000, 1.0,
                            block_t=block_t, block_k=block_k, interpret=True)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_gumbel_max_is_exact_categorical():
    """Empirical law of the Gumbel-max sampler matches the true posterior."""
    T, K = 4000, 12
    weights = RNG.integers(1, 40, K).astype(np.float32)
    phi = jnp.broadcast_to(jnp.array(weights)[None, :], (T, K))
    psi = jnp.full((T, K), 400.0)
    theta = jnp.zeros((T, K))
    alpha = jnp.ones(K)
    uid = jnp.arange(T, dtype=jnp.uint32)
    z = ops.gibbs_argmax(phi, psi, theta, alpha, jnp.float32(0.1), uid,
                         jnp.uint32(9), 100, 1.0, force="ref")
    p_emp = np.bincount(np.asarray(z), minlength=K) / T
    p_true = weights + 0.1
    p_true = p_true / p_true.sum()
    assert np.abs(p_emp - p_true).max() < 0.03


def test_seed_and_uid_decorrelate():
    phi, psi, theta, alpha, uid = _inputs(64, 128)
    base = ops.gibbs_argmax(phi, psi, theta, alpha, jnp.float32(0.01), uid,
                            jnp.uint32(1), 1000, 1.0, force="ref")
    other_seed = ops.gibbs_argmax(phi, psi, theta, alpha, jnp.float32(0.01),
                                  uid, jnp.uint32(2), 1000, 1.0, force="ref")
    other_uid = ops.gibbs_argmax(phi, psi, theta, alpha, jnp.float32(0.01),
                                 uid + 1000, jnp.uint32(1), 1000, 1.0, force="ref")
    assert (np.asarray(base) != np.asarray(other_seed)).any()
    assert (np.asarray(base) != np.asarray(other_uid)).any()


@given(seed=st.integers(0, 2**32 - 1), a=st.integers(0, 2**32 - 1),
       b=st.integers(0, 2**32 - 1))
@settings(max_examples=50, deadline=None)
def test_prng_uniform_range(seed, a, b):
    u = float(prng.uniform01(jnp.uint32(seed), jnp.uint32(a), jnp.uint32(b)))
    assert 0.0 < u < 1.0


def test_prng_unit_interval_is_open_at_both_ends():
    """The extreme 24-bit draws stay inside (0, 1), so Gumbel noise is
    finite: the top value once rounded to 1.0 and gave +inf, a topic that
    beat the posterior about once per 170 tokens at K = 10⁵. Every other
    value is the float32 (bits + 0.5) / 2**24 it always was."""
    low = np.array([0, 1, 2**23, 2**24 - 2], np.float32)
    bits = jnp.array([0, 1, 2**23, 2**24 - 2, 2**24 - 1], jnp.uint32)
    u = np.asarray(prng.unit_interval(bits))
    assert (u > 0).all() and (u < 1).all()
    np.testing.assert_array_equal(
        u[:4], (low + np.float32(0.5)) * np.float32(2.0 ** -24))
    g = -np.log(-np.log(u.astype(np.float64)))
    assert np.isfinite(g).all()


def test_prng_avalanche():
    """Adjacent counters must produce decorrelated bits (murmur3 finalizer)."""
    n = 4096
    bits = np.asarray(prng.hash_bits(jnp.uint32(5),
                                     jnp.arange(n, dtype=jnp.uint32),
                                     jnp.uint32(0)))
    as_bits = np.unpackbits(bits.view(np.uint8))
    assert abs(as_bits.mean() - 0.5) < 0.02          # balanced
    assert len(np.unique(bits)) == n                 # no collisions at 4k
