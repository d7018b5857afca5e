"""Counter-based stateless RNG shared by the XLA path, the Pallas kernel and the oracle.

A murmur3-finalizer hash of (seed, token, k) gives i.i.d. uniform bits without any
carried RNG state. Consequences we rely on:

  * kernel == ref **bitwise** (both evaluate the identical integer formula);
  * the sample drawn for a token is invariant to sharding layout and to
    fault-recovery replay (determinism across restarts, which the paper's Go
    implementation could not offer);
  * no PRNG key threading through scan/shard_map bodies.

All arithmetic is uint32 with wraparound (XLA semantics), valid inside Pallas.
"""
from __future__ import annotations

import jax.numpy as jnp

# plain Python ints: they stay weak-typed literals (never captured consts in Pallas)
_C1 = 0x85EB_CA6B
_C2 = 0xC2B2_AE35
_GOLDEN = 0x9E37_79B9


def fmix32(h):
    """murmur3 32-bit finalizer — full avalanche."""
    h = jnp.asarray(h, jnp.uint32)
    h ^= h >> 16
    h *= jnp.uint32(_C1)
    h ^= h >> 13
    h *= jnp.uint32(_C2)
    h ^= h >> 16
    return h


def hash_bits(seed, a, b):
    """uint32 hash of (seed, a, b); broadcasts like jnp ops."""
    seed = jnp.asarray(seed, jnp.uint32)
    a = jnp.asarray(a, jnp.uint32)
    b = jnp.asarray(b, jnp.uint32)
    h = fmix32(seed ^ jnp.uint32(_GOLDEN))
    h = fmix32(h ^ (a * jnp.uint32(_C1) + jnp.uint32(_GOLDEN)))
    h = fmix32(h ^ (b * jnp.uint32(_C2) + jnp.uint32(_GOLDEN)))
    return h


def uniform01(seed, a, b):
    """Uniform in (0, 1): top 24 bits of the hash, offset to avoid exact 0."""
    return unit_interval(hash_bits(seed, a, b) >> 8)


def unit_interval(bits):
    """24-bit integers → (bits + 0.5) / 2**24, strictly inside (0, 1)."""
    # through int32: Mosaic has no uint32 -> float32 cast; exact, since the
    # bits are below 2**24
    u = (bits.astype(jnp.int32).astype(jnp.float32) + 0.5) * jnp.float32(
        1.0 / (1 << 24))
    # the top value rounds to exactly 1.0 in float32 (2**24 - 0.5 is not
    # representable), where gumbel() would return +inf: one draw in 2**24,
    # a topic that wins regardless of the posterior about once in 170
    # tokens at K = 10⁵
    return jnp.minimum(u, jnp.float32(1.0 - 2.0 ** -24))


def gumbel(seed, a, b):
    """Standard Gumbel noise: -log(-log(U))."""
    u = uniform01(seed, a, b)
    return -jnp.log(-jnp.log(u))
