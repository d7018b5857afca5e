"""Dispatching wrappers for the alias-table build / MH probe ops.

Same contract as ``kernels/gibbs/ops.py``: ``force`` in {None, "pallas",
"interpret", "ref"}; None defers to the pinned process default
(``repro.kernels.set_kernel_mode``) and then to the backend: both CPU and TPU
run the jnp implementations (``ref.py``), compiled by XLA. On TPU that is
:data:`TPU_MODE`, for the reason given there.

``alias_tables`` normalizes + partitions ONCE here (``_prepare``); both sweep
implementations take their inputs from it: the kernel reads the weights by
slot (wn, order, ns), the ref sweep reads them in stream order
(order, wn_ord, ns) so that it can take them from windows of the stream. Ref
vs kernel agreement is bitwise because only the K-step sweep differs in
execution strategy, never in arithmetic. ``mh_resample``
likewise mixes the sampler seed with a sampler-family salt here (the MH
uniform stream must not collide with the dense path's Gumbel stream at equal
(seed, uid) counters).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro import kernels as kernels_mod
from repro.core import prng
from repro.kernels.alias.kernel import alias_build_pallas, mh_resample_pallas
from repro.kernels.alias.ref import build_alias_ref, mh_resample_ref

# decorrelates the MH uniform stream from the dense sampler's Gumbel stream
MH_SALT = 0x5EED_A11A

# What the alias ops run on a TPU when nothing is forced or pinned. The Pallas
# kernels bind whole [rows, K] tables as VMEM blocks (rows·K ≲ 10⁶ entries,
# against 821 × 10⁵ for one chip's share of the paper deployment) and gather
# single lanes at dynamic offsets, which Mosaic refuses; the MH kernel also
# needs a cumsum that has no TPU lowering. So on TPU the jnp implementations
# run under XLA. The rule is the backend's alone: nothing is caught and
# nothing falls back at run time, and ``force="pallas"`` still reaches the
# kernel (and the compiler's error). The HBM-resident kernel is ROADMAP 1d.
TPU_MODE = "ref"


def dispatch_mode(force: str | None = None) -> str:
    """The implementation the alias ops run for ``force`` on this backend."""
    return kernels_mod.kernel_mode(force, tpu=TPU_MODE)


def _prepare(weights):
    """Mean-1 normalization + stable small/large partition of [R, K] rows.

    Returns (wn, order, wn_ord, ns): ``order`` lists small slots (w < 1) in
    index order, then large slots; ``wn_ord`` = ``wn[order]``, the weights in
    that stream order; ``ns`` is the per-row small count. The Pallas sweep
    reads (wn, order, ns); the ref sweep reads (order, wn_ord, ns), so that it
    can take each step's operands from C-wide windows of the stream instead
    of gathering from the [R, K] rows on every step (``ref.py``).
    """
    K = weights.shape[-1]
    total = jnp.maximum(jnp.sum(weights, axis=-1, keepdims=True),
                        jnp.float32(1e-30))
    wn = (weights * (jnp.float32(K) / total)).astype(jnp.float32)
    is_large = wn >= 1.0
    small = ~is_large
    ns = jnp.sum(small, axis=-1, dtype=jnp.int32)
    # the stable argsort of is_large, from two prefix sums instead of a sort
    # (a K = 10⁵ sort costs the TPU compiler ~15 s and every rebuild a sort)
    pos = jnp.where(small, jnp.cumsum(small, axis=-1, dtype=jnp.int32),
                    ns[:, None] + jnp.cumsum(is_large, axis=-1,
                                             dtype=jnp.int32)) - 1
    slots = jnp.arange(K, dtype=jnp.int32)
    # row by row: one [R, K] scatter costs the TPU compiler ~25 s at
    # R·K ≈ 10⁸, a loop of [K] scatters about 2 s. wn_ord rides the same
    # scatter indices rather than a gather pass of its own over [R, K].
    order, wn_ord = jax.lax.map(
        lambda a: (jnp.zeros_like(a[0]).at[a[0]].set(slots),
                   jnp.zeros_like(a[1]).at[a[0]].set(a[1])), (pos, wn))
    return wn, order, wn_ord, ns


def alias_tables(weights, *, force: str | None = None):
    """Batched Walker alias tables over the trailing axis (traceable; the
    program is :func:`build_alias`).

    weights [..., K] nonneg f32 → (prob [..., K] f32, alias [..., K] int32)
    with the table identity  q(k) = (prob_k + Σ_j (1−prob_j)·1[alias_j = k])/K
    = weights_k / Σ weights  (exactly, up to f32 rounding).
    """
    lead = weights.shape[:-1]
    K = weights.shape[-1]
    wn, order, wn_ord, ns = _prepare(
        weights.reshape(-1, K).astype(jnp.float32))
    mode = dispatch_mode(force)
    if mode == "pallas":
        prob, alias = alias_build_pallas(wn, order, ns)
    elif mode == "interpret":
        prob, alias = alias_build_pallas(wn, order, ns, interpret=True)
    else:
        prob, alias = build_alias_ref(order, wn_ord, ns)
    return prob.reshape(*lead, K), alias.reshape(*lead, K)


@functools.partial(jax.jit, static_argnames=("force",))
def build_alias(weights, *, force: str | None = None):
    """:func:`alias_tables` as a program of its own."""
    return alias_tables(weights, force=force)


def mh_resample(
    phi, psi, doc_topic, doc_count, wq, wp, wa, alpha, ap, aa,
    w, d, z, uid, seed, beta,
    vocab_size: int, n_mh: int, *, force: str | None = None,
    batch_by_word: bool | None = None,
):
    """n_mh alias-MH steps per token; returns z_new [T] int32.

    See ``ref.mh_resample_ref`` for the array contract and the proposal
    cycle. ``seed`` is the raw sweep seed — the MH salt is mixed here.

    ``batch_by_word`` (default: on for the compiled kernel, off for the
    oracles) stable-sorts the token stream by word id before dispatch and
    scatters results back (DESIGN.md §10): same-word probes land in one
    kernel tile, so every ``wq``/``wp``/``wa``/``phi`` row fetched from HBM
    serves a whole run of probes instead of one. The reorder is bitwise-free
    — every token samples independently against the round-start snapshots
    with its own uid-keyed counter stream — which the shard conformance
    suite asserts.
    """
    seed2 = prng.fmix32(jnp.asarray(seed, jnp.uint32)
                        ^ jnp.uint32(MH_SALT))
    alpha_sum = jnp.sum(alpha).astype(jnp.float32)
    mode = dispatch_mode(force)
    if batch_by_word is None:
        batch_by_word = mode == "pallas"
    order = None
    if batch_by_word:
        order = jnp.argsort(w, stable=True).astype(jnp.int32)
        w, d, z, uid = w[order], d[order], z[order], uid[order]
    if mode == "pallas":
        out = mh_resample_pallas(
            phi, psi, doc_topic, doc_count, wq, wp, wa, alpha, ap, aa,
            w, d, z, uid, seed2, beta, alpha_sum, vocab_size, n_mh)
    elif mode == "interpret":
        out = mh_resample_pallas(
            phi, psi, doc_topic, doc_count, wq, wp, wa, alpha, ap, aa,
            w, d, z, uid, seed2, beta, alpha_sum, vocab_size, n_mh,
            interpret=True)
    else:
        out = mh_resample_ref(
            phi, psi, doc_topic, doc_count, wq, wp, wa, alpha, ap, aa,
            w, d, z, uid, seed2, jnp.float32(beta), alpha_sum, vocab_size,
            n_mh)
    if order is not None:
        out = jnp.zeros_like(out).at[order].set(out)
    return out
