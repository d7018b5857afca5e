"""Sparsity-aware alias-table / Metropolis–Hastings Pallas TPU kernels.

Two kernels (DESIGN.md §9):

``alias_build_pallas`` — Walker/Vose alias-table construction, one vocab row
per grid step. The small/large partition order and the mean-1 normalization
are precomputed OUTSIDE the kernel (``ops._prepare``), so the kernel is the
pure K-step sweep: scalar carry (small ptr, large ptr, active large, pending
demotion), one finalized slot per step, dynamic single-element stores into
the (prob, alias) row. O(K) per row, amortized across the rebuild cadence.

``mh_resample_pallas`` — the per-token MH probe loop. Grid over token tiles;
the token metadata (w, d, z, uid) rides in scalar-prefetch SMEM so the kernel
can index VMEM tables per token. Per token it draws from the stale word
alias table / the sparse doc pairs, and runs ``n_mh`` accept/reject steps
against the true collapsed posterior ratio — reading O(k_d + n_mh) table
entries per token instead of streaming K-wide VMEM tiles like the dense
``kernels/gibbs`` plane scan.

Capacity note: tables and count planes are bound as whole-array VMEM blocks
(~16 MB/core → rows·K ≲ 1M table entries per shard), and the scalar gathers
at dynamic lane offsets (``_get``) plus the MH kernel's ``cumsum`` are refused
by the TPU compiler. So these kernels run only under ``interpret=True`` (CI,
bitwise against ``ref.py``) or when forced; on TPU the dispatch in ``ops.py``
runs ``ref.py`` under XLA (``ops.TPU_MODE``).

HBM-resident tables (DESIGN.md §10, design gated on TPU): word-sharded
model parallelism already divides rows·K per device by the slice count P, and
``ops.mh_resample``'s by-word probe batching sorts each tile's probes so
same-word runs share row fetches. The remaining step for shards that still
exceed VMEM is binding ``wq``/``wp``/``wa``/``phi`` with
``pltpu.MemorySpace.ANY`` (HBM) and double-buffering row windows via
``pltpu.make_async_copy`` keyed on the scalar-prefetched, sorted word ids —
a per-tile gather of the O(distinct words) rows the tile touches instead of
the whole table. That variant changes only BlockSpecs + copy scheduling, not
the per-token arithmetic, so the bitwise contract with ``ref.py`` (and hence
the shard conformance suite) is unchanged; it stays behind the ``force``
dispatch until TPU time is available because interpret mode cannot validate
DMA overlap.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.core import prng


def _get(ref, i, j):
    """Scalar gather ref[i, j] with traced indices."""
    return ref[pl.ds(i, 1), pl.ds(j, 1)][0, 0]


# --------------------------------------------------------------- build ------


def _alias_build_kernel(ns_ref, wn_ref, order_ref, prob_ref, alias_ref,
                        *, n_topics: int):
    """One row's Walker sweep — the same branch-free slot/value algebra as
    ``ref._sweep_step`` (keep edits mirrored)."""
    K = n_topics
    r = pl.program_id(0)
    ns = ns_ref[r]

    def wn_at(idx):
        return _get(wn_ref, 0, idx)

    def order_at(idx):
        return _get(order_ref, 0, idx)

    has_l = ns < K
    first = order_at(jnp.minimum(ns, K - 1))
    cur0 = jnp.where(has_l, first, -1)
    curw0 = jnp.where(has_l, wn_at(first), 0.0)

    def step(_, carry):
        i, j, cur, curw, pend, pendw = carry
        has_pend = pend >= 0
        has_small = i < ns
        oi = order_at(jnp.minimum(i, K - 1))
        s_slot = jnp.where(has_pend, pend, jnp.where(has_small, oi, -1))
        sw = jnp.where(has_pend, pendw,
                       jnp.where(has_small, wn_at(oi), 0.0))
        i2 = jnp.where(jnp.logical_and(~has_pend, has_small), i + 1, i)

        use_small = jnp.logical_and(s_slot >= 0, cur >= 0)
        slot = jnp.where(s_slot >= 0, s_slot, cur)
        val = jnp.where(use_small, jnp.clip(sw, 0.0, 1.0), 1.0)
        ali = jnp.where(use_small, cur, slot)
        do_write = slot >= 0
        slot_safe = jnp.maximum(slot, 0)
        old_p = _get(prob_ref, 0, slot_safe)
        old_a = _get(alias_ref, 0, slot_safe)
        prob_ref[pl.ds(0, 1), pl.ds(slot_safe, 1)] = jnp.where(
            do_write, val, old_p).reshape(1, 1)
        alias_ref[pl.ds(0, 1), pl.ds(slot_safe, 1)] = jnp.where(
            do_write, ali, old_a).reshape(1, 1)

        curw2 = jnp.where(use_small, curw - (1.0 - sw), curw)
        demote = jnp.logical_and(use_small, curw2 < 1.0)
        advance = jnp.logical_or(
            demote, jnp.logical_and(s_slot < 0, cur >= 0))
        pend2 = jnp.where(demote, cur, -1)
        pendw2 = jnp.where(demote, curw2, 0.0)
        nl = ns + j
        has_next = nl < K
        onl = order_at(jnp.minimum(nl, K - 1))
        cur2 = jnp.where(advance, jnp.where(has_next, onl, -1), cur)
        curw3 = jnp.where(advance,
                          jnp.where(has_next, wn_at(onl), 0.0), curw2)
        j2 = jnp.where(advance, j + 1, j)
        return (i2, j2, cur2, curw3, pend2, pendw2)

    jax.lax.fori_loop(
        0, K, step,
        (jnp.int32(0), jnp.int32(1), cur0, curw0, jnp.int32(-1),
         jnp.float32(0.0)))


@functools.partial(jax.jit, static_argnames=("interpret",))
def alias_build_pallas(wn, order, ns, interpret: bool = False):
    """wn [R, K] f32 mean-1 rows, order [R, K] int32, ns [R] int32 (from
    ``ops._prepare``) → (prob [R, K] f32, alias [R, K] int32)."""
    R, K = wn.shape
    row = lambda i, *_: (i, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(R,),
        in_specs=[
            pl.BlockSpec((1, K), row),
            pl.BlockSpec((1, K), row),
        ],
        out_specs=[
            pl.BlockSpec((1, K), row),
            pl.BlockSpec((1, K), row),
        ],
    )
    return pl.pallas_call(
        functools.partial(_alias_build_kernel, n_topics=K),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((R, K), jnp.float32),
                   jax.ShapeDtypeStruct((R, K), jnp.int32)],
        interpret=interpret,
    )(ns, wn, order)


# --------------------------------------------------------------- probe ------


def _mh_kernel(
    # scalar prefetch (SMEM)
    w_s, d_s, z_s, uid_s, meta_s, seed_s,
    # VMEM tables / counts
    phi_ref,     # [rows, K] int32 live counts
    psi_ref,     # [1, K] int32
    dt_ref,      # [D, cap] int32 sparse Θ topics (-1 pad)
    dc_ref,      # [D, cap] int32 sparse Θ counts
    wq_ref,      # [rows, K] f32 stale proposal weights
    wp_ref,      # [rows, K] f32 alias probs
    wa_ref,      # [rows, K] int32 alias indices
    alpha_ref,   # [1, K] f32
    ap_ref,      # [1, K] f32
    aa_ref,      # [1, K] int32
    # output
    out_ref,     # [block_t, 1] int32
    *,
    block_t: int,
    n_mh: int,
    n_topics: int,
):
    K = n_topics
    pid = pl.program_id(0)
    beta = meta_s[0]
    vb = meta_s[1]
    asum = meta_s[2]
    seed2 = seed_s[0]

    def token(i, _):
        t = pid * block_t + i
        wt = w_s[t]
        dt = d_s[t]
        z0 = z_s[t]
        ut = uid_s[t]
        trow = dt_ref[pl.ds(dt, 1), :]                       # [1, cap]
        crow = dc_ref[pl.ds(dt, 1), :].astype(jnp.float32)   # [1, cap]
        total = jnp.sum(crow)

        def lookup(k):
            return jnp.sum(jnp.where(trow == k, crow, 0.0))

        def p_of(k):
            ex = (k == z0).astype(jnp.float32)
            ph = _get(phi_ref, wt, k).astype(jnp.float32) - ex
            ps = _get(psi_ref, 0, k).astype(jnp.float32) - ex
            th = lookup(k) - ex
            return (ph + beta) * (th + alpha_at(k)) / (ps + vb)

        def alpha_at(k):
            return _get(alpha_ref, 0, k)

        s = z0
        p_s = p_of(s)
        for step in range(n_mh):
            b0 = jnp.uint32(4 * step)
            u_draw = prng.uniform01(seed2, ut, b0 + jnp.uint32(1))
            u_coin = prng.uniform01(seed2, ut, b0 + jnp.uint32(2))
            if step % 2 == 0:
                # doc proposal: q_d(k) ∝ n_dk + α_k
                u_mix = prng.uniform01(seed2, ut, b0)
                r = u_draw * total
                cum = jnp.cumsum(crow, axis=1)
                prev = cum - crow
                mask = (cum > r) & (prev <= r) & (crow > 0.0)
                t_cnt = jnp.sum(jnp.where(mask, trow, 0))
                t_cnt = jnp.where(jnp.any(mask), t_cnt, s)
                jk = jnp.minimum((u_draw * K).astype(jnp.int32), K - 1)
                t_al = jnp.where(u_coin < _get(ap_ref, 0, jk), jk,
                                 _get(aa_ref, 0, jk))
                use_counts = u_mix * (total + asum) < total
                t_prop = jnp.where(use_counts, t_cnt, t_al).astype(jnp.int32)
                q_s = lookup(s) + alpha_at(s)
                q_t = lookup(t_prop) + alpha_at(t_prop)
            else:
                # word proposal: stale alias table, O(1) probes
                jk = jnp.minimum((u_draw * K).astype(jnp.int32), K - 1)
                t_prop = jnp.where(u_coin < _get(wp_ref, wt, jk), jk,
                                   _get(wa_ref, wt, jk))
                q_s = _get(wq_ref, wt, s)
                q_t = _get(wq_ref, wt, t_prop)
            u_acc = prng.uniform01(seed2, ut, b0 + jnp.uint32(3))
            p_t = p_of(t_prop)
            ratio = (p_t * q_s) / (p_s * q_t)
            acc = u_acc < ratio
            s = jnp.where(acc, t_prop, s)
            p_s = jnp.where(acc, p_t, p_s)
        out_ref[pl.ds(i, 1), pl.ds(0, 1)] = s.astype(jnp.int32).reshape(1, 1)
        return _

    jax.lax.fori_loop(0, block_t, token, 0)


@functools.partial(
    jax.jit, static_argnames=("vocab_size", "n_mh", "block_t", "interpret"))
def mh_resample_pallas(
    phi, psi, doc_topic, doc_count, wq, wp, wa, alpha, ap, aa,
    w, d, z, uid, seed2, beta, alpha_sum,
    vocab_size: int, n_mh: int, block_t: int = 8, interpret: bool = False,
):
    """Same contract as ``ref.mh_resample_ref`` (z_new [T] int32)."""
    T = w.shape[0]
    K = psi.shape[0]
    t_pad = (-T) % block_t
    pad1 = lambda x: jnp.pad(x, (0, t_pad))
    w_p, d_p, z_p = pad1(w), pad1(d), pad1(z)
    uid_p = pad1(uid)
    meta = jnp.stack([jnp.float32(beta),
                      jnp.float32(vocab_size) * jnp.float32(beta),
                      jnp.float32(alpha_sum)])
    seed_arr = jnp.asarray(seed2, jnp.uint32).reshape(1)
    Tp = T + t_pad

    full = lambda i, *_: (0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(Tp // block_t,),
        in_specs=[
            pl.BlockSpec(phi.shape, full),
            pl.BlockSpec((1, K), full),
            pl.BlockSpec(doc_topic.shape, full),
            pl.BlockSpec(doc_count.shape, full),
            pl.BlockSpec(wq.shape, full),
            pl.BlockSpec(wp.shape, full),
            pl.BlockSpec(wa.shape, full),
            pl.BlockSpec((1, K), full),
            pl.BlockSpec((1, K), full),
            pl.BlockSpec((1, K), full),
        ],
        out_specs=pl.BlockSpec((block_t, 1), lambda i, *_: (i, 0)),
    )
    out = pl.pallas_call(
        functools.partial(_mh_kernel, block_t=block_t, n_mh=n_mh,
                          n_topics=K),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Tp, 1), jnp.int32),
        interpret=interpret,
    )(w_p, d_p, z_p, uid_p, meta, seed_arr,
      phi, psi.reshape(1, K), doc_topic, doc_count, wq, wp, wa,
      alpha.reshape(1, K), ap.reshape(1, K), aa.reshape(1, K))
    return out[:T, 0]
