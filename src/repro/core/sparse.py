"""Sparse doc-topic bookkeeping for the alias-MH sampler (DESIGN.md §9).

The dense sampler rebuilds Θ as a [docs, K] plane; at K = 10⁵ that plane IS
the per-token O(K) cost. Here Θ lives as **capped (topic, count) pairs** —
``topic [D, cap] int32`` (−1 = empty slot) + ``count [D, cap] int32`` — the
jit-static-shape equivalent of a CSR ``[doc_ptr, topic, count]`` layout: row
d's non-empty slots are document d's nonzero topics, and ``cap`` (≥ max
distinct topics per doc, i.e. ≥ max doc length — see :func:`suggest_cap`) is
the static row pitch standing in for the ragged ``doc_ptr`` offsets. Per-token
sampler cost touching Θ is O(cap) = O(k_d), never O(K).

Three vectorized primitives (no per-token host loops, all jit-safe):

* :func:`pairs_from_assignments` — build pairs from (d, z) in one
  sort + segment-sum pass (O(T log T));
* :func:`apply_deltas` — the incremental z-flip update: net per-(doc, topic)
  deltas are aggregated the same way, matched against existing slots, and
  new topics claim empty (−1) slots by per-doc allocation rank;
* :func:`sample_block_mh` — the alias-MH mirror of
  ``core/gibbs.py:sample_block``: same snapshot semantics (all tokens see
  block-start counts with exact ¬ivd self-exclusion; deltas land at block
  end), but the per-token draw is ``kernels/alias``'s O(k_d + n_mh) probe
  instead of the O(K) plane scan.

Table builders (:func:`make_word_tables`, :func:`make_alpha_table`) produce
the stale proposal tables the MH probe corrects against; the Trainer rebuilds
them at aggregation boundaries from merged Φ. Each build is a program of its
own name (``build_alias_word``, ``build_alias_alpha``,
``build_alias_alpha_prep``), so a device trace tells them apart. The word
tables are built per shard, in Φ's own layout on the mesh: each chip builds
the rows it holds. When both are due, α's row is normalized and partitioned
in a program of its own (:func:`prepare_alpha_table`) and swept as one more
row of the word build, on every chip.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.kernels.alias import ops as alias_ops


class AliasTables(NamedTuple):
    """Stale proposal state for one vocab shard: word tables + α table."""

    wq: jax.Array   # [rows, K] f32 — proposal weights (ñ_wk+β)/(ψ̃_k+Vβ)
    wp: jax.Array   # [rows, K] f32 — Walker probs
    wa: jax.Array   # [rows, K] int32 — Walker alias indices
    ap: jax.Array   # [K] f32 — α-table probs
    aa: jax.Array   # [K] int32 — α-table alias indices


def suggest_cap(doc_lengths, n_topics: int) -> int:
    """Static pair-row pitch: distinct topics per doc never exceeds the doc's
    token count (nor K), so ``min(K, max_len)`` is a hard bound — overflow is
    impossible by construction, not by runtime check."""
    import numpy as np

    longest = int(np.max(np.asarray(doc_lengths))) if len(doc_lengths) else 1
    return max(1, min(int(n_topics), longest))


# ------------------------------------------------- sorted-segment helper ----


def segment_totals(d, k, delta, n_docs: int):
    """Aggregate per-(d, k) net deltas via one lexsort.

    Returns (ds, ks, tot, active): sorted doc/topic ids, the inclusive
    running total within each (d, k) segment, and an ``active`` mask that is
    True exactly at each segment's END position when the net total is nonzero
    and the doc id is a real row (< n_docs; the ``n_docs`` sentinel parks
    masked-out entries past every real segment).
    """
    order = jnp.lexsort((k, d))
    ds = d[order]
    ks = k[order]
    dl = delta[order]
    n = ds.shape[0]
    idx = jnp.arange(n)
    new_seg = jnp.concatenate(
        [jnp.ones((1,), bool), (ds[1:] != ds[:-1]) | (ks[1:] != ks[:-1])])
    cum = jnp.cumsum(dl)
    before = cum - dl
    seg_start = jax.lax.cummax(jnp.where(new_seg, idx, 0))
    tot = cum - before[seg_start]
    is_end = jnp.concatenate([new_seg[1:], jnp.ones((1,), bool)])
    active = is_end & (tot != 0) & (ds < n_docs)
    return ds, ks, tot, active


def _doc_rank(ds, flag):
    """Ordinal of each flagged position among same-doc flagged positions
    (ds sorted by doc). Used for first-build slot placement and empty-slot
    allocation ranks."""
    n = ds.shape[0]
    idx = jnp.arange(n)
    new_doc = jnp.concatenate([jnp.ones((1,), bool), ds[1:] != ds[:-1]])
    inc = flag.astype(jnp.int32)
    before = jnp.cumsum(inc) - inc
    doc_start = jax.lax.cummax(jnp.where(new_doc, idx, 0))
    return before - before[doc_start]


# ----------------------------------------------------------- pair layout ----


@partial(jax.jit, static_argnames=("n_docs", "cap"))
def pairs_from_assignments(d, z, valid, n_docs: int, cap: int):
    """Build capped (topic, count) pairs from token assignments.

    d/z [T] int32, valid [T] bool → (topic [n_docs, cap] int32 with −1
    padding, count [n_docs, cap] int32). Slot order within a row is topic
    order (the segments come out of a lexsort).
    """
    d_s = jnp.where(valid, d, n_docs)
    ds, ks, tot, active = segment_totals(
        d_s, z, valid.astype(jnp.int32), n_docs)
    rank = _doc_rank(ds, active)
    row = jnp.where(active, ds, n_docs)
    col = jnp.where(active, rank, 0)
    topic = jnp.full((n_docs + 1, cap), -1, jnp.int32)
    count = jnp.zeros((n_docs + 1, cap), jnp.int32)
    topic = topic.at[row, col].set(ks.astype(jnp.int32), mode="drop")
    count = count.at[row, col].set(tot.astype(jnp.int32), mode="drop")
    # scratch row may hold one stray write from the masked entries; real rows
    # (and the sampler) never see it
    return topic[:n_docs], count[:n_docs]


@partial(jax.jit, static_argnames=("n_topics",))
def pairs_to_dense(topic, count, n_topics: int):
    """[D, cap] pairs → dense [D, K] doc-topic counts (tests/oracles)."""
    D, cap = topic.shape
    rows = jnp.broadcast_to(jnp.arange(D)[:, None], (D, cap))
    col = jnp.maximum(topic, 0)
    val = jnp.where(topic >= 0, count, 0)
    return jnp.zeros((D, n_topics), jnp.int32).at[rows, col].add(val)


def pairs_lookup(topic, count, d, k):
    """n_dk gathered from pairs for token vectors d, k [T] → [T] int32."""
    rows_t = topic[d]
    rows_c = count[d]
    return jnp.sum(jnp.where(rows_t == k[:, None], rows_c, 0), axis=1)


@jax.jit
def apply_deltas(topic, count, d, z_old, z_new, valid):
    """Incremental pair update for one block's z-flips.

    Aggregates the block's (−1 @ (d, z_old), +1 @ (d, z_new)) deltas per
    (doc, topic) and applies them in TWO passes: net-negative deltas first
    (they always match an existing slot; slots whose count reaches zero are
    freed to −1), then net-positive deltas against the freed rows (matching
    slots add in place; first-seen topics claim empty slots by per-doc
    allocation rank, which keeps concurrent allocations collision-free).
    The ordering matters: a row at full capacity that loses one topic and
    gains another in the same block must free before it allocates — a
    single-pass update would see the pre-free row and drop the gain.
    Requires cap headroom (guaranteed when cap ≥ max doc length: the
    post-flip distinct-topic count never exceeds the doc's token count).
    """
    D, cap = topic.shape
    changed = valid & (z_old != z_new)
    act2 = jnp.concatenate([changed, changed])
    dd = jnp.where(act2, jnp.concatenate([d, d]), D)
    kk = jnp.concatenate([z_old, z_new])
    sgn = jnp.concatenate(
        [-changed.astype(jnp.int32), changed.astype(jnp.int32)])
    ds, ks, tot, active = segment_totals(dd, kk, sgn, D)
    row_ix = jnp.where(ds < D, ds, 0)

    # ---- pass 1: net-negative deltas; free zeroed slots ----------------
    neg = active & (tot < 0)
    rows_t = topic[row_ix]                                    # [N, cap]
    match = (rows_t == ks[:, None]) & (rows_t >= 0)
    ok = neg & jnp.any(match, axis=1)
    slot = jnp.argmax(match, axis=1)
    row = jnp.where(ok, ds, D)
    count_p = jnp.concatenate([count, jnp.zeros((1, cap), jnp.int32)])
    count_p = count_p.at[row, slot].add(
        jnp.where(ok, tot, 0).astype(jnp.int32))
    count = count_p[:D]
    topic = jnp.where(count == 0, -1, topic)

    # ---- pass 2: net-positive deltas; match or allocate ----------------
    pos = active & (tot > 0)
    rows_t = topic[row_ix]
    match = (rows_t == ks[:, None]) & (rows_t >= 0)
    found = jnp.any(match, axis=1)
    slot_m = jnp.argmax(match, axis=1)
    is_alloc = pos & ~found
    rank = _doc_rank(ds, is_alloc)
    empty = rows_t < 0
    ecum = jnp.cumsum(empty, axis=1)
    tgt = empty & (ecum == (rank + 1)[:, None])
    slot_a = jnp.argmax(tgt, axis=1)
    has_slot = jnp.any(tgt, axis=1)

    ok = pos & (found | (is_alloc & has_slot))
    slot = jnp.where(found, slot_m, slot_a)
    row = jnp.where(ok, ds, D)
    topic_p = jnp.concatenate([topic, jnp.full((1, cap), -1, jnp.int32)])
    count_p = jnp.concatenate([count, jnp.zeros((1, cap), jnp.int32)])
    alloc_row = jnp.where(ok & is_alloc, ds, D)
    topic_p = topic_p.at[alloc_row, slot].set(ks.astype(jnp.int32))
    count_p = count_p.at[row, slot].add(
        jnp.where(ok, tot, 0).astype(jnp.int32))
    # positive deltas cannot zero a slot — no second free pass needed
    return topic_p[:D], count_p[:D]


# --------------------------------------------------------- table builders ---


def _table_build(name: str):
    """``alias_ops.alias_tables`` as a program named ``name``: the traced
    function of ``alias_ops.build_alias``, so the same HLO, under a name of
    its own. Given ``row``, a prepared row (``prepare_alpha_table``), it is
    ``alias_ops.alias_tables_with``: that row's tables come out too.

    Given a ``mesh`` and the ``spec`` of the weights' layout on it, the
    build runs under ``shard_map`` with that spec in and out: each chip
    normalises, partitions and sweeps only the rows it holds (and ``row``,
    replicated), and the tables come out in the weights' layout. Without,
    it is one program over the whole array, and the partitioner gathers
    every row of a sharded input onto every chip (the row loops of the
    build cannot be split)."""
    def build(weights, row=None, *, mesh=None, spec=None):
        if row is None:
            fn, args = alias_ops.alias_tables, (weights,)
            in_specs, out_specs = (spec,), (spec, spec)
        else:
            fn, args = alias_ops.alias_tables_with, (weights, row)
            in_specs, out_specs = (spec, P()), (spec, spec, P(), P())
        if mesh is None:
            return fn(*args)
        return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)(*args)

    build.__name__ = build.__qualname__ = name
    return jax.jit(build, static_argnames=("mesh", "spec"))


build_alias_word = _table_build("build_alias_word")
build_alias_alpha = _table_build("build_alias_alpha")


@jax.jit
def build_alias_alpha_prep(weights):
    """``alias_ops.prepare`` of α's [1, K] row, in a program whose input is
    α alone: the row total rounds as in ``build_alias_alpha``, whatever
    build its stream then joins."""
    return alias_ops.prepare(weights)


def _layout(x):
    """``(mesh, spec)`` of an array laid out on a mesh, else ``(None,
    None)`` (one device, or a tracer inside another program)."""
    sharding = (None if isinstance(x, jax.core.Tracer)
                else getattr(x, "sharding", None))
    if isinstance(sharding, jax.sharding.NamedSharding):
        return sharding.mesh, sharding.spec
    return None, None


def make_word_tables(phi, psi, beta, vocab_size: int,
                     alpha_prep=None) -> Tuple[jax.Array, ...]:
    """Stale word-proposal tables from a Φ snapshot.

    phi [..., rows, K] int32, psi [..., K] int32 (leading pod/shard dims ride
    along) → (wq, wp, wa) with wq = (φ+β)/(ψ+Vβ) — the LightLDA word
    proposal including its denominator, so staleness covers both factors.
    A Φ laid out on a mesh is built per shard in that layout: each chip
    builds the tables of its own rows (``_table_build``).

    Given ``alpha_prep`` (:func:`prepare_alpha_table`), α's row is swept with
    the word rows (replicated on Φ's mesh), and the result is
    (wq, wp, wa, ap, aa), ``(ap, aa)`` bit for bit :func:`make_alpha_table`'s.
    """
    mesh, spec = _layout(phi)
    beta = jnp.float32(beta)
    psi_b = psi.astype(jnp.float32)
    while psi_b.ndim < phi.ndim:
        psi_b = jnp.expand_dims(psi_b, -2)
    wq = (phi.astype(jnp.float32) + beta) / (
        psi_b + jnp.float32(vocab_size) * beta)
    if alpha_prep is not None and mesh is not None:
        alpha_prep = jax.device_put(alpha_prep, NamedSharding(mesh, P()))
    return (wq,) + tuple(build_alias_word(wq, alpha_prep, mesh=mesh,
                                          spec=spec))


def prepare_alpha_table(alpha):
    """α's row normalized and partitioned for the Walker sweep: the stream
    :func:`make_word_tables` sweeps beside the word rows."""
    return build_alias_alpha_prep(alpha[None, :].astype(jnp.float32))


def make_alpha_table(alpha):
    """α alias table (ap [K] f32, aa [K] int32), built alone: for a rebuild
    of α while the word tables stand (one row's sweep on its own)."""
    ap, aa = build_alias_alpha(alpha[None, :].astype(jnp.float32))
    return ap[0], aa[0]


def make_tables(phi, psi, alpha, beta, vocab_size: int) -> AliasTables:
    return AliasTables(*make_word_tables(phi, psi, beta, vocab_size,
                                         prepare_alpha_table(alpha)))


# ------------------------------------------------------------ block MH ------


@partial(jax.jit, static_argnames=("vocab_size", "n_mh"))
def sample_block_mh(
    phi: jax.Array,          # [rows, K] int32
    psi: jax.Array,          # [K] int32
    doc_topic: jax.Array,    # [D, cap] int32 (−1 pad)
    doc_count: jax.Array,    # [D, cap] int32
    z: jax.Array,            # [T] int32 current assignments
    w: jax.Array,            # [T] int32 word ids (rows-local)
    dloc: jax.Array,         # [T] int32 doc ids local to the pair rows
    token_uid: jax.Array,    # [T] uint32 global token uids
    alpha: jax.Array,        # [K] f32
    beta: jax.Array,         # [] f32
    seed,                    # uint32 scalar
    vocab_size: int,
    tables: AliasTables,
    n_mh: int = 4,
):
    """One alias-MH sweep over a token block — ``sample_block``'s sparse
    mirror. Returns (z_new, phi', psi', doc_topic', doc_count')."""
    z_new = alias_ops.mh_resample(
        phi, psi, doc_topic, doc_count, tables.wq, tables.wp, tables.wa,
        alpha, tables.ap, tables.aa, w, dloc, z, token_uid,
        jnp.asarray(seed, jnp.uint32), beta, vocab_size, n_mh)
    one = jnp.ones_like(z)
    phi = phi.at[w, z].add(-one).at[w, z_new].add(one)
    psi = psi.at[z].add(-one).at[z_new].add(one)
    doc_topic, doc_count = apply_deltas(
        doc_topic, doc_count, dloc, z, z_new,
        jnp.ones(z.shape, bool))
    return z_new, phi, psi, doc_topic, doc_count
