"""Peacock layer-1 on a TPU mesh: the diagonal-ring distributed Gibbs sampler.

Mapping (DESIGN.md §3): every device on the flattened ("data","model") ring is
simultaneously one Peacock *data server* (it owns one document shard's token
stack) and one *sampling server* (it owns one vocabulary shard of Φ). The
M×M block-diagonal schedule becomes a **ring rotation**:

  round r: device v samples the sub-block B_{(v-r) mod M, v} — the tokens of
  data shard (v-r) whose words live in vocab shard v — against its resident
  Φ_v, then forwards the whole visiting stack one hop around the ring.

Properties preserved from the paper:
  * lock-freedom by construction — Φ_v has exactly one owner; no replicas of Φ
    are ever written concurrently inside a pod;
  * sampler-side freshness — Φ_v sees data shard i's updates before sampling
    data shard i+1's block (the per-diagonal serialization of Fig. 2);
  * relaxed Ψ synchronization — Ψ deltas are psum'd once per segment (Fig. 4),
    not per diagonal;
  * static load balance — weighted round-robin vocab placement makes every
    (data, vocab) sub-block ≈ equal tokens, so one static capacity suffices
    (the shapes ARE the load-balance proof);
  * pipeline — within a round the sub-block is sampled in T packages of L
    tokens (lax.scan) and the next hop's collective-permute is issued *before*
    sampling starts, so XLA overlaps transfer with compute (§3.1.2).

Θ is never stored globally (SparseLDA): each visiting stack carries its z, and
the doc-topic counts for the visiting shard's documents are rebuilt locally per
round.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.data.corpus import ShardedCorpus
from repro.dist import sharding as shd
from repro.dist.sharding import (RING_AXES, flat_ring_index, ring_perm,
                                 ring_size)
from repro.kernels.gibbs import ops as gibbs_ops


def prng_gumbel(seed, uid, n_topics: int):
    from repro.core import prng

    return prng.gumbel(jnp.asarray(seed, jnp.uint32),
                       uid.astype(jnp.uint32)[:, None],
                       jnp.arange(n_topics, dtype=jnp.uint32)[None, :])


@dataclasses.dataclass(frozen=True)
class RingConfig:
    n_topics: int
    vocab_size: int            # global V (for the V*beta smoothing term)
    rows_per_shard: int
    docs_per_shard: int
    cap: int                   # tokens per (data, vocab) sub-block
    package_len: int           # L — pipeline package size (§3.1.2)
    n_rounds: int              # = ring size M
    use_kernel: bool = False
    model_shards: int = 1      # P — word-sharded model parallelism (§10):
                               # P > 1 rotates the ring over "data" only and
                               # keeps Φ row slices resident on "model";
                               # rows_per_shard/cap stay the TOTAL per-coarse-
                               # shard sizes (P·rpm / P·capb)
    # ---- sampler family (DESIGN.md §9) -----------------------------------
    sampler: str = "dense"     # "dense" = exact [T, K] plane scan;
                               # "alias" = sparsity-aware alias-table MH
                               # (O(k_d + n_mh) per token, stale proposal
                               # tables passed as extra epoch args)
    n_mh: int = 4              # MH steps per token (alias sampler)
    doc_topic_cap: int = 0     # pair-row pitch for sparse Θ (0 → n_topics);
                               # must be ≥ max distinct topics per doc
                               # (sparse.suggest_cap)
    # §Perf hillclimb knobs (EXPERIMENTS.md §Perf / peacock-lda):
    theta_dtype: Any = jnp.int32   # int8 → 4× less Θ-rebuild traffic (query
                                   # docs never exceed 127 repeats of a topic)
    column_exclusion: bool = False # ¬ivd via per-token column scatters instead
                                   # of materialized one-hot [cap, K] planes
    small_theta: bool = False      # rebuild Θ only for the ≤cap docs actually
                                   # sampled this round ([cap+1, K] instead of
                                   # [docs_per_shard, K]) — also removes the
                                   # Θ-size bound on segment size


def _sample_subblock(phi, psi, theta, w, d, z, uid, alpha, beta, seed, cfg: RingConfig):
    """Sample one sub-block in packages of L tokens (the pipeline inner loop).

    phi [rows, K] int32 (THIS device's vocab shard), psi [K] int32, theta
    [docs_per_shard, K] int32; w/d/z/uid [cap]. Sentinels (w < 0) are skipped via
    masked count updates. Returns updated (phi, psi, theta, z).
    """
    K = cfg.n_topics
    L = cfg.package_len
    n_pkg = cfg.cap // L
    wp = w.reshape(n_pkg, L)
    dp = d.reshape(n_pkg, L)
    zp = z.reshape(n_pkg, L)
    up = uid.reshape(n_pkg, L)

    def package(carry, xs):
        phi, psi, theta = carry
        w, d, z, uid = xs
        valid = w >= 0
        w_s = jnp.where(valid, w, 0)
        d_s = jnp.where(valid, d, 0)
        rows = jnp.arange(w.shape[0])
        if cfg.column_exclusion:
            # ¬ivd as three per-token column scatters — no one-hot planes
            phi_rows = phi[w_s].astype(jnp.float32).at[rows, z].add(-1.0)
            theta_rows = theta[d_s].astype(jnp.float32).at[rows, z].add(-1.0)
            vb = cfg.vocab_size * beta
            psi_z = psi[z].astype(jnp.float32)
            if cfg.use_kernel:
                # fused Pallas path: psi stays a [K] row; its ¬ivd correction
                # folds into phi's z-column so the kernel streams only two
                # [T, K] planes + two [K] rows and writes [T] ids
                corr = (psi_z + vb) / (psi_z - 1.0 + vb)
                phi_rows = phi_rows.at[rows, z].set(
                    (phi_rows[rows, z] + beta) * corr - beta)
                z_new = gibbs_ops.gibbs_argmax(
                    phi_rows, psi.astype(jnp.float32), theta_rows, alpha,
                    beta, uid.astype(jnp.uint32), jnp.asarray(seed, jnp.uint32),
                    cfg.vocab_size, 1.0, force="pallas")
            else:
                logits = (
                    jnp.log(phi_rows + beta)
                    - jnp.log(psi.astype(jnp.float32)[None, :] + vb)
                    + jnp.log(theta_rows + alpha[None, :])
                )
                # psi self-exclusion touches exactly one column per token
                logits = logits.at[rows, z].add(
                    jnp.log(psi_z + vb) - jnp.log(psi_z - 1.0 + vb))
                g = prng_gumbel(seed, uid, K)
                z_new = jnp.argmax(logits + g, axis=1).astype(jnp.int32)
        else:
            onehot = jax.nn.one_hot(z, K, dtype=jnp.float32)
            phi_rows = phi[w_s].astype(jnp.float32) - onehot
            theta_rows = theta[d_s].astype(jnp.float32) - onehot
            psi_rows = psi.astype(jnp.float32)[None, :] - onehot
            z_new = gibbs_ops.gibbs_argmax(
                phi_rows, psi_rows, theta_rows, alpha, beta,
                uid.astype(jnp.uint32), jnp.asarray(seed, jnp.uint32),
                cfg.vocab_size, 1.0,
                force="pallas" if cfg.use_kernel else None,
            )
        z_new = jnp.where(valid, z_new, z)
        delta = valid.astype(jnp.int32)
        dtheta = valid.astype(theta.dtype)
        phi = phi.at[w_s, z].add(-delta).at[w_s, z_new].add(delta)
        psi = psi.at[z].add(-delta).at[z_new].add(delta)
        theta = theta.at[d_s, z].add(-dtheta).at[d_s, z_new].add(dtheta)
        return (phi, psi, theta), z_new

    (phi, psi, theta), z_new = jax.lax.scan(package, (phi, psi, theta), (wp, dp, zp, up))
    return phi, psi, theta, z_new.reshape(-1)


def _sample_subblock_mh(phi, psi, pairs, w, d, z, uid, alpha, beta, seed,
                        cfg: RingConfig, tables):
    """Alias-MH twin of :func:`_sample_subblock` (DESIGN.md §9).

    Same package pipeline and snapshot semantics, but each token runs
    ``cfg.n_mh`` accept/reject probes against the stale proposal ``tables``
    instead of scanning the [L, K] posterior plane; Θ rides as sparse
    (topic, count) ``pairs`` updated incrementally at package boundaries.
    Returns (phi, psi, pairs, z_new).
    """
    from repro.core import sparse
    from repro.kernels.alias import ops as alias_ops

    L = cfg.package_len
    n_pkg = cfg.cap // L
    wp_ = w.reshape(n_pkg, L)
    dp = d.reshape(n_pkg, L)
    zp = z.reshape(n_pkg, L)
    up = uid.reshape(n_pkg, L)

    def package(carry, xs):
        phi, psi, tp, ct = carry
        w, d, z, uid = xs
        valid = w >= 0
        w_s = jnp.where(valid, w, 0)
        d_s = jnp.where(valid, d, 0)
        with jax.named_scope("mh_resample"):
            z_new = alias_ops.mh_resample(
                phi, psi, tp, ct, tables.wq, tables.wp, tables.wa, alpha,
                tables.ap, tables.aa, w_s, d_s, z, uid.astype(jnp.uint32),
                jnp.asarray(seed, jnp.uint32), beta, cfg.vocab_size,
                cfg.n_mh)
        z_new = jnp.where(valid, z_new, z)
        delta = valid.astype(jnp.int32)
        phi = phi.at[w_s, z].add(-delta).at[w_s, z_new].add(delta)
        psi = psi.at[z].add(-delta).at[z_new].add(delta)
        with jax.named_scope("apply_deltas"):
            tp, ct = sparse.apply_deltas(tp, ct, d_s, z, z_new, valid)
        return (phi, psi, tp, ct), z_new

    (phi, psi, tp, ct), z_new = jax.lax.scan(
        package, (phi, psi) + tuple(pairs), (wp_, dp, zp, up))
    return phi, psi, (tp, ct), z_new.reshape(-1)


def build_epoch_body(mesh, cfg: RingConfig, pod_axis=None):
    """The per-device ring-epoch body — THE one implementation of the round
    loop, shared by the single-pod path (``ring_epoch_parts``) and the
    pod-batched path (``hierarchy.pod_ring_epoch_parts``).

    ``pod_axis=None`` builds the single-pod body (phi [1, rows, K] views);
    naming the pod axis adds one leading singleton dim to every per-device
    view ([1, 1, rows, K] etc.) and decorrelates the sampler seed per pod.

    ``cfg.model_shards = P > 1`` switches to word-sharded model parallelism
    (DESIGN.md §10): the ring rotates over "data" only (M = data axis size),
    "model" holds resident row slices of each coarse Φ shard, and per round
    every device samples just its own bucket of the visiting sub-block
    (capb = cap/P tokens against its rpm = rows/P resident Φ rows). Θ and the
    sparse pairs still need the FULL visiting stack's (doc, z), which is
    gathered with P−1 one-hop rotations around the model axis; Ψ deltas are
    re-synced over "model" every round so round-start snapshots — and
    therefore every sampled z — stay bitwise identical to the replicated
    (P = 1) path, which doubles as the conformance oracle.
    """
    Pm = cfg.model_shards
    if Pm > 1:
        M = int(mesh.shape[RING_AXES[0]])
        assert int(mesh.shape[RING_AXES[1]]) == Pm, \
            "mesh model axis must equal cfg.model_shards"
        assert cfg.rows_per_shard % Pm == 0 and cfg.cap % Pm == 0, \
            "rows/cap must be padded to model_shards (shard_corpus does this)"
        assert cfg.package_len == cfg.cap, \
            "word-sharded rounds sample one package (package_len must = cap)"
        rot_axes = RING_AXES[0]        # stacks rotate over "data" only
        rpm = cfg.rows_per_shard // Pm
        capb = cfg.cap // Pm
        # the per-device sampler sees its own bucket/slice geometry
        cfg_l = dataclasses.replace(cfg, cap=capb, package_len=capb)
        perm_m = ring_perm(Pm)
    else:
        M = ring_size(mesh)
        rot_axes = RING_AXES
        cfg_l = cfg
    assert cfg.n_rounds == M, "ring rounds must equal ring size"
    axis_sizes = (int(mesh.shape[RING_AXES[0]]), int(mesh.shape[RING_AXES[1]]))
    perm = ring_perm(M)
    lead = 2 if pod_axis is not None else 1     # leading singleton view dims
    plead = lead - 1                            # psi has one fewer (replicated
                                                # intra-pod, P() or P(pod))

    alias = cfg.sampler == "alias"

    def epoch(phi, psi, wl, dl, uid, z, alpha, beta, seed, *tables):
        """``tables`` is empty on the dense path; the alias path appends the
        per-shard stale proposal state (wq, wp, wa sharded like phi; ap, aa
        replicated like alpha — rebuilt by the coordinator at aggregation
        boundaries, constant within an epoch)."""
        me = (jax.lax.axis_index(RING_AXES[0]) if Pm > 1
              else flat_ring_index(axis_sizes))
        seed = jnp.asarray(seed, jnp.uint32)
        if pod_axis is not None:
            # pods derive decorrelated seeds so replica samplers do not shadow
            # each other
            pod = jax.lax.axis_index(pod_axis)
            seed = seed + pod.astype(jnp.uint32) * jnp.uint32(0x9E3779B9)
        sq = lambda a: a.reshape(a.shape[lead:])
        phi_l = sq(phi)                               # [rows, K]
        psi_l = psi.reshape(psi.shape[plead:])        # [K]
        if alias:
            from repro.core import sparse as sparse_mod

            wq, wp_t, wa, ap, aa = tables
            tabs = sparse_mod.AliasTables(sq(wq), sq(wp_t), sq(wa), ap, aa)
        stack0 = tuple(sq(a) for a in (wl, dl, uid, z))   # each [M, cap]
        psi0 = psi_l
        # psi becomes device-varying once local deltas accumulate; mark it so
        # (JAX 0.8 varying-manual-axes typing for shard_map scan carries)
        psi_l = jax.lax.pcast(psi_l, RING_AXES, to="varying")

        def model_gather(a, mj):
            """[M, capb] bucket view → [M, P·capb] full sub-blocks, rotating
            the model ring P−1 hops; slot order is bucket-major — exactly the
            replicated stack layout, so downstream scatters are bitwise."""
            buf = jnp.zeros((Pm,) + a.shape, a.dtype)
            buf = jax.lax.dynamic_update_slice(buf, a[None], (mj, 0, 0))
            cur = a
            for h in range(1, Pm):
                with jax.named_scope("ring_exchange"):
                    cur = jax.lax.ppermute(cur, RING_AXES[1], perm_m)
                # hop h delivers the bucket of model rank (mj − h) % P
                buf = jax.lax.dynamic_update_slice(
                    buf, cur[None], ((mj - h) % Pm, 0, 0))
            return jnp.swapaxes(buf, 0, 1).reshape(
                a.shape[0], Pm * a.shape[1])

        def round_fn(carry, r):
            phi_l, psi_l, stack = carry
            wl, dl, uid, z = stack
            psi_r0 = psi_l            # round-start Ψ (model-resync baseline)

            # ship the immutable stack arrays for the NEXT round first — XLA
            # overlaps the collective-permute with this round's sampling
            # (pipeline, §3.1.2); z ships after sampling updates it.
            with jax.named_scope("ring_exchange"):
                nxt = tuple(
                    jax.lax.ppermute(a, rot_axes, perm) for a in (wl, dl, uid)
                )

            # Θ for the visiting shard's documents, rebuilt from the stack's z
            if Pm > 1:
                # every slice holds only its bucket; Θ/pairs need the whole
                # visiting stack's (doc, z) — gather it around the model
                # axis, encoding the valid mask as doc = −1 so two arrays
                # suffice (pads carry doc_local = 0, so max(·, 0) restores
                # the replicated flat views exactly)
                mj = jax.lax.axis_index(RING_AXES[1])
                d_full = model_gather(jnp.where(wl >= 0, dl, -1), mj)
                flat_d_enc = d_full.reshape(-1)
                flat_z = model_gather(z, mj).reshape(-1)
                flat_valid = flat_d_enc >= 0
                flat_d = jnp.maximum(flat_d_enc, 0)
            else:
                flat_d = dl.reshape(-1)
                flat_z = z.reshape(-1)
                flat_valid = wl.reshape(-1) >= 0
            valid = flat_valid.astype(cfg.theta_dtype)

            # my vocab sub-block of the visiting stack
            take = lambda a: jax.lax.dynamic_slice_in_dim(a, me, 1, axis=0)[0]
            w_sub, d_sub, u_sub, z_sub = take(wl), take(dl), take(uid), take(z)
            if Pm > 1:
                # resident rows are slice mj: rebase to [0, rpm)
                w_sub = jnp.where(w_sub >= 0, w_sub - mj * rpm, w_sub)

            if alias:
                # sparse Θ: capped (topic, count) pairs instead of a
                # [docs, K] plane — the doc-side O(k_d) term of §9
                from repro.core import sparse as sparse_mod

                cap_p = cfg.doc_topic_cap or cfg.n_topics
                with jax.named_scope("doc_pairs"):
                    pairs = sparse_mod.pairs_from_assignments(
                        flat_d, flat_z, flat_valid, cfg.docs_per_shard, cap_p)
                phi_l, psi_l, _, z_new = _sample_subblock_mh(
                    phi_l, psi_l, pairs, w_sub, d_sub, z_sub, u_sub,
                    alpha, beta, seed, cfg_l, tabs)
            else:
                if cfg.small_theta:
                    # Θ only for docs actually sampled this round: remap
                    # their doc ids into [0, cap) (one row per present doc;
                    # absent docs hit the scratch row). Θ build cost:
                    # [cap+1, K] instead of [docs_per_shard, K] — and
                    # segment size no longer bounds Θ.
                    inv = jnp.full((cfg.docs_per_shard,), cfg_l.cap, jnp.int32)
                    inv = inv.at[d_sub].set(
                        jnp.arange(cfg_l.cap, dtype=jnp.int32))
                    idx = inv[flat_d]
                    theta = jnp.zeros((cfg_l.cap + 1, cfg.n_topics),
                                      cfg.theta_dtype).at[idx, flat_z].add(valid)
                    d_sub_local = inv[d_sub]
                else:
                    theta = jnp.zeros((cfg.docs_per_shard, cfg.n_topics),
                                      cfg.theta_dtype).at[flat_d, flat_z].add(valid)
                    d_sub_local = d_sub

                phi_l, psi_l, _, z_new = _sample_subblock(
                    phi_l, psi_l, theta, w_sub, d_sub_local, z_sub, u_sub,
                    alpha, beta, seed, cfg_l,
                )
            if Pm > 1:
                # per-round Ψ resync over the model axis: each slice applied
                # only its bucket's deltas; summing them restores the
                # replicated round-end Ψ, so the next round's snapshot — and
                # every z it samples — matches the P = 1 path bitwise
                with jax.named_scope("ring_exchange"):
                    psi_l = psi_r0 + jax.lax.psum(psi_l - psi_r0,
                                                  RING_AXES[1])
            # write updated z back into the (already-shipped view of the) stack:
            # the z we forward must include this round's update, so we update
            # BEFORE shipping in program order — instead we re-ship z only.
            z_upd = jax.lax.dynamic_update_slice_in_dim(z, z_new[None], me,
                                                        axis=0)
            with jax.named_scope("ring_exchange"):
                z_next = jax.lax.ppermute(z_upd, rot_axes, perm)
            stack = (nxt[0], nxt[1], nxt[2], z_next)
            return (phi_l, psi_l, stack), None

        (phi_l, psi_l, stack), _ = jax.lax.scan(
            round_fn, (phi_l, psi_l, stack0), jnp.arange(M)
        )
        # relaxed per-segment Ψ synchronization (Fig. 4); with model sharding
        # the per-round resync already made model ranks replicas, so the
        # epoch-end psum runs over the data ring only
        with jax.named_scope("ring_exchange"):
            psi_out = psi0 + jax.lax.psum(psi_l - psi0, rot_axes)
        unsq = lambda a: a.reshape((1,) * lead + a.shape)
        return (unsq(phi_l), psi_out.reshape((1,) * plead + psi_out.shape),
                *(unsq(s) for s in stack))

    return epoch


def ring_epoch_parts(mesh, cfg: RingConfig):
    """Build the one-epoch ring sampler for ``mesh`` (unjitted + its specs).

    Global array layout (S = M = ring size):
      phi   [M, rows, K] int32  — sharded over the ring (leading dim)
      psi   [K]          int32  — replicated
      stack [S, M, cap]  int32  — word_local / doc_local / z (+uid uint32),
                                   sharded over the ring (leading dim)

    With ``cfg.model_shards = P > 1`` (§10) the ring is "data"-only (M = data
    axis size) and the same global shapes shard 2-D instead: phi/tables put
    their row dim over "model" (each device holds [1, rows/P, K]) and the
    stacks put their bucket-major cap dim over "model" ([1, M, cap/P]).
    """
    epoch = build_epoch_body(mesh, cfg)
    if cfg.model_shards > 1:
        phi_s = shd.wshard_spec()
        stk_s = shd.wshard_stack_spec()
    else:
        phi_s = stk_s = shd.ring_spec()
    in_specs = (phi_s, P(), stk_s, stk_s, stk_s, stk_s, P(), P(), P())
    if cfg.sampler == "alias":
        # stale proposal tables: wq/wp/wa ride the vocab sharding like phi,
        # the α table is replicated like alpha
        in_specs = in_specs + (phi_s, phi_s, phi_s, P(), P())
    out_specs = (phi_s, P(), stk_s, stk_s, stk_s, stk_s)
    epoch_sm = jax.shard_map(epoch, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
    return epoch_sm, in_specs, out_specs


def make_ring_epoch(mesh, cfg: RingConfig):
    epoch_sm, _, _ = ring_epoch_parts(mesh, cfg)
    return jax.jit(epoch_sm, donate_argnums=(0, 2, 3, 4, 5))


def host_counts(sc: ShardedCorpus, n_topics: int, phi=None, psi=None):
    """Accumulate one segment's z0 into host (phi [M, rows, K], psi [K]).

    Pass the previous segment's output back in to fold several segments into
    ONE global count state — the n_t that streamed training carries across
    segment swaps (Fig. 3).
    """
    import numpy as np

    S, M, cap = sc.word_local.shape
    if phi is None:
        phi = np.zeros((M, sc.rows_per_shard, n_topics), np.int64)
    if psi is None:
        psi = np.zeros((n_topics,), np.int64)
    valid = np.asarray(sc.word_local) >= 0
    # vocab shard of sub-block index m is m (by construction)
    for m in range(M):
        w = np.asarray(sc.word_local[:, m])[valid[:, m]]
        zz = np.asarray(sc.z0[:, m])[valid[:, m]]
        np.add.at(phi[m], (w, zz), 1)
        np.add.at(psi, zz, 1)
    return phi, psi


def device_arrays(sc: ShardedCorpus, n_topics: int):
    """Host → device: the [S, M, cap] stacks + phi/psi built from z0."""
    import numpy as np

    phi, psi = host_counts(sc, n_topics)
    return (
        jnp.asarray(phi.astype(np.int32)),
        jnp.asarray(psi.astype(np.int32)),
        jnp.asarray(sc.word_local),
        jnp.asarray(sc.doc_local),
        jnp.asarray(sc.uid),
        jnp.asarray(sc.z0),
    )


def gather_phi(phi_sharded, sc: ShardedCorpus, n_topics: int):
    """Reassemble the global [V, K] phi from ring shards (for eval / serving)."""
    import numpy as np

    phi = np.asarray(phi_sharded)      # [M, rows, K]
    out = np.zeros((sc.vocab_size, n_topics), np.int32)
    for v in range(sc.vocab_size):
        out[v] = phi[sc.shard_of_word[v], sc.local_of_word[v]]
    return out
