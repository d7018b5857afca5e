"""``Trainer`` — the typed training driver that owns the train side of the loop.

Replaces the script-shaped ``launch/train.py`` body: corpus sharding, state
init (single-pod ring or pod-hierarchical), the epoch/aggregation loop, and
an event protocol through which checkpointing, α optimization, liveness,
metrics and model publication plug in (``training/callbacks.py``). The loop
itself is ``hierarchy.run_hierarchical`` — the Trainer supplies timed
epoch/aggregate fns and adapts the two loop hooks into the callback events,
so the coordinator schedule exists exactly once.

    cfg = TrainerConfig(n_docs=3000, n_topics=32, data_shards=2,
                        model_shards=2, ckpt_dir="/tmp/ck")
    tr = Trainer(cfg, callbacks=[Checkpointing(), AlphaOptimizer(),
                                 Metrics(), ModelPublisher("/tmp/snaps")])
    result = tr.fit()
    model, info = tr.export_model()        # dedup + merge → RT-LDA

``export_model`` is the shared train→serve export: one O(K²V) L1 distance
pass, blocked on the device, feeds both the duplicate-fraction diagnostic
and the cluster merge,
then the merged counts become an :class:`RTLDAModel` (R cache, Eq. 3).
``ModelPublisher`` calls the same method on a cadence and writes versioned
snapshots a serving-side ``SnapshotWatcher`` hot-swaps into a
``TopicEngine`` — the paper's continuously-refreshing industrial loop.
"""
from __future__ import annotations

import collections
import dataclasses
from typing import Any, Dict, Optional, Sequence, Tuple

import numpy as np

from repro.training import spans
from repro.training.callbacks import (AlphaOptimizer, ElasticLiveness,
                                      TrainerCallback)
from repro.training.config import TrainerConfig


@dataclasses.dataclass
class TrainResult:
    """What ``fit()`` hands back: final device state + session metrics."""

    state: Tuple[Any, ...]       # (phi, psi, wl, dl, uid, z); streamed
                                 # sessions carry only (phi, psi) — the
                                 # stacks live in the SegmentStream/z store
    alpha: Any                   # [K] f32 — final asymmetric prior
    epochs_run: int              # epochs executed by THIS fit (excl. resume)
    start_epoch: int             # where the run began (0 unless resumed)
    metrics: Dict[str, list]


class Trainer:
    """Owns mesh/source/state and drives the epoch loop through callbacks.

    Data always enters through a :class:`repro.data.CorpusSource`: pass one
    via ``source=``, a resident :class:`Corpus` via ``corpus=`` (wrapped in
    an ``InMemorySource``), set ``config.corpus_dir`` (opened as a
    ``DiskSource``), or pass nothing — the synthetic fallback is an explicit
    ``SyntheticSource``, and ``setup()`` logs which source (type, docs,
    tokens, segments) the session trains on. With more than one segment the
    epoch loop streams: (phi, psi) stay on device across segment swaps while
    the token stacks ride through a double-buffered ``SegmentStream``.
    """

    def __init__(self, config: TrainerConfig,
                 callbacks: Sequence[TrainerCallback] = (),
                 corpus=None, source=None):
        self.config = config
        self.callbacks = list(callbacks)
        self.metrics: Dict[str, list] = collections.defaultdict(list)
        self.epoch = 0               # completed epochs (resume fast-forwards)
        self.segment = 0             # segments completed in the current epoch
        self.corpus = corpus         # resident corpus (None for DiskSource)
        self.source = source         # CorpusSource (built in setup if None)
        self.state: Optional[Tuple[Any, ...]] = None
        self.alpha = None
        self.beta = None
        self.mesh = None
        self.sc0 = None              # pod-0 / single-pod / segment-0 shards
        self.ring_cfg = None
        self._scs = None             # per-pod shards (multi-pod)
        self._epoch_fn = None
        self._agg_fn = None
        self._refs = None            # (phi_ref, psi_ref) of the last boundary
        self._doc_len_hist = None
        self._z = None               # global [n_tokens] z store (streaming)
        self._tables = None          # alias sampler proposal tables (§9)
        self._tables_built_at = -1   # epoch of the last word-table rebuild
        self._tables_alpha = None    # the α the current α table was built from
        # α table builds that rode a word build, and those built alone
        self._alpha_builds = {"with_word": 0, "alone": 0}
        self._streaming = False
        self._ep_time = 0.0          # per-epoch accumulator (streaming)
        self._omega_from = None      # first epoch that folds Ω incrementally
        self._omega_parts = {}       # segment id → this epoch's Ω part
        self._built = False
        # the process's span totals so far: bench_record() reports only
        # what this session adds to them
        self._spans_from = spans.recorder().totals()

    # ------------------------------------------------------------ build ----

    def log(self, msg: str) -> None:
        print(msg, flush=True)

    def notify(self, event: str, *args) -> None:
        """Fire one event on every callback, in list order."""
        with spans.span("peacock.train.callbacks", event=event):
            for cb in self.callbacks:
                getattr(cb, event)(self, *args)

    def _build_source(self):
        """Resolve the session's CorpusSource (explicit > corpus_dir >
        corpus= > synthetic) and validate its geometry against the config."""
        from repro.data import sources as data_sources

        cfg = self.config
        K, M = cfg.n_topics, cfg.ring_size
        if self.source is None:
            if cfg.corpus_dir is not None:
                self.source = data_sources.open_segments(cfg.corpus_dir)
            elif self.corpus is not None:
                self.source = data_sources.InMemorySource(
                    self.corpus, cfg.n_segments, M, M, K,
                    seed=cfg.shard_seed,
                    n_model_shards=cfg.n_model_shards)
            else:
                # the synthetic fallback is an EXPLICIT, logged source — a
                # misconfigured corpus_dir raises in open_segments above
                # instead of silently training on synthetic data
                self.source = data_sources.SyntheticSource(
                    n_docs=cfg.n_docs, vocab_size=cfg.vocab_size,
                    true_topics=cfg.true_topics,
                    doc_len_mean=cfg.doc_len_mean, gen_seed=cfg.seed,
                    n_segments=cfg.n_segments, n_data_shards=M,
                    n_vocab_shards=M, n_topics=K, seed=cfg.shard_seed,
                    n_model_shards=cfg.n_model_shards)
        src = self.source
        self.corpus = src.corpus
        if src.n_data_shards != M or src.n_vocab_shards != M:
            raise ValueError(
                f"source ring geometry {src.n_data_shards}x"
                f"{src.n_vocab_shards} does not match the session's "
                f"{M}x{M} (data_shards*model_shards)")
        if src.n_topics != K:
            raise ValueError(f"source was sharded for K={src.n_topics}, "
                             f"session has n_topics={K}")
        if getattr(src, "n_model_shards", 1) != cfg.n_model_shards:
            raise ValueError(
                f"source was bucketed for n_model_shards="
                f"{getattr(src, 'n_model_shards', 1)} but the session has "
                f"n_model_shards={cfg.n_model_shards} (re-save the segments "
                f"or match the config)")
        if cfg.corpus_dir and cfg.n_segments not in (1, src.n_segments):
            raise ValueError(
                f"config n_segments={cfg.n_segments} but {cfg.corpus_dir!r} "
                f"holds {src.n_segments} segments")
        self.log(f"[data] {src.describe()}")
        return src

    @property
    def n_segments(self) -> int:
        """Segments per epoch (1 on the resident and multi-pod paths)."""
        return self.source.n_segments if self._streaming else 1

    def setup(self) -> "Trainer":
        """Build source, mesh, sharded device state and the compiled fns.
        Idempotent; ``fit()`` calls it automatically."""
        if self._built:
            return self
        spans.at_epoch(self.epoch, self.segment)
        with spans.span("peacock.train.setup"):
            with spans.span("peacock.train.setup.source"):
                self._setup_source()
            with spans.span("peacock.train.setup.state"):
                self._setup_state()
            with spans.span("peacock.train.setup.programs"):
                self._setup_programs()
        self._built = True
        return self

    def _setup_source(self) -> None:
        """The session's source and its first segment's (or every pod's)
        shards: the corpus sharding."""
        cfg = self.config
        src = self._build_source()
        # streaming = any session whose stacks are not resident device state:
        # more than one segment, or an out-of-core (corpus-less) source
        self._streaming = src.n_segments > 1 or src.corpus is None
        if cfg.multi_pod and self._streaming:
            raise ValueError("segment streaming is single-configuration "
                             "(got a multi-pod session with a streaming "
                             "source)")
        if cfg.multi_pod:
            from repro.data import corpus as corpus_mod

            self._scs = corpus_mod.shard_corpus_pods(
                self.corpus, cfg.n_pods, cfg.ring_size, cfg.ring_size,
                cfg.n_topics, seed=cfg.shard_seed,
                n_model_shards=cfg.n_model_shards)
            self.sc0 = self._scs[0]
        else:
            self.sc0 = src.segment(0)

    def _setup_state(self) -> None:
        """The mesh and the initial device state."""
        import jax

        from repro.core import distributed as dist, hierarchy

        cfg = self.config
        if cfg.multi_pod:
            self.mesh = jax.make_mesh(
                (cfg.n_pods, cfg.data_shards, cfg.model_shards),
                ("pod", "data", "model"),
                axis_types=(jax.sharding.AxisType.Auto,) * 3)
            self.state = hierarchy.init_pod_state(self._scs, cfg.n_topics)
            return
        self.mesh = jax.make_mesh(
            (cfg.data_shards, cfg.model_shards), ("data", "model"),
            axis_types=(jax.sharding.AxisType.Auto,) * 2)
        if self._streaming:
            # (phi, psi) + the global z store materialize lazily in fit():
            # a resume restores all three from the checkpoint, and the
            # init pass over every segment would be thrown away
            self.state = None
            self._z = None
        else:
            self.state = dist.device_arrays(self.sc0, cfg.n_topics)

    def _setup_programs(self) -> None:
        """Ring configuration, epoch and aggregate programs, α and β."""
        import jax
        import jax.numpy as jnp

        from repro.core import distributed as dist, hierarchy

        cfg = self.config
        src = self.source
        K, M = cfg.n_topics, cfg.ring_size
        doc_cap = 0
        if cfg.sampler == "alias":
            from repro.core import sparse

            doc_cap = sparse.suggest_cap(src.doc_lengths(), K)
        cap = self.sc0.word_local.shape[-1]
        self.ring_cfg = dist.RingConfig(
            n_topics=K, vocab_size=src.vocab_size,
            rows_per_shard=self.sc0.rows_per_shard,
            docs_per_shard=self.sc0.docs_per_shard,
            cap=cap, package_len=cfg.package_len or cap, n_rounds=M,
            sampler=cfg.sampler, n_mh=cfg.n_mh, doc_topic_cap=doc_cap,
            model_shards=cfg.n_model_shards)
        elastic = any(isinstance(cb, ElasticLiveness) for cb in self.callbacks)
        parts = (hierarchy.pod_ring_epoch_parts if cfg.multi_pod
                 else dist.ring_epoch_parts)(self.mesh, self.ring_cfg)
        # every epoch call gets its arguments in these placements: the
        # host-built first-epoch state, later epochs' sharded outputs and a
        # freshly optimized α would otherwise be three signatures, i.e.
        # three compiles of one program
        self._epoch_in = tuple(jax.sharding.NamedSharding(self.mesh, s)
                               for s in parts[1])
        if cfg.multi_pod:
            self._epoch_fn = hierarchy.make_pod_ring_epoch(self.mesh,
                                                           self.ring_cfg)
            if elastic:
                self._agg_fn = hierarchy.make_elastic_aggregate(self.mesh)
            else:
                self._agg_fn = hierarchy.make_aggregate(self.mesh)
            # every pod starts from the same global replica: the initial
            # state is its own aggregation ref (copied — epochs donate)
            self._refs = (jnp.copy(self.state[0]), jnp.copy(self.state[1]))
        else:
            if elastic:
                raise ValueError(
                    "ElasticLiveness requires aggregation boundaries "
                    "(n_pods > 1); a single-pod session would silently "
                    "never consult the probe")
            self._epoch_fn = dist.make_ring_epoch(self.mesh, self.ring_cfg)
            self._agg_fn = None

        self.alpha = jnp.full((K,), cfg.alpha0 / K, jnp.float32)
        self.beta = jnp.float32(cfg.beta)
        if self._streaming:
            # fold the α-optimizer's Ω histogram during the epoch (at each
            # segment's SaveShard) instead of re-reading every segment at
            # epoch end — only when an AlphaOptimizer will consume it
            starts = [cfg.alpha_opt_from if cb.from_epoch is None
                      else cb.from_epoch
                      for cb in self.callbacks
                      if isinstance(cb, AlphaOptimizer)]
            self._omega_from = min(starts) if starts else None

    def _materialize_stream_state(self) -> None:
        """ONE pass over the segments building the initial (phi, psi) and
        the global z store together (z0 scattered by uid). Skipped when a
        checkpoint restore already supplied both."""
        import jax.numpy as jnp

        from repro.core import distributed as dist

        src = self.source
        K = self.config.n_topics
        phi = psi = None
        z = np.zeros(src.n_tokens, np.int32)
        for g in range(src.n_segments):
            sc = src.segment(g)
            phi, psi = dist.host_counts(sc, K, phi, psi)
            valid = np.asarray(sc.word_local) >= 0
            z[np.asarray(sc.uid)[valid]] = np.asarray(sc.z0)[valid]
        self.state = (jnp.asarray(phi.astype(np.int32)),
                      jnp.asarray(psi.astype(np.int32)))
        self._z = z

    # -------------------------------------------------------------- fit ----

    def fit(self) -> TrainResult:
        """Run the session: ``on_train_start`` (restore happens here), the
        epoch/boundary loop with events, then ``on_train_end``. A
        ``KillSwitch`` (or any callback) aborting with an exception skips
        ``on_train_end`` — exactly the crash the resume path recovers from."""
        from repro.core import hierarchy

        self.setup()
        cfg = self.config
        self.notify("on_train_start")
        start_epoch = self.epoch
        spans.at_epoch(self.epoch, self.segment)    # a restore may move both
        if start_epoch >= cfg.n_epochs:
            self.log(f"[train] nothing to do: resumed at epoch {start_epoch} "
                     f"of {cfg.n_epochs}")
        liveness = None
        for cb in self.callbacks:
            if isinstance(cb, ElasticLiveness):
                liveness = cb.probe
        stream = None
        if self._streaming:
            from repro.data.stream import SegmentStream

            if self.state is None:      # fresh run (no checkpoint restored)
                self._materialize_stream_state()
            self._omega_parts.clear()
            stream = SegmentStream(self.source, self._z,
                                   prefetch=cfg.prefetch)
        if self._alias and self._tables is None:
            # fresh run (or a resume whose checkpoint predates §9 tables):
            # build from whatever (phi, psi, α) the session starts from
            self._rebuild_tables()
            self._tables_built_at = self.epoch
        state = hierarchy.run_hierarchical(
            self._timed_epoch, self._timed_agg if self._agg_fn else None,
            self.state, self.alpha, self.beta, cfg.n_epochs, cfg.agg_every,
            seed0=cfg.seed * 131 + 7, liveness=liveness,
            start_epoch=start_epoch,
            on_epoch_end=self._hook_epoch_end,
            on_aggregate=self._hook_aggregate,
            refs=self._refs,
            segments=stream, start_segment=self.segment,
            on_segment_end=self._hook_segment_end if stream else None,
            epoch_aux=self._epoch_tables if self._alias else None,
        )
        self.state = tuple(state)
        self.notify("on_train_end")
        return TrainResult(state=self.state, alpha=self.alpha,
                           epochs_run=max(0, cfg.n_epochs - start_epoch),
                           start_epoch=start_epoch,
                           metrics={k: list(v) for k, v in self.metrics.items()})

    # loop plumbing: timed fns + hook→event adaptation -----------------------

    def _timed_epoch(self, *args):
        import jax

        with spans.span("peacock.train.ring_epoch") as sp:
            with spans.span("peacock.train.ring_epoch.put"):
                args = jax.device_put(args, self._epoch_in)
            with spans.span("peacock.train.ring_epoch.dispatch"):
                out = self._epoch_fn(*args)
            with spans.span("peacock.train.ring_epoch.wait"):
                jax.block_until_ready(out)
        if self._streaming:
            # per-segment wall time; _hook_epoch_end folds the epoch total
            self.metrics["segment_s"].append(sp.duration)
            self._ep_time += sp.duration
        else:
            self.metrics["epoch_s"].append(sp.duration)
        return out

    def _timed_agg(self, *args, **kwargs):
        import jax

        with spans.span("peacock.train.aggregate") as sp:
            out = self._agg_fn(*args, **kwargs)
            jax.block_until_ready(out)
        self.metrics["agg_s"].append(sp.duration)
        return out

    def _hook_aggregate(self, ep: int, state) -> None:
        import jax.numpy as jnp

        self.state = tuple(state)
        # merged state IS the new ref; keep a copy that survives donation so
        # mid-window checkpoints carry the exact refs a resume must replay
        # against (see run_hierarchical's refs contract)
        self._refs = (jnp.copy(state[0]), jnp.copy(state[1]))
        if self._alias:
            # §9 rebuild cadence: stale word-proposal tables refresh from the
            # just-merged Φ — before notify, so boundary checkpoints capture
            # the tables the next epoch samples with
            self._rebuild_tables()
            self._tables_built_at = ep + 1
        self.notify("on_aggregate", ep)

    def _hook_segment_end(self, ep: int, seg, state) -> None:
        self.state = tuple(state)
        self.epoch = ep
        self.segment = seg.pos + 1
        if self._omega_from is not None and ep >= self._omega_from:
            self._fold_segment_omega(seg)
        self.notify("on_segment_end", ep, seg.pos + 1)
        spans.at_epoch(ep, self.segment)

    def _segment_omega(self, dl, z, valid):
        """Ω_kn histogram of one segment's (doc_local, z, valid) host views —
        the ONE histogram call shared by the incremental fold and the
        full-scan fallback."""
        import jax.numpy as jnp

        from repro.core import dedup

        return dedup.topic_count_histogram(
            jnp.asarray(self._global_docs(np.asarray(dl)).reshape(-1)),
            jnp.asarray(np.asarray(z).reshape(-1)),
            jnp.asarray(np.asarray(valid).reshape(-1)),
            self.ring_cfg.docs_per_shard * self.config.ring_size,
            self.config.n_topics)

    def _global_docs(self, dl):
        """[S, M, cap] doc ids local to each data shard → ids unique over
        the ring (stack s holds data shard s at every epoch boundary), so
        the Ω histogram never merges two shards' documents."""
        shard = np.arange(dl.shape[0], dtype=np.int32)[:, None, None]
        return dl + shard * np.int32(self.ring_cfg.docs_per_shard)

    def _fold_segment_omega(self, seg) -> None:
        """Ω_kn part for one just-committed segment (its z is final for this
        epoch), from the stream's already-loaded host views — no re-read."""
        self._omega_parts[seg.gid] = self._segment_omega(
            seg.host_dl, self._z[seg.host_uid], seg.host_valid)

    def _hook_epoch_end(self, ep: int, state, alpha):
        self.state = tuple(state)
        self.alpha = alpha
        self.epoch = ep + 1
        self.segment = 0
        if self._streaming:
            self.metrics["epoch_s"].append(self._ep_time)
            self._ep_time = 0.0
        self.notify("on_epoch_end", ep)
        self._omega_parts.clear()     # next epoch folds fresh parts
        spans.at_epoch(self.epoch)
        return self.alpha       # callbacks may have replaced it

    # --------------------------------------------- state views / helpers ---

    @property
    def _alias(self) -> bool:
        return self.config.sampler == "alias"

    def _rebuild_tables(self, word: bool = True) -> None:
        """Refresh the alias sampler's stale proposal state from the current
        (phi, psi, α). ``word=False`` refreshes only the α table — used when
        α moved but Φ is mid-window. With the word tables, α's row rides
        their build: prepared in a program of its own, then swept and sorted
        with the word rows (``sparse.make_word_tables``)."""
        import jax

        from repro.core import sparse

        if word or self._tables is None:
            # Φ and Ψ in the epoch's layout first, and kept as the state: a
            # state built on the host or restored from a checkpoint sits on
            # one chip, which would then make the proposal weights of the
            # whole Φ, and hold the whole Φ beside its shards until the
            # first epoch replaced it
            phi, psi = jax.device_put(self.state[:2], self._epoch_in[:2])
            self.state = (phi, psi) + tuple(self.state[2:])
            with spans.span("peacock.train.tables.alpha"):
                alpha_prep = sparse.prepare_alpha_table(self.alpha)
            with spans.span("peacock.train.tables.word"):
                # built per shard, in Φ's layout on the mesh
                tables = sparse.make_word_tables(
                    phi, psi, self.beta, self.ring_cfg.vocab_size,
                    alpha_prep)
            self._alpha_builds["with_word"] += 1
        else:
            with spans.span("peacock.train.tables.alpha"):
                ap, aa = sparse.make_alpha_table(self.alpha)
            tables = self._tables[:3] + (ap, aa)
            self._alpha_builds["alone"] += 1
        self._tables = sparse.AliasTables(*tables)
        self._tables_alpha = self.alpha

    def _epoch_tables(self) -> tuple:
        """``run_hierarchical``'s ``epoch_aux``: hand the loop the proposal
        tables, refreshing them LAZILY at epoch start. Rebuilding here — not
        in the epoch-end hook — keeps the checkpoint contract trivial: a save
        always captures exactly the tables its epoch sampled with, and a
        resumed run re-derives any due rebuild from the restored state (equal
        to the uninterrupted run's epoch-start state), so replay stays
        bitwise. Single-configuration sessions rebuild word tables on the
        ``agg_every`` cadence (multi-pod rebuilds ride ``_hook_aggregate``'s
        merged Φ instead); the α table refreshes whenever α moved — the MH
        correction assumes the drawn proposal and the q ratio share one α.
        """
        ep = self.epoch
        if (not self.has_aggregation and ep > 0
                and ep % self.config.agg_every == 0
                and self._tables_built_at != ep):
            self._rebuild_tables()
            self._tables_built_at = ep
        elif self._tables_alpha is not self.alpha:
            self._rebuild_tables(word=False)
        return tuple(self._tables)

    @property
    def has_aggregation(self) -> bool:
        """Whether this session has aggregation boundaries (multi-pod)."""
        return self._agg_fn is not None

    @property
    def agg_fn(self):
        """The boundary-merge callable (None in single-pod sessions)."""
        return self._agg_fn

    def local_model(self):
        """(phi_shards, psi) of pod 0 (multi-pod) or the single pod."""
        phi, psi = self.state[0], self.state[1]
        if self.config.multi_pod:
            return phi[0], psi[0]
        return phi, psi

    def gather_phi(self) -> np.ndarray:
        """Reassembled global [V, K] topic-count matrix."""
        from repro.core import distributed as dist

        phi0, _ = self.local_model()
        return np.asarray(dist.gather_phi(phi0, self.sc0,
                                          self.config.n_topics))

    def log_likelihood(self) -> float:
        import jax.numpy as jnp

        from repro.core import lda

        _, psi0 = self.local_model()
        return float(lda.word_log_likelihood(jnp.asarray(self.gather_phi()),
                                             psi0, self.beta))

    def alpha_statistics(self):
        """Coordinator stats for the Minka fixed point: (Ω_kn histogram,
        doc-length histogram) — two small arrays, never per-document state.
        Streamed sessions fold the histogram over every segment (z gathered
        from the global store, stacks re-read from the source — mmap'd, so
        this stays out-of-core too)."""
        import jax.numpy as jnp
        import numpy as np

        from repro.core import dedup

        cfg = self.config
        if self._streaming:
            n = self.source.n_segments
            if len(self._omega_parts) == n:
                # folded at each segment's SaveShard this epoch — no re-read
                omega = sum(self._omega_parts[g] for g in range(n))
            else:
                # fallback (call outside the fold window, or a partially
                # replayed resume epoch): one pass over the source
                omega = None
                for g in range(n):
                    sc = self.source.segment(g)
                    o = self._segment_omega(
                        sc.doc_local, self._z[np.asarray(sc.uid)],
                        np.asarray(sc.word_local) >= 0)
                    omega = o if omega is None else omega + o
        else:
            multi = cfg.multi_pod
            wl = self.state[2][0] if multi else self.state[2]
            dl = self.state[3][0] if multi else self.state[3]
            z = self.state[5][0] if multi else self.state[5]
            omega = dedup.topic_count_histogram(
                self._global_docs(dl).reshape(-1), z.reshape(-1),
                (wl >= 0).reshape(-1),
                self.ring_cfg.docs_per_shard * cfg.ring_size, cfg.n_topics)
        if self._doc_len_hist is None:
            self._doc_len_hist = dedup.doc_length_histogram(
                jnp.array(self.source.doc_lengths()))
        return omega, self._doc_len_hist

    # ------------------------------------------------- checkpoint plumbing -

    def checkpoint_tree(self) -> dict:
        tree = {"state": tuple(self.state), "alpha": self.alpha}
        if self._alias and self._tables is not None:
            # the stale proposal tables are part of the sampler's state: a
            # resume must replay against the SAME staleness the uninterrupted
            # run sampled with (rebuilding from the restored Φ would hand the
            # resumed run fresher proposals and break bitwise replay)
            tree["tables"] = tuple(self._tables)
        if self._streaming:
            # streamed sessions checkpoint (phi, psi) + the GLOBAL z store:
            # the stacks are reproducible from the source, z is not — and a
            # resume must land bitwise on the recorded (epoch, segment)
            # boundary regardless of what the source dir holds by then
            tree["z"] = np.array(self._z)
        if self.config.multi_pod:
            # aggregation refs ride along so a resume from a mid-window
            # checkpoint replays against the SAME last-boundary refs —
            # re-deriving them from the restored (per-pod-divergent) state
            # would break the pods-agree invariant at the next merge
            tree["refs"] = tuple(self._refs)
        return tree

    def _tables_like(self, phi_shape) -> tuple:
        """Structure-only stand-in for the alias tables (wq, wp, wa, ap, aa)
        — same treedef/leaf count as ``tuple(self._tables)``."""
        K = self.config.n_topics
        return (np.zeros(phi_shape, np.float32),
                np.zeros(phi_shape, np.float32),
                np.zeros(phi_shape, np.int32),
                np.zeros((K,), np.float32),
                np.zeros((K,), np.int32))

    def checkpoint_like(self) -> dict:
        self.setup()
        if self._streaming and self.state is None:
            # restore template before the lazy init pass: the loader only
            # needs the tree STRUCTURE (leaf count + order), not values
            cfg = self.config
            K, M = cfg.n_topics, cfg.ring_size
            phi_shape = (M, self.sc0.rows_per_shard, K)
            like = {"state": (np.zeros(phi_shape, np.int32),
                              np.zeros((K,), np.int32)),
                    "alpha": np.zeros((K,), np.float32),
                    "z": np.zeros(self.source.n_tokens, np.int32)}
            if self._alias:
                like["tables"] = self._tables_like(phi_shape)
            return like
        tree = self.checkpoint_tree()
        if self._alias and "tables" not in tree:
            # restore runs before fit()'s lazy table build — synthesize the
            # template from the phi shape (values never reach the loader)
            tree["tables"] = self._tables_like(tuple(self.state[0].shape))
        return tree

    def load_checkpoint(self, tree: dict, meta: dict) -> None:
        import jax.numpy as jnp

        ck_p = int(meta.get("n_model_shards", 1))
        if ck_p != self.config.n_model_shards:
            # the checkpoint was written under a different word-shard layout:
            # permute Φ/tables/refs rows through the coarse vocabulary ids and
            # rebuild the stacks from this session's sharding (§10)
            from repro.training import reshard

            scs = self._scs if self.config.multi_pod else [self.sc0]
            tree = reshard.reshard_checkpoint(
                tree, ck_p, self.config.n_model_shards, scs)
            self.log(f"[ckpt] resharded checkpoint n_model_shards={ck_p} -> "
                     f"{self.config.n_model_shards}")
        self.state = tuple(jnp.asarray(x) for x in tree["state"])
        self.alpha = jnp.asarray(tree["alpha"])
        if "z" in tree:
            self._z = np.array(tree["z"], np.int32)
        if "refs" in tree:
            self._refs = tuple(jnp.asarray(x) for x in tree["refs"])
        self.epoch = int(meta.get("epoch", meta["step"]))
        self.segment = int(meta.get("segment", 0))
        if "tables" in tree:
            from repro.core import sparse

            self._tables = sparse.AliasTables(
                *(jnp.asarray(x) for x in tree["tables"]))
            # mid-epoch (segment) checkpoints already carry this epoch's
            # tables; epoch-boundary ones let _epoch_tables re-derive a due
            # rebuild from the restored state — both replay bitwise. The α
            # table is value-rebuilt at the next epoch start (deterministic
            # from the restored α).
            self._tables_built_at = self.epoch if self.segment > 0 else -1
            self._tables_alpha = None
        else:
            # structurally a dense/pre-§9 checkpoint: an alias session never
            # reaches here (checkpoint_like's template makes io.load fail
            # loudly on the leaf-count mismatch — resuming a dense run with
            # --sampler alias is a config change, not a recovery)
            self._tables = None

    # --------------------------------------------------- train→serve export

    def export_model(self, merge_l1: Optional[float] = None,
                     dup_l1: Optional[float] = None):
        """Dedup + merge + RT-LDA build (paper §3.3 → §3.2 handoff).

        One shared ``l1_scan`` distance pass (blocked, on the device) feeds
        the duplicate-fraction diagnostic and the cluster merge; merged
        counts + merged α become the serving model. Returns
        ``(RTLDAModel, info)`` with
        ``info = {duplicate_fraction, n_topics, n_topics_raw}``.
        """
        import jax.numpy as jnp

        from repro.core import dedup, rtlda

        cfg = self.config
        merge_l1 = cfg.dedup_merge_l1 if merge_l1 is None else merge_l1
        dup_l1 = cfg.dedup_dup_l1 if dup_l1 is None else dup_l1
        _, psi0 = self.local_model()
        phi_full = self.gather_phi()
        scan = dedup.l1_scan(phi_full, self.beta, merge_l1)
        frac = dedup.duplicate_fraction(phi_full, self.beta, dup_l1,
                                        scan=scan)
        cl, ncl = dedup.cluster_topics(phi_full, self.beta,
                                       l1_threshold=merge_l1, scan=scan)
        phi_m, psi_m, alpha_m = dedup.merge_topics(phi_full, psi0, self.alpha,
                                                   cl, ncl)
        model = rtlda.build_model(jnp.asarray(phi_m), self.beta,
                                  jnp.asarray(alpha_m))
        info = {"duplicate_fraction": float(frac), "n_topics": int(ncl),
                "n_topics_raw": int(cfg.n_topics)}
        return model, info

    # ------------------------------------------------------------- bench ---

    def bench_record(self) -> dict:
        """Machine-readable training bench record (BENCH_train.json), with
        the totals of the spans this session recorded."""
        cfg = self.config
        ep_s = self.metrics.get("epoch_s", [])
        seg_s = self.metrics.get("segment_s", [])
        agg_s = self.metrics.get("agg_s", [])
        pub_s = self.metrics.get("publish_s", [])
        ll = self.metrics.get("ll", [])
        src = self.source
        tokens = int(src.n_tokens) if src is not None else (
            int(self.corpus.n_tokens) if self.corpus is not None else 0)
        mean = lambda xs: float(np.mean(xs)) if xs else None
        return {
            "bench": "train",
            "n_docs": int(src.n_docs) if src else cfg.n_docs,
            "n_tokens": tokens,
            "n_topics": cfg.n_topics,
            "mesh": {"pods": cfg.n_pods, "data": cfg.data_shards,
                     "model": cfg.model_shards},
            "sampler": cfg.sampler,
            "n_mh": cfg.n_mh if cfg.sampler == "alias" else None,
            "source": type(src).__name__ if src else None,
            "n_segments": src.n_segments if src else 1,
            "prefetch": bool(cfg.prefetch) if self._streaming else None,
            "n_epochs": cfg.n_epochs,
            "epochs_timed": len(ep_s),
            "epoch_s_mean": mean(ep_s),
            "epoch_s_last": ep_s[-1] if ep_s else None,
            "tokens_per_s": (tokens / mean(ep_s)) if ep_s else None,
            "segment_s_mean": mean(seg_s),
            "agg_s_mean": mean(agg_s),
            "n_aggregates": len(agg_s),
            "publish_s_mean": mean(pub_s),
            "n_publishes": len(pub_s),
            "ll_final": ll[-1] if ll else None,
            "ring": self._ring_record(),
            "alpha_tables": dict(self._alpha_builds),
            "spans": spans.recorder().totals(since=self._spans_from),
        }

    def _ring_record(self) -> Optional[dict]:
        """The ring's geometry (of pod 0, or of the first segment when
        streamed): rounds per epoch, sub-block capacity, stack slots
        (M·M·cap: rounds × sub-blocks × cap, sentinels included) and the
        valid tokens among them. Slots beyond the tokens are sentinels,
        sampled and masked: MH work spent on nothing."""
        if self.sc0 is None or self.ring_cfg is None:
            return None
        wl = np.asarray(self.sc0.word_local)
        return {"rounds": int(self.ring_cfg.n_rounds),
                "cap": int(self.ring_cfg.cap),
                "slots": int(wl.size),
                "tokens": int(np.count_nonzero(wl >= 0))}
