"""Plain reference of one alias epoch of the M-ring (Peacock, arXiv:1405.4402
§3.1), replayed round by round. It imports nothing of the program: it gets
the corpus, the token layout the program's stacks hold (which data shard,
vocabulary shard and row each token uid lives in) and the assignments the
program's chain moved through, and reuses ``ref_lda``'s counter hash, Walker
sweep and MH transition.

Every chip is a data server (one data shard's tokens) and a sampling server
(one vocabulary shard of Φ). In round r of M, shard v samples the tokens of
data shard i = (v − r) mod M whose words it holds:

* Φ_v: the histogram of the current z over shard v's rows;
* Ψ_v: the epoch-start Ψ plus shard v's own deltas so far (the other
  shards' arrive with the epoch-end sum);
* each document's topics: from its data shard's current z;
* the stale proposal tables: built once per epoch per shard, from the
  epoch-start weights (Φ_v + β)/(Ψ + Vβ) as [1, rows, K], in a program of
  their own; the α table from α;
* n_mh MH steps per token from the counter hash of (seed, uid, counter).

After each round every shard's draws are written into z; the epoch's Ψ is
the histogram of z. Departures from the paper: the paper's sampler is Gibbs
with live counts inside a sub-block, where the program (and this) draws a
round's sub-block against its round-start snapshot with alias MH; only the
replicated layout (one vocabulary shard per chip) is replayed.

``fault`` plants a defect in the replay, for the checks that must fail:
``psum_every_round`` (every shard's Ψ sees all shards' deltas after each
round), ``phi_frozen`` (shard 0's Φ stays at its epoch-start counts),
``z_unforwarded`` (the documents' topics of later rounds come from the
epoch-start z: each round's draws reach only the epoch's output).

Shard v's work runs on ``devices[v % len(devices)]``.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Dict, List, Optional

import numpy as np

from . import ref_lda

FAULTS = ("psum_every_round", "phi_frozen", "z_unforwarded")


@dataclasses.dataclass
class Layout:
    """Each token uid's place on the ring: ``data`` shard, ``vocab`` shard,
    ``row`` within the vocabulary shard; M shards of ``rows`` rows."""

    M: int
    rows: int
    data: np.ndarray
    vocab: np.ndarray
    row: np.ndarray

    @classmethod
    def from_stacks(cls, wl, uid, n_tokens: int, rows: int) -> "Layout":
        """From the program's [S, M, cap] word-row and uid stacks (row −1:
        an empty slot)."""
        wl, uid = np.asarray(wl), np.asarray(uid)
        M = wl.shape[1]
        i, v, _ = np.nonzero(wl >= 0)
        u = uid[wl >= 0].astype(np.int64)
        out = cls(M, rows, np.full(n_tokens, -1, np.int32),
                  np.full(n_tokens, -1, np.int32),
                  np.full(n_tokens, -1, np.int32))
        out.data[u], out.vocab[u], out.row[u] = i, v, wl[wl >= 0]
        if (out.data < 0).any():
            raise ValueError("the stacks leave out some tokens")
        return out

    def blocks(self):
        """Token uids of each sub-block (r, v), the round r in which shard
        v samples data shard (v − r) mod M."""
        key = ((self.vocab - self.data) % self.M) * self.M + self.vocab
        order = np.argsort(key, kind="stable")
        cuts = np.searchsorted(key[order], np.arange(self.M * self.M + 1))
        return [[order[cuts[r * self.M + v]:cuts[r * self.M + v + 1]]
                 for v in range(self.M)] for r in range(self.M)]


def _padded_size(largest: int, mean: float, granule: int = 4096) -> int:
    """The least multiple of ``granule`` that holds ``largest`` and 1.02×
    ``mean`` items."""
    return -(-max(largest, math.ceil(1.02 * mean)) // granule) * granule


@functools.lru_cache(maxsize=None)
def _hist_fn(R: int, K: int):
    import jax
    import jax.numpy as jnp

    return jax.jit(lambda row, z: jnp.zeros((R, K), jnp.int32)
                   .at[row, z].add(1, mode="drop"))


@functools.lru_cache(maxsize=None)
def _wq_fn(V: int, dtype: str):
    """The stale word-proposal weights (Φ_v + β)/(Ψ + Vβ), [1, rows, K]."""
    import jax
    import jax.numpy as jnp

    dt = jnp.dtype(dtype)

    def run(phi, psi, beta):
        b = beta.astype(dt)
        return ((phi.astype(dt) + b) / (psi.astype(dt)[None, :]
                                        + jnp.asarray(V, dt) * b))[None]
    return jax.jit(run)


@functools.lru_cache(maxsize=None)
def _walker3_fn():
    """Walker tables of [1, rows, K] weights, in a program of its own whose
    input is the finished weights (``ref_lda._walker_fn``)."""
    import jax

    return jax.jit(lambda w: ref_lda.walker(w.reshape(-1, w.shape[-1])))


@dataclasses.dataclass
class EpochRecord:
    """What a host replay of single draws needs: the z at each round's
    start, each (round, shard)'s Ψ, each shard's word tables (on its
    device), the α table."""

    z_round: List[np.ndarray]
    psi: Dict[tuple, np.ndarray]
    tables: List[tuple]
    ap: np.ndarray
    aa: np.ndarray
    alpha: np.ndarray
    seed: int


def epoch(lay: Layout, docs, n_docs: int, V: int, K: int, z_start,
          alpha, beta, seed: int, n_mh: int, devices, dtype="float32",
          fault: Optional[str] = None, blocks=None):
    """z after one ring epoch from ``z_start`` (by uid) at sweep seed
    ``seed``, and the epoch's record for the replay."""
    import jax
    import jax.numpy as jnp

    if fault not in (None,) + FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    M, R = lay.M, lay.rows
    blocks = lay.blocks() if blocks is None else blocks
    dev = [devices[v % len(devices)] for v in range(M)]
    put = jax.device_put
    mine = [np.nonzero(lay.vocab == v)[0] for v in range(M)]
    # padded shapes set by the cell's size, not by the seed's placement (the
    # largest sub-block moves by ~0.5% between seeds): every run then loads
    # the same compiled programs, and only a seed with a block 2% above the
    # mean compiles its own
    T = len(lay.data)
    pad = _padded_size(max(len(b) for rb in blocks for b in rb), T / M ** 2)
    n_mine = _padded_size(max(len(m) for m in mine), T / M)

    def padded(a, n, fill):
        out = np.full(n, fill, a.dtype)
        out[:len(a)] = a
        return out

    rows_v = [put(padded(lay.row[m], n_mine, R), dev[v])
              for v, m in enumerate(mine)]

    def phi_of(v, z):
        return _hist_fn(R, K)(rows_v[v],
                              put(padded(z[mine[v]], n_mine, 0), dev[v]))

    z = np.asarray(z_start, np.int32).copy()
    psi0 = np.bincount(z, minlength=K).astype(np.int64)
    beta32 = np.float32(beta)
    alpha32 = np.asarray(alpha, np.float32)
    phi0 = [phi_of(v, z) for v in range(M)]
    tables = []
    for v in range(M):
        wq = _wq_fn(V, dtype)(phi0[v], put(psi0.astype(np.int32), dev[v]),
                              put(beta32, dev[v]))
        wp, wa = _walker3_fn()(wq)
        tables.append((wq[0], wp, wa))
    ap, aa = ref_lda._walker_fn()(jnp.asarray(alpha32).astype(dtype)[None, :])
    ap, aa = np.asarray(ap[0]), np.asarray(aa[0])
    seed2 = np.uint32(ref_lda.mh_seed(seed))
    fn = ref_lda._transition_fn(V, K, n_mh, dtype)
    psi = [psi0.copy() for _ in range(M)]
    record = EpochRecord([], {}, [(wp, wa) for _, wp, wa in tables], ap, aa,
                         alpha32, seed)
    for r in range(M):
        record.z_round.append(z.copy())
        if fault == "psum_every_round" and r > 0:
            psi = [np.bincount(z, minlength=K).astype(np.int64)] * M
        rows, lengths = ref_lda.doc_topic_rows(
            docs, z_start if fault == "z_unforwarded" else z, n_docs, K)
        out = []
        for v in range(M):
            t = blocks[r][v]
            record.psi[(r, v)] = psi[v].copy()
            phi = (phi0[v] if fault == "phi_frozen" and v == 0
                   else phi_of(v, z))
            d = dev[v]
            doc_rows = np.full((pad, rows.shape[1]), K, np.int32)
            doc_rows[:len(t)] = rows[docs[t]]
            args = (phi, put(psi[v].astype(np.int32), d), *tables[v],
                    put(ap, d), put(aa, d),
                    put(padded(lay.row[t], pad, 0), d),
                    put(padded(z[t], pad, 0), d),
                    put(padded(t.astype(np.uint32), pad, 0), d),
                    put(doc_rows, d),
                    put(padded(lengths[docs[t]], pad, 0), d),
                    put(alpha32, d), put(beta32, d), put(seed2, d))
            out.append(fn(*args))
        for v in range(M):
            t = blocks[r][v]
            z_new = np.asarray(out[v])[:len(t)]
            psi[v] = psi[v] + (np.bincount(z_new, minlength=K)
                               - np.bincount(z[t], minlength=K))
            out[v] = z_new
        for v in range(M):
            z[blocks[r][v]] = out[v]
    return z, record


class _RoundReplay:
    """``ref_lda.replay_outcomes``'s data for the draws of one (round,
    shard): the round-start z and Ψ_v, shard v's Φ rows and word tables."""

    def __init__(self, lay: Layout, rec: EpochRecord, r: int, v: int,
                 docs, doc_tokens, V: int, K: int, beta, n_mh: int,
                 tokens: np.ndarray):
        self.V, self.K, self.n_mh = V, K, n_mh
        self.beta = float(beta)
        self.alpha = rec.alpha.astype(np.float64)
        self.alpha_sum = float(np.float32(np.sum(rec.alpha)))
        self.seed2 = ref_lda.mh_seed(rec.seed)
        self.z = rec.z_round[r]
        self.w = lay.row                 # the token's row in shard v
        self.doc_ids = docs
        self.doc_tokens = doc_tokens
        self.psi = rec.psi[(r, v)].astype(np.float64)
        rows = np.unique(lay.row[tokens])
        self.row = {int(x): i for i, x in enumerate(rows)}
        mask = (lay.vocab == v) & np.isin(lay.row, rows)
        phi = np.zeros((len(rows), K), np.float64)
        np.add.at(phi, (np.searchsorted(rows, lay.row[mask]),
                        self.z[mask]), 1)
        self.phi = phi
        self.jk = {}
        pw, pk = [], []
        for t in tokens:
            per_step = []
            for step in range(n_mh):
                u = float(ref_lda.uniform(self.seed2, np.uint32(t),
                                          4 * step + 1))
                ks = ref_lda._floor_candidates(u * K, K - 1)
                per_step.append(ks)
                pw.extend([int(lay.row[t])] * len(ks))
                pk.extend(ks)
            self.jk[int(t)] = per_step
        wp, wa = rec.tables[v]
        ix = (np.asarray(pw, np.int32), np.asarray(pk, np.int32))
        self.word_table = {(a, b): (float(p), int(q)) for a, b, p, q in zip(
            pw, pk, np.asarray(wp[ix]), np.asarray(wa[ix]))}
        self.ap = rec.ap.astype(np.float64)
        self.aa = rec.aa


def replay(lay: Layout, rec: EpochRecord, docs, doc_tokens, V: int,
           K: int, beta, n_mh: int, tokens: np.ndarray) -> Dict[int, set]:
    """Every topic each of ``tokens``' MH chains can end on when each
    comparison within a tie may go either way (float64 host replay)."""
    out = {}
    r_of = (lay.vocab[tokens] - lay.data[tokens]) % lay.M
    for r in range(lay.M):
        for v in range(lay.M):
            sel = tokens[(r_of == r) & (lay.vocab[tokens] == v)]
            if len(sel) == 0:
                continue
            data = _RoundReplay(lay, rec, r, v, docs, doc_tokens, V, K,
                                beta, n_mh, sel)
            for t in sel:
                out[int(t)] = ref_lda.replay_outcomes(data, int(t))
    return out
