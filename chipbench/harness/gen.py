"""Traffic made from a seed, in bulk.

One general generator reads a traffic mix's parameters (``traffic/*.json``):

* ``lengths``: ``{"dist": "poisson", "mean", "min", "max"}`` or
  ``{"dist": "lognormal", "mean", "sigma", "min", "max"}`` (log-normal with
  that arithmetic mean, redrawn outside ``[min, max]``); with ``"total"``
  the lengths are nudged, one token at a time inside ``[min, max]``, until
  they sum to exactly that many tokens, and one document is set to ``max``,
  so every seed gives the program the same static shapes;
* ``words``: ``{"bumps", "words_per_bump", "zipf", "doc_mix"}``: each
  document mixes ``len(doc_mix)`` of ``bumps`` Zipf word bumps with those
  weights (the search-query corpus of the PR 11 chip smoke, generalised).
"""
from __future__ import annotations

import numpy as np


def doc_lengths(spec: dict, n_docs: int, rng) -> np.ndarray:
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "poisson":
        draw = lambda n: rng.poisson(float(spec["mean"]), n)
    elif spec["dist"] == "lognormal":
        sigma = float(spec["sigma"])
        mu = np.log(float(spec["mean"])) - sigma ** 2 / 2
        draw = lambda n: np.rint(rng.lognormal(mu, sigma, n))
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    if spec["dist"] == "poisson":
        lengths = np.clip(draw(n_docs), lo, hi)
    else:
        lengths = draw(n_docs)
        bad = (lengths < lo) | (lengths > hi)
        while bad.any():
            lengths[bad] = draw(int(bad.sum()))
            bad = (lengths < lo) | (lengths > hi)
    lengths = lengths.astype(np.int64)
    if "total" in spec:
        lengths[0] = hi
        total = int(spec["total"])
        while lengths.sum() != total:
            diff = total - int(lengths.sum())
            step = 1 if diff > 0 else -1
            ok = np.nonzero((lengths + step >= lo) & (lengths + step <= hi))[0]
            ok = ok[ok != 0]
            pick = rng.choice(ok, size=min(abs(diff), len(ok)), replace=False)
            lengths[pick] += step
    return lengths


def bump_words(spec: dict, lengths: np.ndarray, vocab: int, rng):
    """Word ids for documents of ``lengths``: each document draws its
    tokens from ``len(doc_mix)`` Zipf bumps with the ``doc_mix`` weights."""
    n_bumps, per = int(spec["bumps"]), int(spec["words_per_bump"])
    per = min(per, vocab)
    keys = rng.random((n_bumps, vocab), dtype=np.float32)
    part = np.argpartition(keys, per - 1, axis=1)[:, :per]
    order = np.argsort(np.take_along_axis(keys, part, axis=1), axis=1)
    words = np.take_along_axis(part, order, axis=1)
    cum = np.cumsum(np.arange(1, per + 1) ** -float(spec["zipf"]))
    cum /= cum[-1]
    mix = np.cumsum(np.asarray(spec["doc_mix"], np.float64))
    mix /= mix[-1]
    n_docs = len(lengths)
    doc_of = np.repeat(np.arange(n_docs), lengths)
    chosen = rng.integers(0, n_bumps, (n_docs, len(mix)))[doc_of]
    comp = np.minimum(np.searchsorted(mix, rng.random(len(doc_of))),
                      len(mix) - 1)
    bump = chosen[np.arange(len(doc_of)), comp]
    rank = np.minimum(np.searchsorted(cum, rng.random(len(doc_of))), per - 1)
    return words[bump, rank].astype(np.int32), doc_of.astype(np.int32)


def corpus(mix: dict, n_docs: int, vocab: int, seed: int):
    """(word_ids, doc_ids) of a training corpus, token-contiguous docs."""
    rng = np.random.default_rng(seed)
    lengths = doc_lengths(mix["lengths"], n_docs, rng)
    return bump_words(mix["words"], lengths, vocab, rng)
