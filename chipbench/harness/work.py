"""Required work from shapes, and the chip's peaks.

The work counted is what the algorithm needs, not what today's program
moves: a later change of representation (dropping the stale ``wq`` plane)
leaves these denominators valid. Compulsory terms:

* training epoch: the alias MH probes of every token (pair rows of the
  token's document on each doc proposal, O(1) gathers per probe) and one
  word-table rebuild per epoch (read Φ once, write the proposal tables):
  ``sampler_epoch_bytes``, copied from ``repro.dist.analysis``.
"""
from __future__ import annotations

import json
import math
import os
from typing import Dict, Tuple

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "peaks.json")


def peaks(device_kind: str) -> Dict[str, float]:
    """Peaks of one chip of ``device_kind``; an unknown kind is an error."""
    with open(_PEAKS) as f:
        table = json.load(f)
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{_PEAKS}: add them with their source")
    return table["devices"][device_kind]


def least_time(flops: float, nbytes: float, kind: str) -> Tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    p = peaks(kind)
    t_flops = flops / p["flops_per_s"]
    t_bytes = nbytes / p["hbm_bytes_per_s"]
    return (t_flops, "flops") if t_flops >= t_bytes else (t_bytes, "bytes")


def sampler_epoch_bytes(n_tokens: float, n_topics: int, k_d: float,
                        n_mh: int = 4, vocab: int | None = None,
                        rebuild_epochs: int = 1) -> Dict[str, float]:
    """Analytic per-epoch HBM traffic of the two sampler families (copied
    from ``repro.dist.analysis.sampler_epoch_bytes``).

    Dense: three f32 [T, K] planes per token block. Alias MH: the doc's
    (topic, count) pair rows on each doc proposal (⌈n_mh/2⌉ of the n_mh
    steps) plus O(1) scalar gathers per probe, ⌈n_mh/2⌉·2·k_d·4 +
    n_mh·10·4 B per token; word-table rebuilds read int32 Φ once and
    write three table planes, amortised over ``rebuild_epochs``.
    """
    dense = float(n_tokens) * 3.0 * n_topics * 4.0
    per_token = (math.ceil(n_mh / 2) * 2.0 * k_d * 4.0
                 + float(n_mh) * 10.0 * 4.0)
    alias_sample = float(n_tokens) * per_token
    alias_rebuild = 0.0
    if vocab:
        alias_rebuild = float(vocab) * n_topics * 4.0 * 4.0 / max(
            1, rebuild_epochs)
    total = alias_sample + alias_rebuild
    return {
        "dense_bytes_per_epoch": dense,
        "alias_sample_bytes_per_epoch": alias_sample,
        "alias_rebuild_bytes_per_epoch": alias_rebuild,
        "alias_bytes_per_epoch": total,
        "dense_over_alias": dense / total if total else float("inf"),
    }


def alias_epoch_work(n_tokens: int, n_docs: int, n_topics: int, vocab: int,
                     n_mh: int) -> Tuple[float, float]:
    """(FLOPs, bytes) one alias epoch with a word-table rebuild requires.
    FLOPs: about 20 per MH probe (posterior ratio and proposal)."""
    k_d = n_tokens / max(1, n_docs)
    b = sampler_epoch_bytes(n_tokens, n_topics, k_d, n_mh, vocab, 1)
    return 20.0 * n_tokens * n_mh, b["alias_bytes_per_epoch"]
