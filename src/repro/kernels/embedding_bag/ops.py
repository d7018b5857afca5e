"""Dispatching wrapper for EmbeddingBag (padded + ragged forms)."""
from __future__ import annotations

from repro import kernels as kernels_mod
from repro.kernels.embedding_bag.kernel import embedding_bag_pallas
from repro.kernels.embedding_bag.ref import (
    embedding_bag_padded_ref,
    embedding_bag_ragged_ref,
)


def embedding_bag(table, ids, weights=None, combiner: str = "sum",
                  *, force: str | None = None):
    """Padded multi-hot lookup. force in {None, "pallas", "interpret", "ref"};
    None defers to the cached backend probe (``repro.kernels.kernel_mode``)."""
    mode = kernels_mod.kernel_mode(force)
    if mode == "pallas":
        return embedding_bag_pallas(table, ids, weights, combiner)
    if mode == "interpret":
        return embedding_bag_pallas(table, ids, weights, combiner, interpret=True)
    return embedding_bag_padded_ref(table, ids, weights, combiner)


def embedding_bag_ragged(table, flat_ids, segment_ids, n_bags: int,
                         weights=None, combiner: str = "sum"):
    """Ragged form — always take+segment_sum (XLA fuses this well already)."""
    return embedding_bag_ragged_ref(table, flat_ids, segment_ids, n_bags, weights, combiner)
