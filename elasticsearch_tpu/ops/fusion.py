"""Reciprocal-rank fusion (RRF) of ranked retriever legs.

Reference analog: x-pack rank-rrf's RRFQueryPhaseRankCoordinatorContext —
score = Σ over legs of 1/(rank_constant + rank), exact-doc dedup, top-k.

The serving path (`IndexService._run_rrf`: the `rrf` retriever and
`rank: {rrf: ...}`) fuses on the HOST (`rrf_fuse_ranked`): when the last
leg's waiter wakes, both legs' hits are Python objects already, so a
dictionary over legs x window keys is the whole work and nothing is
uploaded, launched or downloaded (PERF.md section 6, PR 34: a device
program's round trip was 1.56 ms a request for 200 ids; that program is
gone). `rrf_fuse_host` is the NumPy oracle the tests hold it to.

Ordering contract of both: fused score desc, then ASCENDING key (doc id)
among ties.
"""

from __future__ import annotations

from typing import Hashable, List, Sequence, Tuple

import numpy as np


def rrf_fuse_ranked(
    legs: Sequence[Sequence[Hashable]], k: int, rank_constant: int = 60
) -> List[Tuple[Hashable, float]]:
    """One request's legs fused on the host, as the serving path asks
    it: each leg its keys in rank order (global doc ints, or `_id`
    strings where the legs have no integer identity). Returns the first
    `k` (key, score) by score desc, then key asc. The sum is Python
    floats in leg order: the plain reference's own arithmetic
    (benchmarks/references/rrf_match_knn.py), so equal rank sets give
    bit-equal scores and the served tie groups are the reference's."""
    fused: dict = {}
    for keys in legs:
        for rank, key in enumerate(keys, 1):
            fused[key] = fused.get(key, 0.0) + 1.0 / (rank_constant + rank)
    # ascending key first: the sort by score is stable (`reverse` keeps
    # equal scores in the order they had), so ties stay on the key
    order = sorted(fused)
    order.sort(key=fused.__getitem__, reverse=True)
    return [(key, fused[key]) for key in order[:k]]


def rrf_fuse_host(
    legs: Sequence, k: int, rank_constant: int = 60
) -> Tuple[np.ndarray, np.ndarray]:
    """NumPy oracle with identical semantics (the parity reference):
    dict accumulation over legs, dedup by doc id, order by score desc
    then doc id asc, -1/-inf padding to k' = min(k, Σ k_leg)."""
    legs = [np.asarray(ld, np.int64) for ld in legs]
    B = legs[0].shape[0]
    width = min(int(k), int(sum(ld.shape[1] for ld in legs)))
    scores = np.full((B, width), -np.inf, np.float32)
    docs = np.full((B, width), -1, np.int32)
    for bi in range(B):
        fused: dict = {}
        for ld in legs:
            for rank, doc in enumerate(ld[bi], 1):
                if doc < 0:
                    continue
                doc = int(doc)
                # float32 accumulation in leg order
                fused[doc] = np.float32(
                    fused.get(doc, np.float32(0.0))
                    + np.float32(1.0) / np.float32(rank_constant + rank)
                )
        ordered = sorted(fused.items(), key=lambda kv: (-kv[1], kv[0]))[:width]
        for i, (doc, sc) in enumerate(ordered):
            docs[bi, i] = doc
            scores[bi, i] = sc
    return scores, docs
