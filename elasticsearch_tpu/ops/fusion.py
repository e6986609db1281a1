"""Reciprocal-rank fusion (RRF) of ranked retriever legs.

Reference analog: x-pack rank-rrf's RRFQueryPhaseRankCoordinatorContext —
score = Σ over legs of 1/(rank_constant + rank), exact-doc dedup, top-k.

Two call sites, two forms:
  * the serving path (`IndexService._run_rrf`: the `rrf` retriever and
    `rank: {rrf: ...}`) fuses on the HOST (`rrf_fuse_ranked`): when the
    last leg's waiter wakes, both legs' hits are Python objects already,
    so a dictionary over legs x window keys is the whole work and nothing
    is uploaded, launched or downloaded (PERF.md section 6, PR 34: the
    device program's round trip was 1.56 ms a request for 200 ids);
  * the SPMD multi-chip path (`parallel/sharded.rrf_fuse`) fuses
    all-gathered per-shard top-k lists that are device-resident already
    (`rrf_fuse_device`: rank maps, dedup compare and top-k as one jitted
    program), with `rrf_fuse_host` as its NumPy oracle.

Ordering contract of all three: fused score desc, then ASCENDING key
(doc id) among ties. On the device candidates are pre-sorted
doc-ascending before the cut, so a `lax.top_k` that keeps the lowest
index among equal scores (the CPU's) gives that tie-break by itself; the
TPU's returns exact ties in no particular order (PERF.md section 6,
PR 31), which a caller that downloads the list has to settle
(`ops/scoring.rank_order`).
"""

from __future__ import annotations

import functools
from typing import Hashable, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

_PAD_SORT_KEY = np.iinfo(np.int32).max


@functools.partial(jax.jit, static_argnames=("rank_constant", "k"))
def _fuse_ranked(legs, rank_constant: int, k: int):
    """legs: tuple of int32[B, k_leg] ranked doc arrays (-1 = padding).
    Returns (scores f32[B, k], docs i32[B, k])."""
    docs = jnp.concatenate(legs, axis=1)  # [B, sum(k_leg)] candidate union
    fused = jnp.zeros(docs.shape, jnp.float32)
    for ld in legs:
        ranks = jnp.arange(1, ld.shape[1] + 1, dtype=jnp.float32)[None, :]
        contrib = jnp.where(ld >= 0, 1.0 / (rank_constant + ranks), 0.0)
        # each candidate collects this leg's contribution where doc ids
        # match (exact-doc identity, no hashing)
        fused = fused + jnp.where(
            (docs[:, :, None] == ld[:, None, :]) & (ld[:, None, :] >= 0),
            contrib[:, None, :],
            0.0,
        ).sum(-1)
    fused = jnp.where(docs >= 0, fused, -jnp.inf)
    # dedup: a candidate with an earlier occurrence of the same doc is
    # dropped (its score is already fully accumulated on the first slot)
    pos = jnp.arange(docs.shape[1])
    dup = (docs[:, :, None] == docs[:, None, :]) & (
        pos[None, None, :] < pos[None, :, None]
    )
    fused = jnp.where(dup.any(-1), -jnp.inf, fused)
    # doc-ascending layout so a lowest-index tie-keep in top_k IS the
    # ascending-doc tie-break (pads sort last)
    order = jnp.argsort(jnp.where(docs >= 0, docs, _PAD_SORT_KEY), axis=1)
    docs_sorted = jnp.take_along_axis(docs, order, axis=1)
    fused_sorted = jnp.take_along_axis(fused, order, axis=1)
    s, i = jax.lax.top_k(fused_sorted, min(k, fused_sorted.shape[1]))
    d = jnp.take_along_axis(docs_sorted, i, axis=1)
    return s, jnp.where(s > -jnp.inf, d, -1)


def rrf_fuse_device(
    legs: Sequence, k: int, rank_constant: int = 60
) -> Tuple[jax.Array, jax.Array]:
    """Fuses N ranked legs on device. Each leg is an int32[B, k_leg]
    array of doc ids in rank order (-1 padding). Returns device arrays
    (scores[B, k'], docs[B, k']) with k' = min(k, Σ k_leg); docs with no
    contribution come back as -1 with -inf score."""
    if len(legs) < 2:
        raise ValueError("rrf fusion needs at least two legs")
    return _fuse_ranked(
        tuple(jnp.asarray(np.asarray(ld, np.int32)) for ld in legs),
        int(rank_constant),
        int(k),
    )


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
                # float32 accumulation in leg order — bit-identical to
                # the device sum, so score parity is exact, not approximate
                fused[doc] = np.float32(
                    fused.get(doc, np.float32(0.0))
                    + np.float32(1.0) / np.float32(rank_constant + rank)
                )
        ordered = sorted(fused.items(), key=lambda kv: (-kv[1], kv[0]))[:width]
        for i, (doc, sc) in enumerate(ordered):
            docs[bi, i] = doc
            scores[bi, i] = sc
    return scores, docs
