"""Device-side reciprocal-rank fusion (RRF) of ranked retriever legs.

Reference analog: x-pack rank-rrf's RRFQueryPhaseRankCoordinatorContext —
score = Σ over legs of 1/(rank_constant + rank), exact-doc dedup, top-k.
The reference fuses on the coordinator heap; here the legs' top-window
(doc, score) arrays are already device-resident (or trivially uploaded),
so the rank maps, the dedup compare, and the final top-k all run as one
jitted program with a single [B, k] download.

Used by two call sites:
  * the serving path (`IndexService._retriever_search` /
    `rank: {rrf: ...}`) fusing the concurrent BM25 + kNN batcher legs
    (`rrf_fuse_request`: one upload, one packed download a request);
  * the SPMD multi-chip path (`parallel/sharded.rrf_fuse`) fusing
    all-gathered per-shard top-k lists.

Ordering contract (matched by the host oracle `rrf_fuse_host`, and by
the engine's cross-segment merges everywhere else): fused score desc,
then ASCENDING doc id among ties. Candidates are pre-sorted
doc-ascending before the cut, so a `lax.top_k` that keeps the lowest
index among equal scores (the CPU's) gives that tie-break by itself; the
TPU's returns exact ties in no particular order (PERF.md section 6,
PR 31), so the serving path puts the downloaded list in that order on
the host (`IndexService._fuse_legs_device`).
"""

from __future__ import annotations

import functools
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..common.tracing import note_transfer

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


@functools.partial(jax.jit, static_argnames=("rank_constant", "k"))
def _fuse_ranked_packed(legs, rank_constant: int, k: int):
    """`_fuse_ranked` of one query, its legs the rows of one int32
    [n_legs, k_leg] array and its answer one int32[2, k'] array (row 0
    the scores' bits, row 1 the docs)."""
    s, d = _fuse_ranked(
        tuple(legs[i][None, :] for i in range(legs.shape[0])),
        rank_constant, k,
    )
    return jnp.stack([jax.lax.bitcast_convert_type(s[0], jnp.int32), d[0]])


def rrf_fuse_request(
    legs: np.ndarray, k: int, rank_constant: int = 60
) -> Tuple[np.ndarray, np.ndarray]:
    """One request's legs fused on device, as the serving path asks it:
    `legs` a host int32[n_legs, k_leg] array of doc ids in rank order
    (-1 padding), handed to the program as it is (the launch uploads
    it); the answer comes back in one blocking download. Returns host
    (scores f32[k'], docs i32[k']), padding as `rrf_fuse_device`. Both
    transfers are noted (`transfer.scoring`)."""
    if legs.shape[0] < 2:
        raise ValueError("rrf fusion needs at least two legs")
    note_transfer("h2d", legs.nbytes)
    out = np.asarray(_fuse_ranked_packed(legs, int(rank_constant), int(k)))
    note_transfer("d2h", out.nbytes)
    return out[0].view(np.float32), out[1]


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
