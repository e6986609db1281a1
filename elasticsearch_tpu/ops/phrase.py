"""The phrase kernel: exact `match_phrase` over a field's positions plane.

Reference analog: Lucene's `ExactPhraseMatcher` under `PhraseWeight`
(what Elasticsearch's `match_phrase` builds at slop 0): leapfrog the
words' postings to a common document, then walk their position lists for
offsets that line up; the number of line-ups is the phrase frequency, the
one term frequency `BM25Similarity` scores, under the SUM of the words'
idfs. The TPU formulation reads the converse index (index/segment.py
`PositionsPlane`: which term stands at position p of document d, a
position-major int32 matrix a class of document lengths): a phrase of W
slots is W row-shifted compares against W scalars, AND-ed and summed
down the position axis. Every document's phrase frequency comes out of
one streaming pass with no gather, no sort and no shape that follows a
word's frequency: a phrase of two of the language's commonest words
costs what a phrase of two rare ones costs.

One program a (segment, field, span W in slots, row bucket):

  plan int32[B, 2W + 1] (`pack_phrase_plans`), a row a job:
    [0:W)    the term id at each slot of the phrase, ANY where the
             analyzer left a hole (a removed stop word: any token or
             none may stand there), ABSENT for a word the segment does
             not hold (nothing matches)
    [W:2W)   1 where the slot's word counts in `candidate_occurrences`
             (the first slot of each distinct word), else 0
    [2W]     the job's weight, boost x the summed idfs, float32 bitcast
  result int32[B, 2k + 1 + PHRASE_EXTRA]: the fused text kernel's packed
    row (scores bitcast, doc ids, `hits.total`; MultiFusedScorer) and two
    trailing counters of the job and the segment, whatever their
    liveness: the documents that hold every word, and the occurrences of
    the phrase's words inside those documents (the bytes no exact
    implementation can leave unread: benchmarks/readers/
    phrase_scan_roofline.py).
"""

from __future__ import annotations

import functools
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..index.segment import PLANE_MIN_WIDTH

# slots a phrase may span (its last word's relative position + 1): the
# narrowest class of the plane holds that many positions
PHRASE_TERMS_MAX = PLANE_MIN_WIDTH
# a launch's slot count is its phrases' span exactly (one program a
# span): the last slot then holds a word, so every start at which the
# phrase fits a class's width is a start inside the document
PHRASE_WIDTHS = tuple(range(2, PHRASE_TERMS_MAX + 1))
ANY = -2  # a slot any position matches, empty or not
ABSENT = -3  # a word the segment does not hold; no stored id is negative
PHRASE_EXTRA = 2  # trailing int32 counters of a result row


def phrase_width(span: int) -> Optional[int]:
    """The launch width a phrase of `span` slots rides, or None: one
    word, or more slots than the plane's narrowest class holds."""
    return span if span in PHRASE_WIDTHS else None


def pack_phrase_plans(
    pf, phrases: Sequence[Tuple[Sequence[str], Sequence[int], float]],
    rows: int, width: int,
) -> Tuple[np.ndarray, List[int]]:
    """(`phrase_topk`'s plan, each job's rarest word's df on the segment)
    for one launch over one segment: `pf` the field's PostingsField
    there, `phrases` each job's (words, their relative positions, weight).
    A pad row holds ABSENT and matches nothing."""
    plan = np.zeros((rows, 2 * width + 1), np.int32)
    plan[:, :width] = ANY
    plan[len(phrases):, 0] = ABSENT
    weights = plan[:, 2 * width].view(np.float32)
    df_min: List[int] = []
    for ji, (words, rel, weight) in enumerate(phrases):
        seen = set()
        dfs = []
        for word, r in zip(words, rel):
            tid = pf.term_id(word)
            plan[ji, r] = tid if tid >= 0 else ABSENT
            plan[ji, width + r] = word not in seen
            seen.add(word)
            dfs.append(int(pf.term_df[tid]) if tid >= 0 else 0)
        weights[ji] = weight
        df_min.append(min(dfs))
    return plan, df_min


@functools.partial(jax.jit, static_argnames=("k",))
def phrase_topk(
    mats: Tuple[jax.Array, ...],  # the plane's classes, int32[w_c, n_c]
    order: jax.Array,  # int32[n_plane] the document of a plane column
    inv_norm: jax.Array,  # float32[n_plane] BM25's 1/(k1 (1-b+b dl/avgdl))
    live: Optional[jax.Array],  # bool[n_plane], None: no deletes
    plan: jax.Array,  # int32[B, 2W + 1]
    *, k: int,
) -> jax.Array:
    B = plan.shape[0]
    W = (plan.shape[1] - 1) // 2
    terms, counted = plan[:, :W], plan[:, W: 2 * W]
    weight = jax.lax.bitcast_convert_type(plan[:, 2 * W], jnp.float32)
    freqs, holds, occs = [], [], []
    for T in mats:
        span = T.shape[0] - W + 1  # starts at which the phrase fits
        match = hold = None
        occ = jnp.zeros((B, T.shape[1]), jnp.int32)
        for r in range(W):
            t = terms[:, r][:, None, None]
            wild = t == ANY
            eq = T[None] == t  # [B, w_c, n_c]: the slot's word stands here
            here = eq[:, r: r + span] | wild
            match = here if match is None else match & here
            n_r = eq.sum(axis=1, dtype=jnp.int32)
            has = (n_r > 0) | wild[:, 0]
            hold = has if hold is None else hold & has
            occ = occ + n_r * counted[:, r][:, None]
        freqs.append(match.sum(axis=1, dtype=jnp.int32))
        holds.append(hold)
        occs.append(occ)
    freq = jnp.concatenate(freqs, axis=1)  # [B, n_plane]
    hold = jnp.concatenate(holds, axis=1)
    occ = jnp.where(hold, jnp.concatenate(occs, axis=1), 0)
    # BM25 of one pseudo-term whose tf is the phrase frequency
    # (models/bm25.score_freqs, the formula every text path shares)
    w = weight[:, None]
    score = w - w / (jnp.float32(1.0) + freq.astype(jnp.float32) * inv_norm)
    mask = freq > 0
    if live is not None:
        mask = mask & live[None, :]
    top_s, top_i = jax.lax.top_k(jnp.where(mask, score, -jnp.inf), k)
    return jnp.concatenate(
        [
            jax.lax.bitcast_convert_type(top_s, jnp.int32),
            order[top_i],
            mask.sum(axis=1, dtype=jnp.int32)[:, None],
            hold.sum(axis=1, dtype=jnp.int32)[:, None],
            occ.sum(axis=1, dtype=jnp.int32)[:, None],
        ],
        axis=1,
    )


def least_bytes(df_min: int, n_docs: int, candidate_occurrences: int) -> int:
    """A lower bound on what ANY exact phrase search must read through
    HBM: the rarest word's document set in its smaller encoding (a
    sorted list of 4 B ids or a bitset) and one byte for every
    occurrence of the phrase's words inside the documents that hold
    them all (adjacency cannot be told without them)."""
    return min(4 * df_min, (n_docs + 7) // 8) + candidate_occurrences
