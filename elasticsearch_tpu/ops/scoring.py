"""Device scoring kernels (JAX/XLA) over tiled postings.

Reference analog: the Lucene scoring hot loop — BM25Similarity.score inside
WANDScorer/ConjunctionDISI iteration with ForUtil block decode
(SURVEY.md §3.3 "THE LOOP TO PUT ON TPU"). The TPU formulation replaces
doc-at-a-time iterators with:

  gather tile rows (XLA gather from HBM-resident [n_tiles, 128] arrays)
  → elementwise BM25 on the VPU
  → scatter-add into a dense per-doc accumulator (term-at-a-time)
  → lax.top_k, then `rank_order` on the host: score desc, doc asc among
    exact ties, matching Lucene (the TPU's top_k alone returns exact
    ties in no particular order; the CPU's keeps the lowest index).

Scatter-add also accumulates a per-doc *matching-term count*, which makes
conjunctions (operator=and) and minimum_should_match pure elementwise
masks — Lucene's leapfrog intersection becomes arithmetic.

All shapes are static, realized by two serving engines (both batch up
to BPAD concurrent queries per launch — the "score query batches in
parallel" idea from BASELINE.json's north star):

* `ChunkedScorer` — shared fixed shapes: every launch scores a
  [BPAD, TCHUNK, block] slab of gathered tiles into a persistent
  per-doc accumulator; a query's tile list is split into TCHUNK-sized
  chunks, so a handful of programs total cover every (segment, query)
  combination. Used for small segments and as the overflow path.
* `MultiFusedScorer` — one round trip per large segment: the whole
  query phase of a `match` (one field), a `bool` or a `multi_match`
  (rare-tile gather + dense hot-term rows + match mask + top-k) runs as
  a single compiled program, `_fused_query_mf`, fed by one packed int32
  plan upload and returning one packed download. On the attached chip a
  round trip is 0.4-0.5 ms and the program 1-2 ms, against ~27 launches
  and two blocking downloads on the chunked path (see the cost model
  below).

Scores are float32 end-to-end for oracle parity.
"""

from __future__ import annotations

import functools
import time
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from ..common.tracing import launch, note_download, note_transfer


def _to_host(x) -> np.ndarray:
    """The blocking download of one device array (a host sync), noted
    for `_nodes/stats` `transfer.scoring`; entry to return it is the
    `download` span of a dispatcher worker's group and `es.download` on
    the profiler's clock: the wait for the awaited program and the way
    back. Uploads note themselves where they happen: an explicit
    `device_put`, or a host array handed to a jitted program."""
    t0 = time.perf_counter_ns()
    with TraceAnnotation("es.download"):
        out = np.asarray(x)
    note_download(t0, out.nbytes)
    return out


def _to_device(x: np.ndarray) -> jax.Array:
    """The upload of one host array, noted the same way."""
    note_transfer("h2d", x.nbytes)
    return jnp.asarray(x)


def next_bucket(n: int, minimum: int = 8) -> int:
    """Round up to a power of two for shape-stable compilation."""
    b = minimum
    while b < n:
        b *= 2
    return b


def pad_tiles(
    tile_idx: np.ndarray, tile_weights: np.ndarray, bucket: Optional[int] = None
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Pads per-query tile index/weight lists to a bucket size.

    Returns (tile_idx[T], tile_weights[T], tile_valid[T]) with T a power
    of two. Padded entries point at tile 0 with weight 0 and valid=False.
    """
    t = len(tile_idx)
    bucket = bucket or next_bucket(t)
    idx = np.zeros(bucket, np.int32)
    w = np.zeros(bucket, np.float32)
    v = np.zeros(bucket, bool)
    idx[:t] = tile_idx
    w[:t] = tile_weights
    v[:t] = True
    return idx, w, v


@functools.partial(jax.jit, static_argnames=("n_docs",))
def score_tiles(
    doc_rows: jax.Array,  # int32[T, 128] gathered doc-id tiles
    tf_rows: jax.Array,  # int32[T, 128]
    tile_weights: jax.Array,  # float32[T] boost*idf per tile
    tile_valid: jax.Array,  # bool[T]
    inv_norm: jax.Array,  # float32[n_docs] cache[norm_byte] per doc
    n_docs: int,
) -> Tuple[jax.Array, jax.Array]:
    """Returns (scores[float32, n_docs], match_counts[int32, n_docs]).

    score contribution per posting: w - w / (1 + tf * inv_norm[doc])
    (BM25Similarity.score with the 256-entry norm-inverse cache folded
    into a dense per-doc array).
    """
    return _score_tiles_inner(
        doc_rows, tf_rows, tile_weights, tile_valid, inv_norm, n_docs
    )


@functools.partial(jax.jit, static_argnames=("k",))
def topk_hits(scores: jax.Array, mask: jax.Array, k: int) -> Tuple[jax.Array, jax.Array]:
    """(top scores, top doc ids), score desc / doc asc (lax.top_k keeps the
    lowest index among equals). Masked-out docs get -inf and surface as
    doc id entries with -inf score; callers trim by count."""
    masked = jnp.where(mask, scores, -jnp.inf)
    return jax.lax.top_k(masked, k)


class BatchedScoreResult(NamedTuple):
    scores: jax.Array  # float32[B, k]
    docs: jax.Array  # int32[B, k]
    totals: jax.Array  # int32[B] number of matching docs


# ---------------------------------------------------------------------------
# Fixed-shape chunked batched scorer — the serving hot path.
#
# The round-2 lesson: compiling one XLA program per (B, T) bucket melts
# down at corpus scale (T grows with term df under Zipf; warmup was 14
# minutes). The fix is the standard TPU serving recipe: FIX every shape.
# Tile lists of any length stream through launches of exactly TCHUNK
# tiles per row, accumulating into a DONATED dense per-doc accumulator.
#
# The round-7 refinement: the query-row dimension is no longer a single
# fixed width. Every kernel family compiles at a small LADDER of row
# buckets (common/settings.batch_buckets, default 1/4/8/16/32, capped at
# BPAD) and dispatch pads a group to the smallest bucket >= occupancy —
# so a lone query pays a 1-wide launch, not a 32-wide one, and closed-
# loop batches still coalesce up to BPAD. The ladder stays tiny and
# data-independent (row counts, never tile counts), so the compile-count
# blowup the round-2 lesson warns about cannot recur: the serving path
# compiles len(buckets) programs per family total, eagerly warmed after
# a family's first collect (search/batcher.py _warm_ladder).
# ---------------------------------------------------------------------------

BPAD = 32  # max query rows per launch (top of the bucket ladder)
TCHUNK = 512  # fixed tiles per row per launch

# ---- FLOP estimates for the `"profile": true` breakdown -----------------
# Useful (non-padding) work per scored element, counted at dispatch time
# into the group's `flops` (the launch bracket: search/batcher.py
# `_Group.launch`).
# Per posting slot the BM25 kernel does ~6 flops (tf·inv_norm multiply,
# 1+x add, divide, w−x subtract, validity select, scatter add); a dense
# hot-term row does ~4 per doc (no gather/scatter). top_k selection is
# not counted (comparisons, not flops). These are estimates of USEFUL
# work — padded rows/slots are excluded: what a fused launch's rare-term
# pass really scores is `rare_slots_scattered` (every row rides every
# trip of the loop, and the last trip is padded to RARE_CHUNK), a
# chunked launch its TCHUNK-padded chunks.

FLOPS_PER_POSTING_SLOT = 6
FLOPS_PER_DENSE_SLOT = 4
TILE_WIDTH = 128


def text_plan_flops(n_tile_slots: int, n_hot_rows: int, n_docs: int) -> int:
    """Estimated useful flops of one job's text-scoring plan on one
    segment: the tile slots that hold one of its postings tiles (not the
    pad slots of the trip or chunk they ride in, nor other rows') and
    the dense rows it reads."""
    return (
        n_tile_slots * TILE_WIDTH * FLOPS_PER_POSTING_SLOT
        + n_hot_rows * n_docs * FLOPS_PER_DENSE_SLOT
    )


def knn_flops(n_queries: int, n_docs: int, dims: int) -> int:
    """Flops of the brute-force similarity matmul (2·B·N·d)."""
    return 2 * n_queries * n_docs * dims


@functools.partial(jax.jit, donate_argnums=(3,))
def _chunk_add(doc_ids, tfs, inv_norm, acc, ti, tw, tv):
    """acc[B, n+1] += BM25 contributions of one [B, TCHUNK] tile chunk."""
    tgt, s, _ = _chunk_scores(doc_ids, tfs, inv_norm, ti, tw, tv)
    return jax.vmap(lambda a, d, v: a.at[d.ravel()].add(v.ravel()))(acc, tgt, s)


@functools.partial(jax.jit, donate_argnums=(3, 4))
def _chunk_add_cnt(doc_ids, tfs, inv_norm, acc, cnt, ti, tw, tv):
    """Like _chunk_add but also counts matching terms per doc (for
    minimum_should_match / operator=and semantics)."""
    tgt, s, valid = _chunk_scores(doc_ids, tfs, inv_norm, ti, tw, tv)
    acc = jax.vmap(lambda a, d, v: a.at[d.ravel()].add(v.ravel()))(acc, tgt, s)
    cnt = jax.vmap(lambda c, d, v: c.at[d.ravel()].add(v.ravel().astype(jnp.int32)))(
        cnt, tgt, valid
    )
    return acc, cnt


def bm25_tile_contrib(rows_d, rows_t, w, valid, inv_norm, n_docs):
    """The ONE BM25 tile-contribution formula, shared by the chunked
    serving kernel and the mesh SPMD step (parallel/sharded.py) so the
    two paths are float-identical by construction: per posting slot,
    contribution = w - w / (1 + tf · inv_norm[doc]); invalid slots score
    exactly 0 and target the n_docs overflow row. Returns (tgt, s)."""
    tgt = jnp.where(valid, rows_d, n_docs)  # padding → overflow slot
    inv = inv_norm[jnp.clip(rows_d, 0, max(n_docs - 1, 0))]
    s = w - w / (jnp.float32(1.0) + rows_t.astype(jnp.float32) * inv)
    return tgt, jnp.where(valid, s, 0.0)


def _chunk_scores(doc_ids, tfs, inv_norm, ti, tw, tv):
    n_docs = inv_norm.shape[0]
    rows_d = doc_ids[ti]  # [B, TC, 128]
    rows_t = tfs[ti]
    valid = (rows_d >= 0) & tv[:, :, None]
    tgt, s = bm25_tile_contrib(
        rows_d, rows_t, tw[:, :, None], valid, inv_norm, n_docs
    )
    return tgt, s, valid


@functools.partial(jax.jit, static_argnames=("k", "block_size"))
def _threshold(acc, live, k, block_size):
    """(theta[B], accmax[B, n_blocks]) after the essential-terms pass.

    theta = kth best accumulated score over matching LIVE docs (the
    top-k floor the pruning bound must beat); accmax keeps deleted docs
    in — an overestimate is a sound upper bound."""
    a = acc[:, :-1]
    n = a.shape[1]
    masked = jnp.where(a > 0, a, -jnp.inf)
    if live is not None:
        masked = jnp.where(live[None, :], masked, -jnp.inf)
    theta = jax.lax.top_k(masked, min(k, n))[0][:, -1]
    n_blocks = -(-n // block_size)
    pad = n_blocks * block_size - n
    ap = jnp.pad(a, ((0, 0), (0, pad)))
    accmax = ap.reshape(a.shape[0], n_blocks, block_size).max(axis=2)
    return theta, accmax


@functools.partial(jax.jit, static_argnames=("k", "tie_window"))
def _finalize(acc, cnt, live, msm, k, tie_window=0):
    """(scores[B,k], docs[B,k], totals[B]); score desc / doc asc. With
    `tie_window` a fourth, `window_tie_refill`'s i32[B,k]."""
    a = acc[:, :-1]
    n = a.shape[1]
    if cnt is None:
        mask = a > 0
    else:
        mask = cnt[:, :-1] >= jnp.maximum(msm, 1)[:, None]
    if live is not None:
        mask = mask & live[None, :]
    masked = jnp.where(mask, a, -jnp.inf)
    s, d = jax.lax.top_k(masked, min(k, n))
    totals = mask.sum(axis=1, dtype=jnp.int32)
    if tie_window:
        return s, d, totals, window_tie_refill(masked, s, tie_window)
    return s, d, totals


class ChunkedScorer:
    """Batched BM25 scoring over one segment's tiled postings with fixed
    launch shapes (see module comment above).

    Reference analog: the per-leaf BM25 scoring loop
    (BM25Similarity.score inside Weight.scorer iteration); the dense
    [BPAD, n_docs] accumulator replaces the doc-at-a-time heap, and the
    threshold/finalize split is the WAND phase boundary.
    """

    def __init__(self, doc_ids, tfs, inv_norm, live=None, block_size: int = 4096):
        self.doc_ids = jnp.asarray(doc_ids)
        self.tfs = jnp.asarray(tfs)
        self.inv_norm = jnp.asarray(inv_norm, jnp.float32)
        self.live = jnp.asarray(live) if live is not None else None
        self.n_docs = int(self.inv_norm.shape[0])
        self.block_size = block_size

    def new_acc(self, with_cnt: bool, rows: int = BPAD):
        """`rows` is the launch's query-row bucket (<= BPAD): the whole
        chunked pipeline — accumulators, staged tile planes, finalize —
        compiles per bucket, so short batches pay small launches."""
        acc = jnp.zeros((rows, self.n_docs + 1), jnp.float32)
        cnt = jnp.zeros((rows, self.n_docs + 1), jnp.int32) if with_cnt else None
        return acc, cnt

    def score_into(self, acc, cnt, tile_lists, weight_lists, staging=None):
        """Streams per-row tile/weight lists (≤ acc rows, any length)
        through TCHUNK-wide launches into the donated accumulators.

        `staging` optionally supplies reusable host buffers — a callable
        (family, shape, dtype) → np.ndarray (the executor's persistent
        staging slabs) — instead of fresh allocations per chunk. Only the
        validity plane needs clearing: stale tile ids/weights under
        tv=False rows contribute exactly zero (and gathers clamp)."""
        rows = int(acc.shape[0])
        t_max = max((len(t) for t in tile_lists), default=0)
        for c0 in range(0, t_max, TCHUNK):
            if staging is not None:
                ti = staging("chunk_ti", (rows, TCHUNK), np.int32)
                tw = staging("chunk_tw", (rows, TCHUNK), np.float32)
                tv = staging("chunk_tv", (rows, TCHUNK), np.bool_)
                tv[:] = False
            else:
                ti = np.zeros((rows, TCHUNK), np.int32)
                tw = np.zeros((rows, TCHUNK), np.float32)
                tv = np.zeros((rows, TCHUNK), bool)
            for j, (tl, wl) in enumerate(zip(tile_lists, weight_lists)):
                sl = tl[c0 : c0 + TCHUNK]
                m = len(sl)
                if m:
                    ti[j, :m] = sl
                    tw[j, :m] = wl[c0 : c0 + TCHUNK]
                    tv[j, :m] = True
            for plane in (ti, tw, tv):  # host arrays: the launch uploads them
                note_transfer("h2d", plane.nbytes)
            with launch(
                "_chunk_add" if cnt is None else "_chunk_add_cnt", 3,
                ti.nbytes + tw.nbytes + tv.nbytes,
                text_plan_flops(int(tv.sum()), 0, 0),
            ):
                if cnt is None:
                    acc = _chunk_add(
                        self.doc_ids, self.tfs, self.inv_norm, acc, ti, tw, tv)
                else:
                    acc, cnt = _chunk_add_cnt(
                        self.doc_ids, self.tfs, self.inv_norm, acc, cnt,
                        ti, tw, tv,
                    )
        return acc, cnt

    def threshold(self, acc, k: int, live=None):
        """`live` optionally overrides the constructor's live-docs mask
        (a cached filter bitset ANDed with live docs rides here — same
        traced operand, no recompile)."""
        with launch("_threshold"):
            theta, accmax = _threshold(
                acc,
                live if live is not None else self.live,
                k=min(k, self.n_docs),
                block_size=self.block_size,
            )
        return _to_host(theta), _to_host(accmax)

    def finalize(self, acc, cnt, msm: np.ndarray, k: int, live=None):
        s, d, tot = self.finalize_device(acc, cnt, msm, k, live=live)
        return _to_host(s), _to_host(d), _to_host(tot)

    def finalize_device(self, acc, cnt, msm: np.ndarray, k: int, live=None,
                        tie_window: int = 0):
        """Like finalize() but the (scores, docs, totals) triple STAYS on
        device, so the cross-segment merge kernel can consume it with no
        per-segment host sync. `tie_window` (a rescore's first stage):
        a fourth array, `window_tie_refill`'s."""
        note_transfer("h2d", 4 * len(msm))
        k = min(k, self.n_docs)
        with launch("_finalize", 1, 4 * len(msm)):
            return _finalize(
                acc,
                cnt,
                live if live is not None else self.live,
                jnp.asarray(msm, jnp.int32),
                k=k,
                **({"tie_window": min(int(tie_window), k)}
                   if tie_window else {}),
            )


def _score_tiles_inner(doc_rows, tf_rows, tile_weights, tile_valid, inv_norm, n_docs):
    valid = (doc_rows >= 0) & tile_valid[:, None]
    docs = jnp.where(valid, doc_rows, n_docs)
    safe = jnp.clip(doc_rows, 0, max(n_docs - 1, 0))
    inv = inv_norm[safe]
    tf = tf_rows.astype(jnp.float32)
    w = tile_weights[:, None]
    s = w - w / (jnp.float32(1.0) + tf * inv)
    s = jnp.where(valid, s, 0.0)
    acc = jnp.zeros(n_docs + 1, jnp.float32).at[docs.ravel()].add(s.ravel())
    cnt = (
        jnp.zeros(n_docs + 1, jnp.int32)
        .at[docs.ravel()]
        .add(valid.ravel().astype(jnp.int32))
    )
    return acc[:n_docs], cnt[:n_docs]


# ---------------------------------------------------------------------------
# Fused single-round-trip scorer — the serving hot path.
#
# The design: one fused program per batch. Upload ONE packed int32 plan,
# run the whole query phase on device, download ONE packed int32 result,
# and keep several batches in flight from parallel dispatcher workers
# (search/batcher.py). Hot terms (high doc_freq) score from DENSE
# per-doc tf rows — a pure vectorized add with no scatter — and rare
# terms through the tile scatter. Totals come out exact, so
# track_total_hits semantics reduce to response shaping. Block-max
# pruning (ops/wand.py) stays off this path: its θ-broadcast is a
# blocking download between two rounds of chunk launches. The chunked
# pruned path remains as the fallback for segments without dense rows
# (under FUSED_MIN_DOCS), for queries over a slot budget below, and as
# the scale-out strategy when dense rows exceed the HBM budget.
#
# What it costs on the attached chip (TPU v5e; PERF.md sections 5, 6):
# a host<->device round trip is 0.4-0.5 ms (4 bytes: 0.51 ms up, 0.37 ms
# down; PR 21), not the ~100 ms this design was first tuned against. Since
# PR 30 the program itself is 0.35-0.45 ms a one-row launch at a
# question's 20-60 rare tiles, so the one upload in front of it and the
# one download behind it (~1.1 ms together) are a third of a 3.3 ms
# request and three times the kernel. What is expensive is LEAVING this
# program: a query that overflows a slot budget takes its whole launch
# group to the chunked path, where a hot term is scored through its
# tiles (a term of rank 10 in a 1M-doc segment is ~2,700 tiles = 5 chunk
# launches of ~1.1 ms) and a request is ~27 launches, ~126 KB of uploads
# and two blocking downloads: 48.7 ms (PR 25).
#
# Hence the budgets. FUSED_H covers every word of a natural-language
# question: the standard analyzer keeps stop words, `match` and
# `multi_match` OR them in, and questions of 2-12 words drawn from a
# Zipf(1) collection hold at most 12 terms that hold a dense row, in
# both deployments the benchmark builds. MS MARCO passage (1M passages
# of ~56 tokens a shard): 500 terms pass the dense threshold and all
# hold a row; 14.5% of questions hold more than 4 of them, 2.2% more
# than 6, 0.15% more than 8, none more than 12. MS MARCO document
# (401,729 documents of ~1,150 tokens): 9,510 body terms pass it, the
# row budget holds 2,672 (executor_jax.DENSE_ROWS_HBM_BUDGET), and no
# question of 5,000 holds more than 10. Both budgets only widen the plan
# row (537 int32 a field): the program loops over the slots a launch
# uses, hot rows one at a time (`_add_hot_rows`) and rare tiles
# RARE_CHUNK at a time (`_add_rare_tiles`), so an unused hot slot costs
# nothing and unused tile slots cost at most the padding of the last
# chunk (a tile ~2 us where the one pass over 256 slots cost 0.84 ms
# whatever it held). FUSED_T_RARE is what a long-document shard
# overflows: a term left without a row costs its tiles (up to ~90
# there, 61 in passages); the 99.9th percentile question carries 169
# tiles of 256 in passages, the 99th 157 in documents, where 0.02% pass
# 256. In `_nodes/stats`, `pipeline.batching.fused_hot_slots` /
# `serve_hot_slots` count the hot slots fused jobs really use,
# `thread_pool.search.fused_rare_tiles` / `serve_rare_tiles` their
# tiles, `pipeline.batching.rare_slots_scattered` of
# `rare_slots_budget` the tile slots their launches walked,
# `fused_overflow_jobs` / `serve_fallback_jobs` the jobs that did not
# fit.
# ---------------------------------------------------------------------------

FUSED_T_RARE = 256  # rare tile slots per query (fixed compile shape)
FUSED_H = 12  # dense hot-term slots per query (fixed compile shape)
DENSE_TF_MAX = 255  # uint8 dense rows
# uint16 rows for the few hot terms whose tf passes DENSE_TF_MAX somewhere
# (a stop word in a document of thousands of tokens); past this a term
# stays sparse: a row never clips a tf
WIDE_TF_MAX = 65535


def build_dense_rows(doc_ids, tfs, hot_tiles, hot_rank_of_tile, n_hot, n_docs,
                     dtype=jnp.uint8):
    """`dtype`[n_hot, n_docs] per-doc tf rows for hot terms, built ON
    DEVICE from the already-resident postings tiles (nothing is
    uploaded). The caller gives a term a row of a dtype that holds its
    largest tf (uint8, or uint16 past DENSE_TF_MAX); a posting over the
    dtype's range would be stored as 0, never clipped."""

    @functools.partial(jax.jit, static_argnames=("n_hot", "n_docs", "dtype"))
    def build(doc_ids, tfs, hot_tiles, rank_of_tile, n_hot, n_docs, dtype):
        rows_d = doc_ids[hot_tiles]  # [T_hot, 128]
        rows_t = tfs[hot_tiles]
        valid = (rows_d >= 0) & (rows_t <= jnp.iinfo(dtype).max)
        docs = jnp.where(valid, rows_d, n_docs)
        flat = rank_of_tile[:, None] * (n_docs + 1) + docs
        tf = jnp.where(valid, rows_t, 0).astype(dtype)
        dense = jnp.zeros(n_hot * (n_docs + 1), dtype)
        dense = dense.at[flat.ravel()].set(tf.ravel())
        return dense.reshape(n_hot, n_docs + 1)[:, :n_docs]

    return build(doc_ids, tfs, hot_tiles, hot_rank_of_tile, n_hot, n_docs,
                 jnp.dtype(dtype))


def wide_rows_first(hot_rows: list, hot_w: list, dense) -> None:
    """Orders a plan's hot slots in place, uint16 rows (numbered on from
    the rows of the uint8 plane `dense`) before uint8 ones, each kind in
    its given order: `_add_hot_terms` then walks one run of slots a
    plane. A serve plan's rows may carry their clause above
    SLOT_ID_BITS (`clause_slot_ids`)."""
    n8 = 0 if dense is None else dense.shape[0]
    order = sorted(range(len(hot_rows)),
                   key=lambda i: (hot_rows[i] & SLOT_ID_MASK) < n8)
    hot_rows[:] = [hot_rows[i] for i in order]
    hot_w[:] = [hot_w[i] for i in order]


def _add_hot_terms(acc, cnt, dense, wide, inv_norm, hot_ids, hot_w, signed,
                   clauses=False):
    """The hot-term pass of the fused program over a field's two dense
    planes: `dense` uint8[n8, n] and, where some hot term's tf passes
    DENSE_TF_MAX, `wide` uint16[n16, n] (hot id r >= n8 is its row
    r - n8). Without `wide` (every deployment whose documents are short)
    this is `_add_hot_rows` over `dense`, the same program as before.
    With `clauses` the ids carry their clause counter above SLOT_ID_BITS
    (`_add_hot_rows`), which taking n8 off a wide row's id leaves as it
    is."""
    n8 = 0 if dense is None else dense.shape[0]
    if wide is None or wide.shape[0] == 0:
        if n8:
            acc, cnt = _add_hot_rows(
                acc, cnt, dense, inv_norm, hot_ids, hot_w, signed,
                clauses=clauses)
        return acc, cnt
    row = hot_ids & SLOT_ID_MASK if clauses else hot_ids
    is_wide = (hot_ids >= 0) & (row >= n8)
    acc, cnt = _add_hot_rows(
        acc, cnt, wide, inv_norm, jnp.where(is_wide, hot_ids - n8, -1),
        hot_w, signed, from_first_used=True, clauses=clauses)
    if n8:
        acc, cnt = _add_hot_rows(
            acc, cnt, dense, inv_norm, jnp.where(is_wide, -1, hot_ids),
            hot_w, signed, from_first_used=True, clauses=clauses)
    return acc, cnt


def _add_hot_rows(acc, cnt, dense, inv_norm, hot_ids, hot_w, signed,
                  from_first_used=False, clauses=False):
    """Adds a launch's dense hot-term rows to its accumulators: `acc`
    f32[B, n], `cnt` i32[B, >= n] or None (no count plane), `hot_ids`
    i32[B, H] rows of `dense` (-1 = unused), `hot_w` f32[B, H]. With
    `signed` a weight's sign says whether the term counts (w > 0) and
    |w| scores (a counted launch); without, every match counts. With
    `clauses` an id carries its clause counter above SLOT_ID_BITS and a
    counted match adds that counter's unit to the count plane
    (`clause_units`, inside the slot's own trip); without, 1.

    A loop over the slots the launch USES, not over the budget H, and
    one dynamic-slice per row, not a gather of rows. Measured on the
    TPU v5e at 1M docs, 500 dense rows, H = 12 (PERF.md section 6,
    PR 26): an unrolled `for h in range(H)` over `dense[hid]` costs
    47 us a slot and row whether the slot is used or not (a one-row
    launch 1.63 ms where H = 4 took 1.25: a row of the (8,128)(4,1)-
    tiled uint8 plane is read as whole 32-row tiles). This form takes
    1.18-1.21 ms at one row and 2.7-2.9 at four (H = 4 unrolled: 3.97;
    the gather of rows also cost the rest of the program its layout),
    6.6-7.2 at 8 and 13.5-14.8 at 16 (unrolled 8.45, 15.5); only at 32
    rows is the unrolled gather ahead (32.0 against 35-40). An unused
    slot added 0.0, so the sums are the same floats as before.

    `from_first_used` starts the loop at the lowest used slot instead of
    slot 0: the caller has split the slots between two planes, and the
    other plane's run (`wide_rows_first`) reads as unused here."""
    n = acc.shape[1]
    H = hot_ids.shape[1]
    # the highest slot any row uses (pack_plans fills slots from 0 up)
    slots = jnp.arange(1, H + 1, dtype=jnp.int32)
    used = jnp.max(jnp.where(hot_ids >= 0, slots, 0))
    first = 0
    if from_first_used:
        first = jnp.min(jnp.where(hot_ids >= 0, slots - 1, H))

    def slot(h, carry):
        acc, cnt = carry
        hid = jax.lax.dynamic_index_in_dim(hot_ids, h, axis=1, keepdims=False)
        w = jax.lax.dynamic_index_in_dim(hot_w, h, axis=1, keepdims=False)
        if clauses:
            hid, unit = clause_units(hid)
        ok = hid >= 0
        safe = jnp.clip(hid, 0, dense.shape[0] - 1)
        row_tf = jnp.concatenate(
            [
                jax.lax.dynamic_slice(dense, (safe[b], 0), (1, n))
                for b in range(hot_ids.shape[0])
            ],
            axis=0,
        ).astype(jnp.float32)
        wa = jnp.where(ok, jnp.abs(w) if signed else w, 0.0)[:, None]
        contrib = wa - wa / (jnp.float32(1.0) + row_tf * inv_norm[None, :])
        match = (row_tf > 0) & ok[:, None]
        acc = acc + jnp.where(match, contrib, 0.0)
        if cnt is not None:
            counted = match & (w > 0)[:, None] if signed else match
            cnt = cnt.at[:, :n].add(
                jnp.where(counted, unit[:, None], 0) if clauses
                else counted.astype(jnp.int32))
        return acc, cnt

    return jax.lax.fori_loop(first, used, slot, (acc, cnt))


# Tile slots a trip of `_add_rare_tiles` gathers, scores and scatters.
# Measured on the TPU v5e at the passage cell's shapes (1M docs, one-row
# launch, device ms by tiles used 16 / 32 / 64 / 128 / 256; PERF.md
# section 6, PR 30): chunks of 8 0.350 / 0.386 / 0.458 / 0.602 / 0.889,
# of 16 0.347 / 0.379 / 0.444 / 0.574 / 0.834, of 32 0.379 / 0.376 /
# 0.437 / 0.559 / 0.805, the one pass over all 256 slots 1.04-0.98. A
# trip costs its elements (16 tiles: scatter 15.9 us, norm gather 14.7,
# ~2 us of everything else), so the chunk only sets how much padding
# the last trip carries against ~2 us a trip: 16 is within 4% of the
# best at every count, and questions carry a median of ~23 tiles.
RARE_CHUNK = 16


def rare_slots_scattered(rows: int, tiles) -> int:
    """Tile slots `_add_rare_tiles` gathers and scatters in one launch
    of `rows` query rows (pad rows too) over one field, `tiles` the
    tile count of each job's plan there: every row rides every trip."""
    trips = -(-max(tiles, default=0) // RARE_CHUNK)
    return rows * trips * RARE_CHUNK


def _add_rare_tiles(acc, cnt, doc_ids, tfs, inv_norm, rare_ti, rare_tw,
                    signed, clauses=False):
    """Adds a launch's rare-term postings tiles to its accumulators,
    which are FLAT: `acc` f32[B * (n + 1)] and `cnt` i32[B * (n + 1)] or
    None (no count plane) hold the B rows' planes end to end, row b's
    document d at b * (n + 1) + d and the spill of its pad postings at
    b * (n + 1) + n (`_doc_planes` gives the [B, n] view afterwards).
    `rare_ti` i32[B, T] are tile ids into `doc_ids` / `tfs` (-1 =
    unused), `rare_tw` f32[B, T] their weights. With `signed` a weight's
    sign says whether the term counts (w > 0) and |w| scores
    (a counted launch); without, every posting counts. With `clauses`
    a tile id carries its clause counter above SLOT_ID_BITS and a counted
    posting adds that counter's unit to the count plane (`clause_units`,
    on a trip's own C ids: nothing is decoded in front of the loop);
    without, 1: the same one scatter a trip either way.

    A loop over the slots the launch USES, RARE_CHUNK at a trip, not
    one pass over the budget T: everything that is proportional to
    slots (the gathers of tile rows and norms, the score, the scatter-
    adds) is inside it, and `pack_plans` fills slots from 0 up, so the
    trip count is ceil(highest used slot of any row / RARE_CHUNK); no
    tile, no trip. Chunks go in slot order and a chunk's postings in
    slot order, so a document's contributions are added in the order
    the one-pass form added them: the same float32 sums (bit-equal to
    it on the chip in every case measured).

    Why flat: the TPU's scatter works on the flat plane, and a loop
    that carries [B, n + 1] planes is relaid to it and back every trip
    (117 us a trip of 16 tiles at one row where this form takes 32;
    a 256-tile launch 2.16 ms against 0.83, the one pass 0.98)."""
    n = inv_norm.shape[0]
    B, T = rare_ti.shape
    C = RARE_CHUNK
    pad = -T % C  # a budget that is no multiple of the chunk: pad slots
    rare_ti = jnp.pad(rare_ti, ((0, 0), (0, pad)), constant_values=-1)
    rare_tw = jnp.pad(rare_tw, ((0, 0), (0, pad)))
    slots = jnp.arange(1, rare_ti.shape[1] + 1, dtype=jnp.int32)
    used = jnp.max(jnp.where(rare_ti >= 0, slots, 0))
    last_tile = doc_ids.shape[0] - 1
    row_base = (jnp.arange(B, dtype=jnp.int32) * (n + 1))[:, None, None]

    def chunk(i, carry):
        acc, cnt = carry
        ti = jax.lax.dynamic_slice_in_dim(rare_ti, i * C, C, axis=1)
        tw = jax.lax.dynamic_slice_in_dim(rare_tw, i * C, C, axis=1)
        if clauses:
            ti, unit = clause_units(ti)
        safe = jnp.clip(ti, 0, last_tile)
        rows_d = doc_ids[safe]  # [B, C, 128]
        rows_t = tfs[safe]
        valid = (rows_d >= 0) & (ti >= 0)[:, :, None]
        tgt = (jnp.where(valid, rows_d, n) + row_base).ravel()
        inv = inv_norm[jnp.clip(rows_d, 0, n - 1)]
        w = (jnp.abs(tw) if signed else tw)[:, :, None]
        s = w - w / (jnp.float32(1.0) + rows_t.astype(jnp.float32) * inv)
        acc = acc.at[tgt].add(jnp.where(valid, s, 0.0).ravel())
        if cnt is not None:
            counted = valid & (tw > 0)[:, :, None] if signed else valid
            add = (jnp.where(counted, unit[:, :, None], 0) if clauses
                   else counted.astype(jnp.int32))
            cnt = cnt.at[tgt].add(add.ravel())
        return acc, cnt

    return jax.lax.fori_loop(0, (used + C - 1) // C, chunk, (acc, cnt))


def _doc_planes(flat, rows: int, n: int):
    """[rows, n] of a flat accumulator of `_add_rare_tiles`: each row's
    documents, without its spill slot."""
    return flat.reshape(rows, n + 1)[:, :n]


# ---------------------------------------------------------------------------
# The fused scorer's plans: ONE program, `_fused_query_mf`, scores the F
# fields of a plan over one segment, for every text query the batcher
# plans:
#
#   * match on one field → F = 1, every term a counted clause of its own.
#     A launch none of whose jobs holds a count threshold (msm <= 1: any
#     hit matches) goes UNCOUNTED: no count plane, no second scatter, the
#     mask `score > 0`. One `operator: and` / `minimum_should_match` job
#     makes its launch a counted one.
#   * bool must/should multi-term on one field  → per-slot REQUIRED flags
#     (must terms count toward the match threshold, should terms only
#     score). The flag rides the SIGN of the packed weight: w > 0 counts,
#     w < 0 scores with |w| but does not count. ES analog: BooleanQuery's
#     required vs optional scorers in ConjunctionDISI/WANDScorer.
#   * multi_match title/body → one program scores F fields (each with its
#     own postings/norms/dense rows) and combines per-field accumulators:
#     "sum" = most_fields, "max_tie" = best_fields/dis_max
#     (DisjunctionMaxQuery: max + tie_breaker * (sum - max)).
#
#   * clause-level counts (PR 33; BooleanQuery counts CLAUSES, a clause
#     of several words matches on any of them): the one int32 count
#     plane holds two kinds of counter. Its low COUNT_TERM_BITS count
#     the hits of counted clauses of ONE term, as the whole plane did
#     before (a flat plan - multi_match, most_fields, term-only bools -
#     uses nothing else and reads as it always has); above them stand
#     CLAUSE_DIGITS digits of CLAUSE_DIGIT_BITS bits, one a counted
#     clause of SEVERAL terms, which count that clause's terms the
#     document holds. A slot says which counter it feeds in the bits of
#     its tile / row id above SLOT_ID_BITS (0: the term counter; d + 1:
#     digit d), so the plan is no wider than it was, and a counted
#     posting adds that counter's unit in the scatter it already made. A
#     document's matched clauses are then its term counter plus its
#     nonzero digits, held to `msm` (`clauses_hit`): every `must` clause
#     (msm = their number) or `minimum_should_match` should clauses. A
#     digit holds 2**CLAUSE_DIGIT_BITS - 1 terms and there are
#     CLAUSE_DIGITS of them: the planner (search/batcher.py) turns away
#     what passes either.
#   * bool `filter` / `must_not` (PR 48): a launch whose jobs carry a
#     keyword filter builds every row's own mask INSIDE the program from
#     the filter field's postings tiles and bit rows (`filter_row_masks`,
#     the knn family's mask) and ANDs it in; a launch whose jobs carry
#     excluded terms reads the count plane's last digit as a veto
#     (VETO_DIGIT). Both are static properties of the launch, as
#     `counted` is: a launch of neither is the program it was.
#
# Either way: one packed int32 plan upload, the whole query phase on the
# device, one packed download.
# ---------------------------------------------------------------------------

SLOT_ID_BITS = 27  # a tile or dense-row id; the slot's counter above them
SLOT_ID_MASK = (1 << SLOT_ID_BITS) - 1
COUNT_TERM_BITS = 16  # the count plane's low bits: one-term clauses hit
CLAUSE_DIGITS = 4  # counted clauses of several terms a plan may hold
CLAUSE_DIGIT_BITS = 4
CLAUSE_TERMS_MAX = (1 << CLAUSE_DIGIT_BITS) - 1  # terms of such a clause
# A NEGATED launch (one whose jobs hold `must_not` terms) reads the last
# digit as a veto: an excluded term is planned as a term of "clause"
# VETO_DIGIT (batcher.FieldGroup count VETO_COUNT) with a positive
# weight, so its hit adds that digit's unit in the scatter (a rare term)
# or row pass (a hot one) every counted term makes, and the mask drops a
# document whose digit is not zero. What the hit added to the score goes
# with the document. No second plane: the count plane is read and
# written by those passes as it was; a plan with excluded terms may hold
# CLAUSE_DIGITS - 1 counted clauses of several terms and
# CLAUSE_TERMS_MAX excluded terms (the planner's limits).
VETO_DIGIT = CLAUSE_DIGITS - 1
VETO_COUNT = 2 + VETO_DIGIT


def clause_slot_ids(ids, count: int):
    """Plan slot ids (tiles or dense rows) of a term whose `count` is
    the planner's (batcher.FieldGroup): 0 or 1, the ids as they are (a
    term that only scores, or feeds the term counter); 2 + d, the ids
    with digit d's counter, d + 1, above SLOT_ID_BITS."""
    return [i | ((count - 1) << SLOT_ID_BITS) for i in ids] if count > 1 else ids


def clause_units(ids):
    """(plain ids, units) of a plan's slot ids (int32, any shape; -1 =
    unused): the tile / row ids without their counter bits, and what a
    counted hit of each slot adds to the count plane."""
    used = ids >= 0
    counter = ids >> SLOT_ID_BITS
    shift = jnp.where(
        counter > 0,
        COUNT_TERM_BITS - CLAUSE_DIGIT_BITS + CLAUSE_DIGIT_BITS * counter, 0)
    return (jnp.where(used, ids & SLOT_ID_MASK, -1),
            jnp.where(used, jnp.left_shift(jnp.int32(1), shift), 0))


def clauses_hit(cnt, negated: bool = False):
    """Counted clauses each document matched, of a count plane built
    from `clause_units`: its one-term clauses' hits plus its multi-term
    clauses' nonzero digits (`negated`: but the last, VETO_DIGIT,
    which then counts the hits of excluded terms)."""
    digits = jax.lax.shift_right_logical(cnt, jnp.int32(COUNT_TERM_BITS))
    nz = digits | (digits >> 1)
    # a digit's lowest bit: any bit set
    nz = (nz | (nz >> 2)) & (0x0111 if negated else 0x1111)
    return (cnt & ((1 << COUNT_TERM_BITS) - 1)) + jax.lax.population_count(nz)


def vetoed(cnt):
    """Documents that hold an excluded term, of a negated launch's
    count plane: VETO_DIGIT, the plane's top bits, is not zero."""
    return jax.lax.shift_right_logical(cnt, jnp.int32(
        COUNT_TERM_BITS + CLAUSE_DIGIT_BITS * VETO_DIGIT)) != 0


class MultiFusedScorer:
    """One-call batched BM25 query phase over one segment and F fields.

    Plan packing (int32[B, F * (2*T + 2*H) + 1]), a section a field:
      [0:T)          rare tile ids into the postings arrays (-1 = pad)
      [T:2T)         float32 tile weights, bitcast
      [2T:2T+H)      dense hot rows (-1 = pad): r < n8 is row r of the
                     uint8 plane, r >= n8 row r - n8 of the uint16 one
                     (those first: `wide_rows_first`)
      [2T+H:2T+2H)   float32 hot weights, bitcast
    and one trailing int32: msm, the counted clauses a document must
    match (`clauses_hit`). In a counted launch a POSITIVE weight counts,
    into the counter its slot's id names above SLOT_ID_BITS
    (`clause_slot_ids`), and a negative one scores with |w|.

    Result packing (int32[B, 2k + 1]):
      [0:k) float32 scores bitcast · [k:2k) doc ids · [2k] total
    A row is in its final order (score desc, doc asc, -inf pads last),
    so a shard with one scoring segment downloads it as it is
    (`packed_segment_topk`); with more, `_merge_segments` unpacks it
    inside its own trace. Nothing unpacks it eagerly on the device.
    """

    def __init__(self, fields, parts, live, t_rare=FUSED_T_RARE,
                 n_hot_slots=FUSED_H):
        # parts: per field dict(doc_ids, tfs, inv_norm, dense, wide, hot_rank)
        self.fields = tuple(fields)
        self.parts = parts
        self.live = jnp.asarray(live) if live is not None else None
        self.n_docs = int(parts[0]["inv_norm"].shape[0])
        self.t_rare = t_rare
        self.n_hot_slots = n_hot_slots
        if any(p["doc_ids"].shape[0] > SLOT_ID_MASK for p in parts):
            raise ValueError("a field's tiles pass the plan's slot ids")

    def plan_shape_rows(self, rows: int):
        """Plan shape at one query-row bucket of the launch ladder."""
        sec = 2 * self.t_rare + 2 * self.n_hot_slots
        return (rows, len(self.fields) * sec + 1)

    def pack_plans(self, plans, out=None, rows=None) -> np.ndarray:
        """plans: per job, a list of F per-field tuples
        (rare_tiles i64[], rare_w_signed f32[], hot_ranks i64[],
        hot_w_signed f32[]) plus a trailing msm int. `rows` picks the
        launch's query-row bucket (default BPAD); `out` optionally
        reuses a persistent staging slab (fully rewritten)."""
        T, H = self.t_rare, self.n_hot_slots
        F = len(self.fields)
        sec = 2 * T + 2 * H
        if out is None:
            out = np.empty(self.plan_shape_rows(rows or BPAD), np.int32)
        out[:] = -1
        for f in range(F):
            base = f * sec
            out[:, base + T: base + 2 * T] = 0
            out[:, base + 2 * T + H: base + sec] = 0
        out[:, F * sec] = 0
        fout = out.view(np.float32)
        for j, (field_plans, msm) in enumerate(plans):
            for f, (rt, rw, hr, hw) in enumerate(field_plans):
                base = f * sec
                nt, nh = len(rt), len(hr)
                out[j, base: base + nt] = rt
                fout[j, base + T: base + T + nt] = rw
                out[j, base + 2 * T: base + 2 * T + nh] = hr
                fout[j, base + 2 * T + H: base + 2 * T + H + nh] = hw
            out[j, F * sec] = msm
        return out

    def search_async(self, plans, k: int, combine: str, tie, live=None,
                     staging=None, rows=None, counted: bool = True,
                     fmask=None, negated: bool = False,
                     tie_window: int = 0):
        """Launches the fused kernel WITHOUT waiting for the result:
        returns (device_out, k) for decode_result(). Device dispatch is
        async in jax, so a caller can launch several groups (e.g. the
        BM25 and kNN legs of a hybrid search) back-to-back and only
        block when it collects. `live` optionally overrides the
        live-docs mask (cached filter bitsets ride here: a traced arg,
        no recompile); `staging` optionally supplies the reusable
        plan-upload buffer (a (family, shape, dtype) → np.ndarray
        callable); `rows` the launch's query-row bucket (default BPAD).
        `tie` is `max_tie`'s tie breaker, or None where nothing reads
        it (one field): no scalar is uploaded then. `counted`, `fmask`
        (the rows' filters: the filter field's doc-id tiles, the host
        plan of `pack_filter_plans`, the field's bit-row plane or None)
        and `negated` as `_fused_query_mf` takes them; with `fmask` the
        packed row ends in one more int32, the documents the row's
        filter passed. `tie_window` (a rescore's first stage): the
        packed row ends in k more int32, `window_tie_refill`'s."""
        k = min(k, self.n_docs)
        shape = self.plan_shape_rows(rows or BPAD)
        buf = staging("fused_plan", shape, np.int32) if staging else None
        packed = self.pack_plans(plans, out=buf, rows=shape[0])
        note_transfer("h2d", packed.nbytes)
        host_operands, h2d_bytes = 1, packed.nbytes
        if tie is not None:
            note_transfer("h2d", 4)
            tie = np.float32(tie)
            host_operands, h2d_bytes = 2, h2d_bytes + 4
        # a launch of neither makes the call it always made
        special = {}
        if fmask is not None:
            special["fmask"] = fmask
            # the rows' filter plan, a host array too (noted where it
            # was packed)
            host_operands, h2d_bytes = (
                host_operands + 1, h2d_bytes + fmask[1].nbytes)
        if negated:
            special["negated"] = True
        if tie_window:
            special["tie_window"] = min(int(tie_window), k)
        flops = sum(
            text_plan_flops(len(rt), len(hr), self.n_docs)
            for field_plans, _msm in plans
            for rt, _rw, hr, _hw in field_plans
        )
        with launch("_fused_query_mf", host_operands, h2d_bytes, flops):
            out = _fused_query_mf(
                tuple(p["doc_ids"] for p in self.parts),
                tuple(p["tfs"] for p in self.parts),
                tuple(p["inv_norm"] for p in self.parts),
                tuple(p["dense"] for p in self.parts),
                live if live is not None else self.live,
                packed,  # the jitted call uploads both: no eager device_put
                tie,
                tuple(p["wide"] for p in self.parts),
                t_rare=self.t_rare,
                n_hot=self.n_hot_slots,
                k=k,
                combine=combine,
                counted=counted,
                **special,
            )
        return out, k

    def search(self, plans, k: int, combine: str, tie, live=None,
               rows=None, counted: bool = True):
        return decode_result(self.search_async(
            plans, k, combine, tie, live=live, rows=rows, counted=counted
        ))


def decode_result(pending, extra: int = 0):
    """Blocks on the device transfer of `search_async`'s packed result
    and unpacks to (scores f32[B,k], docs i32[B,k], totals i64[B]); a
    row with `extra` trailing counters gives them fourth, i64[B, extra]."""
    out, k = pending
    out = _to_host(out)
    scores = out[:, :k].copy().view(np.float32)
    docs = out[:, k : 2 * k]
    totals = out[:, 2 * k].astype(np.int64)
    if extra:
        return scores, docs, totals, out[:, 2 * k + 1:].astype(np.int64)
    return scores, docs, totals


# ---------------------------------------------------------------------------
# A rescore window's cut, Lucene's: the first `window` by (score desc, doc
# asc). `lax.top_k` on the TPU returns exact ties in no particular order
# and, at the cut, not the lowest-index members; `rank_order` repairs the
# order of what was fetched, not WHICH members of a tie group the cut
# split were fetched. For a page that is forgiven (a tie the page cuts may
# fall either way); a window decides which documents are rescored at all.
# So a first stage that feeds a window (`tie_window`) also returns, where
# the tie group at the window's last rank runs past the k it fetched, the
# k LOWEST doc ids at that score (`window_tie_refill`, a second selection
# under a `cond`: a launch no row of which needs it pays a compare), and
# the host takes the group's members from there (`window_cut`).
# ---------------------------------------------------------------------------


def window_tie_refill(masked, top_s, window: int):
    """i32[B, k]: for a row whose k fetched all score >= its `window`-th
    score theta AND whose k-th equals theta (the tie group at the
    window's edge may run past the fetch), the k lowest doc ids scoring
    exactly theta, ascending, -1 past the group; -1 everywhere for a
    row that needs none. `masked` f32[B, n] (-inf = no match), `top_s`
    its `lax.top_k(masked, k)` scores."""
    B, n = masked.shape
    k = top_s.shape[1]
    theta = top_s[:, min(window, k) - 1]
    need = jnp.isfinite(theta) & (top_s[:, k - 1] == theta)
    lowest = jnp.iinfo(jnp.int32).min

    def refill(_):
        key = jnp.where(
            (masked == theta[:, None]) & need[:, None],
            -jnp.arange(n, dtype=jnp.int32)[None, :], lowest)
        neg, _ = jax.lax.top_k(key, k)
        return jnp.where(neg == lowest, -1, -neg)

    return jax.lax.cond(
        need.any(), refill, lambda _: jnp.full((B, k), -1, jnp.int32), None)


def window_cut(scores: np.ndarray, docs: np.ndarray, refill: np.ndarray,
               window: int):
    """One row of one segment: its first `window` by (score desc, doc
    asc), exactly, from the host rows of a top-k download put in rank
    order (`rank_order`; -inf padding last) and the launch's refill.
    -> (scores, docs, refilled)."""
    k = len(scores)
    n = int(np.isfinite(scores).sum())
    w = min(window, k)
    if n < k or scores[k - 1] != scores[w - 1]:
        # everything that matched was fetched, or the tie group at the
        # window's edge ends inside the fetch: the rank order is exact
        return scores[: min(n, w)], docs[: min(n, w)], False
    theta = scores[w - 1]
    above = int((scores > theta).sum())
    tied = refill[: w - above]
    return (np.concatenate([scores[:above],
                            np.full(len(tied), theta, scores.dtype)]),
            np.concatenate([docs[:above], tied.astype(docs.dtype)]), True)


@functools.partial(
    jax.jit,
    static_argnames=("t_rare", "n_hot", "k", "combine", "counted", "negated",
                     "tie_window"),
)
def _fused_query_mf(
    doc_ids_f, tfs_f, inv_norm_f, dense_f, live, plan, tie=None, wide_f=None,
    fmask=None, *, t_rare, n_hot, k, combine, counted=True, negated=False,
    tie_window=0,
):
    """`counted` (the launch's) says whether any job holds documents to
    a count of clauses: with it a weight's sign says whether its term
    counts and the mask is `clauses_hit(cnt) >= msm`; without, there is
    no count plane, weights score as they are and a document matches
    where its score is positive. `tie` is read by `max_tie` alone.

    `negated` (a counted launch's) says that some job holds excluded
    terms: they ride the plan as terms of "clause" VETO_DIGIT, and
    a document whose digit is not zero is dropped, whatever it scored.
    `fmask` (the launch's too) is (doc-id tiles of the filter field,
    the rows' filter plan, the field's bit rows or None): every row's
    own mask is built here by `filter_row_masks` over the live
    documents and ANDed in, and the documents it passed are appended to
    the packed row. Without either the program is the one it was.

    `tie_window` (a rescore's first stage, whose first `tie_window`
    ranks are a window cut Lucene's way): `window_tie_refill`'s k doc
    ids are appended to the packed row, last."""
    F = len(doc_ids_f)
    n = inv_norm_f[0].shape[0]
    T, H = t_rare, n_hot
    sec = 2 * T + 2 * H
    B = plan.shape[0]
    msm = plan[:, F * sec]
    wide_f = wide_f or (None,) * F

    def f32(x):
        return jax.lax.bitcast_convert_type(x, jnp.float32)

    def section(f):
        """(rare_ti, rare_tw, hot_ids, hot_w) of field f's plan section."""
        base = f * sec
        return (
            plan[:, base: base + T],
            f32(plan[:, base + T: base + 2 * T]),
            plan[:, base + 2 * T: base + 2 * T + H],
            f32(plan[:, base + 2 * T + H: base + sec]),
        )

    # rare terms of every field first: the tile slots in use, into flat
    # planes (one count plane over the fields); counted, |w| scores and
    # w>0 counts its slot's unit
    cnt = jnp.zeros(B * (n + 1), jnp.int32) if counted else None
    accs = []
    for f in range(F):
        rare_ti, rare_tw, _, _ = section(f)
        acc, cnt = _add_rare_tiles(
            jnp.zeros(B * (n + 1), jnp.float32), cnt, doc_ids_f[f],
            tfs_f[f], inv_norm_f[f], rare_ti, rare_tw, signed=counted,
            clauses=counted,
        )
        accs.append(_doc_planes(acc, B, n))
    if counted:
        cnt = _doc_planes(cnt, B, n)
    # then each field's hot terms: dense rows, scored and counted alike
    for f in range(F):
        _, _, hot_ids, hot_w = section(f)
        accs[f], cnt = _add_hot_terms(
            accs[f], cnt, dense_f[f], wide_f[f], inv_norm_f[f],
            hot_ids, hot_w, signed=counted, clauses=counted,
        )
    if F == 1:
        combined = accs[0]
    elif combine == "sum":
        combined = accs[0]
        for a in accs[1:]:
            combined = combined + a
    else:  # max_tie (DisjunctionMaxQuery)
        stack = jnp.stack(accs)
        best = stack.max(axis=0)
        combined = best + tie * (stack.sum(axis=0) - best)
    if counted:
        mask = clauses_hit(cnt, negated) >= jnp.maximum(msm, 1)[:, None]
        if negated:
            mask = mask & ~vetoed(cnt)
    else:
        mask = combined > 0
    cols = []
    if fmask is not None:
        f_doc_ids, f_plan, f_bits = fmask
        passes, passed = filter_row_masks(
            f_doc_ids, jnp.ones(n, jnp.bool_) if live is None else live,
            f_plan, f_bits)
        mask = mask & passes
        cols = [passed[:, None]]
    elif live is not None:
        mask = mask & live[None, :]
    masked = jnp.where(mask, combined, -jnp.inf)
    top_s, top_d = jax.lax.top_k(masked, k)
    totals = mask.sum(axis=1, dtype=jnp.int32)
    if tie_window:
        cols.append(window_tie_refill(masked, top_s, tie_window))
    return jnp.concatenate(
        [
            jax.lax.bitcast_convert_type(top_s, jnp.int32),
            top_d,
            totals[:, None],
            *cols,
        ],
        axis=1,
    )


# ---------------------------------------------------------------------------
# Cross-segment top-k merge: one blocking download a group.
#
# Each scoring segment leaves its candidates on the device, as the fused
# kernel's packed row (i32[B, 2k+1], see MultiFusedScorer) or as the chunked /
# sparse paths' (scores, docs, totals) triple. What the group then costs
# follows from how many there are:
#
#   one fused launch   its packed row IS the merged answer (a top-k of k
#                      sorted candidates returns them; totals are the one
#                      segment's): `packed_segment_topk` downloads it as
#                      the kernel wrote it. No merge program, no upload.
#   anything else      `_merge_segments`, ONE program: it unpacks the
#                      packed rows inside its trace (no eager slicing),
#                      concatenates the candidates, selects the group-wide
#                      winners and packs one result. The segment of each
#                      slot is a constant of the trace (static ids and
#                      widths), not an upload. S segments: S + 1 programs.
#
# Ordering parity with the host merge (score desc, (segment, doc) asc):
# slots are concatenated (segment asc, per-segment rank asc) and
# lax.top_k keeps the LOWEST slot among equal scores on the CPU; on the
# TPU it returns exact ties in no particular order, so the host puts the
# downloaded rows in rank order (`rank_order`). Selection only, scores
# untouched → float-exact.
# ---------------------------------------------------------------------------


def _part_width(part, extra: int = 0) -> int:
    """Candidates a row of one segment's part holds (`extra`: int32
    counters a packed row carries past its total, ops/phrase.py)."""
    if isinstance(part, tuple):
        return int(part[0].shape[1])
    return (int(part.shape[1]) - 1 - extra) // 2


def _unpack_part(part, extra: int = 0):
    """(scores f32[B,k], docs i32[B,k], totals i32[B]) of one segment's
    candidates, traced: a triple as it is, a fused launch's packed row
    sliced and bitcast."""
    if isinstance(part, tuple):
        return part
    k = _part_width(part, extra)
    scores = jax.lax.bitcast_convert_type(part[:, :k], jnp.float32)
    return scores, part[:, k : 2 * k], part[:, 2 * k]


@functools.partial(jax.jit, static_argnames=("segs", "k", "extra"))
def _merge_segments(parts, segs, k, extra=0):
    s_list, d_list, t_list = zip(*(_unpack_part(p, extra) for p in parts))
    scores = jnp.concatenate(s_list, axis=1)  # [B, total_slots]
    docs = jnp.concatenate(d_list, axis=1)
    seg_of_slot = jnp.asarray(np.repeat(
        np.asarray(segs, np.int32), [s.shape[1] for s in s_list]
    ))
    s, idx = jax.lax.top_k(scores, k)
    seg = seg_of_slot[idx]
    doc = jnp.take_along_axis(docs, idx, axis=1)
    totals = jnp.stack([t.astype(jnp.int32) for t in t_list], axis=1)
    cols = [jax.lax.bitcast_convert_type(s, jnp.int32), seg, doc, totals]
    if extra:
        # packed rows' trailing counters, summed over the segments
        cols.append(sum(p[:, -extra:] for p in parts))
    return jnp.concatenate(cols, axis=1)


def is_packed(part) -> bool:
    """A segment's candidates as the fused kernel packed them (one
    array), not a (scores, docs, totals) triple."""
    return not isinstance(part, tuple)


def rank_order(scores: np.ndarray, segs: np.ndarray, docs: np.ndarray):
    """Host rows [B, k] of a top-k download, put in the engine's rank
    order: score descending, then (segment, doc) ascending (Lucene's).
    The device's selection does not promise the second key. On the CPU
    `lax.top_k` keeps the lowest index among equal scores; on the TPU it
    returns exact ties in no particular order (PERF.md section 6, PR 31:
    of 69 passage questions whose first seven BM25 ranks hold an exact
    tie - a sixth of all questions - 28 came back with the tied passages
    reversed, their float32 scores bit-equal). Rows without a tie among
    their real candidates stay as they are; -inf padding stays last.
    Which passages of a tie group that the cut at k splits were selected
    stays the device's choice.

    A row with a tie is sorted as Python lists, not by `np.lexsort`:
    NumPy's sorts release the interpreter lock whatever the size, and a
    dispatcher worker that lets go of it under load waits for it again."""
    tied = (scores[:, 1:] == scores[:, :-1]) & np.isfinite(scores[:, 1:])
    if not tied.any():
        return scores, segs, docs
    scores, segs, docs = scores.copy(), segs.copy(), docs.copy()
    for b in np.flatnonzero(tied.any(axis=1)).tolist():
        ranked = sorted(zip((-scores[b]).tolist(), segs[b].tolist(),
                            docs[b].tolist()))
        negated, segs[b], docs[b] = zip(*ranked)
        scores[b] = [-x for x in negated]
    return scores, segs, docs


def packed_segment_topk(si: int, packed, extra: int = 0):
    """`merge_segment_topk` of one fused launch, with no program: the
    one blocking download of the kernel's packed i32[B, 2k+1+extra],
    decoded on the host. Same return, same floats, ids, order and
    totals (and, with `extra`, the row's trailing counters last)."""
    scores, docs, totals, *counters = decode_result(
        (packed, _part_width(packed, extra)), extra)
    return (scores, np.full_like(docs, si), docs, totals[:, None], *counters)


def merge_segment_topk(items, k: int, extra: int = 0):
    """items: [(si, part)] in ascending segment order, `part` a fused
    launch's packed output i32[B, 2ki+1] or a device triple (scores
    f32[B,ki], docs i32[B,ki], totals i32[B]). Returns host arrays
    (scores f32[B,k], segments i32[B,k], docs i32[B,k], totals
    i64[B, n_segments]) via ONE program and ONE device→host transfer.
    Rows are ordered score desc / (segment, doc) asc; -inf entries pad
    past the real candidates. `extra`: every part is a packed row with
    that many trailing counters; their sums over the segments come
    fifth, i64[B, extra]."""
    k = min(k, sum(_part_width(p, extra) for _, p in items))
    with launch("_merge_segments"):
        merged = _merge_segments(
            tuple(p for _, p in items),
            segs=tuple(int(si) for si, _ in items),
            k=k, extra=extra,
        )
    out = _to_host(merged)
    scores = out[:, :k].copy().view(np.float32)
    segs = out[:, k : 2 * k]
    docs = out[:, 2 * k : 3 * k]
    totals = out[:, 3 * k : 3 * k + len(items)].astype(np.int64)
    if extra:
        return (scores, segs, docs, totals,
                out[:, 3 * k + len(items):].astype(np.int64))
    return scores, segs, docs, totals


@functools.partial(jax.jit, static_argnames=("k",))
def _knn_merge_segments(s_list, d_list, seg_of_slot, nc_cat, k, passed=None):
    scores = jnp.concatenate(s_list, axis=1)  # [B, total_slots]
    docs = jnp.concatenate(d_list, axis=1)
    # per-(job, segment) num_candidates rank cut, applied on device: a
    # slot survives when its within-segment rank is below the job's
    # candidate budget for that segment AND it scored a real candidate
    valid = jnp.isfinite(scores) & nc_cat
    masked = jnp.where(valid, scores, -jnp.inf)
    s, idx = jax.lax.top_k(masked, k)
    seg = seg_of_slot[idx]
    doc = jnp.take_along_axis(docs, idx, axis=1)
    counts = valid.sum(axis=1, dtype=jnp.int32)
    cols = [
        jax.lax.bitcast_convert_type(s, jnp.int32),
        seg,
        doc,
        counts[:, None],
    ]
    if passed is not None:
        # a filtered group: the rows each job's filter passed, summed
        # over the segments, ride the same packed download
        cols.append(sum(passed)[:, None])
    return jnp.concatenate(cols, axis=1)


def knn_merge_segment_topk(items, nc_rows: np.ndarray, k: int, passed=None):
    """kNN variant of merge_segment_topk. items: [(si, scores f32[B,ki],
    docs i32[B,ki])] device pairs (segment asc); nc_rows: host int32
    [B, n_segments] per-(job, segment) num_candidates cut (the
    coordinator's per-segment candidate budget). Returns (scores,
    segments, docs, counts i64[B]) — counts is the number of surviving
    candidates across segments (before the final k cut), in ONE
    device→host transfer. `passed` (a filtered group: one device
    i32[B] a segment, the rows each job's filter passed there) adds a
    fifth result, their sums i64[B], to the same transfer."""
    widths = [int(s.shape[1]) for _, s, _ in items]
    k = min(k, sum(widths))
    seg_of_slot = np.repeat(
        np.asarray([si for si, *_ in items], np.int32), widths)
    rank_of_slot = np.concatenate(
        [np.arange(w, dtype=np.int32) for w in widths]
    )
    # bool [B, total_slots]: slot rank < that (job, segment)'s budget
    nc_cat = (
        rank_of_slot[None, :]
        < np.repeat(nc_rows.astype(np.int32), widths, axis=1)
    )
    # the two uploads stand in front of the runtime as a host operand's
    # staging does: they are the launch's
    with launch("_knn_merge_segments", 2, seg_of_slot.nbytes + nc_cat.nbytes):
        merged = _knn_merge_segments(
            tuple(s for _, s, _ in items),
            tuple(d for _, _, d in items),
            _to_device(seg_of_slot),
            _to_device(nc_cat),
            k=k,
            passed=None if passed is None else tuple(passed),
        )
    out = _to_host(merged)
    scores = out[:, :k].copy().view(np.float32)
    segs = out[:, k : 2 * k]
    docs = out[:, 2 * k : 3 * k]
    counts = out[:, 3 * k].astype(np.int64)
    if passed is not None:
        return scores, segs, docs, counts, out[:, 3 * k + 1].astype(np.int64)
    return scores, segs, docs, counts


# ---------------- kNN ----------------

# kNN scores are NOT bit-identical across launch shapes (ROADMAP D7): the
# row count of `queries @ vectors.T` picks the matmul's internal tiling,
# so the same fp32 dot product is summed in another order, a difference
# bounded by d * 2^-24 (4.6e-5 at d=768). The parity tests and
# chip_smoke.py hold kNN scores to this one relative tolerance; ids and
# their order stay equal.
KNN_SCORE_RTOL = 1e-4


@functools.partial(jax.jit, static_argnames=("similarity",))
def knn_scores(
    queries: jax.Array,  # float32[B, d]
    vectors: jax.Array,  # float32[N, d] (unit-normalized for cosine)
    similarity: str,
    norms: Optional[jax.Array] = None,  # float32[N]: knn_row_norms(vectors)
) -> jax.Array:
    """Dense [B, N] similarity scores: one MXU matmul + the Lucene
    VectorSimilarityFunction transform (see models/similarity.py).
    Rows of an `element_type: byte` field arrive as int8 and are cast
    here, inside the program: the device holds one byte an element.
    Whole numbers of 8 bits are exact in bfloat16 and every product and
    partial sum of them in float32 (192 x 128^2 < 2^24), so an MXU pass
    at the default precision gives their exact dot products. `norms`
    (l2_norm over integer rows alone) stands for the rows' `sum(v * v)`,
    which the program then does not read the rows a second time for;
    without it the program is the one it always was."""
    if jnp.issubdtype(vectors.dtype, jnp.integer):
        vectors = vectors.astype(jnp.float32)
    if similarity == "l2_norm":
        # ||q - v||² = |q|² + |v|² - 2 q·v — matmul-friendly
        dots = queries @ vectors.T
        q2 = jnp.sum(queries * queries, axis=1, keepdims=True)
        if norms is None:
            norms = jnp.sum(vectors * vectors, axis=1)
        scores = _knn_l2_scores(q2, norms[None, :], dots)
    else:
        dots = _knn_unit_queries(queries, similarity) @ vectors.T
        scores = _knn_dot_scores(dots, similarity)
    return scores.astype(jnp.float32)


# The Lucene VectorSimilarityFunction transforms, shared by the program
# that scores every stored row (`knn_scores`) and the one that scores a
# lead clause's rows (`knn_topk_lead`): one arithmetic, two shapes.


def _knn_l2_scores(q2, v2, dots):
    d2 = jnp.maximum(q2 + v2 - 2.0 * dots, 0.0)
    return 1.0 / (1.0 + d2)


def _knn_unit_queries(queries, similarity: str):
    if similarity == "cosine":
        qn = jnp.linalg.norm(queries, axis=1, keepdims=True)
        queries = queries / jnp.where(qn == 0, 1.0, qn)
    return queries


def _knn_dot_scores(dots, similarity: str):
    if similarity in ("cosine", "dot_product"):
        return (1.0 + dots) / 2.0
    if similarity == "max_inner_product":
        return jnp.where(dots < 0, 1.0 / (1.0 - dots), dots + 1.0)
    raise ValueError(f"unknown similarity [{similarity}]")


@functools.partial(jax.jit, static_argnames=("similarity", "k"))
def knn_topk_batch(
    queries: jax.Array,  # float32[BPAD, d] (padded rows are zeros)
    valid: jax.Array,  # bool[BPAD] real rows
    vectors: jax.Array,  # float32[N, d]
    exists: jax.Array,  # bool[N]
    similarity: str,
    k: int,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Serving-path batched brute-force kNN: one MXU matmul scores BPAD
    concurrent queries against a whole segment, one packed download
    (scores[B,k], docs[B,k], totals[B]). The batch dimension rides the
    matmul's M axis — the fused-scorer recipe applied to vectors
    (BASELINE config 4)."""
    scores = knn_scores(queries, vectors, similarity)
    mask = exists[None, :] & valid[:, None]
    masked = jnp.where(mask, scores, -jnp.inf)
    s, d = jax.lax.top_k(masked, k)
    totals = mask.sum(axis=1, dtype=jnp.int32)
    return s, d, totals


# Postings tiles a trip of `knn_filter_mask` gathers and scatters, a
# query row. Measured on the TPU v5e at the filtered cell's shapes (10M
# rows, one-row launch, device ms by the tag's tiles 1 / 76 / 728 /
# 5,613 / 22,130; PERF.md section 6, PR 39): chunks of 16 0.96 / 1.06 /
# 2.07 / 10.11 / 37.06, of 64 0.98 / 1.04 / 2.09 / 10.10 / 37.03, of 256
# 0.97 / 1.04 / 2.11 / 10.07 / 37.08: a launch that scatters costs
# ~0.96 ms (the 40 MB plane zeroed, counted and masked) and 13 ns a
# posting slot scattered, whatever the chunk. Since PR 45 the terms that
# hold a bit row (`FilterBitRows`) scatter nothing, and a launch none of
# whose rows scatters skips the plane too.
FILTER_CHUNK = 64
# Term slots of a filter plan row: the compile buckets of the plan's
# width. A filter of more terms than the last is not planned.
FILTER_SLOT_BUCKETS = (8, 64)
# A plan slot's unit where the slot names a bit row, not a tile range:
# the row opens a clause of its own, or joins (ORs into) the clause the
# bit-row slot before it opened.
FILTER_BIT_OPENS, FILTER_BIT_JOINS = -1, -2
# Words of a bit row are rounded up to this many, so that each of a
# word's 32 bit planes unpacks into whole tiles of lanes.
FILTER_BIT_WORDS_ALIGN = 1024


def filter_slot_bucket(n_terms: int) -> Optional[int]:
    """The plan width `n_terms` filter terms ride, or None: too many."""
    return next((b for b in FILTER_SLOT_BUCKETS if n_terms <= b), None)


def filter_bit_words(n_docs: int) -> int:
    """uint32 words of one bit row over `n_docs` documents."""
    a = FILTER_BIT_WORDS_ALIGN
    return max(-(-n_docs // (32 * a)), 1) * a


@jax.jit
def _pack_bit_rows(masks: jax.Array) -> jax.Array:
    """bool[R, n] -> uint32[R, W], W = filter_bit_words(n): document d
    is bit d // W of word d % W. Lane-strided, so a row unpacks without
    a relayout: bit plane b of the words IS documents [b * W, (b + 1) *
    W), in order (`_unpack_bit_rows`)."""
    r, n = masks.shape
    w = filter_bit_words(n)
    planes = jnp.pad(masks, ((0, 0), (0, 32 * w - n))).reshape(r, 32, w)
    shifts = jnp.arange(32, dtype=jnp.uint32)[None, :, None]
    return jnp.sum(planes.astype(jnp.uint32) << shifts, axis=1,
                   dtype=jnp.uint32)


def _unpack_bit_rows(words: jax.Array, n: int) -> jax.Array:
    """uint32[B, W] -> bool[B, n]: `_pack_bit_rows` undone."""
    rows, w = words.shape
    shifts = jnp.arange(32, dtype=jnp.uint32)[None, :, None, None]
    lanes = words.reshape(rows, 1, w // 128, 128)
    planes = (lanes >> shifts) & jnp.uint32(1)  # [B, 32, W / 128, 128]
    return planes.reshape(rows, 32 * w)[:, :n].astype(jnp.bool_)


class FilterBitRows(NamedTuple):
    """The bit rows a segment's filter field holds on the device for its
    commonest terms (executor_jax.DevicePostings.filter_bits chooses
    them): `plane` uint32[R, W] in `_pack_bit_rows`' layout, row r the
    documents that hold the term with `row_of_term[term id] == r`; no
    plane where no term holds a row."""

    plane: Optional[jax.Array] = None
    row_of_term: dict = {}

    @property
    def nbytes(self) -> int:
        return 0 if self.plane is None else int(self.plane.nbytes)


class FilterPlans(NamedTuple):
    """`pack_filter_plans`' (or `pack_lead_plans`') launch: the plan,
    the postings tiles its tile-range slots name (what the launch
    scatters, or gathers), the terms of its filters and those of them a
    bit row answers."""

    plan: np.ndarray
    tiles: int
    terms: int
    bit_terms: int
    lead_rows: int = 0  # candidate slots, where the launch leads


def _put_bit_row_slots(plan: np.ndarray, ji: int, slot: int, rows) -> int:
    """One clause answered from bit rows, written into row `ji` of a
    filter plan from `slot` on: the first row opens the clause, the
    others join it. -> the next free slot."""
    S = (plan.shape[1] - 1) // 3
    for i, row in enumerate(rows):
        plan[ji, slot] = row
        plan[ji, 2 * S + slot] = FILTER_BIT_JOINS if i else FILTER_BIT_OPENS
        slot += 1
    return slot


def pack_filter_plans(pf, filters, rows: int,
                      bit_rows: FilterBitRows = FilterBitRows()) -> FilterPlans:
    """`knn_filter_mask`'s plan int32[rows, 3 * S + 1] for one launch
    over one segment: `pf` the filter field's PostingsField there,
    `filters` each job's clauses (tuples of terms;
    batcher.KeywordFilter.clauses), S the slot bucket of the widest,
    `bit_rows` the rows the field holds there. A slot names a tile range
    to scatter or a bit row to read. A clause whose every term the
    segment holds has a row rides the rows (one term: its row; several:
    their OR); any other clause is scattered whole: one term feeds the
    count plane's term counter, the d-th scattered clause of several
    terms its digit d (the planner admits at most CLAUSE_DIGITS of them,
    of CLAUSE_TERMS_MAX terms each); a term the segment does not hold
    keeps an empty range."""
    S = filter_slot_bucket(max(sum(map(len, f)) for f in filters))
    plan = np.zeros((rows, 3 * S + 1), np.int32)
    row_of = bit_rows.row_of_term
    tiles = terms = bit_terms = 0
    for ji, clauses in enumerate(filters):
        slot = digit = 0
        for clause in clauses:
            terms += len(clause)
            tids = [tid for tid in map(pf.term_id, clause) if tid >= 0]
            if tids and all(tid in row_of for tid in tids):
                slot = _put_bit_row_slots(
                    plan, ji, slot, [row_of[tid] for tid in tids])
                bit_terms += len(tids)
                continue
            unit = 1
            if len(clause) > 1:
                unit = 1 << (COUNT_TERM_BITS + CLAUSE_DIGIT_BITS * digit)
                digit += 1
            for tid in tids:
                plan[ji, slot] = pf.term_tile_start[tid]
                plan[ji, S + slot] = pf.term_tile_count[tid]
                tiles += int(pf.term_tile_count[tid])
                plan[ji, 2 * S + slot] = unit
                slot += 1
        plan[ji, 3 * S] = len(clauses)
    return FilterPlans(plan, tiles, terms, bit_terms)


def filter_row_masks(
    doc_ids: jax.Array,  # int32[n_tiles, 128] the filter field's postings
    cand: jax.Array,  # bool[N] rows that hold a vector and are live
    plan: jax.Array,  # int32[B, 3 * S + 1]
    bits: Optional[jax.Array] = None,  # uint32[R, W] FilterBitRows.plane
) -> Tuple[jax.Array, jax.Array]:
    """Each query row's candidate mask under its own filter, built on
    the device from the filter field's postings tiles and bit rows:
    (bool[B, N], rows passed int32[B]). Traced inside its caller: the
    knn family's mask program `knn_filter_mask`, and the fused text
    program of a launch whose jobs carry a filter (`_fused_query_mf`).

    A row of `plan` holds S term slots and the number of clauses a
    document must match (0 on a pad row, whose mask is empty). A slot
    is (start, count, unit). unit > 0: a term's contiguous tile range
    (its first tile and length; 0 tiles = a term the segment does not
    hold) whose postings add `unit` to the count plane (`clause_units`'
    units: 1 for a clause of one term, a digit's unit for a clause of
    several, which counts once however many of its terms a document
    holds). unit < 0: `start` is a row of `bits`, which opens a clause
    (FILTER_BIT_OPENS) or joins the one the bit-row slot before it
    opened (FILTER_BIT_JOINS). unit 0: unused; the used slots come
    first. The mask is `clauses_hit(cnt) >= the scattered clauses` &
    the AND, over the bit-row clauses, of the OR of each clause's rows
    & cand.

    The rows' tile lists are never uploaded: a term's tiles are
    consecutive, so trip t takes tiles [t * C, (t + 1) * C) of the row's
    concatenated ranges, found from the ranges' running sums, gathers
    their doc ids and scatter-adds their slot's unit into the flat count
    plane (`_add_rare_tiles`' layout: row b's document d at
    b * (N + 1) + d, pad postings spill at b * (N + 1) + N). The trip
    count is the longest row's, a value of the launch: one program
    serves a tag of one tile and one of tens of thousands. Where the
    field holds bit rows, a launch none of whose rows names a tile
    (another value of the launch) neither zeroes, counts nor masks the
    plane: its masks are the unpacked rows alone. Without `bits` (a
    segment too small for rows, or none held) the program is the one it
    always was."""
    n = cand.shape[0]
    B = plan.shape[0]
    S = (plan.shape[1] - 1) // 3
    C = FILTER_CHUNK
    starts, counts = plan[:, :S], plan[:, S : 2 * S]
    units, need = plan[:, 2 * S : 3 * S], plan[:, 3 * S]
    ends = jnp.cumsum(counts, axis=1)  # [B, S]
    begins = ends - counts
    total = ends[:, -1]
    last_tile = doc_ids.shape[0] - 1
    row_base = (jnp.arange(B, dtype=jnp.int32) * (n + 1))[:, None, None]
    lane = jnp.arange(C, dtype=jnp.int32)[None, :]

    def trip(t, cnt):
        i = t * C + lane  # [1, C] positions in a row's tile list
        slot = jnp.sum(i[:, :, None] >= ends[:, None, :], axis=2)  # [B, C]
        slot = jnp.minimum(slot, S - 1)
        tile = (jnp.take_along_axis(starts, slot, axis=1)
                + i - jnp.take_along_axis(begins, slot, axis=1))
        used = i < total[:, None]
        rows_d = doc_ids[jnp.clip(tile, 0, last_tile)]  # [B, C, 128]
        valid = (rows_d >= 0) & used[:, :, None]
        tgt = (jnp.where(valid, rows_d, n) + row_base).ravel()
        unit = jnp.take_along_axis(units, slot, axis=1)[:, :, None]
        return cnt.at[tgt].add(jnp.where(valid, unit, 0).ravel())

    def scattered(need):
        cnt = jax.lax.fori_loop(
            0, (jnp.max(total) + C - 1) // C, trip,
            jnp.zeros(B * (n + 1), jnp.int32),
        )
        return clauses_hit(_doc_planes(cnt, B, n)) >= need[:, None]

    def masks(hit, in_rows=None):
        # hit bool[B, N] or [B, 1]: the row's scattered clauses hold
        mask = hit & (need > 0)[:, None] & cand[None, :]
        if in_rows is not None:
            mask = mask & _unpack_bit_rows(in_rows, n)
        return mask, mask.sum(axis=1, dtype=jnp.int32)

    if bits is None:
        return masks(scattered(need))

    def slot_rows(s, carry):
        met, clause = carry  # uint32[B, W]: the clauses closed; the open one
        unit = jax.lax.dynamic_index_in_dim(units, s, 1, keepdims=False)
        row = jnp.take(
            bits, jax.lax.dynamic_index_in_dim(starts, s, 1, keepdims=False),
            axis=0, mode="clip")
        row = jnp.where((unit < 0)[:, None], row, jnp.uint32(0))
        opens = (unit == FILTER_BIT_OPENS)[:, None]
        return (jnp.where(opens, met & clause, met),
                jnp.where(opens, row, clause | row))

    ones = jnp.full((B, bits.shape[1]), 0xFFFFFFFF, jnp.uint32)
    met, clause = jax.lax.fori_loop(
        0, jnp.max(jnp.sum(units != 0, axis=1)), slot_rows, (ones, ones))
    in_rows = met & clause
    scatters = need - jnp.sum(units == FILTER_BIT_OPENS, axis=1)
    return jax.lax.cond(
        jnp.max(total) > 0,
        lambda: masks(scattered(scatters), in_rows),
        lambda: masks((scatters <= 0)[:, None], in_rows),
    )


@jax.jit
def knn_filter_mask(doc_ids, cand, plan, bits=None):
    """`filter_row_masks` as a program of its own: the knn family's mask
    launch in front of its scan, and the launch a bit row is built by."""
    return filter_row_masks(doc_ids, cand, plan, bits)


def build_filter_bit_rows(doc_ids, term_tile_start, term_tile_count, held,
                          n_docs: int) -> FilterBitRows:
    """Rows for the terms `held` (row r = held[r]), built ON the device
    from the resident doc-id tiles: a row is the packed mask
    `knn_filter_mask` scatters for a filter of its term alone over
    every document (a launch a term: the launch's 0.96 ms and 13 ns a
    posting slot at 10M documents), so "the row == the scattered mask"
    holds by construction."""
    S = FILTER_SLOT_BUCKETS[0]
    everyone = jnp.ones(n_docs, jnp.bool_)
    rows = []
    for tid in held:
        plan = np.zeros((1, 3 * S + 1), np.int32)
        plan[0, 0], plan[0, S] = term_tile_start[tid], term_tile_count[tid]
        plan[0, 2 * S] = plan[0, 3 * S] = 1  # one clause of one term
        mask, _ = knn_filter_mask(doc_ids, everyone, plan)
        rows.append(_pack_bit_rows(mask))
    return FilterBitRows(
        jnp.concatenate(rows), {int(t): r for r, t in enumerate(held)})


@jax.jit
def knn_row_norms(vectors: jax.Array) -> jax.Array:
    """float32[N] `sum(v * v)` of integer-typed stored rows, built once
    when a field's rows are uploaded (executor_jax.DeviceSegment): the
    plane `knn_scores` takes as `norms`. Every square is at most 128^2
    and every sum at most d x 128^2, a whole number float32 holds for
    d <= 1024, so the plane equals what `knn_scores` computes inside its
    program bit for bit, in whatever order either sums. Float rows get
    none: another program would sum theirs in another order."""
    v = vectors.astype(jnp.float32)
    return jnp.sum(v * v, axis=1)


# `_block_topk` views a [B, N] plane as groups of KNN_BLOCK rows of
# KNN_BLOCK lanes; a block is one lane of one group.
KNN_BLOCK = 128


def knn_block_select(n: int, k: int) -> bool:
    """Whether `knn_topk_filtered` over `n` stored rows selects its top
    `k` from block maxima: from 8 x k whole blocks on. Below that the
    k chosen blocks are an eighth of the plane or more and the plain
    `top_k` stays (a segment of 20,000 rows gains nothing at k = 128).
    Read from the shapes at trace time; the batcher counts launches by
    it."""
    return n // (KNN_BLOCK * KNN_BLOCK) * KNN_BLOCK >= 8 * k


def _block_topk(masked: jax.Array, k: int) -> Tuple[jax.Array, jax.Array]:
    """`lax.top_k(masked, k)` over a wide [B, N] plane without sorting
    it. The plane's first columns are viewed as [groups, G, L] (G = L =
    KNN_BLOCK): a block is the G columns of one lane of one group (a
    stride of L apart), its maximum an elementwise maximum over the
    group's G rows: no reduction across lanes, and the view compiles in
    seconds at every row bucket (blocks of L consecutive columns cost
    the same on the device and 25-45 s of compile from two rows on:
    PERF.md section 6, PR 42). Theta is the k-th largest block maximum;
    the top k are selected among the k x G scores of the k blocks of
    the largest maxima (their groups gathered whole, G x L contiguous
    floats each, the block's lane picked out after) and the columns
    past the last whole group. Exact: the k chosen maxima are k scores
    >= theta, so the k-th best score is >= theta, and a block whose
    maximum is > theta is among the chosen; a score EQUAL to theta in a
    block not chosen ties the k-th, which `top_k` cuts either way too.
    Sorted by score descending; -inf (and an arbitrary column) where
    fewer than k columns hold a score."""
    B, n = masked.shape
    G = L = KNN_BLOCK
    groups = n // (G * L)
    m = groups * G * L
    body = masked[:, :m].reshape(B, groups, G, L)
    _, chosen = jax.lax.top_k(body.max(axis=2).reshape(B, groups * L), k)
    group, lane = chosen // L, (chosen % L)[:, :, None]  # [B, k], [B, k, 1]
    slabs = jnp.take_along_axis(body, group[:, :, None, None], axis=1)
    in_lane = jnp.arange(L, dtype=jnp.int32) == lane[:, :, :, None]
    cand = jnp.max(jnp.where(in_lane, slabs, -jnp.inf), axis=3)  # [B, k, G]
    rows = group[:, :, None] * G + jnp.arange(G, dtype=jnp.int32)
    cand, cols = (x.reshape(B, k * G) for x in (cand, rows * L + lane))
    if m < n:
        tail = jnp.arange(m, n, dtype=jnp.int32)
        cand = jnp.concatenate([cand, masked[:, m:]], axis=1)
        cols = jnp.concatenate(
            [cols, jnp.broadcast_to(tail, (B, n - m))], axis=1)
    s, pos = jax.lax.top_k(cand, k)
    return s, jnp.take_along_axis(cols, pos, axis=1)


@functools.partial(jax.jit, static_argnames=("similarity", "k"))
def knn_topk_filtered(
    queries: jax.Array,  # float32[B, d] (padded rows are zeros)
    vectors: jax.Array,  # [N, d] float rows, or int8 of a byte field
    mask: jax.Array,  # bool[B, N]: each row's own candidates
    similarity: str,
    k: int,
    norms: Optional[jax.Array] = None,  # float32[N], see knn_scores
) -> Tuple[jax.Array, jax.Array]:
    """`knn_topk_batch` where every query row brings a candidate mask of
    its own (`knn_filter_mask`): every row of `vectors` is scored, the
    rows a filter passes compete. (scores[B, k], docs[B, k]). The scan
    path of a filtered launch: one whose rows all lead by short
    postings never gets here (`knn_topk_lead`). The
    program does only what depends on the query, in one pass over the
    rows: their norms come as an operand where the field holds integers,
    and a plane wide enough (`knn_block_select`) is never sorted: its top
    k are selected from block maxima (`_block_topk`)."""
    scores = knn_scores(queries, vectors, similarity, norms)
    masked = jnp.where(mask, scores, -jnp.inf)
    if knn_block_select(masked.shape[1], k):
        return _block_topk(masked, k)
    return jax.lax.top_k(masked, k)


# The lead route of a filtered kNN launch (`pack_lead_plans`,
# `knn_topk_lead`). Measured on the TPU v5e at the filtered cell's
# shapes (10M x 192 int8 rows held along the lanes, one query row,
# top 112; scripts/probe_knn_lead.py, the profiler's device ms a launch;
# PERF.md section 6, PR 50), by the lead's candidate slots 128 / 1,024 /
# 2,048 / 8,192 / 32,768: the lead alone 0.24 / 0.30 / 0.37 / 0.81 /
# 3.22; with a bit-row clause checked 0.31 / 0.37 / 0.44 / 0.88 / 3.49;
# a second range of 64 tiles checked costs what a bit row costs (+0.05 at
# 8,192; the lead is the shorter of two ranges); 78,080 slots read 8.2;
# beside them one mask launch and one scan, 4.04 (the mask 0.89). So
# ~0.22 ms a launch and ~0.09 us a slot (the block fetch 0.072 of it:
# ops/pallas_lead.py), against 0.40 ns a scanned row: a slot costs what
# ~230 scanned rows cost, and a lead pays while each of its slots stands
# for KNN_LEAD_SCAN_ROWS rows of the segment or more (39,062 slots, 305
# tiles at 10M rows: 3.8 ms against the scan's 4.04 and its second
# launch's ~0.3 ms of host time; the cell's median lead is 8 tiles). The
# program as first written, a row gather `vectors[docs]`, read 10.1 ms
# whatever the lead: the compiler relays the whole matrix out (2.56 GB),
# so the rows are fetched block by block. A verified range is compared
# whole with every candidate (64 tiles: 8,192 x 8,192 compares a trip).
KNN_LEAD_SCAN_ROWS = 256
KNN_LEAD_VERIFY_TILES_MAX = 64
# Lead tiles a trip of `knn_topk_lead` takes over all its query rows. A
# trip gathers over all its slots whatever the lead holds: 16 tiles a
# trip read 0.09 / 0.15 / 0.22 / 0.88 / 3.49 on the line above (short
# leads 0.15 ms sooner, long ones 0.07-0.27 later) and the cell the same
# to 1% (p50 4.28 against 4.34 ms, 192.9 against 192.3 req/s).
KNN_LEAD_CHUNK = 64


def knn_lead_tiles_max(n_docs: int) -> int:
    """The longest lead, in postings tiles, over a segment of `n_docs`
    stored rows: each of its slots must stand for KNN_LEAD_SCAN_ROWS
    rows the scan would read (305 tiles at 10M rows; no lead but an
    empty one under 32,768 rows, where a scan costs next to nothing)."""
    return n_docs // (KNN_LEAD_SCAN_ROWS * TILE_WIDTH)


def rows_on_lanes(vectors) -> bool:
    """Whether the device holds `vectors[N, d]` with its ROWS along the
    lanes (dimension 0 minor), as the TPU lays out a `byte` field's
    10M x 192 rows: the layout `knn_topk_lead` fetches blocks from
    (ops/pallas_lead.py). Host arrays and row-major device arrays (every
    CPU array) say no, and are gathered by row."""
    layout = getattr(getattr(vectors, "format", None), "layout", None)
    return (layout is not None
            and tuple(layout.major_to_minor) == (1, 0)
            and vectors.shape[0] >= TILE_WIDTH)


def pack_lead_plans(pf, filters, rows: int, n_docs: int,
                    bit_rows: FilterBitRows = FilterBitRows(),
                    ) -> Optional[FilterPlans]:
    """`knn_topk_lead`'s plan for one launch over one segment of
    `n_docs` rows, or None where a job of the launch does not LEAD BY
    POSTINGS (the launch then builds masks and scans:
    `pack_filter_plans`). Other arguments and the layout as
    `pack_filter_plans`', int32[rows, 3 * S + 1]; slot 0 is the lead.

    The rows an AND of clauses can pass are among the postings of any
    one of them, so a job whose rarest clause is short is answered from
    that clause's rows alone (Lucene's `AbstractKnnVectorQuery
    .exactSearch` over a conjunction led by its cheapest iterator). A
    job's lead is the clause of fewest tiles among its clauses of ONE
    term that holds no bit row. The job leads when the lead has at most
    `knn_lead_tiles_max(n_docs)` tiles and every other clause can be
    checked a candidate at a time: a bit-row clause
    (`pack_filter_plans`' test: slots of unit FILTER_BIT_OPENS /
    FILTER_BIT_JOINS) or one more term of at most
    KNN_LEAD_VERIFY_TILES_MAX tiles (a tile-range slot, unit 1). A
    clause of several terms that hold no rows could repeat a document:
    such a job does not lead. A clause of one term the segment does not
    hold passes nothing whatever the others say: the job leads by an
    empty range. All read from the launch's own inputs (the field's
    tile counts on this segment, its rows); nothing is set.

    `tiles` counts the leads' and the verified ranges' tiles,
    `lead_rows` the candidate slots the launch scores."""
    S = filter_slot_bucket(max(sum(map(len, f)) for f in filters))
    plan = np.zeros((rows, 3 * S + 1), np.int32)
    row_of = bit_rows.row_of_term
    lead_max = knn_lead_tiles_max(n_docs)
    tiles = terms = bit_terms = lead_tiles = 0
    for ji, clauses in enumerate(filters):
        terms += sum(map(len, clauses))
        ranges, in_rows = [], []  # (tiles, first tile); bit-row clauses
        for clause in clauses:
            tids = [tid for tid in map(pf.term_id, clause) if tid >= 0]
            if tids and all(tid in row_of for tid in tids):
                in_rows.append(tids)
            elif len(clause) == 1:
                ranges.append(
                    (int(pf.term_tile_count[tids[0]]),
                     int(pf.term_tile_start[tids[0]])) if tids else (0, 0))
            else:
                return None
        ranges.sort()
        if not ranges or ranges[0][0] > lead_max:
            return None
        if ranges[0][0] == 0:
            ranges, in_rows = ranges[:1], []
        elif any(c > KNN_LEAD_VERIFY_TILES_MAX for c, _start in ranges[1:]):
            return None
        lead_tiles += ranges[0][0]
        for slot, (count, start) in enumerate(ranges):
            plan[ji, slot], plan[ji, S + slot] = start, count
            plan[ji, 2 * S + slot] = 1
            tiles += count
        slot = len(ranges)
        for tids in in_rows:
            slot = _put_bit_row_slots(
                plan, ji, slot, [row_of[tid] for tid in tids])
            bit_terms += len(tids)
        plan[ji, 3 * S] = len(clauses)
    return FilterPlans(plan, tiles, terms, bit_terms, lead_tiles * TILE_WIDTH)


@functools.partial(
    jax.jit, static_argnames=("similarity", "k", "blocks", "interpret"))
def knn_topk_lead(
    queries: jax.Array,  # float32[B, d] (padded rows are zeros)
    vectors: jax.Array,  # [N, d] float rows, or int8 of a byte field
    norms: Optional[jax.Array],  # float32[N], see knn_scores
    cand: jax.Array,  # bool[N] rows that hold a vector and are live
    doc_ids: jax.Array,  # int32[n_tiles, 128] the filter field's postings
    bits: Optional[jax.Array],  # uint32[R, W] FilterBitRows.plane
    plan: jax.Array,  # int32[B, 3 * S + 1] of pack_lead_plans
    similarity: str,
    k: int,
    blocks: bool = False,  # rows_on_lanes(vectors)
    interpret: bool = False,  # the block kernel off the chip (tests)
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """A filtered kNN launch every row of which leads by postings
    (`pack_lead_plans`): each row's lead clause's documents are
    gathered, checked against its other clauses and scored; no other
    stored row is scored, no mask plane and no score plane is made.
    (scores[B, k], docs[B, k], rows passed int32[B]): what
    `knn_filter_mask` + `knn_topk_filtered` return for the same filters,
    -inf (and an arbitrary document) where fewer than k rows pass.

    Trip t takes the next KNN_LEAD_CHUNK // B tiles of each row's lead
    range (the trip count is the longest row's, a value of the launch,
    as `filter_row_masks`'): their doc ids and those documents' `cand`
    bits are gathered. A candidate fails where it is a pad posting,
    `cand` is false, or one of the row's other clauses misses it: a
    bit-row slot reads bit d // W of word d % W of its row
    (`_pack_bit_rows`' layout; slots that join OR into the clause the
    slot before opened), a tile-range slot looks the document up among
    the range's gathered ids (at most KNN_LEAD_VERIFY_TILES_MAX tiles).
    The candidates left are scored by `knn_scores`' arithmetic: their
    products with the query (`blocks`: from the 128-row block around
    each, fetched by ops/pallas_lead.py where the device holds the rows
    along the lanes; else from the gathered rows), their norms, the
    similarity's transform. Integer rows are cast here; whole numbers
    of 8 bits, so the sums are exact in whatever order and equal the
    scan's bit for bit; float rows agree to KNN_SCORE_RTOL. The trip's
    scores fold into the running top k: a lead's postings ascend, so
    `top_k`'s preference for the earlier of equal scores is the scan's
    preference for the lower document."""
    n = cand.shape[0]
    B = plan.shape[0]
    S = (plan.shape[1] - 1) // 3
    V = KNN_LEAD_VERIFY_TILES_MAX
    chunk = max(KNN_LEAD_CHUNK // B, 1)
    M = chunk * TILE_WIDTH
    starts, counts = plan[:, :S], plan[:, S : 2 * S]
    units, need = plan[:, 2 * S : 3 * S], plan[:, 3 * S]
    last_tile = doc_ids.shape[0] - 1
    lane = jnp.arange(chunk, dtype=jnp.int32)[None, :]
    queries = _knn_unit_queries(queries, similarity)
    nothing = jnp.zeros((B, M), jnp.bool_)

    def others(docs):
        """bool[B, M]: the row's clauses past the lead hold docs[B, M]."""
        def in_rows(row):
            w = bits.shape[1]
            words = jnp.take(bits, row, axis=0, mode="clip")  # [B, W]
            word = jnp.take_along_axis(words, docs % w, axis=1)
            return ((word >> (docs // w).astype(jnp.uint32)) & 1) > 0

        def in_range(start, count):
            held = jnp.arange(V, dtype=jnp.int32)[None, :]  # [1, V]
            ids = doc_ids[jnp.clip(start[:, None] + held, 0, last_tile)]
            ids = jnp.where((held < count[:, None])[:, :, None], ids, -1)
            return jnp.any(
                docs[:, :, None] == ids.reshape(B, 1, V * TILE_WIDTH),
                axis=2)

        def slot(s, carry):
            met, clause = carry  # bool[B, M]: clauses closed; the open one
            a, count, unit = (
                jax.lax.dynamic_index_in_dim(x, s, 1, keepdims=False)
                for x in (starts, counts, units))
            hit = jax.lax.cond(
                jnp.any(unit > 0), lambda: in_range(a, count),
                lambda: nothing)
            if bits is not None:
                hit = jnp.where(
                    (unit < 0)[:, None],
                    jax.lax.cond(jnp.any(unit < 0), lambda: in_rows(a),
                                 lambda: nothing),
                    hit)
            opens = ((unit > 0) | (unit == FILTER_BIT_OPENS))[:, None]
            joins = (unit == FILTER_BIT_JOINS)[:, None]
            return (jnp.where(opens, met & clause, met),
                    jnp.where(opens, hit,
                              jnp.where(joins, clause | hit, clause)))

        ones = jnp.ones((B, M), jnp.bool_)
        met, clause = jax.lax.fori_loop(
            1, jnp.max(jnp.sum(units != 0, axis=1)), slot, (ones, ones))
        return met & clause

    def dots_by_row(docs):
        """(q . each candidate's gathered row, the rows' own `sum(v *
        v)` where l2_norm has no norm plane to read)."""
        rows = vectors[docs]  # [B, M, d]
        if jnp.issubdtype(rows.dtype, jnp.integer):
            rows = rows.astype(jnp.float32)
        v2 = None
        if similarity == "l2_norm" and norms is None:
            v2 = jnp.sum(rows * rows, axis=2)
        return jnp.einsum("bd,bmd->bm", queries, rows), v2

    def dots_by_block(docs, ok, left):
        """q . each candidate still `ok`, picked out of its block's 128
        products; `left` the slots of each row's range from this trip
        on. The rows past the last whole block (fewer than 128) are
        multiplied whole."""
        from .pallas_lead import BLOCK, block_dots

        whole = n // BLOCK
        blk = jnp.where(ok, jnp.minimum(docs // BLOCK, whole - 1), -1)
        # the candidate's lane, picked by a compare and a sum over the
        # block's 128 products (a gather of M elements costs 8 ns each)
        lanes = (jnp.arange(BLOCK, dtype=jnp.int32)
                 == (docs % BLOCK)[:, :, None])
        dots = jnp.sum(
            jnp.where(lanes, block_dots(
                queries, vectors, blk, jnp.clip(left, 0, M),
                interpret=interpret), 0.0), axis=2)
        if whole * BLOCK < n:
            tail = vectors[whole * BLOCK:].astype(jnp.float32)
            at = jnp.clip(docs - whole * BLOCK, 0, n - whole * BLOCK - 1)
            dots = jnp.where(
                docs >= whole * BLOCK,
                jnp.take_along_axis(queries @ tail.T, at, axis=1), dots)
        return dots

    def trip(t, carry):
        top_s, top_d, passed = carry
        i = t * chunk + lane  # [1, chunk] positions in a row's lead range
        tile = jnp.clip(starts[:, :1] + i, 0, last_tile)
        docs = doc_ids[tile]  # [B, chunk, 128]
        ok = ((docs >= 0) & (i < counts[:, :1])[:, :, None]).reshape(B, M)
        docs = jnp.maximum(docs, 0).reshape(B, M)
        ok = ok & (need > 0)[:, None] & cand[docs] & others(docs)
        if blocks and not (similarity == "l2_norm" and norms is None):
            dots, v2 = dots_by_block(
                docs, ok, (counts[:, 0] - t * chunk) * TILE_WIDTH), None
        else:
            dots, v2 = dots_by_row(docs)
        if similarity == "l2_norm":
            q2 = jnp.sum(queries * queries, axis=1, keepdims=True)
            scores = _knn_l2_scores(
                q2, norms[docs] if v2 is None else v2, dots)
        else:
            scores = _knn_dot_scores(dots, similarity)
        scores = jnp.where(ok, scores.astype(jnp.float32), -jnp.inf)
        top_s, pos = jax.lax.top_k(
            jnp.concatenate([top_s, scores], axis=1), k)
        top_d = jnp.take_along_axis(
            jnp.concatenate([top_d, docs], axis=1), pos, axis=1)
        return top_s, top_d, passed + ok.sum(axis=1, dtype=jnp.int32)

    return jax.lax.fori_loop(
        0, (jnp.max(counts[:, 0]) + chunk - 1) // chunk, trip,
        (jnp.full((B, k), -jnp.inf, jnp.float32),
         jnp.zeros((B, k), jnp.int32), jnp.zeros(B, jnp.int32)),
    )


@functools.partial(jax.jit, static_argnames=("similarity", "k"))
def knn_topk(
    queries: jax.Array,  # float32[B, d]
    vectors: jax.Array,  # float32[N, d] (unit-normalized for cosine)
    exists: jax.Array,  # bool[N]
    similarity: str,
    k: int,
) -> Tuple[jax.Array, jax.Array]:
    """Brute-force kNN: one MXU matmul + top_k per query batch."""
    scores = knn_scores(queries, vectors, similarity)
    scores = jnp.where(exists[None, :], scores, -jnp.inf)
    return jax.lax.top_k(scores, k)
