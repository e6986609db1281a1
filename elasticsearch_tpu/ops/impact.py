"""Impact-tile scoring kernels for learned sparse retrieval.

GPUSparse (PAPERS.md 2606.26441) serves SPLADE-style learned sparse
queries from accelerator-resident impact tiles; BM25S (2407.03618)
shows that with impacts precomputed at index time, query-time scoring
is pure gather + weighted sum. This module is the query side of the
`sparse_vector` subsystem (index side: index/segment.SparseField,
ops/index_build.sparse_planes_device):

  gather impact tiles for the query's terms (XLA gather from the
  HBM-resident [n_tiles, 128] planes, int8 or fp32 — the kernel casts
  to f32 AFTER the gather so the int8 column keeps its 4x HBM saving)
  → contribution = query_weight * impact on the VPU
  → scatter-add into a dense per-doc accumulator (term-at-a-time)
  → lax.top_k (ties broken by lowest index = doc asc).

`ImpactScorer` hands the tile lists of a scoring to ONE launch of
`_impact_chunk_add` a row bucket: the lists ride one staged int32 plan
of TILE_CAP tiles a query row (tile ids, -1 past a row's last, and the
weights' bits), and the program loops over the tiles the plan uses,
TILE_STEP at a trip, on flat donated accumulators; a row longer than
TILE_CAP takes a further launch. Rows ride the power-of-two bucket
ladder ops/scoring's scorers ride, and finalize reuses the ONE finalize
kernel so its device triples feed ops/scoring.merge_segment_topk
unchanged.

`SparseBlockMax` is the ops/wand.py analog for impact-ordered tiles.
Because every term's postings are sorted by impact DESC, the per-tile
`tile_max` sidecar is non-increasing within a term and the term's
global maximum lives in its FIRST tile. Those first tiles alone give
theta = kth best partial score, and the HOST reads it from the ≤ terms
x 128 postings it already holds (`SparseBlockMax.host_theta`): no
launch, no download, so the device runs one pass, over the surviving
tiles. A tail tile of term t is dropped iff

    qw_t * tile_bound[tile] + sum_{t' != t} qw_t' * term_max_t' < theta

A doc occurs at most once in a term's postings, so that bound caps the
doc's TOTAL score: dropped docs score strictly below theta and can
never displace the top-k — the surviving-hits answer is EXACT. The
COUNT of matches is not: docs that only dropped tiles hold go
uncounted, so the caller (search/batcher._dispatch_sparse_group) lets a
job drop tiles only where its reported `hits.total` cannot move by it
(totals untracked, or some query term's postings alone prove more
matches than `track_total_hits` counts to).

Hot terms of the int8 column leave the scatter (`ImpactRows`). A
scatter-add is serial on the chip (~7 ns a slot), so a term frequent
enough (the caller's rule, executor_jax.impact_scorer: the text
family's df threshold and HBM budget) holds a dense ROW: one int8 a
document, the stored `q` where the term has a posting and ROW_ABSENT
(-128, which the quantizer's clip to -127..127 never stores) where it
has none, so a posting whose `q` is 0 is still present: it matches and
counts as its tile slot does. Rows are built ON the device from the
resident tile planes (`build_impact_rows`), and `_impact_dense_add`
streams a query's rows into the accumulators: per row `acc += tw *
f32(q)`, `cnt += 1` where present — the product `impact_tile_contrib`
forms, the same postings in another layout. A query's terms without a
row (and its hot terms past DENSE_SLOTS) go through their tiles as
before, and so does every term of the float32 column, whose bit
equality with the oracle rests on pure term order. A document's score
is therefore the same set of float32 addends in another order: its
rows' products first, in term order, then its tiles' in term order
(`SparseBlockMax.kept`). `host_theta`'s soundness argument covers any
order; block-max bounds still sum EVERY term's maximum and hot terms
are never dropped, which keeps more tiles, never fewer.

Every host<->device transfer of the family is noted where it happens
(`common/tracing.note_transfer`): the one staged plan a chunk launch
uploads and the two planes of a row launch; the packed collect notes
itself in ops/scoring.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..common.tracing import launch, note_transfer
from .scoring import BPAD, _finalize, _to_host

TILE_WIDTH = 128

# Per posting slot the impact kernel does ~4 flops (int8→f32 cast,
# weight multiply, validity select, scatter add) — the BM25S payoff row:
# ops/scoring counts 6 for the text kernel because of the norm math
# this layout folded into the index.
FLOPS_PER_IMPACT_SLOT = 4


# Hot-term row slots a query row carries into `_impact_dense_add`: a
# compile shape like scoring.FUSED_H. The program loops over the slots a
# launch USES, so an unused slot costs nothing but 8 bytes of upload; a
# query's hot terms past it (the least frequent of them) go through
# their tiles, which stay resident: an overflow costs time, never an
# answer. In the SPLADE deployment (1,069 rows held at 1M passages) a
# 49-token query holds ~16 hot terms, a 128-token one ~41.
DENSE_SLOTS = 64
ROW_ABSENT = -128  # no posting: below the quantizer's -127..127
# A row's stride in the plane: past n_docs + 1 (the accumulators'
# width) to a multiple of 4096, so every row starts on a tile boundary
# of the 8-bit layout and a launch streams the rows it names, not their
# neighbours.
ROW_ALIGN = 4096
ROWS_FILL_TILES = 32768  # tiles a build launch scatters into the plane


def sparse_flops(n_tile_slots: int, n_row_slots: int = 0) -> int:
    """Estimated useful flops of one sparse job's plan on one segment:
    its tiles' posting slots and its rows' document slots."""
    return (
        n_tile_slots * TILE_WIDTH + n_row_slots
    ) * FLOPS_PER_IMPACT_SLOT


# Tiles a query row hands ONE launch of `_impact_chunk_add` (the staged
# plan's width, a compile shape) and tiles a trip of its loop gathers and
# scatters. The program runs the trips its plan USES, so a short query
# pays for its own tiles rounded up to a trip, whatever TILE_CAP is; a
# row longer than TILE_CAP takes a further launch. Measured on the TPU
# v5e at 1M documents, one query row, int8 (PERF.md section 6, PR 52,
# Step 0; device ms a tile pass by tiles in use 1 / 128 / 512 / 1,264 /
# 2,048 / 4,096): trips of 128 0.457 / 0.391 / 1.077 / 2.458 / 3.822 /
# 7.480, of 256 0.747 / 0.681 / 1.067 / 2.432 / 3.780 / 7.398, of 512
# 1.328 / 1.262 / 1.063 / 3.003 / 3.763 / 7.362; launches of a fixed
# 512 tiles (the program before) 1.327 / 1.261 / 1.062 / 3.328 / 4.250
# / 8.500. A launch costs 0.16 ms fixed (the planes relaid in and out),
# a trip ~5 us, a tile in use 1.74 us and a PAD tile 2.24 (its 128
# slots meet in the one spill cell), so the step sets the padding of the
# last trip, half a step on average, against 5 us a trip: 128 (0.14 ms
# of padding, ten trips a 1,264-tile query) reads within 0.03 ms of 256
# where both pad alike and 0.3 under it where 256 pads a trip more (at
# 1,100 / 1,200 / 1,300 / 1,400 tiles, trips of 64 2.295 / 2.393 / 2.642
# / 2.741, of 128 2.247 / 2.491 / 2.734 / 2.682, of 256 2.517 / 2.465 /
# 2.998 / 2.946: means 2.52 / 2.54 / 2.73). A plan of 2,048 tiles costs
# the host what one of 4,096 does (the call, not the 32 KB) and a
# 4,096-tile query a second launch's 0.16 ms.
TILE_CAP = 4096
TILE_STEP = 128


def chunk_launches(tile_lists) -> int:
    """`_impact_chunk_add` launches `ImpactScorer.score_into` makes for
    these per-row tile lists: the longest row, TILE_CAP tiles a launch."""
    t_max = max((len(t) for t in tile_lists), default=0)
    return -(-t_max // TILE_CAP)


def tile_trips(tile_lists) -> int:
    """Trips of TILE_STEP tiles those launches' loops run: every row
    rides every trip, so the longest row decides, launch by launch."""
    t_max = max((len(t) for t in tile_lists), default=0)
    full, rest = divmod(t_max, TILE_CAP)
    return full * (TILE_CAP // TILE_STEP) + -(-rest // TILE_STEP)


def impact_tile_contrib(rows_d, rows_v, tw, valid, n_docs):
    """The ONE sparse tile-contribution formula, shared by the chunked
    serving kernel and the mesh SPMD step (parallel/sharded.py) so the
    two paths are float-identical by construction: per posting slot,
    contribution = tw * f32(value). `tw` carries the query-term weight
    (with the per-term dequant scale folded in ON HOST for int8
    columns, so the same kernel serves both storage modes); invalid
    slots score exactly 0 and target the n_docs overflow row."""
    tgt = jnp.where(valid, rows_d, n_docs)
    s = tw * rows_v.astype(jnp.float32)
    return tgt, jnp.where(valid, s, 0.0)


def _impact_tile_loop(doc_ids, values, acc, cnt, plan, step: int):
    """`_impact_chunk_add`'s body at `step` tiles a trip (TILE_STEP in
    the served program; scripts/probe_impact_loop.py measures others).

    A loop over the tiles the plan USES, `step` at a trip, everything
    proportional to tiles inside it (the gathers of tile rows, the
    product, the two scatter-adds): no tile, no trip. The planes ride
    the loop FLAT, row b's document d at b * (n + 1) + d and the spill
    of its pad slots at b * (n + 1) + n (PR 30's finding in
    `scoring._add_rare_tiles`): the TPU's scatter works on the flat
    plane, so a [B, n + 1] plane is relaid into it and back around
    every scatter; here once on the way into the program and once on
    the way out. Trips go in plan order and a trip's postings in plan
    order, so a document receives its addends in the order the plan
    lists them: the float32 sums of one pass over the whole plan."""
    rows, width = acc.shape
    n_docs = width - 1
    ids = plan[0]
    tws = jax.lax.bitcast_convert_type(plan[1], jnp.float32)
    slots = jnp.arange(1, ids.shape[1] + 1, dtype=jnp.int32)
    used = jnp.max(jnp.where(ids >= 0, slots, 0))
    last_tile = doc_ids.shape[0] - 1
    row_base = (jnp.arange(rows, dtype=jnp.int32) * width)[:, None, None]

    def trip(i, carry):
        acc, cnt = carry
        ti = jax.lax.dynamic_slice_in_dim(ids, i * step, step, axis=1)
        tw = jax.lax.dynamic_slice_in_dim(tws, i * step, step, axis=1)
        safe = jnp.clip(ti, 0, last_tile)
        rows_d = doc_ids[safe]  # [B, step, 128]
        valid = (rows_d >= 0) & (ti >= 0)[:, :, None]
        tgt, s = impact_tile_contrib(
            rows_d, values[safe], tw[:, :, None], valid, n_docs
        )
        tgt = (tgt + row_base).ravel()
        acc = acc.at[tgt].add(s.ravel())
        cnt = cnt.at[tgt].add(valid.ravel().astype(jnp.int32))
        return acc, cnt

    acc, cnt = jax.lax.fori_loop(
        0, (used + step - 1) // step, trip, (acc.ravel(), cnt.ravel())
    )
    return acc.reshape(rows, width), cnt.reshape(rows, width)


@functools.partial(jax.jit, donate_argnums=(2, 3))
def _impact_chunk_add(doc_ids, values, acc, cnt, plan):
    """(acc, cnt)[B, n+1] += the impact contributions of ONE staged plan
    `plan` i32[2, B, TILE_CAP]: plane 0 a query row's tile ids from slot
    0 up (-1 = unused), plane 1 their folded weights' float32 bits. cnt
    counts matching postings per doc (one per term: the sparse match
    mask is cnt > 0). The trips run are read from the plan, so one
    program serves every tile count of a row bucket."""
    return _impact_tile_loop(doc_ids, values, acc, cnt, plan, TILE_STEP)


@functools.partial(jax.jit, static_argnames=("rows", "width"))
def _impact_zeros(rows: int, width: int):
    """The two accumulator planes of a launch bucket, zeroed: ONE
    program where a scoring starts from its tiles (no row launch makes
    them). Eager `jnp.zeros` are two programs a plane, each a dispatch
    the host pays before the first kernel (PERF.md section 6, PR 46)."""
    return (
        jnp.zeros((rows, width), jnp.float32),
        jnp.zeros((rows, width), jnp.int32),
    )


@functools.partial(jax.jit, static_argnames=("width",))
def _impact_dense_add(plane, ids, tw, width: int):
    """(acc, cnt)[B, width = n+1]: the dense rows a launch names added
    into planes ZEROED HERE, since the row launch is a scoring's first
    program (nothing to donate, no fill in front of it): `ids` i32[B,
    DENSE_SLOTS] rows of `plane` (-1 = unused, filled from slot 0 up),
    `tw` f32[B, DENSE_SLOTS] their folded tile weights. Per slot and
    query row: present = row != ROW_ABSENT, acc += where(present, tw *
    f32(row), 0), cnt += present: `impact_tile_contrib`'s product over
    the same postings, streamed instead of scattered.

    A loop over the slots the launch USES (PR 26's finding in
    `scoring._add_hot_rows`) with one dynamic slice a row of the flat
    plane, whose rows start on tile boundaries: only the rows asked for
    are read. Each query row's two planes ride the loop FLAT (PR 30's
    finding in `scoring._add_rare_tiles`, met again here): measured on
    the TPU v5e at 1M docs, one query row (PERF.md section 6, PR 41),
    adding a flat row slice into the [1, n+1] planes relays them
    through a reshape every slot, 72 us a row; relaid once a launch, as
    `_impact_chunk_add` relays them around its loop, a slot is one
    fused pass a plane, 11 us a row. (Four or eight rows a trip read
    8 us a row: 0.05 ms a launch of 16 rows, not worth the unrolling.)"""
    n_q = ids.shape[0]
    stride = impact_row_stride(width - 1)
    n_rows = plane.shape[0] // stride
    slots = jnp.arange(1, ids.shape[1] + 1, dtype=jnp.int32)
    used = jnp.max(jnp.where(ids >= 0, slots, 0))

    def slot(h, carry):
        accs, cnts = carry
        out_a, out_c = [], []
        for b in range(n_q):
            rid = ids[b, h]
            row = jax.lax.dynamic_slice(
                plane, (jnp.clip(rid, 0, n_rows - 1) * stride,), (width,)
            )
            present = (row != ROW_ABSENT) & (rid >= 0)
            s = tw[b, h] * row.astype(jnp.float32)
            out_a.append(accs[b] + jnp.where(present, s, 0.0))
            out_c.append(cnts[b] + present.astype(jnp.int32))
        return tuple(out_a), tuple(out_c)

    accs, cnts = jax.lax.fori_loop(
        0,
        used,
        slot,
        (
            tuple(jnp.zeros((width,), jnp.float32) for _ in range(n_q)),
            tuple(jnp.zeros((width,), jnp.int32) for _ in range(n_q)),
        ),
    )
    return jnp.stack(accs), jnp.stack(cnts)


def term_tiles(starts: np.ndarray, counts: np.ndarray) -> np.ndarray:
    """int64 tile ids of terms whose contiguous tile ranges begin at
    `starts` and hold `counts` tiles, laid out term after term."""
    counts = counts.astype(np.int64, copy=False)
    first = np.cumsum(counts) - counts  # where each term's range begins
    return np.arange(int(counts.sum()), dtype=np.int64) + np.repeat(
        starts.astype(np.int64, copy=False) - first, counts
    )


def impact_row_stride(n_docs: int) -> int:
    """A row's stride in the plane, which is the device bytes it costs:
    the accumulators' width n_docs + 1 rounded up to ROW_ALIGN."""
    return -(-(n_docs + 1) // ROW_ALIGN) * ROW_ALIGN


@functools.partial(jax.jit, donate_argnums=(0,), static_argnames=("stride",))
def _impact_rows_fill(plane, doc_ids, values, tiles, row_of_tile, stride):
    """Sets the postings of `tiles` (row_of_tile < 0 = padding) into
    the donated flat plane, row by `row_of_tile`."""
    d = doc_ids[tiles]
    ok = (d >= 0) & (row_of_tile >= 0)[:, None]
    at = jnp.where(ok, row_of_tile[:, None] * stride + d, plane.shape[0])
    return plane.at[at.ravel()].set(values[tiles].ravel(), mode="drop")


@dataclass
class ImpactRows:
    """The dense rows one int8 column's hot terms hold on the device
    (module comment): `plane` int8[n_rows * stride], row r the stored
    impacts of term `held[r]` by document, ROW_ABSENT elsewhere;
    `row_of_term` int32[n_terms], -1 for a term without a row."""

    plane: object
    row_of_term: np.ndarray
    n_rows: int

    @property
    def nbytes(self) -> int:
        return int(self.plane.shape[0])


def build_impact_rows(
    doc_ids, values, term_tile_start, term_tile_count, held, n_docs: int
) -> ImpactRows:
    """Rows for the terms `held` (row r = held[r]), built ON the device
    from the resident int8 tile planes `doc_ids` / `values`: nothing is
    uploaded but the tile lists, ROWS_FILL_TILES a launch into the
    donated plane, so the build's temporaries stay a few tens of MB
    whatever the plane holds."""
    held = np.asarray(held, np.int64)
    stride = impact_row_stride(n_docs)
    row_of_term = np.full(len(term_tile_start), -1, np.int32)
    row_of_term[held] = np.arange(len(held), dtype=np.int32)
    counts = term_tile_count[held].astype(np.int64)
    tiles = term_tiles(term_tile_start[held], counts).astype(np.int32)
    total = len(tiles)
    row_of_tile = np.repeat(np.arange(len(held), dtype=np.int32), counts)
    step = min(ROWS_FILL_TILES, 1 << max(total - 1, 0).bit_length())
    plane = jnp.full((len(held) * stride,), ROW_ABSENT, jnp.int8)
    for c0 in range(0, total, step):
        t = np.zeros(step, np.int32)
        r = np.full(step, -1, np.int32)
        m = min(step, total - c0)
        t[:m] = tiles[c0 : c0 + m]
        r[:m] = row_of_tile[c0 : c0 + m]
        plane = _impact_rows_fill(
            plane, doc_ids, values, t, r, stride=stride
        )
    return ImpactRows(plane, row_of_term, len(held))


class ImpactScorer:
    """Batched learned-sparse scoring over one segment's impact-ordered
    tiled postings with fixed launch shapes (one looped program a row
    bucket, whatever the tile counts: see module comment). `rows`
    (ImpactRows, the int8 column's hot terms) serve the terms they hold
    through `add_rows`; without them every term goes through tiles."""

    def __init__(self, doc_ids, values, n_docs: int, live=None):
        self.doc_ids = jnp.asarray(doc_ids)
        # stored dtype (int8 qweights or f32 weights) — cast happens
        # inside the kernel, post-gather
        self.values = jnp.asarray(values)
        self.n_docs = int(n_docs)
        self.live = jnp.asarray(live) if live is not None else None
        # the builder's (executor_jax._impact_rows_build): the rows held
        # and the count of terms that want one
        self.rows: Optional[ImpactRows] = None
        self.rows_wanted = 0
        self._msm: dict = {}  # launch bucket -> int32[rows] of ones

    def row_slots(self, tids: Sequence[int]) -> np.ndarray:
        """int32[len(tids)]: the row each query term is served from, -1
        for a term that goes through its tiles: one without a row, or,
        past DENSE_SLOTS hot terms, the least frequent of them (rows
        number by df rank)."""
        out = np.full(len(tids), -1, np.int32)
        if self.rows is None or not len(tids):
            return out
        out[:] = self.rows.row_of_term[np.asarray(tids, np.int64)]
        hot = np.flatnonzero(out >= 0)
        if len(hot) > DENSE_SLOTS:
            out[hot[np.argsort(out[hot], kind="stable")[DENSE_SLOTS:]]] = -1
        return out

    def add_rows(self, rows: int, row_lists, weight_lists):
        """One `_impact_dense_add` launch, a scoring's first program:
        per query row (≤ `rows`, the launch bucket) the dense rows it
        names (≤ DENSE_SLOTS) and their folded tile weights, into
        accumulators the program zeroes itself."""
        ids = np.full((rows, DENSE_SLOTS), -1, np.int32)
        tw = np.zeros((rows, DENSE_SLOTS), np.float32)
        for j, (rl, wl) in enumerate(zip(row_lists, weight_lists)):
            ids[j, : len(rl)] = rl
            tw[j, : len(rl)] = wl
        note_transfer("h2d", ids.nbytes + tw.nbytes, count=2)
        slots = sum(len(rl) for rl in row_lists)
        with launch("_impact_dense_add", 2, ids.nbytes + tw.nbytes,
                    sparse_flops(0, slots * self.n_docs)):
            return _impact_dense_add(
                self.rows.plane, ids, tw, width=self.n_docs + 1
            )

    def new_acc(self, rows: int = BPAD):
        """Zeroed accumulators at one query-row bucket of the ladder,
        for a scoring no row launch starts: one program."""
        with launch("_impact_zeros"):
            return _impact_zeros(rows=rows, width=self.n_docs + 1)

    def stage_chunks(self, rows: int, tile_lists, weight_lists):
        """The host plans, i32[launches, 2, rows, TILE_CAP], of every
        `_impact_chunk_add` launch that per-row tile/weight lists (≤
        `rows`, any length) need: ONE for rows of up to TILE_CAP tiles.
        Plane 0 holds a row's tile ids from slot 0 up and -1 past them,
        plane 1 the weights' float32 bits: one host operand a launch.
        Allocated once; `add_chunks` hands every launch its own slab,
        never written again: a jitted call may still be reading a host
        operand after it returns (the CPU backend aliases an aligned
        NumPy buffer and runs the program later), so one slab refilled
        launch after launch would score the wrong tiles (PERF.md
        section 4, the sparse deployment's table)."""
        n = chunk_launches(tile_lists)
        plans = np.empty((n, 2, rows, TILE_CAP), np.int32)
        plans[:, 0] = -1
        plans[:, 1] = 0
        for j, (tl, wl) in enumerate(zip(tile_lists, weight_lists)):
            wl = np.asarray(wl, np.float32).view(np.int32)
            for c in range(-(-len(tl) // TILE_CAP)):
                part = slice(c * TILE_CAP, (c + 1) * TILE_CAP)
                m = len(tl[part])
                plans[c, 0, j, :m] = tl[part]
                plans[c, 1, j, :m] = wl[part]
        return plans

    def add_chunks(self, acc, cnt, staged):
        """One `_impact_chunk_add` launch a staged plan into the donated
        accumulators; each launch uploads its plan, one operand."""
        for plan in staged:
            note_transfer("h2d", plan.nbytes)
            with launch("_impact_chunk_add", 1, plan.nbytes,
                        sparse_flops(np.count_nonzero(plan[0] >= 0))):
                acc, cnt = _impact_chunk_add(
                    self.doc_ids, self.values, acc, cnt, plan
                )
        return acc, cnt

    def score_into(self, acc, cnt, tile_lists, weight_lists):
        """`stage_chunks` then `add_chunks` at the accumulators' rows."""
        staged = self.stage_chunks(
            int(acc.shape[0]), tile_lists, weight_lists
        )
        return self.add_chunks(acc, cnt, staged)

    def finalize(self, acc, cnt, k: int, live=None):
        s, d, tot = self.finalize_device(acc, cnt, k, live=live)
        return _to_host(s), _to_host(d), _to_host(tot)

    def finalize_device(self, acc, cnt, k: int, live=None):
        """(scores[B,k], docs[B,k], totals[B]) STAYING on device, in the
        merge_segment_topk-compatible triple shape. The sparse match
        mask is cnt > 0 (every query term is optional), which is exactly
        the finalize kernel at msm=1 — the ONE finalize kernel serves
        text, serve and sparse families alike. The all-ones `msm` is a
        device constant kept a bucket: made once, by the bucket's first
        scoring."""
        rows = int(acc.shape[0])
        msm = self._msm.get(rows)
        if msm is None:
            msm = self._msm[rows] = jnp.ones((rows,), jnp.int32)
        with launch("_finalize"):
            return _finalize(
                acc,
                cnt,
                live if live is not None else self.live,
                msm,
                k=min(k, self.n_docs),
            )


class SparseBlockMax:
    """Impact-ordered block-max pruning plan for ONE query row over one
    SparseField (see module comment for the soundness argument). All
    arrays are host numpy — the plan, theta included, is host work; the
    scoring launches stay on device."""

    def __init__(
        self,
        term_tile_start: np.ndarray,
        term_tile_count: np.ndarray,
        tile_bound: np.ndarray,  # tile_qmax (int8 mode) or tile_max
        tids: Sequence[int],  # query term ids present in the dictionary
        tws: Sequence[float],  # kernel tile weights (scale folded)
        bws: Optional[Sequence[float]] = None,  # bound weights (RAW)
        dense: Optional[np.ndarray] = None,  # bool[T]: served from a row
    ):
        """`tws` multiplies the STORED plane inside the kernel, so for
        the int8 column it carries the dequant scale. The bound sidecar
        (`tile_qmax`) is already DEQUANTIZED — bounding with the folded
        weight would scale twice and prune tiles that still hold
        competitive mass — so the bound math uses `bws`, the raw query
        weights (equal to `tws` for the fp32 column). A `dense` term is
        scored whole from its row (`ImpactScorer.add_rows`): its maximum
        stays in every other term's bound and its first tile in theta,
        but `kept` lists none of its tiles and drops none."""
        self.starts = term_tile_start[np.asarray(tids, np.int64)].astype(
            np.int64
        )
        self.counts = term_tile_count[np.asarray(tids, np.int64)].astype(
            np.int64
        )
        self.tws = np.asarray(tws, np.float32)
        self.bws = (
            np.asarray(bws, np.float32) if bws is not None else self.tws
        )
        self.tile_bound = tile_bound
        self.dense = (
            np.asarray(dense, bool)
            if dense is not None
            else np.zeros(len(self.starts), bool)
        )
        # impact ordering ⇒ a term's global max bound is its first tile's
        self.term_max = (
            tile_bound[self.starts].astype(np.float32)
            if len(self.starts)
            else np.zeros(0, np.float32)
        )
        self.sum_bound = float((self.bws * self.term_max).sum())

    def host_theta(self, doc_ids, values, live, kb: int) -> float:
        """theta: a lower bound on the `kb`-th best FINAL score as the
        device will compute it, read from every query term's FIRST tile
        (the tiles holding each term's maximum impacts, the cheapest
        set that makes theta meaningful) of the host planes: `doc_ids`,
        and `values` = the plane the kernel serves (int8 `qweights`
        under the folded `tws`, or fp32 `weights`); `live` is the
        segment's live mask or None. `-inf` where fewer than `kb` live
        docs score above 0 there.

        Per valid live slot the product p = tw * f32(value) is formed in
        float32, as the kernel forms it (`impact_tile_contrib`); the
        products are summed by doc id in float64 and the `kb`-th
        largest positive sum H is taken. Why the returned value is
        sound, for non-negative weights (the caller's rule): a doc's
        final device score is a float32 sum, in some order, of its
        products over the kept tiles, at most T = len(terms) addends (a
        doc is at most once in a term's postings), and first tiles are
        always kept. Any float32 summation of T non-negative terms is
        >= (1 - (T-1) * 2^-24 / (1 - (T-1) * 2^-24)) times their true
        sum, which is >= the true first-tile sum, which the float64
        accumulation misses by at most T * 2^-53 relative. So
        H * (1 - T * 2^-23) (twice what the sums need: the other half
        covers a device product one ulp below the host's), less T *
        float32's smallest normal (the chip flushes a subnormal product
        to 0), rounded DOWN to float32, is <= the device's final score
        of each of those `kb` docs, hence <= its `kb`-th best. `kept`
        compares float32 bounds with it, so the value handed back is a
        float32 exactly."""
        n_terms = len(self.starts)
        d = doc_ids[self.starts]  # [T, 128]
        p = self.tws[:, None] * values[self.starts].astype(np.float32)
        ok = d >= 0
        d, p = d[ok], p[ok]
        if live is not None:
            ok = live[d]
            d, p = d[ok], p[ok]
        _docs, row = np.unique(d, return_inverse=True)
        sums = np.bincount(row, weights=p.astype(np.float64))
        sums = sums[sums > 0]
        if len(sums) < kb:
            return -np.inf
        kth = np.partition(sums, len(sums) - kb)[len(sums) - kb]
        low = kth * (1.0 - n_terms * 2.0**-23) - n_terms * float(
            np.finfo(np.float32).tiny
        )
        theta = np.float32(low)
        if theta > low:
            theta = np.nextafter(theta, np.float32(-np.inf))
        return float(theta)

    def kept(
        self, theta: float
    ) -> Tuple[np.ndarray, np.ndarray, int]:
        """(tiles, weights, dropped): the FULL surviving tile list of
        the terms not served from a row — first tiles always, tail
        tiles filtered against `theta` — laid out per term in term
        order, so the one device pass accumulates each doc cell in pure
        query-term order: the fp32 serving path (no rows) stays
        bit-identical to the numpy oracle whether or not pruning
        dropped anything.

        Array operations over all those terms' tiles at once (a Python
        loop a term cost the chip machine's host 0.69 ms a 49-token
        query: PERF.md section 5, PR 46). A tile's bound is formed as
        the loop formed it, float32(bw) x float32(tile bound) +
        float32(others), `others` = the summed bound less the term's
        own share, in float64 a term: the tiles, weights and `dropped`
        are that loop's bit for bit (tier-1 holds them to it)."""
        cold = np.flatnonzero(~self.dense)
        counts = self.counts[cold]
        tiles = term_tiles(self.starts[cold], counts)
        weights = np.repeat(self.tws[cold], counts)
        if not np.isfinite(theta) or len(tiles) == np.count_nonzero(counts):
            return tiles, weights, 0  # nothing asked, or no tail tile
        bws = self.bws[cold]
        others = (
            self.sum_bound
            - (bws * self.term_max[cold]).astype(np.float64)
        ).astype(np.float32)
        bound = (
            np.repeat(bws, counts) * self.tile_bound[tiles].astype(np.float32)
            + np.repeat(others, counts)
        )
        keep = bound >= np.float32(theta)
        # a term's first tile anchors theta; never drop
        keep[(np.cumsum(counts) - counts)[counts > 0]] = True
        return tiles[keep], weights[keep], int(len(keep) - keep.sum())

    @property
    def n_tail_tiles(self) -> int:
        """Tiles beyond the first of each term that goes through tiles
        — zero means there is nothing a threshold could drop, and none
        is computed."""
        return int(np.maximum(self.counts[~self.dense] - 1, 0).sum())


def impact_tile_lists(
    sf, terms: Sequence[str], weights: Sequence[float], quantized: bool
) -> Tuple[List[int], np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Resolve a query's term→weight map against one SparseField: (term
    ids present, folded tile weights f32, raw bound weights f32,
    term_tile_start slice, term_tile_count slice). For the int8 column
    the per-term dequant scale folds into the tile weight HERE (one
    host multiply per query term), so the device kernel is identical in
    both storage modes; the RAW weights ride along for SparseBlockMax,
    whose tile_qmax sidecar is already dequantized."""
    tids = sf.term_ids(terms)
    here = tids >= 0
    tids = tids[here]
    bws = np.asarray(weights, np.float32)[here]
    tws = bws
    if quantized:
        tws = (bws * sf.scales[tids]).astype(np.float32, copy=False)
    return (
        tids.tolist(),
        tws,
        bws,
        sf.term_tile_start[tids],
        sf.term_tile_count[tids],
    )
