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

`ImpactScorer` mirrors ops/scoring.ChunkedScorer shape-for-shape: tile
lists of any length stream through [rows, TCHUNK] launches into donated
accumulators, rows ride the same power-of-two bucket ladder, and
finalize reuses the ONE finalize kernel so its device triples feed
ops/scoring.merge_segment_topk unchanged.

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

Every host<->device transfer of the family is noted where it happens
(`common/tracing.note_transfer`): the three staged planes a chunk
launch uploads; the packed collect notes itself in ops/scoring.
"""

from __future__ import annotations

import functools
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..common.tracing import note_transfer
from .scoring import BPAD, TCHUNK, _finalize, _to_host

TILE_WIDTH = 128

# Per posting slot the impact kernel does ~4 flops (int8→f32 cast,
# weight multiply, validity select, scatter add) — the BM25S payoff row:
# ops/scoring counts 6 for the text kernel because of the norm math
# this layout folded into the index.
FLOPS_PER_IMPACT_SLOT = 4


def sparse_flops(n_tile_slots: int) -> int:
    """Estimated useful flops of one sparse job's plan on one segment."""
    return n_tile_slots * TILE_WIDTH * FLOPS_PER_IMPACT_SLOT


def chunk_launches(tile_lists) -> int:
    """`_impact_chunk_add` launches `ImpactScorer.score_into` makes for
    these per-row tile lists: the longest row, TCHUNK tiles a launch."""
    t_max = max((len(t) for t in tile_lists), default=0)
    return -(-t_max // TCHUNK)


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


def _impact_chunk_scores(doc_ids, values, ti, tw, tv):
    rows_d = doc_ids[ti]  # [B, TC, 128]
    rows_v = values[ti]
    valid = (rows_d >= 0) & tv[:, :, None]
    return rows_d, rows_v, valid


@functools.partial(jax.jit, donate_argnums=(2, 3))
def _impact_chunk_add(doc_ids, values, acc, cnt, ti, tw, tv):
    """acc[B, n+1] += impact contributions of one [B, TCHUNK] chunk;
    cnt counts matching postings per doc (one per term — the sparse
    match mask is cnt > 0)."""
    n_docs = acc.shape[1] - 1
    rows_d, rows_v, valid = _impact_chunk_scores(doc_ids, values, ti, tw, tv)
    tgt, s = impact_tile_contrib(
        rows_d, rows_v, tw[:, :, None], valid, n_docs
    )
    acc = jax.vmap(lambda a, d, v: a.at[d.ravel()].add(v.ravel()))(
        acc, tgt, s
    )
    cnt = jax.vmap(
        lambda c, d, v: c.at[d.ravel()].add(v.ravel().astype(jnp.int32))
    )(cnt, tgt, valid)
    return acc, cnt


class ImpactScorer:
    """Batched learned-sparse scoring over one segment's impact-ordered
    tiled postings with fixed launch shapes (ChunkedScorer's serving
    recipe applied to the sparse column — see module comment)."""

    def __init__(self, doc_ids, values, n_docs: int, live=None):
        self.doc_ids = jnp.asarray(doc_ids)
        # stored dtype (int8 qweights or f32 weights) — cast happens
        # inside the kernel, post-gather
        self.values = jnp.asarray(values)
        self.n_docs = int(n_docs)
        self.live = jnp.asarray(live) if live is not None else None

    def new_acc(self, rows: int = BPAD):
        """Donated accumulators at one query-row bucket of the ladder."""
        acc = jnp.zeros((rows, self.n_docs + 1), jnp.float32)
        cnt = jnp.zeros((rows, self.n_docs + 1), jnp.int32)
        return acc, cnt

    def score_into(self, acc, cnt, tile_lists, weight_lists):
        """Streams per-row tile/weight lists (≤ acc rows, any length)
        through TCHUNK-wide launches into the donated accumulators.
        Every launch is handed three host planes of its own, never
        written again: a jitted call may still be reading a host
        operand after it returns (the CPU backend aliases an aligned
        NumPy buffer and runs the program later), so one slab refilled
        chunk after chunk would score the wrong tiles (PERF.md section
        4, the sparse deployment's table)."""
        rows = int(acc.shape[0])
        t_max = max((len(t) for t in tile_lists), default=0)
        for c0 in range(0, t_max, TCHUNK):
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
            acc, cnt = _impact_chunk_add(
                self.doc_ids, self.values, acc, cnt, ti, tw, tv
            )
        return acc, cnt

    def finalize(self, acc, cnt, k: int, live=None):
        s, d, tot = self.finalize_device(acc, cnt, k, live=live)
        return _to_host(s), _to_host(d), _to_host(tot)

    def finalize_device(self, acc, cnt, k: int, live=None):
        """(scores[B,k], docs[B,k], totals[B]) STAYING on device, in the
        merge_segment_topk-compatible triple shape. The sparse match
        mask is cnt > 0 (every query term is optional), which is exactly
        the finalize kernel at msm=1 — the ONE finalize kernel serves
        text, serve and sparse families alike."""
        rows = int(acc.shape[0])
        return _finalize(
            acc,
            cnt,
            live if live is not None else self.live,
            jnp.ones((rows,), jnp.int32),
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
    ):
        """`tws` multiplies the STORED plane inside the kernel, so for
        the int8 column it carries the dequant scale. The bound sidecar
        (`tile_qmax`) is already DEQUANTIZED — bounding with the folded
        weight would scale twice and prune tiles that still hold
        competitive mass — so the bound math uses `bws`, the raw query
        weights (equal to `tws` for the fp32 column)."""
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
        """(tiles, weights, dropped): the FULL surviving tile list —
        first tiles always, tail tiles filtered against `theta` — laid
        out per term in term order, so the one device pass accumulates
        each doc cell in pure query-term order: the fp32 serving path
        stays bit-identical to the numpy oracle whether or not pruning
        dropped anything."""
        tiles: List[np.ndarray] = []
        weights: List[np.ndarray] = []
        dropped = 0
        for i in range(len(self.starts)):
            c = int(self.counts[i])
            rng = np.arange(
                self.starts[i], self.starts[i] + c, dtype=np.int64
            )
            if c > 1 and np.isfinite(theta):
                others = self.sum_bound - float(
                    self.bws[i] * self.term_max[i]
                )
                bound = (
                    self.bws[i] * self.tile_bound[rng].astype(np.float32)
                    + np.float32(others)
                )
                keep = bound >= theta
                keep[0] = True  # first tile anchors theta; never drop
                dropped += int((~keep).sum())
                rng = rng[keep]
            if len(rng):
                tiles.append(rng)
                weights.append(np.full(len(rng), self.tws[i], np.float32))
        return (
            np.concatenate(tiles) if tiles else np.zeros(0, np.int64),
            np.concatenate(weights) if weights else np.zeros(0, np.float32),
            dropped,
        )

    @property
    def n_tail_tiles(self) -> int:
        """Tiles beyond each term's first — zero means there is nothing
        a threshold could drop, and none is computed."""
        return int(np.maximum(self.counts - 1, 0).sum())


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
    tids: List[int] = []
    tws: List[float] = []
    bws: List[float] = []
    for t, w in zip(terms, weights):
        tid = sf.term_id(t)
        if tid < 0:
            continue
        bw = np.float32(w)
        tw = bw
        if quantized:
            tw = np.float32(tw * sf.scales[tid])
        tids.append(tid)
        tws.append(float(tw))
        bws.append(float(bw))
    return (
        tids,
        np.asarray(tws, np.float32),
        np.asarray(bws, np.float32),
        sf.term_tile_start[np.asarray(tids, np.int64)]
        if tids
        else np.zeros(0, np.int32),
        sf.term_tile_count[np.asarray(tids, np.int64)]
        if tids
        else np.zeros(0, np.int32),
    )
