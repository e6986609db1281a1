"""JAX/TPU shard executor — the production scoring path.

Mirrors the NumPy oracle (executor.py) node for node, but evaluates on
device arrays: postings tiles live in HBM, leaves score via the jitted
gather→BM25→scatter kernel in ops/scoring.py, compounds compose dense
masks/scores with elementwise jnp ops, and collection is lax.top_k.
Tests enforce hit-for-hit parity with the oracle.

Per-segment arrays are uploaded once and cached (`DeviceSegment`) — the
analog of Lucene's "open a reader once, search many times", and the
north star's "posting lists block-decoded once into HBM-resident arrays".
"""

from __future__ import annotations

import functools
import threading
import time
from typing import Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..common.tracing import note_transfer
from ..index.mapping import DATE, KEYWORD, TEXT, parse_date_millis
from ..index.segment import Segment
from ..models import bm25
from ..ops import fuzzy as fuzzy_ops
from ..ops import scoring
from . import dsl
from .dsl import (
    BoolQuery,
    ConstantScoreQuery,
    ExistsQuery,
    KnnQueryWrapper,
    KnnSection,
    MatchAllQuery,
    MatchNoneQuery,
    MatchPhraseQuery,
    MatchQuery,
    MultiMatchQuery,
    Query,
    QueryParseError,
    RangeQuery,
    TermQuery,
    TermsQuery,
)
from .executor import Hit, NumpyExecutor, ShardReader, TopDocs, _coerce_numeric

# segments below this size score through the shared-shape chunked path;
# above it the per-segment fused program + dense hot rows pay off
FUSED_MIN_DOCS = 100_000
# HBM budget for one field's dense hot-term tf rows, bytes (uint8 per doc
# per term, uint16 for a term whose tf passes 255). 1M short passages
# want 500 rows (0.5 GB); 401,729 whole documents want 9,510 (3.8 GB):
# under 512 MiB (1,336 rows) 2.5% of six-word questions pass the plan's
# FUSED_T_RARE tiles with the terms left sparse, under 1 GiB (2,672 rows)
# 0.02% (PERF.md section 6, PR 27)
DENSE_ROWS_HBM_BUDGET = 1024 * 1024 * 1024
# rows an upload block of a rerank column holds (`rerank_column`): the
# column is assembled on the device block by block, and its row count is
# a whole number of blocks. 1M rows of 128 bytes = 128 MiB a block
RERANK_BLOCK_ROWS = 1 << 20


def _row_blocks(chunks: List[np.ndarray], block: int):
    """(first row, rows[block, d]) over the concatenation of `chunks`
    in whole blocks, without building it: a block inside one chunk is a
    view of it; one that spans chunks, and the last short one
    (zero-filled to size), is a copy of at most `block` rows."""
    at, buf, fill = 0, None, 0
    for c in chunks:
        pos = 0
        while pos < len(c):
            if not fill and len(c) - pos >= block:
                yield at, c[pos : pos + block]
                at, pos = at + block, pos + block
                continue
            if buf is None:
                buf = np.zeros((block, c.shape[1]), c.dtype)
            take = min(block - fill, len(c) - pos)
            buf[fill : fill + take] = c[pos : pos + take]
            fill, pos = fill + take, pos + take
            if fill == block:
                yield at, buf
                at, buf, fill = at + block, None, 0
    if fill:
        yield at, buf


@functools.partial(jax.jit, donate_argnums=(0,))
def _place_block(buf, block, at):
    """`buf` with `block` written at row `at`, in place (donated)."""
    return jax.lax.dynamic_update_slice(
        buf, block, (at,) + (0,) * (buf.ndim - 1)
    )


def dense_row_min_df(n_docs: int) -> int:
    """The df from which a term of a segment of `n_docs` WANTS a dense
    per-document row: the text fields' hot terms (`_fused_parts_build`)
    and a `sparse_vector` int8 column's (`impact_scorer`) alike."""
    return max(1024, n_docs // 128)


def dense_rows_room(row_bytes: int) -> int:
    """Rows of `row_bytes` the budget holds for one field of one
    segment: the static per-field cap AND the live global ledger — when
    HBM is tight the terms past it stay on their tiles (an optimization
    lost, not correctness); the caller counts it (`note_degraded`)."""
    from ..common.memory import hbm_ledger

    headroom = max(0, hbm_ledger.budget - hbm_ledger.used)
    return min(
        DENSE_ROWS_HBM_BUDGET // row_bytes, headroom // (row_bytes + 1)
    )


def dense_rows_held(df: np.ndarray, n_docs: int, row_bytes: int):
    """(ids of the terms that HOLD a dense row of `row_bytes`, most
    frequent first; how many WANT one) by the rule the sparse column's
    int8 rows and a filter field's bit rows share: a term wants a row
    from df >= dense_row_min_df(n_docs), rows are held by df rank while
    `dense_rows_room` lasts, and terms left without are counted
    (`note_degraded`): they keep their tiles."""
    from ..common.memory import hbm_ledger

    df = np.asarray(df).astype(np.int64)
    wanted = np.flatnonzero(df >= dense_row_min_df(n_docs))
    by_df = wanted[np.argsort(-df[wanted], kind="stable")]
    held = by_df[: dense_rows_room(row_bytes)]
    if len(held) < len(wanted):
        hbm_ledger.note_degraded()
    return held, len(wanted)


class DevicePostings:
    """A field's postings tiles on the device: the doc-id plane at
    once, the tf plane at its first use, the commonest terms' bit rows
    at the first filtered kNN search. A filter reads ids alone (the knn
    family's masks over a keyword field never upload its tfs: half the
    field's bytes); every scoring path reads both; a field no
    `knn.filter` names builds no bit row."""

    def __init__(self, pf, device=None, charge=None, n_docs: int = 0):
        self.doc_ids = jax.device_put(pf.doc_ids, device)
        self._pf, self._device, self._charge = pf, device, charge
        self._n_docs = n_docs
        self._tfs = None
        self._bits = None  # scoring.FilterBitRows once built
        self._lock = threading.Lock()

    @property
    def tfs(self) -> jax.Array:
        if self._tfs is None:
            with self._lock:
                if self._tfs is None:
                    tfs = jax.device_put(self._pf.tfs, self._device)
                    if self._charge is not None:
                        self._charge("postings", int(tfs.nbytes), False)
                    self._tfs = tfs
        return self._tfs

    @property
    def filter_bits(self):
        """The bit rows `scoring.knn_filter_mask` reads instead of
        scattering (scoring.FilterBitRows; without a plane where no
        term holds one). The sparse family's choice of dense rows
        (`dense_rows_held`) read for a filter: a term WANTS a row from
        df >= dense_row_min_df(n) on the segment (122 tags of the
        filtered cell's 200,386, a third of the terms its requests name
        and 97.5% of the tiles they scattered); rows are HELD by df
        rank while `dense_rows_room` lasts (n / 8 bytes a row), built
        once on the device from the resident tiles, charged to the
        ledger with the postings; a wanted term without a row keeps its
        tiles: an optimisation lost, never an answer. The room would
        hold rows down to df ~11,400 there (858 rows, 1.07 GB): not
        taken, what they would save is ~0.25 ms of a mean request of
        ~7 (PERF.md section 6, PR 45)."""
        if self._bits is None:
            with self._lock:
                if self._bits is None:
                    self._bits = self._filter_bits_build()
        return self._bits

    def _filter_bits_build(self):
        pf, n = self._pf, self._n_docs
        held, _wanted = dense_rows_held(
            pf.term_df, n, 4 * scoring.filter_bit_words(n))
        if not len(held):
            return scoring.FilterBitRows()
        rows = scoring.build_filter_bit_rows(
            self.doc_ids, pf.term_tile_start, pf.term_tile_count, held, n)
        if self._charge is not None:
            self._charge("postings", rows.nbytes, False)
        return rows


def _tree_nbytes(v) -> int:
    if hasattr(v, "nbytes"):
        return int(v.nbytes)
    if isinstance(v, (tuple, list)):
        return sum(_tree_nbytes(x) for x in v)
    if hasattr(v, "__dict__"):
        return sum(
            int(x.nbytes) for x in vars(v).values() if hasattr(x, "nbytes")
        )
    return 0


class _LazyDeviceMap:
    """Per-field device uploads, materialized on first use. Uploading
    every field of every segment eagerly (round 2) burns HBM and makes
    executor regeneration after refresh O(index) instead of O(touched
    fields). Every upload charges the HBM ledger; `charge` is a
    (category, nbytes, breaker) recorder owned by the executor so
    close() can release exactly what was charged."""

    def __init__(self, names, build, charge=None, category="other"):
        self._names = set(names)
        self._build = build
        self._cache: Dict[str, object] = {}
        self._lock = threading.Lock()
        self._charge = charge
        self._category = category

    def get(self, name, default=None):
        if name not in self._names:
            return default
        v = self._cache.get(name)
        if v is None:
            with self._lock:
                v = self._cache.get(name)
                if v is None:
                    v = self._build(name)
                    if self._charge is not None:
                        self._charge(self._category, _tree_nbytes(v), False)
                    self._cache[name] = v
        return v

    def __getitem__(self, name):
        v = self.get(name)
        if v is None:
            raise KeyError(name)
        return v


class DeviceVectors(NamedTuple):
    """A vector field's uploads: the stored rows (unit rows for cosine),
    which documents hold one, and for integer rows under l2_norm their
    float32 `sum(v * v)` (None for every other field)."""

    rows: jax.Array
    exists: jax.Array
    norms: Optional[jax.Array] = None


class DevicePositions(NamedTuple):
    """A text field's positions plane on the device (index/segment.py
    `PositionsPlane`; ops/phrase.py reads it): a position-major term-id
    matrix a class of document lengths, and the document of each plane
    column."""

    mats: Tuple[jax.Array, ...]
    order: jax.Array
    occurrences: int  # token positions the plane holds


class DeviceSegment:
    """Device-resident mirror of a Segment's hot arrays (lazy per field)."""

    def __init__(self, seg: Segment, device=None, charge=None):
        self.seg = seg
        self.device = device
        self.postings = _LazyDeviceMap(
            seg.postings,
            lambda f: DevicePostings(
                seg.postings[f], device, charge, seg.num_docs),
            charge=charge, category="postings",
        )
        self.numerics = _LazyDeviceMap(
            seg.numerics,
            lambda f: (
                jax.device_put(seg.numerics[f].values, device),
                jax.device_put(seg.numerics[f].exists, device),
            ),
            charge=charge, category="doc_values",
        )

        def _vec(f):
            vf = seg.vectors[f]
            mat = vf.unit_vectors if vf.similarity == "cosine" else vf.vectors
            # integer rows under l2_norm bring their norms, exact in
            # float32 and built on the device from the uploaded rows
            # (scoring.knn_row_norms); any other field uploads what it
            # always did
            normed = vf.similarity == "l2_norm" and np.issubdtype(
                mat.dtype, np.integer)
            if charge is not None:
                # vectors are the big uploads: trip the breaker BEFORE
                # shipping them (HierarchyCircuitBreakerService
                # .addEstimateBytesAndMaybeBreak)
                charge(
                    "vectors",
                    int(mat.nbytes) + int(vf.exists.nbytes)
                    + (4 * len(mat) if normed else 0),
                    True,
                    precheck_only=True,
                )
            rows = jax.device_put(mat, device)
            out = DeviceVectors(
                rows,
                jax.device_put(vf.exists, device),
                scoring.knn_row_norms(rows) if normed else None,
            )
            if charge is not None:
                charge("vectors", _tree_nbytes(out), False)
            return out

        self.vectors = _LazyDeviceMap(seg.vectors, _vec)

        def _positions(f):
            # uploaded when the field is first asked a bare phrase, never
            # for a field that only answers `match` / `bool`; None where
            # the field holds no positions (or passes the layout's limits)
            plane = seg.postings[f].positions_plane(seg.num_docs)
            if plane is None:
                return None
            if charge is not None:
                charge("positions", plane.nbytes, True, precheck_only=True)
            out = DevicePositions(
                tuple(jax.device_put(m, device) for m in plane.mats),
                jax.device_put(plane.order, device), plane.occurrences,
            )
            if charge is not None:
                charge("positions", _tree_nbytes(out), False)
            return out

        self.positions = _LazyDeviceMap(seg.postings, _positions)
        # multi-value ordinal CSR for device range/terms masks
        self.ordinals = _LazyDeviceMap(
            seg.ordinals,
            lambda f: (
                jax.device_put(seg.ordinals[f].mv_ords, device),
                jax.device_put(seg.ordinals[f].mv_offsets.astype(np.int32), device),
            ),
            charge=charge, category="doc_values",
        )
        self._adopt_charge = charge

    def adopt_from(self, other: "DeviceSegment") -> None:
        """Cross-generation reuse: a refresh appends segments but never
        mutates existing ones, so the NEW executor generation adopts the
        previous generation's device uploads for every already-uploaded
        field of this (same, immutable) segment instead of uploading
        them again. Adopted bytes are re-charged to THIS
        executor's ledger records — the old executor's close() releases
        its own — so accounting stays per-generation while the arrays
        are shared."""
        for mine, theirs, cat in (
            (self.postings, other.postings, "postings"),
            (self.numerics, other.numerics, "doc_values"),
            (self.vectors, other.vectors, "vectors"),
            (self.positions, other.positions, "positions"),
            (self.ordinals, other.ordinals, "doc_values"),
        ):
            with theirs._lock:
                items = dict(theirs._cache)
            for k, v in items.items():
                if k in mine._names and k not in mine._cache:
                    mine._cache[k] = v
                    if self._adopt_charge is not None:
                        self._adopt_charge(cat, _tree_nbytes(v), False)


class JaxExecutor:
    """Walks the query tree producing dense device (mask, scores) pairs."""

    def __init__(
        self,
        reader: ShardReader,
        k1: float = bm25.DEFAULT_K1,
        b: float = bm25.DEFAULT_B,
        device=None,
        reuse_from: "Optional[JaxExecutor]" = None,
    ):
        self.reader = reader
        self.k1 = k1
        self.b = b
        self.device = device
        # HBM ledger integration: every device upload is charged and
        # released when the executor is discarded (reader generation
        # change); see common/memory.py
        self._charges: List[Tuple[str, int]] = []
        self._charges_lock = threading.Lock()
        self._closed = False
        # filter-bitset cache identity (set by IndexService._executor);
        # None disables the node-level cache (bare test executors)
        self.cache_ctx = None
        self.device_segments = [
            DeviceSegment(s, device, charge=self._charge)
            for s in reader.segments
        ]
        if reuse_from is not None:
            # NRT generation lifecycle: segments are immutable and a
            # refresh only appends, so the new generation adopts the
            # old one's device uploads for unchanged segments — the
            # swap re-uploads only the NEW segment's columns
            prev = {
                id(ds.seg): ds for ds in reuse_from.device_segments
            }
            for ds in self.device_segments:
                old = prev.get(id(ds.seg))
                if old is not None:
                    ds.adopt_from(old)
        # the oracle is reused for stats, weights, and host-only nodes
        # (match_phrase position verification)
        self._oracle = NumpyExecutor(reader, k1, b)
        self._inv_norm_cache: Dict[Tuple[int, str], jax.Array] = {}
        self._id_maps: Dict[int, Dict[str, int]] = {}
        # block-max / chunked-scorer caches keyed (si, field): reused
        # across requests for the lifetime of this executor (= one reader
        # generation). The underlying tilings + device arrays are cached
        # on the immutable segments and survive executor regeneration.
        self._block_indexes: Dict[Tuple[int, str], object] = {}
        self._chunked_scorers: Dict[Tuple[int, str], object] = {}
        self._fused_parts: Dict[Tuple[int, str], object] = {}
        self._fused_mf: Dict[Tuple[int, tuple], object] = {}
        self._sort_rank_cache: Dict[Tuple[int, str, bool], tuple] = {}
        self._entry_docs_dev_cache: Dict[Tuple[int, str], object] = {}
        # device-aggregations engine caches (search/aggs_device.py):
        # per-(segment, field) column exactness profiles plus the int32
        # offset / value-ordinal agg columns (charged to the `aggs`
        # HbmLedger category, released with the executor on generation
        # bump — exactly the invalidation the agg plans need)
        self._agg_profiles: Dict[Tuple[int, str], object] = {}
        self._agg_cols: Dict[tuple, object] = {}
        # IVF ANN tier (ops/ivf.py, search/ann.py): per-(segment, field,
        # build-shape) cluster indexes, built lazily per executor
        # generation — the same invalidation as the agg tables — and
        # charged to the `ann` HbmLedger category. None caches a miss
        # (small segment / budget degrade) so the exact path is chosen
        # without re-locking per batch.
        self._ann_indexes: Dict[tuple, object] = {}
        # learned-sparse serving (ops/impact.py, search/sparse.py):
        # per-(segment, field, storage-mode) ImpactScorers over the
        # impact-ordered postings column, charged to the `impacts`
        # HbmLedger category; an upload that would not fit degrades to
        # the host dense oracle (None cached)
        self._impact_scorers: Dict[tuple, object] = {}
        # second-stage reranker columns (search/rescorer.py): per-model
        # shard-level concatenated `rank_vectors` token arrays, built
        # lazily per executor generation and charged to the `rerank`
        # HbmLedger category; a build that would not fit DEGRADES TO
        # SKIP (None cached — the request keeps its first-stage order)
        self._rerank_columns: Dict[tuple, object] = {}
        # the phrase family's operands a (segment, field): the shared
        # positions plane with this generation's inverse norms and live
        # mask in the plane's column order (`phrase_plane`)
        self._phrase_planes: Dict[Tuple[int, str], tuple] = {}
        # (si, field) -> the fuzzy family's (dictionary plane on the
        # device, wide fused scorer, each term's dense row or -1) | None
        self._fuzzy_parts: Dict[Tuple[int, str], object] = {}
        self._seg_weights: Dict[Tuple[int, str], np.ndarray] = {}
        self._df_maps: Dict[str, Dict[str, int]] = {}
        self._shard_dfs: Dict[Tuple[str, str], int] = {}
        self._deleted_count: Optional[int] = None
        # cache-miss builds are guarded so concurrent batcher workers
        # can't duplicate a dense hot-row build (each one is up to
        # DENSE_ROWS_HBM_BUDGET of HBM) or a tiling/compile; RLock
        # because fused_scorer → _inv_norm/_segment_weights nest
        self._build_lock = threading.RLock()
        # persistent padded staging slabs: per-(family, shape) rings of
        # reusable query-operand buffers (fused plan uploads, kNN query
        # rows, chunk tile planes) handed out round-robin to the batcher
        # instead of fresh allocations every batch; bytes ride the
        # `serving` ledger category
        self._staging_slabs: Dict[tuple, list] = {}
        self._staging_lock = threading.Lock()

    # ---- per-(segment, field) dense inverse-norm array ----

    def _charge(
        self, category: str, nbytes: int, breaker: bool,
        precheck_only: bool = False,
    ) -> None:
        from ..common.memory import hbm_ledger

        if precheck_only:
            if breaker and nbytes and not hbm_ledger.would_fit(nbytes):
                from ..common.memory import CircuitBreakingException

                hbm_ledger.stats_counters["tripped"] += 1
                raise CircuitBreakingException(
                    f"[hbm] Data too large for [{category}]: "
                    f"{nbytes} bytes would exceed the budget",
                    bytes_wanted=nbytes,
                    limit=hbm_ledger.budget,
                )
            return
        with self._charges_lock:
            if self._closed:
                # a pinned scroll/PIT context kept using this executor
                # after its generation was replaced: don't record bytes
                # nobody will ever release
                return
            hbm_ledger.add(category, nbytes, breaker=False)
            self._charges.append((category, nbytes))

    def staging_slab(self, family: str, shape, dtype=np.int32) -> np.ndarray:
        """A reusable pre-allocated query-operand buffer for the serving
        hot path (batcher dispatch). Buffers are handed out from a
        fixed-size ring per (family, shape, dtype) so a buffer is never
        rewritten while an earlier batch's upload can still be reading
        it: the ring is sized to cover every dispatcher worker at full
        pipeline depth with one spare each. Callers must fully rewrite
        the regions they use (pack_plans/score_into do)."""
        key = (family, tuple(int(x) for x in shape), np.dtype(dtype).str)
        with self._staging_lock:
            entry = self._staging_slabs.get(key)
            if entry is None:
                from ..common.settings import pipeline_depth
                from .batcher import WORKERS

                ring = max(2, WORKERS * (pipeline_depth() + 1))
                bufs = [np.zeros(shape, dtype) for _ in range(ring)]
                self._charge(
                    "serving", int(sum(b.nbytes for b in bufs)), False
                )
                entry = [0, bufs]
                self._staging_slabs[key] = entry
            i, bufs = entry
            entry[0] = (i + 1) % len(bufs)
            return bufs[i]

    def close(self) -> None:
        """Releases this executor's HBM ledger charges (the device
        arrays themselves are freed by JAX when the references die)."""
        from ..common.memory import hbm_ledger

        with self._charges_lock:
            self._closed = True
            charges, self._charges = self._charges, []
        for category, nbytes in charges:
            hbm_ledger.release(category, nbytes)

    def prewarm(self, settings=None) -> None:
        """Generation-lifecycle prewarm (the NRT refresher calls this
        right after a generation swap, BEFORE queries observe the new
        executor): uploads the serving-hot device columns and builds
        the per-generation serving caches — postings tilings +
        block-max indexes + chunked scorers, inverse norms, vector
        columns, the IVF indexes (when `index.knn.type: ivf`) and the
        rerank token columns — so the first query after a refresh pays
        neither uploads nor k-means. Best-effort by design: any failure
        (HBM breaker, fault injection) leaves the lazy path to do what
        it always did."""
        from ..index.mapping import RANK_VECTORS

        settings = settings or {}
        for si, seg in enumerate(self.reader.segments):
            n = seg.num_docs
            if n == 0:
                continue
            for fname in seg.postings:
                try:
                    self.device_segments[si].postings.get(fname)
                    self._inv_norm(si, fname, n)
                    self.block_index(si, fname)
                    self.chunked_scorer(si, fname)
                except Exception:
                    pass
            for fname in seg.vectors:
                try:
                    self.device_segments[si].vectors.get(fname)
                except Exception:
                    pass  # breaker: the lazy path degrades identically
                if str(settings.get("knn.type", "exact")) == "ivf":
                    try:
                        from . import ann as ann_mod

                        class _Sec:
                            nprobe = None

                        spec = ann_mod.resolve(settings, _Sec(), False)
                        if spec is not None:
                            self.ann_index(si, fname, spec)
                    except Exception:
                        pass
            for fname in getattr(seg, "sparse", None) or {}:
                try:
                    quant = (
                        str(settings.get("sparse.quantization", "int8"))
                        == "int8"
                    )
                    self.impact_scorer(si, fname, quant)
                except Exception:
                    pass
        for fname, mf in list(self.reader.mappings.fields.items()):
            if getattr(mf, "type", None) == RANK_VECTORS:
                try:
                    from ..models import rerank as rerank_model

                    model = rerank_model.resolve_model(
                        self.reader.mappings, settings, fname
                    )
                    if model is not None:
                        self.rerank_column(model)
                except Exception:
                    pass

    # ---- filter-context evaluation via the device bitset cache ----

    def filter_mask(self, q: Query, si: int) -> jax.Array:
        """Match mask of one filter-context clause on one segment. On
        the jax backend cached bitsets are DEVICE-RESIDENT boolean
        arrays (HBM, `query_cache` ledger category) that the scoring
        kernels consume directly — a hit skips the whole filter
        sub-tree evaluation."""
        ctx = self.cache_ctx
        if ctx is None or not dsl.is_cacheable_filter(q):
            return self._exec(q, si)[0]
        from .query_cache import filter_cache

        fkey = dsl.canonical_key(q)
        cached = filter_cache.get(ctx, si, fkey)
        if cached is not None:
            return cached
        mask = self._exec(q, si)[0]
        if mask.dtype != jnp.bool_:
            mask = mask.astype(jnp.bool_)
        mask = jax.device_put(mask, self.device)
        filter_cache.put(ctx, si, fkey, mask, int(mask.nbytes))
        return mask

    def combined_filter_mask(self, fclauses, si: int) -> jax.Array:
        """AND of the (cached) filter bitsets and the live-docs bitmap —
        the combined mask the scoring kernels take as their live
        operand."""
        mask = None
        for c in fclauses:
            m = self.filter_mask(c, si)
            mask = m if mask is None else (mask & m)
        live = self.reader.live_docs[si]
        if live is not None:
            l = jnp.asarray(live)
            mask = l if mask is None else (mask & l)
        if mask is None:
            mask = jnp.ones(self.reader.segments[si].num_docs, bool)
        return mask

    # ---- bitset-masked plan serving (the filtered-bool hot path) ----

    def search_plan_filtered(
        self, stripped, fclauses, k: int, tth, mappings, analysis
    ) -> Optional[TopDocs]:
        """Serving path for a bool query whose filter clauses resolve to
        cached bitsets: the scoring part reduces to a flat Match/Serve
        plan and the combined bitset rides the fused kernels' live-mask
        operand (ops/scoring.py) — filter re-evaluation is skipped
        entirely on a warm cache. Returns None when the scoring part
        can't be planned (caller falls back to the generic tree walk,
        which also consumes the cached bitsets)."""
        from .batcher import extract_match_plan, extract_serve_plan

        mplan = None
        splan = None
        if (
            isinstance(stripped, dsl.BoolQuery)
            and len(stripped.must) == 1
            and not stripped.should
            and stripped.boost == 1.0
            and isinstance(stripped.must[0], MatchQuery)
        ):
            # single-must match: the single-field fused/chunked engine
            # (with block-max pruning when totals are untracked)
            mplan = extract_match_plan(
                stripped.must[0], mappings, analysis, tth
            )
        if mplan is None:
            splan = extract_serve_plan(stripped, mappings, analysis)
            if splan is None:
                return None
        kb = 16 if k <= 16 else scoring.next_bucket(k, 16)
        cands: List[Tuple[float, int, int]] = []
        total = 0
        pruned = False
        for si, seg in enumerate(self.reader.segments):
            n = seg.num_docs
            if n == 0:
                continue
            base = self.combined_filter_mask(fclauses, si)
            if mplan is not None:
                got = self._match_segment_filtered(mplan, si, base, kb)
            else:
                got = self._serve_segment_filtered(splan, si, base, kb)
            if got is None:
                # small segment / slot overflow: dense scoring with the
                # bitset masked straight into the top-k kernel
                mask, sc = self._exec(stripped, si)
                mask = mask & base
                s, d = scoring.topk_hits(sc, mask, min(kb, n))
                got = (
                    np.asarray(s),
                    np.asarray(d),
                    int(np.asarray(mask.sum())),
                    False,
                )
            s, d, tot, seg_pruned = got
            pruned = pruned or seg_pruned
            total += tot
            finite = np.isfinite(s)
            for sc_, doc in zip(s[finite], d[finite]):
                cands.append((float(sc_), si, int(doc)))
        cands.sort(key=lambda c: (-c[0], c[1], c[2]))
        page = cands[:k]
        hits = [
            Hit(
                score=s,
                segment=si,
                local_doc=d,
                doc_id=self.reader.segments[si].doc_ids[d],
            )
            for s, si, d in page
        ]
        return TopDocs(
            total=total,
            hits=hits,
            max_score=hits[0].score if hits else None,
            # pruned tiles make the collected count a lower bound
            relation="gte" if pruned else "eq",
        )

    def _match_segment_filtered(self, plan, si: int, base, kb: int):
        """(scores[k], docs[k], total, pruned) for one MatchPlan on one
        segment, the filter bitset masking the kernels; None → dense
        fallback."""
        field = plan.field
        n = self.reader.segments[si].num_docs
        kk = min(kb, n)
        fs = self.fused_scorer_mf(si, (field,))
        # a boost <= 0 cannot ride the fused plan's weights, whose sign
        # says whether a term counts: the chunked path scores it
        if fs is not None and plan.boost > 0:
            sec = self.fused_plan_field(
                si, field, fs.parts[0],
                [(t, 1.0, 1) for t in plan.terms], plan.boost,
            )
            if sec is not None:
                # single-request path: a 1-row launch (the smallest
                # ladder bucket), not the full padded width
                s, d, tot = fs.search(
                    [([sec], plan.msm)], kk, "sum", None, live=base,
                    rows=1, counted=plan.msm > 1,
                )
                return s[0], d[0], int(tot[0]), False
        bmx = self.block_index(si, field)
        cs = self.chunked_scorer(si, field)
        if bmx is None or cs is None:
            return None
        # pruning only when totals are untracked: a term's doc_freq
        # can't prove >= cap FILTERED matches, so the unfiltered path's
        # capped-total shortcut is unsound here
        prune_ok = plan.wand_ok and plan.tth_cap == 0
        with_cnt = plan.msm > 1
        acc, cnt = cs.new_acc(with_cnt, rows=1)
        plans = bmx.plan(list(plan.terms), plan.boost)
        empty_i = np.empty(0, np.int64)
        empty_w = np.empty(0, np.float32)
        ess, hots = [], []
        for p in plans:
            (hots if (prune_ok and p.hot) else ess).append(p)
        if not ess and hots:
            # the essential set must be non-empty or θ is -inf
            hots.sort(key=lambda p: p.tile_count)
            ess.append(hots.pop(0))

        def tiles_of(ps):
            tl = [
                np.arange(
                    p.tile_start, p.tile_start + p.tile_count, dtype=np.int64
                )
                for p in ps
            ]
            wl = [np.full(p.tile_count, p.weight, np.float32) for p in ps]
            return (
                np.concatenate(tl) if tl else empty_i,
                np.concatenate(wl) if wl else empty_w,
            )

        t_ess, w_ess = tiles_of(ess)
        acc, cnt = cs.score_into(acc, cnt, [t_ess], [w_ess])
        pruned = False
        if hots:
            theta, accmax = cs.threshold(acc, kk, live=base)
            # blocks with zero filter-passing docs can never contribute
            # a candidate — mask them out of the survival test
            bl = np.asarray(base)
            bs = bmx.tiling.block_size
            nb = bmx.tiling.n_blocks
            padded = np.zeros(nb * bs, bool)
            padded[: len(bl)] = bl
            block_live = padded.reshape(nb, bs).any(axis=1)
            sum_bounds = np.zeros(nb, np.float32)
            for p in hots:
                sum_bounds += bmx.block_bounds(p)
            potential = accmax[0] + sum_bounds
            tl2, wl2 = [], []
            for p in hots:
                kept = bmx.surviving_tiles(
                    p, potential, theta[0], block_live=block_live
                )
                if len(kept) < p.tile_count:
                    pruned = True
                if len(kept):
                    tl2.append(kept)
                    wl2.append(np.full(len(kept), p.weight, np.float32))
            acc, cnt = cs.score_into(
                acc,
                cnt,
                [np.concatenate(tl2) if tl2 else empty_i],
                [np.concatenate(wl2) if wl2 else empty_w],
            )
        msm_arr = np.asarray([plan.msm], np.int32)
        s, d, tot = cs.finalize(acc, cnt, msm_arr, kk, live=base)
        return s[0], d[0], int(tot[0]), pruned

    def _serve_segment_filtered(self, plan, si: int, base, kb: int):
        """(scores[k], docs[k], total, pruned) for one ServePlan on one
        segment via the multi-field fused kernel with the bitset as its
        live operand; None → dense fallback."""
        n = self.reader.segments[si].num_docs
        kk = min(kb, n)
        fs = self.fused_scorer_mf(si, plan.fields)
        if fs is None:
            return None
        sections = []
        for g in plan.groups:
            parts = self.fused_parts(si, g.field)
            if parts is None:
                return None
            sec = self.fused_plan_field(si, g.field, parts, g.terms, plan.boost)
            if sec is None:
                return None
            sections.append(sec)
        s, d, tot = fs.search(
            [(sections, plan.msm)], kk, plan.combine, plan.tie, live=base,
            rows=1,
        )
        return s[0], d[0], int(tot[0]), False

    def _inv_norm(self, si: int, field: str, n: int) -> jax.Array:
        from .executor import DFS_STATS

        dfs = DFS_STATS.get()
        if dfs is not None and field in dfs.get("fields", {}):
            # DFS avgdl differs from the shard's — cached per request
            # (DFS_NORM_CACHE contextvar) so each (segment, field) norm
            # array uploads at most once per request
            from .executor import DFS_NORM_CACHE

            req_cache = DFS_NORM_CACHE.get()
            key = (id(self), si, field)
            if req_cache is not None:
                arr = req_cache.get(key)
                if arr is not None:
                    return arr
            cache = self._oracle._field_cache(field)  # ctx-aware
            pf = self.reader.segments[si].postings.get(field)
            mf = self.reader.mappings.get(field)
            if pf is None:
                host = np.zeros(n, np.float32)
            elif mf is not None and mf.type != TEXT:
                host = np.full(n, cache[1], np.float32)
            else:
                host = cache[pf.norms.astype(np.int64)]
            arr = jax.device_put(host, self.device)
            if req_cache is not None:
                req_cache[key] = arr
            return arr
        key = (si, field)
        arr = self._inv_norm_cache.get(key)
        if arr is None:
            with self._build_lock:
                arr = self._inv_norm_cache.get(key)
                if arr is not None:
                    return arr
                cache = self._oracle._field_cache(field)
                pf = self.reader.segments[si].postings.get(field)
                mf = self.reader.mappings.get(field)
                if pf is None:
                    host = np.zeros(n, np.float32)
                elif mf is not None and mf.type != TEXT:
                    # omitted norms → encodedNorm 1 for every doc
                    host = np.full(n, cache[1], np.float32)
                else:
                    host = cache[pf.norms.astype(np.int64)]
                arr = jax.device_put(host, self.device)
                self._charge("norms", int(host.nbytes), False)
                self._inv_norm_cache[key] = arr
        return arr

    # ---- entry point (mirrors NumpyExecutor.search) ----

    def search(
        self,
        query: Optional[Query],
        size: int = 10,
        from_: int = 0,
        knn: Optional[List[KnnSection]] = None,
        min_score: Optional[float] = None,
    ) -> TopDocs:
        return self.execute(query, size, from_, knn, min_score)[0]

    def execute(
        self,
        query: Optional[Query],
        size: int = 10,
        from_: int = 0,
        knn: Optional[List[KnnSection]] = None,
        min_score: Optional[float] = None,
    ) -> Tuple[TopDocs, List[np.ndarray]]:
        from .executor import PROFILE_CTX

        prof = PROFILE_CTX.get()
        t0 = time.perf_counter_ns() if prof is not None else 0
        knn_sets = [self._knn_topk_global(sec) for sec in (knn or [])]
        device_pairs: List[Tuple[jax.Array, jax.Array]] = []
        for si, seg in enumerate(self.reader.segments):
            n = seg.num_docs
            if query is None and not knn_sets:
                q: Optional[Query] = MatchAllQuery()
            else:
                q = query
            if q is not None:
                mask, scores = self._exec(q, si)
            else:
                mask = jnp.zeros(n, bool)
                scores = jnp.zeros(n, jnp.float32)
            for ks in knn_sets:
                kmask, kscores = ks[si]
                scores = jnp.where(kmask, scores + kscores, scores)
                mask = mask | kmask
            live = self.reader.live_docs[si]
            if live is not None:
                mask = mask & jnp.asarray(live)
            if min_score is not None:
                mask = mask & (scores >= jnp.float32(min_score))
            device_pairs.append((mask, scores))
        if prof is not None:
            # phase boundary: everything queued so far is device work
            jax.block_until_ready([a for pair in device_pairs for a in pair])
            t1 = time.perf_counter_ns()
            prof["device_scoring_ns"] = prof.get("device_scoring_ns", 0) + (
                t1 - t0
            )
        per_segment: List[Tuple[np.ndarray, np.ndarray]] = [
            (np.asarray(m), np.asarray(s)) for m, s in device_pairs
        ]
        if prof is not None:
            t2 = time.perf_counter_ns()
            prof["device_transfer_ns"] = prof.get("device_transfer_ns", 0) + (
                t2 - t1
            )
            t0 = t2  # host merge starts here

        # global collection (same ordering as the oracle): score desc,
        # (segment, doc) asc — vectorized over the matching docs only
        total = int(sum(m.sum() for m, _ in per_segment))
        cand_scores: List[np.ndarray] = []
        cand_seg: List[np.ndarray] = []
        cand_doc: List[np.ndarray] = []
        for si, (mask, scores) in enumerate(per_segment):
            idx = np.nonzero(mask)[0]
            if len(idx):
                cand_scores.append(scores[idx].astype(np.float64))
                cand_seg.append(np.full(len(idx), si, np.int64))
                cand_doc.append(idx.astype(np.int64))
        masks = [m for m, _ in per_segment]
        if not cand_scores:
            if prof is not None:
                prof["host_merge_ns"] = prof.get("host_merge_ns", 0) + (
                    time.perf_counter_ns() - t0
                )
            return TopDocs(total=total, hits=[], max_score=None), masks
        s = np.concatenate(cand_scores)
        sg = np.concatenate(cand_seg)
        dc = np.concatenate(cand_doc)
        need = from_ + size
        if need < len(s):
            part = np.argpartition(-s, need)[: need + 1]
            # keep enough candidates to break ties deterministically: take
            # everything scoring >= the partition's lowest kept score
            thresh = s[part].min()
            keep = np.nonzero(s >= thresh)[0]
            s, sg, dc = s[keep], sg[keep], dc[keep]
        order = np.lexsort((dc, sg, -s))
        max_score = float(s[order[0]])
        top = order[from_ : from_ + size]
        hits = [
            Hit(
                score=float(s[i]),
                segment=int(sg[i]),
                local_doc=int(dc[i]),
                doc_id=self.reader.segments[int(sg[i])].doc_ids[int(dc[i])],
            )
            for i in top
        ]
        if prof is not None:
            prof["host_merge_ns"] = prof.get("host_merge_ns", 0) + (
                time.perf_counter_ns() - t0
            )
        return TopDocs(total=total, hits=hits, max_score=max_score), masks

    # ---- node dispatch ----

    def _exec(self, q: Query, si: int) -> Tuple[jax.Array, jax.Array]:
        seg = self.reader.segments[si]
        n = seg.num_docs
        if isinstance(q, MatchAllQuery):
            return jnp.ones(n, bool), jnp.full(n, np.float32(q.boost), jnp.float32)
        if isinstance(q, MatchNoneQuery):
            return jnp.zeros(n, bool), jnp.zeros(n, jnp.float32)
        if isinstance(q, MatchQuery):
            return self._exec_match(q, si)
        if isinstance(q, TermQuery):
            return self._exec_term(q, si)
        if isinstance(q, TermsQuery):
            return self._exec_terms(q, si)
        if isinstance(q, RangeQuery):
            return self._exec_range(q, si)
        if isinstance(q, ExistsQuery):
            # host-computed masks are cheap and static; reuse oracle
            hm, hs = self._oracle._exec(q, seg)
            return jnp.asarray(hm), jnp.asarray(hs)
        if isinstance(q, BoolQuery):
            return self._exec_bool(q, si)
        if isinstance(q, ConstantScoreQuery):
            m = self.filter_mask(q.filter_query, si)
            return m, jnp.where(m, jnp.float32(q.boost), 0.0)
        if isinstance(q, MultiMatchQuery):
            return self._exec_multi_match(q, si)
        if isinstance(q, MatchPhraseQuery):
            return self._exec_phrase(q, si)
        if isinstance(q, KnnQueryWrapper):
            return self._exec_knn_query(q.knn, si)
        if isinstance(q, dsl.IdsQuery):
            return self._exec_ids(q, si)
        if isinstance(
            q, (dsl.PrefixQuery, dsl.WildcardQuery, dsl.RegexpQuery)
        ):
            # MultiTermQuery constant-score rewrite: dictionary expansion
            # stays on the host (as the reference's rewrites do), but the
            # expanded terms score as ONE device kernel launch
            return self._exec_expanded(q, si)
        if isinstance(q, dsl.DisMaxQuery):
            masks, scores = [], []
            for sub in q.queries:
                m, s = self._exec(sub, si)
                masks.append(m)
                scores.append(jnp.where(m, s, 0.0))
            mask = jnp.stack(masks).any(axis=0)
            mat = jnp.stack(scores)
            best = mat.max(axis=0)
            total = best + jnp.float32(q.tie_breaker) * (mat.sum(axis=0) - best)
            return mask, jnp.where(mask, total * jnp.float32(q.boost), 0.0)
        # term-expansion and scripted-function nodes run host-side via the
        # oracle (the reference keeps MultiTermQuery rewrites on the CPU
        # too — expansion is dictionary work, not scoring work)
        hm, hs = self._oracle._exec(q, seg)
        return jnp.asarray(hm), jnp.asarray(hs)

    # ---- text leaves via the tile kernel ----

    def term_tiles(
        self, si: int, field: str, terms: List[str], boost: float
    ) -> Tuple[List[int], List[float]]:
        """Unpadded (tile indices, per-tile weights) for terms in one
        field of one segment — the host-side query plan the kernels eat."""
        pf = self.reader.segments[si].postings.get(field)
        tile_idx: List[int] = []
        tile_w: List[float] = []
        if pf is None:
            return tile_idx, tile_w
        for t in terms:
            tid = pf.term_id(t)
            if tid < 0:
                continue
            start = int(pf.term_tile_start[tid])
            count = int(pf.term_tile_count[tid])
            w = np.float32(boost) * np.float32(self._oracle._term_weight(field, t))
            tile_idx.extend(range(start, start + count))
            tile_w.extend([float(w)] * count)
        return tile_idx, tile_w

    def _field_terms_scored(
        self, si: int, field: str, terms: List[str], boost: float
    ) -> Tuple[jax.Array, jax.Array]:
        """(scores, match_counts) for a list of terms in one field."""
        seg = self.reader.segments[si]
        n = seg.num_docs
        dp = self.device_segments[si].postings.get(field)
        if dp is None:
            return jnp.zeros(n, jnp.float32), jnp.zeros(n, jnp.int32)
        tile_idx, tile_w = self.term_tiles(si, field, terms, boost)
        if not tile_idx:
            return jnp.zeros(n, jnp.float32), jnp.zeros(n, jnp.int32)
        idx, w, v = scoring.pad_tiles(
            np.asarray(tile_idx, np.int32), np.asarray(tile_w, np.float32)
        )
        # uploads noted (`transfer.scoring`): the batcher's serve family
        # falls back here per job (`segment_topk`)
        idx = scoring._to_device(idx)
        rows_doc = dp.doc_ids[idx]
        rows_tf = dp.tfs[idx]
        inv_norm = self._inv_norm(si, field, n)
        scores, cnt = scoring.score_tiles(
            rows_doc, rows_tf, scoring._to_device(w), scoring._to_device(v),
            inv_norm, n,
        )
        return scores, cnt

    # ---- serving-path scorer plumbing (batcher entry points) ----

    def _segment_weights(self, si: int, field: str) -> np.ndarray:
        """float32[n_terms] SHARD-level BM25 idf per local term id of one
        segment (IndexSearcher.collectionStatistics — same stats the
        unpruned path uses, so batched/pruned scores match the oracle)."""
        key = (si, field)
        w = self._seg_weights.get(key)
        if w is None:
            with self._build_lock:
                w = self._seg_weights.get(key)
                if w is not None:
                    return w
                pf = self.reader.segments[si].postings[field]
                dc, _ = self.reader.field_stats(field)
                if len(self.reader.segments) == 1:
                    df = pf.term_df.astype(np.float64)
                else:
                    dfmap = self._df_map(field)
                    df = np.array([dfmap.get(t, 0) for t in pf.terms], np.float64)
                # same float path as bm25.idf (float64 math, float32 result)
                w = np.float32(np.log(1.0 + (dc - df + 0.5) / (df + 0.5)))
                self._seg_weights[key] = w
        return w

    def _df_map(self, field: str) -> Dict[str, int]:
        m = self._df_maps.get(field)
        if m is None:
            with self._build_lock:
                m = self._df_maps.get(field)
                if m is not None:
                    return m
                m = {}
                for seg in self.reader.segments:
                    pf = seg.postings.get(field)
                    if pf is not None:
                        for t, d in zip(pf.terms, pf.term_df.tolist()):
                            m[t] = m.get(t, 0) + int(d)
                self._df_maps[field] = m
        return m

    def phrase_weight(self, field: str, terms: List[str], boost: float):
        """The oracle's: boost x the summed idfs of a phrase's words."""
        return self._oracle.phrase_weight(field, terms, boost)

    def phrase_plane(self, si: int, field: str) -> Optional[tuple]:
        """(DevicePositions, inverse norms f32[n_plane], live bool[n_plane]
        or None) of one segment's field, each in the plane's column
        order: what `ops/phrase.phrase_topk` reads. None where the field
        holds no positions there. The plane uploads once a field (and
        is adopted across generations), under the `positions` category;
        an upload the HBM breaker refuses raises, and the batcher serves
        the segment's phrases per job on `_exec_phrase`."""
        key = (si, field)
        got = self._phrase_planes.get(key)
        if got is None:
            with self._build_lock:
                got = self._phrase_planes.get(key)
                if got is not None:
                    return got or None
                dev = self.device_segments[si].positions.get(field)
                if dev is None:
                    got = False
                else:
                    n = self.reader.segments[si].num_docs
                    inv = self._inv_norm(si, field, n)[dev.order]
                    live = self.reader.live_docs[si]
                    if live is not None:
                        note_transfer("h2d", np.asarray(live).nbytes)
                        live = jnp.asarray(live)[dev.order]
                    self._charge(
                        "norms", int(inv.nbytes)
                        + (0 if live is None else int(live.nbytes)), False)
                    got = (dev, inv, live)
                self._phrase_planes[key] = got
        return got or None

    def shard_df(self, field: str, term: str) -> int:
        key = (field, term)
        df = self._shard_dfs.get(key)
        if df is None:
            df, _ = self.reader.term_stats(field, term)
            self._shard_dfs[key] = df
        return df

    def sparse_shard_max_df(self, field: str, terms) -> int:
        """The most postings any ONE of `terms` holds in a
        `sparse_vector` field over the shard's segments (each a
        distinct doc that matches any query holding the term): what
        proves a capped total before tiles may drop. Array look-ups a
        segment, no Python call a term."""
        df = np.zeros(len(terms), np.int64)
        for seg in self.reader.segments:
            sf = (getattr(seg, "sparse", None) or {}).get(field)
            if sf is not None:
                tids = sf.term_ids(terms)
                df += np.where(tids >= 0, sf.term_df[tids], 0)
        return int(df.max(initial=0))

    @property
    def deleted_count(self) -> int:
        if self._deleted_count is None:
            self._deleted_count = int(
                sum(int((~l).sum()) for l in self.reader.live_docs if l is not None)
            )
        return self._deleted_count

    def block_index(self, si: int, field: str):
        """Cached BlockMaxIndex (shard-level stats over the segment's
        block-aligned tiling) — None when the field has no postings.

        Also the source of truth for the mesh serving stack
        (parallel/mesh_executor.MeshExecutor builds its per-entry tile
        plans and weights from this index, and its norm operands from
        `_inv_norm`), which is what keeps the SPMD path's scoring
        inputs identical to the sequential kernels'."""
        key = (si, field)
        if key in self._block_indexes:
            return self._block_indexes[key]
        with self._build_lock:
            if key in self._block_indexes:
                return self._block_indexes[key]
            from ..ops.wand import BlockMaxIndex, get_tiling

            seg = self.reader.segments[si]
            pf = seg.postings.get(field)
            if pf is None:
                bmx = None  # cache the miss: no re-lock per batch
            else:
                tiling = get_tiling(pf, seg.num_docs)
                bmx = BlockMaxIndex(
                    tiling,
                    self._segment_weights(si, field),
                    self._oracle._field_cache(field),
                )
            self._block_indexes[key] = bmx
            return bmx

    def chunked_scorer(self, si: int, field: str):
        """Cached fixed-shape ChunkedScorer over the block-aligned tiling
        of one segment (the batcher's launch engine)."""
        key = (si, field)
        if key in self._chunked_scorers:
            return self._chunked_scorers[key]
        with self._build_lock:
            if key in self._chunked_scorers:
                return self._chunked_scorers[key]
            bmx = self.block_index(si, field)
            if bmx is None:
                cs = None  # cache the miss: no re-lock per batch
            else:
                seg = self.reader.segments[si]
                cs = scoring.ChunkedScorer(
                    bmx.tiling.doc_ids,
                    bmx.tiling.tfs,
                    self._inv_norm(si, field, seg.num_docs),
                    self.reader.live_docs[si],
                    block_size=bmx.tiling.block_size,
                )
            self._chunked_scorers[key] = cs
            return cs

    def fused_parts(self, si: int, field: str):
        """Cached per-(segment, field) device arrays for fused scoring:
        dict(doc_ids, tfs, inv_norm, dense, hot_rank), or None when the
        field has no postings / the segment is below FUSED_MIN_DOCS.
        Shared by every MultiFusedScorer over the field, so dense hot
        rows are built once per field."""
        key = (si, field)
        if key in self._fused_parts:
            return self._fused_parts[key]
        with self._build_lock:
            if key in self._fused_parts:
                return self._fused_parts[key]
            parts = self._fused_parts_build(si, field)
            self._fused_parts[key] = parts
            return parts

    @staticmethod
    def _dense_rows_stats(parts) -> Dict[str, int]:
        return {
            "dense_rows_wanted": sum(p["rows_wanted"] for p in parts),
            "dense_rows_held": sum(p["rows_held"] for p in parts),
            "dense_tf_overflow_postings": sum(
                p["tf_overflow_postings"] for p in parts
            ),
        }

    def dense_rows_stats(self) -> Dict[str, int]:
        """Over the fields whose fused parts are loaded: terms that want
        a dense row, terms that hold one, and the postings whose tf the
        uint16 rows carry past DENSE_TF_MAX (`_fused_parts_build`)."""
        return self._dense_rows_stats(
            [p for p in list(self._fused_parts.values()) if p is not None])

    def fused_scorer_mf(self, si: int, fields: tuple):
        """Cached MultiFusedScorer over one segment and a field tuple
        (a `match`'s one field, a bool's, a multi_match's several;
        ops/scoring.py's module comment says why one fused call beats
        multi-phase pruning); None when any field lacks parts (a small
        segment, which the chunked path serves, or no postings)."""
        key = (si, tuple(fields))
        if key in self._fused_mf:
            return self._fused_mf[key]
        with self._build_lock:
            if key in self._fused_mf:
                return self._fused_mf[key]
            parts = [self.fused_parts(si, f) for f in fields]
            if any(p is None for p in parts):
                fs = None
            else:
                fs = scoring.MultiFusedScorer(
                    fields, parts, self.reader.live_docs[si]
                )
            self._fused_mf[key] = fs
            return fs

    def _fused_parts_build(self, si: int, field: str):
        """The field's device arrays for fused scoring, and its choice of
        hot terms. A term WANTS a dense per-doc tf row when its df is at
        least max(1024, n / 128): left sparse it costs a plan at most
        n / 16384 of its FUSED_T_RARE tile slots (25 at 401,729 docs, 61
        at 1M). Rows are HELD by df rank, most frequent first, while the
        budget lasts (DENSE_ROWS_HBM_BUDGET a field, and the ledger's
        free HBM): under any budget that leaves sparse the wanted terms
        of fewest tiles, so every question's plan carries the fewest
        rare tiles that budget allows. A row is uint8, or uint16 (two
        rows of the budget) where the term's tf passes DENSE_TF_MAX in
        some document; only a tf past WIDE_TF_MAX keeps a term sparse.
        The questions that still pass a slot budget are counted
        (`fused_overflow_jobs`, `serve_fallback_jobs`)."""
        seg = self.reader.segments[si]
        pf = seg.postings.get(field)
        if pf is None or seg.num_docs < FUSED_MIN_DOCS:
            return None
        n = seg.num_docs
        dp = self.device_segments[si].postings[field]
        n_terms = len(pf.terms)
        counts = pf.term_tile_count.astype(np.int64)
        starts = pf.term_tile_start.astype(np.int64)
        tile_of = (
            np.arange(int(counts.sum()), dtype=np.int64)
            - np.repeat(np.cumsum(counts) - counts, counts)
            + np.repeat(starts, counts)
        )
        term_of_tile = np.repeat(np.arange(n_terms, dtype=np.int64), counts)
        term_max_tf = np.zeros(n_terms, np.int64)
        np.maximum.at(term_max_tf, term_of_tile, pf.tile_max_tf[tile_of])
        df = pf.term_df.astype(np.int64)
        wanted = np.nonzero(
            (df >= dense_row_min_df(n)) & (term_max_tf <= scoring.WIDE_TF_MAX)
        )[0]
        from ..common.memory import hbm_ledger

        by_df = wanted[np.argsort(-df[wanted], kind="stable")]
        is_wide = term_max_tf[by_df] > scoring.DENSE_TF_MAX
        held = by_df[np.cumsum(1 + is_wide) <= dense_rows_room(n)]
        if len(held) < len(wanted):
            hbm_ledger.note_degraded()
        planes = []
        hot_rank: Dict[int, int] = {}
        tf_overflow_postings = 0
        for wide in (False, True):
            ids = np.sort(held[(term_max_tf[held] > scoring.DENSE_TF_MAX) == wide])
            if not len(ids):
                planes.append(None)
                continue
            sel = np.isin(term_of_tile, ids)
            if wide:
                tf_overflow_postings = int(
                    (pf.tfs[tile_of[sel]] > scoring.DENSE_TF_MAX).sum()
                )
            plane = scoring.build_dense_rows(
                dp.doc_ids,
                dp.tfs,
                jnp.asarray(tile_of[sel].astype(np.int32)),
                jnp.asarray(
                    np.searchsorted(ids, term_of_tile[sel]).astype(np.int32)
                ),
                n_hot=len(ids),
                n_docs=n,
                dtype=jnp.uint16 if wide else jnp.uint8,
            )
            self._charge("dense_rows", _tree_nbytes(plane), False)
            base = len(hot_rank)  # uint16 rows number on from the uint8 ones
            hot_rank.update((int(t), base + r) for r, t in enumerate(ids))
            planes.append(plane)
        dense, wide_rows = planes
        return {
            "doc_ids": dp.doc_ids,
            "tfs": dp.tfs,
            "inv_norm": self._inv_norm(si, field, n),
            "dense": dense,
            "wide": wide_rows,
            "hot_rank": hot_rank,
            # for `_nodes/stats` (pipeline.batching.dense_rows_*): terms
            # that want a row, terms that hold one, and the postings
            # whose tf the uint16 rows carry past DENSE_TF_MAX
            "rows_wanted": int(len(wanted)),
            "rows_held": int(len(held)),
            "tf_overflow_postings": tf_overflow_postings,
        }

    def fuzzy_parts(self, si: int, field: str):
        """What the batcher's `fuzzy` family reads of one segment's text
        field, built at the field's FIRST fuzzy search (a field no fuzzy
        search names pays nothing) and kept: the term dictionary as a
        device plane (ops/fuzzy.py `DeviceTermPlane`, charged to the HBM
        ledger with the postings: 36 B a term), the fused scorer at the
        family's own slot budgets over the field's resident postings and
        dense rows (`scoring.FUZZY_T_RARE`, `FUZZY_H`: a program of its
        own, so no other family's changes), and `hot_row`, each term's
        dense row or -1. None: no postings, or a segment under
        FUSED_MIN_DOCS (the unbatched executor serves it)."""
        key = (si, field)
        if key in self._fuzzy_parts:
            return self._fuzzy_parts[key]
        with self._build_lock:
            if key in self._fuzzy_parts:
                return self._fuzzy_parts[key]
            parts = self.fused_parts(si, field)
            built = None
            if parts is not None:
                pf = self.reader.segments[si].postings[field]
                plane = fuzzy_ops.DeviceTermPlane(
                    pf.term_plane(), len(pf.terms), self.device)
                self._charge("postings", plane.nbytes, False)
                hot_row = np.full(len(pf.terms), -1, np.int64)
                if parts["hot_rank"]:
                    tids = np.fromiter(parts["hot_rank"], np.int64)
                    hot_row[tids] = np.fromiter(
                        parts["hot_rank"].values(), np.int64)
                built = {
                    "plane": plane,
                    "scorer": scoring.MultiFusedScorer(
                        (field,), [parts], self.reader.live_docs[si],
                        t_rare=scoring.FUZZY_T_RARE,
                        n_hot_slots=scoring.FUZZY_H),
                    "hot_row": hot_row,
                    # terms by code points: `fuzzy_ops.least_work`'s
                    "terms_by_len": np.bincount(pf.term_plane().lens[
                        : len(pf.terms)]),
                    "parts": parts,
                }
            self._fuzzy_parts[key] = built
            return built

    def fuzzy_plan_field(self, si: int, field: str, fz, ords: np.ndarray,
                         weights: np.ndarray):
        """One job's section of the fuzzy family's fused plan from its
        kept terms' ordinals in this segment's dictionary and their
        float32 weights (a term two words kept rides twice, under each
        word's weight): (rare tiles, their weights, hot rows, their
        weights), every term a clause of its own that counts; None where
        the plan passes a slot budget. No loop over the terms: a
        question brings hundreds."""
        pf = self.reader.segments[si].postings[field]
        rows = fz["hot_row"][ords]
        hot = rows >= 0
        hr, hw = rows[hot], weights[hot]
        cold = ords[~hot]
        counts = pf.term_tile_count[cold].astype(np.int64)
        n_tiles = int(counts.sum())
        if n_tiles > scoring.FUZZY_T_RARE or len(hr) > scoring.FUZZY_H:
            return None
        first = np.cumsum(counts) - counts
        rt = (np.arange(n_tiles, dtype=np.int64) - np.repeat(first, counts)
              + np.repeat(pf.term_tile_start[cold].astype(np.int64), counts))
        rw = np.repeat(weights[~hot], counts)
        if fz["parts"]["wide"] is not None:
            hr, hw = list(hr), list(hw)
            scoring.wide_rows_first(hr, hw, fz["parts"]["dense"])
            hr, hw = np.asarray(hr, np.int64), np.asarray(hw, np.float32)
        return rt, rw.astype(np.float32), hr, hw.astype(np.float32)

    def fuzzy_terms(self, field: str, word: str, params):
        return self._oracle.fuzzy_terms(field, word, params)

    def fused_plan_field(
        self, si: int, field: str, parts, terms_flagged, boost: float
    ):
        """One field's section of a MultiFusedScorer plan:
        (rare_tiles, rare_w_signed, hot_ranks, hot_w_signed) — weight
        sign marks whether a term counts toward the match threshold
        (positive = required/counted). terms_flagged: [(term, term_boost,
        count)], `count` as FieldGroup gives it: 0 scores only, 1 a
        clause of its own, 2 + d a term of multi-term clause d, whose
        slots carry that clause's counter above their ids
        (scoring.clause_slot_ids). None on slot-budget overflow."""
        pf = self.reader.segments[si].postings.get(field)
        if pf is None:
            return (
                np.empty(0, np.int64), np.empty(0, np.float32),
                np.empty(0, np.int64), np.empty(0, np.float32),
            )
        weights = self._segment_weights(si, field)
        rt: list = []
        rw: list = []
        hr: list = []
        hw: list = []
        for t, tb, count in terms_flagged:
            tid = pf.term_id(t)
            if tid < 0:
                continue
            w = float(weights[tid]) * boost * tb
            if w < 0.0:
                # a negative weight (e.g. field^-2) would corrupt the
                # sign-encoded count flag — exact path handles it
                return None
            if w == 0.0:
                # a zero weight can't carry the count flag in its sign;
                # nudge to the smallest positive float so required terms
                # still count (score contribution is ~0 either way)
                w = 1e-30
            if not count:
                w = -w
            r = parts["hot_rank"].get(tid)
            if r is not None:
                hr.extend(scoring.clause_slot_ids([r], count))
                hw.append(w)
            else:
                s0 = int(pf.term_tile_start[tid])
                c = int(pf.term_tile_count[tid])
                rt.extend(scoring.clause_slot_ids(range(s0, s0 + c), count))
                rw.extend([w] * c)
        if len(rt) > scoring.FUSED_T_RARE or len(hr) > scoring.FUSED_H:
            return None
        if parts["wide"] is not None:
            scoring.wide_rows_first(hr, hw, parts["dense"])
        return (
            np.asarray(rt, np.int64),
            np.asarray(rw, np.float32),
            np.asarray(hr, np.int64),
            np.asarray(hw, np.float32),
        )

    def _sort_ranks(self, si: int, field: str, desc: bool):
        """Device int32 rank column for one segment's numeric doc-value
        field: rank orders by (value, doc) asc — or (-value, doc) for
        desc — with missing docs ranked last by doc. Ranks are EXACT at
        any magnitude (dates included), unlike float32 keys on a TPU
        without x64; the global-ordinals idea applied to sort keys.
        Returns (device_ranks, host_sorted_values, n_have) or None."""
        key = (si, field, desc)
        cached = self._sort_rank_cache.get(key)
        if cached is not None:
            return cached
        with self._build_lock:
            cached = self._sort_rank_cache.get(key)
            if cached is not None:
                return cached
            seg = self.reader.segments[si]
            nf = seg.numerics.get(field)
            n = seg.num_docs
            if nf is None:
                ranks_host = np.arange(n, dtype=np.int32)
                sorted_vals = np.zeros(0)
                n_have = 0
            else:
                have = nf.exists
                vals = nf.values
                docs = np.arange(n)
                order_vals = -vals if desc else vals
                have_idx = docs[have]
                order = np.lexsort((have_idx, order_vals[have]))
                ranked = have_idx[order]
                missing = docs[~have]
                ranks_host = np.empty(n, np.int32)
                ranks_host[ranked] = np.arange(len(ranked), dtype=np.int32)
                ranks_host[missing] = np.arange(
                    len(ranked), n, dtype=np.int32
                )
                sorted_vals = np.sort(vals[have])
                n_have = int(len(ranked))
            arr = jax.device_put(ranks_host, self.device)
            self._charge("sort_ranks", int(ranks_host.nbytes), False)
            cached = (arr, sorted_vals, n_have)
            self._sort_rank_cache[key] = cached
            return cached

    def execute_sorted_device(
        self,
        query: Optional[Query],
        sort_specs,
        size: int = 10,
        search_after=None,
    ):
        """Device field-sorted collection for SINGLE numeric/date/bool
        sort keys (VERDICT r3 #6: sort keys live on device — collect
        the sorted top-k there and download k rows, not [n_docs]
        masks). Returns (TopDocs, svals) or None when the spec needs
        the oracle (multi-key, keyword keys, missing overrides,
        _score/_doc)."""
        if len(sort_specs) != 1:
            return None
        spec = sort_specs[0]
        field = spec["field"]
        if field in ("_score", "_doc"):
            return None
        mf = self.reader.mappings.get(field)
        if mf is None or not mf.is_numeric():
            return None
        if spec.get("missing") not in (None, "_last"):
            return None
        desc = spec.get("order", "asc") == "desc"
        after_v = None
        if search_after is not None:
            try:
                after_v = float(search_after[0])
            except (TypeError, ValueError):
                return None
        entries = []  # (rank_tuple, si, doc)
        total = 0
        for si, seg in enumerate(self.reader.segments):
            n = seg.num_docs
            if n == 0:
                continue
            got = self._sort_ranks(si, field, desc)
            ranks, sorted_vals, n_have = got
            if query is not None:
                mask, _ = self._exec(query, si)
            else:
                mask = jnp.ones(n, bool)
            live = self.reader.live_docs[si]
            if live is not None:
                mask = mask & jnp.asarray(live)
            # hits.total reports the FULL query match count — the
            # search_after cursor narrows the page, never the total
            total += int(np.asarray(mask.sum()))
            if after_v is not None:
                # strictly-after in VALUE space (ties skipped, matching
                # the oracle): rank >= count of values <=/>= after
                if desc:
                    thr = n_have - int(
                        np.searchsorted(sorted_vals, after_v, side="left")
                    )
                else:
                    thr = int(
                        np.searchsorted(sorted_vals, after_v, side="right")
                    )
                mask = mask & (ranks >= jnp.int32(thr))
            kk = min(size, n)
            # smallest ranks win: top_k over negated ranks; masked docs
            # sink below every real rank
            neg = jnp.where(mask, -ranks, jnp.int32(-(2**31 - 1)))
            top_neg, top_d = jax.lax.top_k(neg, kk)
            host_neg = np.asarray(top_neg)
            host_d = np.asarray(top_d)
            for j in range(kk):
                if host_neg[j] == -(2**31 - 1):
                    continue
                entries.append((int(-host_neg[j]), si, int(host_d[j])))
        # cross-segment merge: segment-local ranks order identically to
        # values WITHIN a segment; across segments compare actual values
        nf_cols = [seg.numerics.get(field) for seg in self.reader.segments]

        def global_key(e):
            _, si, d = e
            nf = nf_cols[si]
            if nf is None or not nf.exists[d]:
                return (1, 0.0, si, d)  # missing last
            v = float(nf.values[d])
            return (0, -v if desc else v, si, d)

        entries.sort(key=global_key)
        page = entries[:size]
        hits = []
        svals = []
        for _, si, d in page:
            hits.append(
                Hit(
                    score=0.0,
                    segment=si,
                    local_doc=d,
                    doc_id=self.reader.segments[si].doc_ids[d],
                )
            )
            nf = nf_cols[si]
            if nf is None or not nf.exists[d]:
                svals.append([None])
            else:
                v = nf.values[d]
                svals.append(
                    [int(v)] if float(v).is_integer() else [float(v)]
                )
        return TopDocs(total=total, hits=hits, max_score=None), svals

    def _entry_docs_dev(self, si: int, field: str):
        """Device int32 doc index per multi-value ordinal entry (the
        CSR row-expansion), cached per (segment, field)."""
        key = (si, field)
        cached = self._entry_docs_dev_cache.get(key)
        if cached is not None:
            return cached
        with self._build_lock:
            cached = self._entry_docs_dev_cache.get(key)
            if cached is not None:
                return cached
            of = self.reader.segments[si].ordinals.get(field)
            if of is None:
                self._entry_docs_dev_cache[key] = None
                return None
            host = np.repeat(
                np.arange(self.reader.segments[si].num_docs, dtype=np.int32),
                np.diff(of.mv_offsets),
            )
            arr = jax.device_put(host, self.device)
            self._charge("doc_values", int(host.nbytes), False)
            self._entry_docs_dev_cache[key] = arr
            return arr

    def execute_with_terms_aggs(self, query, agg_nodes, k: int, tth):
        """Device query + keyword-terms aggregation in one pass
        (VERDICT r3 #6: terms bucketing = segment scatter-add on
        device, host reduce): per segment the downloads are k top-hit
        rows plus one compact count vector per agg — never the full
        [n_docs] masks. Returns (TopDocs, partials dict) or None when
        any agg needs the host collector."""
        from .aggs import _bkey, _int_param, _norm_order, _order_buckets

        for node in agg_nodes:
            if node.type != "terms" or node.subs:
                return None
            f = node.params.get("field")
            if f is None:
                return None
            mf = self.reader.mappings.get(f)
            if mf is None or mf.type != KEYWORD:
                return None
        # per-node global (term → count) accumulation across segments
        per_node_counts: List[Dict[str, int]] = [dict() for _ in agg_nodes]
        cands: List[Tuple[float, int, int]] = []
        total = 0
        for si, seg in enumerate(self.reader.segments):
            n = seg.num_docs
            if n == 0:
                continue
            if query is not None:
                mask, scores = self._exec(query, si)
            else:
                mask = jnp.ones(n, bool)
                scores = jnp.zeros(n, jnp.float32)
            live = self.reader.live_docs[si]
            if live is not None:
                mask = mask & jnp.asarray(live)
            # device count vectors, one per agg node
            count_outs = []
            for node in agg_nodes:
                f = node.params["field"]
                of = seg.ordinals.get(f)
                entry_docs = self._entry_docs_dev(si, f)
                if of is None or entry_docs is None:
                    count_outs.append(None)
                    continue
                dof = self.device_segments[si].ordinals.get(f)
                mv_ords = dof[0] if dof is not None else jnp.asarray(of.mv_ords)
                # int32: segment doc counts are int32-bounded by design
                sel = mask[entry_docs].astype(jnp.int32)
                counts = jnp.zeros(len(of.ord_terms), jnp.int32).at[
                    mv_ords
                ].add(sel)
                count_outs.append(counts)
            s, d = scoring.topk_hits(scores, mask, min(k, n))
            host_s = np.asarray(s)
            host_d = np.asarray(d)
            total += int(np.asarray(mask.sum()))
            finite = np.isfinite(host_s)
            for sc, doc in zip(host_s[finite], host_d[finite]):
                cands.append((float(sc), si, int(doc)))
            for ni, counts in enumerate(count_outs):
                if counts is None:
                    continue
                host_counts = np.asarray(counts)
                of = seg.ordinals[agg_nodes[ni].params["field"]]
                agg = per_node_counts[ni]
                for o in np.nonzero(host_counts)[0]:
                    key = of.ord_terms[o]
                    agg[key] = agg.get(key, 0) + int(host_counts[o])
        # td (relevance order, exact totals)
        cands.sort(key=lambda c: (-c[0], c[1], c[2]))
        page = cands[:k]
        hits = [
            Hit(
                score=s,
                segment=si,
                local_doc=d,
                doc_id=self.reader.segments[si].doc_ids[d],
            )
            for s, si, d in page
        ]
        td = TopDocs(
            total=total,
            hits=hits,
            max_score=hits[0].score if hits else None,
        )
        # partials in the host collector's wire shape (same reduce path)
        partials = {}
        for ni, node in enumerate(agg_nodes):
            counts = per_node_counts[ni]
            size = _int_param(node, "size", 10)
            shard_size = _int_param(
                node, "shard_size", max(int(size * 1.5) + 10, size)
            )
            order = _norm_order(node.params.get("order", {"_count": "desc"}))
            top = _order_buckets(counts, order)[:shard_size]
            shard_error = (
                top[-1][1] if len(counts) > shard_size and top else 0
            )
            partials[node.name] = {
                "t": "terms",
                "buckets": {
                    _bkey(key): {"key": key, "doc_count": cnt, "subs": {}}
                    for key, cnt in top
                },
                "sum_docs": sum(counts.values()),
                "size": size,
                "order": order,
                "shard_error": shard_error,
            }
        return td, partials

    def segment_topk(self, query: Query, si: int, k: int):
        """(scores[k], docs[k], total) for one parsed query on one
        segment — the batcher's per-segment fallback when a fused
        launch isn't available (small segment / slot overflow)."""
        seg = self.reader.segments[si]
        n = seg.num_docs
        if n == 0:
            return (
                np.zeros(0, np.float32), np.zeros(0, np.int32), 0
            )
        mask, scores = self._exec(query, si)
        live = self.reader.live_docs[si]
        if live is not None:
            mask = mask & jnp.asarray(live)
        s, d = scoring.topk_hits(scores, mask, min(k, n))
        total = int(scoring._to_host(mask.sum()))
        return scoring._to_host(s), scoring._to_host(d), total

    def _exec_match(self, q: MatchQuery, si: int) -> Tuple[jax.Array, jax.Array]:
        seg = self.reader.segments[si]
        n = seg.num_docs
        mf = self.reader.mappings.get(q.field)
        if mf is None:
            return jnp.zeros(n, bool), jnp.zeros(n, jnp.float32)
        if mf.type != TEXT:
            return self._exec_term(
                TermQuery(field=q.field, value=q.query, boost=q.boost), si
            )
        if q.fuzzy is not None:
            # a fuzzy `match` no planner took (inside a bool, a shard of
            # several segments' fallback): the oracle's rewrite
            hm, hs = self._oracle._exec(q, seg)
            return jnp.asarray(hm), jnp.asarray(hs)
        analyzer_name = q.analyzer or mf.search_analyzer or mf.analyzer
        terms = self.reader.analysis.get(analyzer_name).terms(q.query)
        if not terms:
            return jnp.zeros(n, bool), jnp.zeros(n, jnp.float32)
        scores, cnt = self._field_terms_scored(si, q.field, terms, q.boost)
        if q.operator == "and":
            mask = cnt >= len(terms)
        else:
            msm = max(1, dsl.parse_minimum_should_match(q.minimum_should_match, len(terms)))
            mask = cnt >= msm
        return mask, jnp.where(mask, scores, 0.0)

    def _id_map(self, si: int) -> Dict[str, int]:
        """_id → local doc hash map per segment (built once; the analog
        of Lucene's per-segment terms dict on the _id field)."""
        m = self._id_maps.get(si)
        if m is None:
            m = {d: i for i, d in enumerate(self.reader.segments[si].doc_ids)}
            self._id_maps[si] = m
        return m

    def _exec_ids(self, q, si: int) -> Tuple[jax.Array, jax.Array]:
        seg = self.reader.segments[si]
        n = seg.num_docs
        idmap = self._id_map(si)
        mask = np.zeros(n, bool)
        for v in q.values:
            loc = idmap.get(str(v))
            if loc is not None:
                mask[loc] = True
        dmask = jnp.asarray(mask)
        return dmask, jnp.where(dmask, jnp.float32(q.boost), 0.0)

    def _exec_expanded(self, q, si: int) -> Tuple[jax.Array, jax.Array]:
        """prefix/wildcard/regexp: host term-dict expansion, then the
        expanded terms score as one device launch (constant score).
        `fuzzy` is not among them: Lucene scores its best
        `max_expansions` terms (TopTermsBlendedFreqScoringRewrite), which
        the batcher's `fuzzy` family serves on the device and, inside
        another query, the oracle (`_exec`'s last branch)."""
        seg = self.reader.segments[si]
        n = seg.num_docs
        terms = self._oracle._expand_terms(q, seg)
        if not terms:
            return jnp.zeros(n, bool), jnp.zeros(n, jnp.float32)
        _, cnt = self._field_terms_scored(si, q.field, terms, 1.0)
        mask = cnt >= 1
        return mask, jnp.where(mask, jnp.float32(q.boost), 0.0)

    def _exec_knn_query(self, sec: KnnSection, si: int) -> Tuple[jax.Array, jax.Array]:
        """knn-as-a-query-node: per-segment num_candidates cut (mirrors
        NumpyExecutor._exec_knn), fully on device."""
        seg = self.reader.segments[si]
        n = seg.num_docs
        dv = self.device_segments[si].vectors.get(sec.field)
        if dv is None:
            return jnp.zeros(n, bool), jnp.zeros(n, jnp.float32)
        vf = seg.vectors[sec.field]
        qv = jnp.asarray(np.asarray(sec.query_vector, np.float32))[None, :]
        scores = scoring.knn_scores(qv, dv.rows, vf.similarity)[0]
        mask = dv.exists
        if sec.filter is not None:
            mask = mask & self.filter_mask(sec.filter, si)
        live = self.reader.live_docs[si]
        if live is not None:
            mask = mask & jnp.asarray(live)
        if sec.similarity is not None:
            mask = mask & (scores >= jnp.float32(sec.similarity))
        cand = min(sec.num_candidates, n)
        masked = jnp.where(mask, scores, -jnp.inf)
        kth = jax.lax.top_k(masked, cand)[0][-1]
        # when fewer than `cand` docs match, kth is -inf and cuts nothing
        # (same as the oracle's "only cut if cand < matches" branch)
        mask = mask & (masked >= kth)
        out = scores * jnp.float32(sec.boost)
        return mask, jnp.where(mask, out, 0.0)

    def _exec_phrase(
        self, q: MatchPhraseQuery, si: int
    ) -> Tuple[jax.Array, jax.Array]:
        """The unbatched phrase (a clause of a `bool`, a sloppy phrase,
        a segment whose positions plane cannot be held): the words'
        conjunction on the device, ONE download of its mask, the
        candidates' phrase frequencies from the columnar positions on
        the host (`executor.phrase_freqs`, PositionsEnum's analog:
        `_source` is never re-analyzed), scored as the oracle scores
        them (phrase frequency as tf, summed idf), one upload back. A
        bare exact phrase rides the batcher's `phrase` family and never
        comes here."""
        seg = self.reader.segments[si]
        n = seg.num_docs
        mf = self.reader.mappings.get(q.field)
        if mf is None or mf.type != TEXT:
            return jnp.zeros(n, bool), jnp.zeros(n, jnp.float32)
        conj, _ = self._exec_match(
            MatchQuery(field=q.field, query=q.query, operator="and",
                       analyzer=q.analyzer, boost=q.boost),
            si,
        )
        mask, scores = self._oracle.phrase_scores(
            q, seg, np.flatnonzero(scoring._to_host(conj)))
        note_transfer("h2d", mask.nbytes + scores.nbytes)
        return jnp.asarray(mask), jnp.asarray(scores)

    def _exec_term(self, q: TermQuery, si: int) -> Tuple[jax.Array, jax.Array]:
        seg = self.reader.segments[si]
        n = seg.num_docs
        mf = self.reader.mappings.get(q.field)
        if q.field == "_id":
            mask = np.zeros(n, bool)
            loc = self._id_map(si).get(str(q.value))
            if loc is not None:
                mask[loc] = True
            dmask = jnp.asarray(mask)
            return dmask, jnp.where(dmask, jnp.float32(q.boost), 0.0)
        if mf is None:
            return jnp.zeros(n, bool), jnp.zeros(n, jnp.float32)
        if mf.type in (TEXT, KEYWORD):
            value = q.value
            if isinstance(value, bool):
                value = "true" if value else "false"
            scores, cnt = self._field_terms_scored(si, q.field, [str(value)], q.boost)
            mask = cnt >= 1
            return mask, jnp.where(mask, scores, 0.0)
        dn = self.device_segments[si].numerics.get(q.field)
        if dn is None:
            return jnp.zeros(n, bool), jnp.zeros(n, jnp.float32)
        values, exists = dn
        target = _coerce_numeric(mf.type, q.value)
        mask = exists & (values == target)
        return mask, jnp.where(mask, jnp.float32(q.boost), 0.0)

    def _exec_terms(self, q: TermsQuery, si: int) -> Tuple[jax.Array, jax.Array]:
        seg = self.reader.segments[si]
        n = seg.num_docs
        mf = self.reader.mappings.get(q.field)
        if q.field != "_id" and mf is not None and mf.type in (TEXT, KEYWORD):
            # one combined kernel launch for all values (constant-score,
            # so only the match counts matter)
            vals = [
                ("true" if v else "false") if isinstance(v, bool) else str(v)
                for v in q.values
            ]
            _, cnt = self._field_terms_scored(si, q.field, vals, 1.0)
            mask = cnt >= 1
            return mask, jnp.where(mask, jnp.float32(q.boost), 0.0)
        if q.field != "_id" and mf is not None:
            dn = self.device_segments[si].numerics.get(q.field)
            if dn is None:
                return jnp.zeros(n, bool), jnp.zeros(n, jnp.float32)
            values, exists = dn
            targets = np.array(
                [_coerce_numeric(mf.type, v) for v in q.values], np.float64
            )
            mask = exists & jnp.isin(values, jnp.asarray(targets))
            return mask, jnp.where(mask, jnp.float32(q.boost), 0.0)
        m = jnp.zeros(n, bool)
        for v in q.values:
            tm, _ = self._exec_term(TermQuery(field=q.field, value=v), si)
            m = m | tm
        return m, jnp.where(m, jnp.float32(q.boost), 0.0)

    def _exec_range(self, q: RangeQuery, si: int) -> Tuple[jax.Array, jax.Array]:
        seg = self.reader.segments[si]
        n = seg.num_docs
        mf = self.reader.mappings.get(q.field)
        if mf is None:
            return jnp.zeros(n, bool), jnp.zeros(n, jnp.float32)
        if mf.type in (TEXT, KEYWORD):
            # host bisect on the sorted ord dictionary picks [lo, hi);
            # the multi-value CSR membership test runs on device
            of = seg.ordinals.get(q.field)
            dof = self.device_segments[si].ordinals.get(q.field)
            if of is None or dof is None:
                return jnp.zeros(n, bool), jnp.zeros(n, jnp.float32)
            import bisect

            terms = of.ord_terms
            lo, hi = 0, len(terms)
            if q.gte is not None:
                lo = bisect.bisect_left(terms, str(q.gte))
            if q.gt is not None:
                lo = max(lo, bisect.bisect_right(terms, str(q.gt)))
            if q.lte is not None:
                hi = min(hi, bisect.bisect_right(terms, str(q.lte)))
            if q.lt is not None:
                hi = min(hi, bisect.bisect_left(terms, str(q.lt)))
            mv_ords, mv_offsets = dof
            in_range = (mv_ords >= lo) & (mv_ords < hi)
            csum = jnp.concatenate(
                [jnp.zeros(1, jnp.int32), jnp.cumsum(in_range.astype(jnp.int32))]
            )
            mask = (csum[mv_offsets[1:]] - csum[mv_offsets[:-1]]) > 0
            return mask, jnp.where(mask, jnp.float32(q.boost), 0.0)
        dn = self.device_segments[si].numerics.get(q.field)
        if dn is None:
            return jnp.zeros(n, bool), jnp.zeros(n, jnp.float32)
        values, exists = dn
        mask = exists
        conv = (lambda v: parse_date_millis(v)) if mf.type == DATE else float
        if q.gte is not None:
            mask = mask & (values >= conv(q.gte))
        if q.gt is not None:
            mask = mask & (values > conv(q.gt))
        if q.lte is not None:
            mask = mask & (values <= conv(q.lte))
        if q.lt is not None:
            mask = mask & (values < conv(q.lt))
        return mask, jnp.where(mask, jnp.float32(q.boost), 0.0)

    def _exec_bool(self, q: BoolQuery, si: int) -> Tuple[jax.Array, jax.Array]:
        seg = self.reader.segments[si]
        n = seg.num_docs
        mask = jnp.ones(n, bool)
        scores = jnp.zeros(n, jnp.float32)
        for c in q.must:
            m, s = self._exec(c, si)
            mask = mask & m
            scores = scores + s
        for c in q.filter:
            mask = mask & self.filter_mask(c, si)
        if q.should:
            sscores = jnp.zeros(n, jnp.float32)
            match_count = jnp.zeros(n, jnp.int32)
            for c in q.should:
                m, s = self._exec(c, si)
                sscores = sscores + jnp.where(m, s, 0.0)
                match_count = match_count + m.astype(jnp.int32)
            default_msm = 0 if (q.must or q.filter) else 1
            msm = (
                dsl.parse_minimum_should_match(q.minimum_should_match, len(q.should))
                if q.minimum_should_match is not None
                else default_msm
            )
            if msm > 0:
                mask = mask & (match_count >= msm)
            scores = scores + jnp.where(match_count > 0, sscores, 0.0)
        for c in q.must_not:
            m, _ = self._exec(c, si)
            mask = mask & ~m
        if q.boost != 1.0:
            scores = scores * jnp.float32(q.boost)
        return mask, jnp.where(mask, scores, 0.0)

    def _exec_multi_match(self, q: MultiMatchQuery, si: int) -> Tuple[jax.Array, jax.Array]:
        from .executor import expand_match_fields

        seg = self.reader.segments[si]
        n = seg.num_docs
        fields = expand_match_fields(self.reader.mappings, q.fields)
        if not fields:
            return jnp.zeros(n, bool), jnp.zeros(n, jnp.float32)
        per_field = [
            self._exec_phrase(
                MatchPhraseQuery(field=fn, query=q.query, boost=q.boost * fb), si
            )
            if q.type == "phrase"
            else self._exec_match(
                MatchQuery(field=fn, query=q.query, operator=q.operator, boost=q.boost * fb),
                si,
            )
            for fn, fb in fields
        ]
        masks = jnp.stack([m for m, _ in per_field])
        score_mat = jnp.stack([s for _, s in per_field])
        mask = masks.any(axis=0)
        if q.type == "best_fields":
            best = score_mat.max(axis=0)
            if q.tie_breaker:
                rest = score_mat.sum(axis=0) - best
                total = best + jnp.float32(q.tie_breaker) * rest
            else:
                total = best
        else:
            total = score_mat.sum(axis=0)
        return mask, jnp.where(mask, total, 0.0)

    # ---- IVF ANN tier (ops/ivf.py): per-segment cluster indexes ----

    def ann_index(self, si: int, field: str, spec):
        """Cached IvfSegmentIndex for one segment's vector column under
        one build shape (spec.nlist / spec.quantized), or None when the
        segment stays exact: below the small-segment floor, no vectors,
        or the HBM ledger can't fit the build (degrade, never trip).
        Built once per executor generation — a refresh/merge that
        touches the shard regenerates the executor, which re-clusters
        exactly like the agg tables re-profile."""
        seg = self.reader.segments[si]
        n = seg.num_docs
        key = (
            si, field, int(spec.nlist), bool(spec.quantized),
            int(spec.min_docs),
        )
        if key in self._ann_indexes:
            return self._ann_indexes[key]
        with self._build_lock:
            if key in self._ann_indexes:
                return self._ann_indexes[key]
            from ..common.memory import hbm_ledger
            from ..ops import ivf
            from . import ann as ann_mod

            idx = None
            vf = seg.vectors.get(field)
            if vf is not None and n >= max(spec.min_docs, 2):
                mat = (
                    vf.unit_vectors
                    if vf.similarity == "cosine"
                    and vf.unit_vectors is not None
                    else vf.vectors
                )
                if np.issubdtype(mat.dtype, np.integer):
                    mat = mat.astype(np.float32)  # a byte field's rows
                nlist = spec.nlist or ivf.auto_nlist(n)
                nlist = max(1, min(nlist, n))
                est = ivf.IvfSegmentIndex.estimate_nbytes(
                    n, int(mat.shape[1]), nlist, spec.quantized,
                    itemsize=mat.dtype.itemsize,
                )
                if not hbm_ledger.would_fit(est):
                    hbm_ledger.note_degraded()
                else:
                    # deterministic seed: a pure function of the build
                    # shape, so re-runs (and the k-means determinism
                    # test) reproduce the same centroids bit-for-bit
                    seed = (si * 2654435761 + n * 97 + nlist) & 0x7FFFFFFF
                    idx = ivf.IvfSegmentIndex(
                        mat,
                        vf.similarity,
                        nlist,
                        seed,
                        quantized=spec.quantized,
                    )
                    self._charge("ann", idx.nbytes, False)
                    ann_mod.note_build(idx.build_ms)
            elif vf is not None and n:
                ann_mod.note("small_segment_exact")
            self._ann_indexes[key] = idx
            return idx

    # ---- learned-sparse impact columns (ops/impact.py scorers) ----

    def impact_scorer(self, si: int, field: str, quantized: bool):
        """Cached ops/impact.ImpactScorer over one segment's
        impact-ordered sparse postings column — the int8 qweights plane
        or the fp32 weights plane, chosen per SparseSpec — or None when
        the segment has no such column or the upload would not fit the
        HBM ledger (degrade to the host dense oracle, never trip).
        Charged to the `impacts` category and cached per executor
        generation, exactly like the agg tables and IVF indexes. The
        int8 column's scorer also holds its hot terms' dense rows
        (`_impact_rows_build`); the float32 column's none, so its
        answers stay the oracle's bit for bit."""
        key = ("sparse", si, field, bool(quantized))
        if key in self._impact_scorers:
            return self._impact_scorers[key]
        with self._build_lock:
            if key in self._impact_scorers:
                return self._impact_scorers[key]
            from ..common.memory import hbm_ledger
            from ..ops import impact as impact_ops

            seg = self.reader.segments[si]
            sf = (getattr(seg, "sparse", None) or {}).get(field)
            sc = None
            if sf is not None and seg.num_docs and sf.n_tiles:
                vals = sf.qweights if quantized else sf.weights
                est = int(sf.doc_ids.nbytes + vals.nbytes)
                if not hbm_ledger.would_fit(est):
                    hbm_ledger.note_degraded()
                else:
                    sc = impact_ops.ImpactScorer(
                        sf.doc_ids,
                        vals,
                        seg.num_docs,
                        self.reader.live_docs[si],
                    )
                    self._charge("impacts", est, False)
                    if quantized:
                        sc.rows = self._impact_rows_build(sc, sf, seg.num_docs)
                    from ..search import sparse as sparse_mod

                    # compression headline: the value plane actually
                    # uploaded vs the same plane at fp32 (doc-id planes
                    # are identical either way — see ledger_bytes)
                    sparse_mod.note("impact_bytes", int(vals.nbytes))
                    sparse_mod.note(
                        "impact_fp32_equivalent_bytes",
                        int(sf.weights.nbytes),
                    )
            self._impact_scorers[key] = sc
            return sc

    def _impact_rows_build(self, sc, sf, n: int):
        """The dense rows of an int8 impact column's hot terms
        (ops/impact.ImpactRows), or None where no term wants one. The
        text family's choice (`_fused_parts_build`) read from this
        column: a term WANTS a row from df >= dense_row_min_df(n) on the
        segment; rows are HELD by df rank while `dense_rows_room` lasts,
        charged to the ledger's `dense_rows`; a wanted term without a
        row keeps its tiles and is counted (`note_degraded`, the
        `sparse.dense_rows_*` gauges)."""
        from ..ops import impact as impact_ops

        held, sc.rows_wanted = dense_rows_held(
            sf.term_df, n, impact_ops.impact_row_stride(n))
        if not len(held):
            return None
        rows = impact_ops.build_impact_rows(
            sc.doc_ids, sc.values, sf.term_tile_start, sf.term_tile_count,
            held, n,
        )
        self._charge("dense_rows", rows.nbytes, False)
        return rows

    @staticmethod
    def _impact_rows_stats(scorers) -> Dict[str, int]:
        held = [sc.rows for sc in scorers if sc.rows is not None]
        return {
            "dense_rows_wanted": sum(sc.rows_wanted for sc in scorers),
            "dense_rows_held": sum(r.n_rows for r in held),
            "dense_rows_bytes": sum(r.nbytes for r in held),
        }

    def impact_rows_stats(self) -> Dict[str, int]:
        """Over the int8 impact columns loaded: terms that want a dense
        row, terms that hold one, and the rows' device bytes."""
        return self._impact_rows_stats(
            [sc for sc in list(self._impact_scorers.values())
             if sc is not None])

    def node_stats(self) -> Dict[str, dict]:
        """This executor's gauges in the node's document, by the dotted
        path of their block (the node sums them over its executors)."""
        return {"pipeline.batching": self.dense_rows_stats(),
                "sparse": self.impact_rows_stats()}

    @classmethod
    def node_stats_zeros(cls) -> Dict[str, dict]:
        """The same gauges over nothing loaded: a node with no executor."""
        return {"pipeline.batching": cls._dense_rows_stats([]),
                "sparse": cls._impact_rows_stats([])}

    # ---- second-stage rerank column (flat rank_vectors gather arrays) ----

    def rerank_column(self, model):
        """Device-resident shard-level `rank_vectors` column for one
        RerankModel: per-doc CSR bounds over the GLOBAL doc encoding
        (segment-base + local doc — the same bases rescorer.build_plan
        uses) plus the flat token matrix, tail-padded with at least
        `tmax` zero rows so the maxsim gather never reads out of bounds.

        The matrix is assembled ON THE DEVICE from the segments' planes
        in blocks of `RERANK_BLOCK_ROWS` rows (`_row_blocks`: a block
        inside one plane is a view; no full-size host copy, and no
        float32 copy of a byte or int8 column): each block is uploaded
        and written into a donated buffer whose row count is a whole
        number of blocks, so the programs' shapes do not move with a
        few rows more or less. A byte field's rows are uploaded as they
        are (the bytes ARE the values: no scales); int8 models
        (`index.rerank.quantization`) quantize block by block and keep
        per-token scales (models/rerank.quantize_tokens). Charged to
        the `rerank` HbmLedger category what is resident; a build that
        would not fit degrades to SKIP (returns None — first-stage
        ranking survives), counted in `rescore.columns_refused`. Cached
        per executor generation, exactly like the agg tables and IVF
        indexes."""
        key = ("rerank", model)
        if key in self._rerank_columns:
            return self._rerank_columns[key]
        with self._build_lock:
            if key in self._rerank_columns:
                return self._rerank_columns[key]
            from ..common.memory import hbm_ledger
            from ..models import rerank as rerank_model

            n_total = sum(s.num_docs for s in self.reader.segments)
            starts = np.zeros(max(n_total, 1), np.int32)
            counts = np.zeros(max(n_total, 1), np.int32)
            chunks: List[np.ndarray] = []
            tmax = 1
            base = 0
            flat = 0
            for seg in self.reader.segments:
                mvf = seg.multi_vectors.get(model.field)
                n = seg.num_docs
                if mvf is not None and len(mvf.tok_vectors):
                    offs = mvf.tok_offsets.astype(np.int64)
                    starts[base : base + n] = flat + offs[:-1]
                    counts[base : base + n] = np.diff(offs)
                    chunks.append(mvf.tok_vectors)
                    flat += int(offs[-1])
                    tmax = max(tmax, mvf.max_tokens)
                base += n
            dims = int(model.dims) or (
                int(chunks[0].shape[1]) if chunks else 1
            )
            as_bytes = bool(chunks) and chunks[0].dtype == np.int8
            block = min(
                RERANK_BLOCK_ROWS, scoring.next_bucket(flat + tmax, 16)
            )
            rows = -(-(flat + tmax) // block) * block
            width = 1 if as_bytes or model.quantized else 4
            nbytes = (
                rows * dims * width
                + (rows * 4 if model.quantized else 0)
                + starts.nbytes
                + counts.nbytes
            )
            if not hbm_ledger.would_fit(nbytes):
                # degrade-to-skip: reranking is an optimization of the
                # ranking, never worth failing (or OOMing) the request
                hbm_ledger.note_degraded()
                rerank_model.note("columns_refused")
                self._rerank_columns[key] = None
                return None
            dtype = np.int8 if width == 1 else np.float32
            toks_dev = jnp.zeros((rows, dims), dtype, device=self.device)
            scales_dev = (
                jnp.zeros((rows,), np.float32, device=self.device)
                if model.quantized else None
            )
            for at, blk in _row_blocks(chunks, block):
                scales = None
                if model.quantized:
                    blk, scales = rerank_model.quantize_tokens(blk)
                elif blk.dtype != dtype:
                    blk = blk.astype(dtype)
                toks_dev = _place_block(
                    toks_dev, jax.device_put(blk, self.device), at
                )
                if scales is not None:
                    scales_dev = _place_block(
                        scales_dev, jax.device_put(scales, self.device), at
                    )
                # one block in flight: uploads enqueued ahead of the
                # device would each hold a block's buffer beside the
                # column (a 15.0e9 B peak on a 16.9e9 B chip, PERF.md
                # section 6, PR 53)
                toks_dev.block_until_ready()
            col = {
                "starts": jax.device_put(starts, self.device),
                "counts": jax.device_put(counts, self.device),
                # the same CSR counts on the host: a launch's work is
                # counted from them, with no download
                "counts_host": counts,
                "toks": toks_dev,
                "scales": scales_dev,
                "tmax": int(tmax),
                "dims": dims,
                "rows": int(flat),
                "nbytes": int(nbytes),
            }
            self._charge("rerank", col["nbytes"], False)
            self._rerank_columns[key] = col
            return col

    # ---- knn (device matmul + global top-k cut) ----

    def knn_filtered_segment(
        self, field: str, vector, filter_query: Query, si: int, k: int
    ) -> Tuple[np.ndarray, np.ndarray, int]:
        """One filtered kNN job on one segment, unbatched: the filter
        through `filter_mask` (the query-tree evaluation and the bitset
        cache), a one-row scan under it. -> (scores[k], docs[k], rows
        the filter passed), on the host. What a filtered job of the
        batcher's knn family falls back to when its mask launch cannot
        be made (search/batcher `_dispatch_knn_filtered`)."""
        seg = self.reader.segments[si]
        vf = seg.vectors[field]
        cand = jnp.asarray(vf.exists) & self.filter_mask(filter_query, si)
        live = self.reader.live_docs[si]
        if live is not None:
            cand = cand & jnp.asarray(live)
        vectors = self.device_segments[si].vectors[field].rows
        q = jnp.asarray(np.asarray(vector, np.float32))[None, :]
        top_s, top_d = scoring.knn_topk(
            q, vectors, cand, vf.similarity, min(k, seg.num_docs))
        return np.asarray(top_s[0]), np.asarray(top_d[0]), int(cand.sum())

    def _knn_topk_global(self, sec: KnnSection) -> List[Tuple[jax.Array, jax.Array]]:
        from ..common.faults import faults
        from . import ann as ann_mod

        spec = getattr(sec, "ann", None)
        per_seg = []
        for si, seg in enumerate(self.reader.segments):
            n = seg.num_docs
            if seg.vectors.get(sec.field) is None:
                per_seg.append(
                    (jnp.zeros(n, bool), jnp.zeros(n, jnp.float32), None)
                )
                continue
            vf = seg.vectors[sec.field]
            q = jnp.asarray(np.asarray(sec.query_vector, np.float32))[None, :]
            cand_mask = jnp.asarray(vf.exists)
            if sec.filter is not None:
                cand_mask = cand_mask & self.filter_mask(sec.filter, si)
            live = self.reader.live_docs[si]
            if live is not None:
                cand_mask = cand_mask & jnp.asarray(live)
            k = min(sec.num_candidates, n)
            idx = None
            if spec is not None:
                # probe-path failures (the `ann.probe` fault site, HBM
                # degrade) fall back DETERMINISTICALLY to the exact
                # brute-force oracle below — slow/approximate is
                # acceptable, a failed request is not
                try:
                    faults.check("ann.probe", field=sec.field, segment=si)
                    idx = self.ann_index(si, sec.field, spec)
                except BaseException:
                    ann_mod.note("exact_fallbacks")
                    idx = None
            if idx is not None:
                from ..ops import ivf

                top_s, top_d = ivf.ann_topk_batch(
                    idx,
                    np.asarray(sec.query_vector, np.float32)[None, :],
                    np.ones(1, bool),
                    cand_mask,
                    spec.nprobe,
                    k,
                    quantized=spec.quantized,
                )
                ann_mod.note_search(spec.nprobe, idx.nlist)
                per_seg.append((cand_mask, top_s[0], top_d[0]))
                continue
            vectors = self.device_segments[si].vectors[sec.field].rows
            top_s, top_d = scoring.knn_topk(q, vectors, cand_mask, vf.similarity, k)
            per_seg.append((cand_mask, top_s[0], top_d[0]))
        # global k cut across segments
        entries = []
        for si, item in enumerate(per_seg):
            if len(item) == 3 and item[2] is not None:
                _, top_s, top_d = item
                s_host = np.asarray(top_s)
                d_host = np.asarray(top_d)
                for s, d in zip(s_host, d_host):
                    if np.isfinite(s) and (
                        sec.similarity is None or s >= sec.similarity
                    ):
                        entries.append((-float(s), si, int(d)))
        entries.sort()
        keep = entries[: sec.k]
        out = []
        for si, seg in enumerate(self.reader.segments):
            n = seg.num_docs
            mask = np.zeros(n, bool)
            scores = np.zeros(n, np.float32)
            for negs, ksi, d in keep:
                if ksi == si:
                    mask[d] = True
                    scores[d] = -negs * sec.boost
            out.append((jnp.asarray(mask), jnp.asarray(scores)))
        return out
