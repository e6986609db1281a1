"""Immutable tiled columnar segments — the TPU-native index format.

Reference analog: a Lucene segment (postings + norms + doc values + stored
fields + vectors), as orchestrated by InternalEngine/IndexWriter
(server/.../index/engine/InternalEngine.java) and read through codecs
(server/.../index/codec/). The *format* is redesigned for TPU execution
rather than ported:

  - Postings are laid out as dense tiles of TILE=128 lanes (the TPU lane
    width): `doc_ids[int32, n_tiles, 128]` / `tfs[int32, n_tiles, 128]`,
    padded with doc_id = -1. A term owns a contiguous tile range
    (`term_tile_start/term_tile_count`), so a query gathers whole tile rows
    — no pointer chasing, no variable-length block decode on device. This
    replaces Lucene's FOR/PFOR-compressed 128-doc postings blocks
    (ForUtil / Lucene postings format): decode happens ONCE at index build,
    not per query (the BASELINE.json north-star layout).
  - Per-tile sidecars `tile_max_tf` / `tile_min_norm` support block-max
    pruning (the WAND analog: an upper score bound per tile is
    max_tf/(max_tf + denom(min_norm)) since tf/(tf+d) is monotone).
  - Norms are Lucene SmallFloat byte4-encoded field lengths (exact BM25
    parity with the reference's quantized doc lengths).
  - Keyword fields get the same postings layout (tf=1) plus sorted-set
    ordinal doc values for aggregations.
  - Numeric/date/boolean fields are dense float64 doc-value columns with
    a missing mask; range/term filters become vectorized comparisons
    (a dense compare beats a BKD tree on this hardware).
  - dense_vector fields are (N, dims) float32 matrices (cosine fields also
    store a unit-normalized copy used for scoring) — brute-force kNN is
    one MXU matmul.

Persistence: one directory per segment holding .npy files plus a
`segment.json` manifest; term dictionaries are a utf-8 blob + offsets
(terms may contain any byte except nothing). Commits are crash-safe via
atomic manifest rename at the shard level (see engine.py).
"""

from __future__ import annotations

import itertools
import json
import math
import os
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..utils.smallfloat import encode_norms
from .mapping import (
    DENSE_VECTOR,
    KEYWORD,
    RANK_VECTORS,
    TEXT,
    Mappings,
    ParsedDocument,
)

TILE = 128  # TPU lane width; one tile = one row of the postings arrays
INVALID_DOC = -1


@dataclass
class FieldStats:
    """Per-field collection statistics (Lucene CollectionStatistics)."""

    doc_count: int = 0  # docs that have this field
    sum_total_term_freq: int = 0  # total tokens across docs
    sum_doc_freq: int = 0  # total (term, doc) postings


@dataclass
class PostingsField:
    """Tiled postings for one indexed field."""

    terms: List[str]  # sorted term dictionary
    term_df: np.ndarray  # int32[n_terms] document frequency
    term_total_tf: np.ndarray  # int64[n_terms] total term frequency
    term_tile_start: np.ndarray  # int32[n_terms]
    term_tile_count: np.ndarray  # int32[n_terms]
    doc_ids: np.ndarray  # int32[n_tiles, TILE], padded with INVALID_DOC
    tfs: np.ndarray  # int32[n_tiles, TILE], padded with 0
    tile_max_tf: np.ndarray  # int32[n_tiles]
    tile_min_norm: np.ndarray  # uint8[n_tiles] min norm byte in tile
    norms: np.ndarray  # uint8[N] SmallFloat-encoded field length per doc
    stats: FieldStats = field(default_factory=FieldStats)
    # columnar positions (text fields; None for keyword/legacy segments).
    # Compact CSR aligned to posting order: posting k of term t lives at
    # global posting index term_pos_start[t] + k, and its sorted positions
    # are pos_data[pos_offsets[p] : pos_offsets[p+1]]. This is the tiled
    # analog of Lucene's PositionsEnum — decoded once at index build, so
    # match_phrase never re-analyzes stored _source (SURVEY.md §2.5
    # postings row; VERDICT round-1 weak #5).
    term_pos_start: Optional[np.ndarray] = None  # int64[n_terms]
    pos_offsets: Optional[np.ndarray] = None  # int64[sum(df)+1]
    pos_data: Optional[np.ndarray] = None  # int32[sum(tf)]
    # precomputed BM25 impacts (text fields; the BM25S eager-scoring
    # layout): per posting, the tf/norm factor 1 - 1/(1 + tf*inv_norm)
    # folded at build time with the SEGMENT-local avgdl, quantized to
    # int8 with per-term symmetric scales. Query-time scoring of a term
    # then reduces to idf * dequantized gather — no norm math on the hot
    # path. Built for text fields on both host and device build paths
    # (bit-identical, parity-gated).
    impacts: Optional[np.ndarray] = None  # int8[n_tiles, TILE]
    impact_scales: Optional[np.ndarray] = None  # float32[n_terms]
    _term_index: Optional[Dict[str, int]] = None
    # PositionsPlane | False (none can be built) | None (not asked yet)
    _positions_plane: object = None
    _term_plane: object = None  # models/fuzzy.TermPlane once asked for

    def term_id(self, term: str) -> int:
        if self._term_index is None:
            self._term_index = {t: i for i, t in enumerate(self.terms)}
        return self._term_index.get(term, -1)

    @property
    def n_tiles(self) -> int:
        return self.doc_ids.shape[0]

    @property
    def has_positions(self) -> bool:
        return self.pos_data is not None

    def positions_plane(self, n_docs: int):
        """The field's `PositionsPlane` (built at its first use and kept:
        a segment is immutable), or None: no positions, a field past
        the layout's limits, or tokens stacked at one position."""
        if self._positions_plane is None:
            self._positions_plane = (
                build_positions_plane(self, n_docs) or False)
        return self._positions_plane or None

    def term_plane(self):
        """The dictionary as the fuzzy expansion reads it (models/fuzzy.py
        `TermPlane`: code points transposed, lengths), built at the
        field's first fuzzy search and kept: a segment is immutable. Its
        columns are padded to whole blocks of the expansion kernel, so
        the device's copy (ops/fuzzy.py) is the same array, reshaped."""
        if self._term_plane is None:
            from ..models.fuzzy import PLANE_PAD, build_term_plane

            self._term_plane = build_term_plane(self.terms, pad_to=PLANE_PAD)
        return self._term_plane

    def term_docs(self, tid: int) -> np.ndarray:
        """Compact (unpadded) sorted doc-id list for one term."""
        start = int(self.term_tile_start[tid])
        count = int(self.term_tile_count[tid])
        return self.doc_ids[start : start + count].ravel()[: int(self.term_df[tid])]

    def doc_positions(self, tid: int, doc: int) -> Optional[np.ndarray]:
        """Sorted positions of term `tid` in local doc `doc`, or None if
        the term does not occur there (or positions are absent)."""
        if self.pos_data is None:
            return None
        docs = self.term_docs(tid)
        k = int(np.searchsorted(docs, doc))
        if k >= len(docs) or docs[k] != doc:
            return None
        p = int(self.term_pos_start[tid]) + k
        return self.pos_data[self.pos_offsets[p] : self.pos_offsets[p + 1]]


# ---- the positions plane: word order as the device reads it ----------
#
# The CSR above answers "where does term t stand in document d"; a
# phrase asks the converse of every document at once: "which term stands
# at position p of document d". The plane is that forward view, laid out
# for a streaming compare on the device: documents are binned by their
# slot count (last position + 1) into classes of fixed width, and a
# class is ONE int32 matrix [width, documents of the class], POSITION-
# MAJOR: row p holds the term id at position p of every document of the
# class (-1 where no token stands: past a document's end, a removed stop
# word, the gap between the values of an array). A phrase of W words is
# then W shifted row-slices compared with W scalars and AND-ed, summed
# down the position axis: the phrase frequency of every document of the
# class, with no gather, no sort and no shape that follows a word's
# frequency. Documents sit along the minor (lane) axis, so a class of
# any width tiles the device's (8, 128) layout without padding waste.

PLANE_MIN_WIDTH = 8  # the narrowest class; also the longest phrase span
# limits of the packed sort key (document 24 bits, position 16, term 24):
# a field past any of them gets no plane and its phrases stay on the
# host path
PLANE_MAX_DOCS = 1 << 24
PLANE_MAX_SLOTS = 1 << 16
PLANE_MAX_TERMS = 1 << 24


def plane_class_widths(max_slots: int) -> List[int]:
    """Class widths up to the first that holds `max_slots` slots: 8, 16,
    24, 32, 48, 64, 96, 128, ... (2^j and 1.5 x 2^j, every one a
    multiple of 8): a document wastes under a third of its class."""
    widths = [PLANE_MIN_WIDTH]
    step = 16
    while widths[-1] < max_slots:
        widths.append(step)
        if widths[-1] < max_slots and step >= 16:
            widths.append(step + step // 2)
        step *= 2
    return widths


@dataclass
class PositionsPlane:
    """One text field's positions as the phrase kernel reads them
    (`build_positions_plane`; ops/phrase.py)."""

    widths: Tuple[int, ...]  # the classes that hold a document, ascending
    mats: List[np.ndarray]  # int32[width, n_class]: term id, -1 = none
    # int32[n_plane]: the document of each plane column, class by class,
    # ascending inside a class; documents without a token have no column
    order: np.ndarray
    occurrences: int  # token positions held (the CSR's len(pos_data))

    @property
    def nbytes(self) -> int:
        return int(sum(m.nbytes for m in self.mats) + self.order.nbytes)


def build_positions_plane(
    pf: "PostingsField", n_docs: int
) -> Optional[PositionsPlane]:
    """The positions plane of one field from the columnar positions a
    refresh leaves (`SegmentBuilder._attach_positions`, on the host and
    the device build path alike); None where the field holds no
    positions, passes a limit of the layout or stacks two tokens at one
    position. Vectorised: one sort of the field's occurrences by
    (document, position)."""
    if pf.pos_data is None or not len(pf.pos_data):
        return None
    n_terms = len(pf.terms)
    if n_docs > PLANE_MAX_DOCS or n_terms > PLANE_MAX_TERMS:
        return None
    if int(pf.pos_data.max()) >= PLANE_MAX_SLOTS:
        return None
    # postings in (term, document) order, as the CSR counts them:
    # posting k of term t is slot k of the term's tile range
    df = pf.term_df.astype(np.int64)
    slot = np.arange(int(df.sum()), dtype=np.int64) + np.repeat(
        pf.term_tile_start.astype(np.int64) * TILE - pf.term_pos_start, df)
    post_doc = pf.doc_ids.ravel()[slot].astype(np.uint64)
    del slot
    per_post = np.diff(pf.pos_offsets)
    key = np.repeat(
        (post_doc << np.uint64(40))
        | np.repeat(np.arange(n_terms, dtype=np.uint64), pf.term_df),
        per_post,
    )
    key |= pf.pos_data.astype(np.uint64) << np.uint64(24)
    key.sort()  # (document, position, term)
    doc = (key >> np.uint64(40)).astype(np.int32)
    pos = ((key >> np.uint64(24)) & np.uint64(0xFFFF)).astype(np.int64)
    term = (key & np.uint64(0xFFFFFF)).astype(np.int32)
    del key
    return plane_from_occurrences(doc, pos, term)


def plane_from_occurrences(
    doc: np.ndarray, pos: np.ndarray, term: np.ndarray
) -> Optional[PositionsPlane]:
    """The positions plane of a field's token occurrences, given as
    parallel arrays SORTED by (document, position): `doc` int32, `pos`
    int64 (consumed), `term` int32 ids of the field's dictionary. What
    `build_positions_plane` calls once it has turned the term-major CSR
    around; a caller that holds the forward stream already (a corpus
    builder) calls it directly. None under the same limits, and where
    two tokens share a position (an index-time synonym filter stacks
    them): a slot of the plane holds one term."""
    if (not len(doc) or int(doc[-1]) >= PLANE_MAX_DOCS
            or int(pos.max()) >= PLANE_MAX_SLOTS):
        return None
    if np.any((doc[1:] == doc[:-1]) & (pos[1:] == pos[:-1])):
        return None
    occurrences = len(doc)
    # a document's slots: its last position + 1 (occurrences are sorted)
    last = np.flatnonzero(doc[1:] != doc[:-1])
    last = np.append(last, len(doc) - 1)
    held = doc[last]  # documents with a token, ascending
    slots = pos[last] + 1
    all_widths = np.asarray(plane_class_widths(int(slots.max())), np.int64)
    cls = np.searchsorted(all_widths, slots)  # smallest width >= slots
    used = np.unique(cls)
    counts = np.bincount(cls, minlength=len(all_widths))
    # one flat buffer, a class after the other, filled DOCUMENT-major
    # (a document's tokens side by side: sequential writes), then each
    # class turned position-major
    sizes = all_widths[used] * counts[used]
    base = np.zeros(len(all_widths), np.int64)
    base[used] = np.r_[0, np.cumsum(sizes)[:-1]]
    by_class = np.argsort(cls, kind="stable")  # documents, class-major
    order = held[by_class].astype(np.int32)
    col = np.empty(len(held), np.int64)  # a document's column in its class
    col[by_class] = np.arange(len(held)) - np.repeat(
        np.r_[0, np.cumsum(counts[used])[:-1]], counts[used])
    row = base[cls] + col * all_widths[cls]  # a document's first slot
    per_doc = np.diff(last, prepend=-1)
    pos += np.repeat(row, per_doc)
    del doc
    flat = np.full(int(sizes.sum()), -1, np.int32)
    flat[pos] = term
    del pos, term
    mats = [
        np.ascontiguousarray(
            flat[base[c]: base[c] + all_widths[c] * counts[c]].reshape(
                int(counts[c]), int(all_widths[c])).T)
        for c in used
    ]
    del flat
    return PositionsPlane(
        widths=tuple(int(all_widths[c]) for c in used), mats=mats,
        order=order, occurrences=occurrences,
    )


@dataclass
class NumericField:
    values: np.ndarray  # float64[N] (first value per doc; arrays keep min)
    exists: np.ndarray  # bool[N]
    # multi-values flattened for exists/terms semantics (round 2: full MV)


@dataclass
class OrdinalField:
    """Sorted-set ordinals for keyword doc values (global ords analog)."""

    ord_terms: List[str]  # sorted unique values
    ords: np.ndarray  # int32[N] ordinal of first value, -1 = missing
    # full multi-value ordinals (CSR): for aggs over keyword arrays
    mv_ords: np.ndarray  # int32[total_values]
    mv_offsets: np.ndarray  # int32[N+1]


def stored_rows(mat: np.ndarray, mf) -> np.ndarray:
    """A dense_vector column as its mapping's `element_type` stores it:
    float32 rows as they are, `byte` rows as int8 (the mapper admitted
    whole numbers in [-128, 127] only, so the cast loses nothing)."""
    if mf is not None and getattr(mf, "element_type", "float") == "byte":
        return mat.astype(np.int8)
    return mat


@dataclass
class VectorField:
    # float32[N, dims], or int8[N, dims] of an `element_type: byte`
    # field (host and device hold one byte an element; the kernels cast);
    # zero rows where missing
    vectors: np.ndarray
    exists: np.ndarray  # bool[N]
    similarity: str
    unit_vectors: Optional[np.ndarray] = None  # normalized copy for cosine


@dataclass
class MultiVectorField:
    """Per-doc token-embedding matrices (`rank_vectors`) in a flat CSR
    layout: doc d owns token rows tok_offsets[d] : tok_offsets[d+1] of
    tok_vectors. The late-interaction reranker gathers whole per-doc
    blocks, so rows stay contiguous per doc; cosine fields store rows
    unit-normalized at build (maxsim over unit rows = cosine maxsim)."""

    # float32[total_tokens, dims], or int8[total_tokens, dims] of an
    # `element_type: byte` field (the stored bytes ARE the values: no
    # normalized twin, no scales; host and device hold one byte an
    # element and the kernels cast)
    tok_vectors: np.ndarray
    tok_offsets: np.ndarray  # int32[N+1]
    exists: np.ndarray  # bool[N]
    similarity: str

    @property
    def max_tokens(self) -> int:
        if len(self.tok_offsets) <= 1:
            return 0
        return int(np.diff(self.tok_offsets).max())

    @property
    def element_type(self) -> str:
        return "byte" if self.tok_vectors.dtype == np.int8 else "float"


def stored_token_rows(mat, mf, similarity: str) -> np.ndarray:
    """One document's `rank_vectors` matrix as its mapping's
    `element_type` stores it: float32 rows (unit-normalized for cosine),
    `byte` rows as int8 (the mapper admitted whole numbers in
    [-128, 127] only, so the cast loses nothing)."""
    if mf is not None and getattr(mf, "element_type", "float") == "byte":
        return np.asarray(mat, dtype=np.float32).astype(np.int8)
    arr = np.asarray(mat, dtype=np.float32)
    return _unit_normalize(arr) if similarity == "cosine" else arr


def byte_multi_vector_field(
    tok_bytes: np.ndarray, tok_offsets: np.ndarray,
    similarity: str = "dot_product",
) -> MultiVectorField:
    """The byte form of a `rank_vectors` column from a prebuilt plane:
    int8[total_tokens, dims] rows and their int32[N+1] CSR offsets, held
    as they are (no copy, no float32 on the host). What a refresh of an
    `element_type: byte` field builds row by row, for a plane too large
    to go through `_bulk`."""
    if tok_bytes.dtype != np.int8 or tok_bytes.ndim != 2:
        raise ValueError(
            "a byte rank_vectors plane is int8[total_tokens, dims], got "
            f"{tok_bytes.dtype}{list(tok_bytes.shape)}"
        )
    offsets = np.ascontiguousarray(tok_offsets, np.int32)
    if len(offsets) < 1 or int(offsets[-1]) != len(tok_bytes):
        raise ValueError(
            "tok_offsets must run from 0 to the plane's row count "
            f"({len(tok_bytes)})"
        )
    if similarity != "dot_product":
        raise ValueError(
            "a byte rank_vectors field supports [dot_product] only"
        )
    return MultiVectorField(
        tok_vectors=tok_bytes,
        tok_offsets=offsets,
        exists=np.diff(offsets) > 0,
        similarity=similarity,
    )


@dataclass
class SparseField:
    """Impact-ordered tiled postings for one `sparse_vector` field (the
    GPUSparse/BM25S layout): a term owns a contiguous tile range whose
    postings are sorted by weight DESC (doc asc tie-break), so the
    highest-impact postings of every term live in its first tiles and a
    per-tile `tile_max` sidecar is non-increasing within a term — the
    block-max pruning invariant. The fp32 `weights` plane is the exact
    oracle source of truth; `qweights` is its int8 per-term-symmetric
    twin (4x smaller in HBM), with `tile_qmax` giving the dequantized
    per-tile bound so pruning stays exact in either serving mode."""

    terms: List[str]  # sorted term dictionary
    term_df: np.ndarray  # int32[n_terms] kept postings per term
    term_tile_start: np.ndarray  # int32[n_terms]
    term_tile_count: np.ndarray  # int32[n_terms]
    doc_ids: np.ndarray  # int32[n_tiles, TILE], impact-ordered, pad -1
    weights: np.ndarray  # float32[n_tiles, TILE], pad 0 (exact plane)
    qweights: np.ndarray  # int8[n_tiles, TILE] per-term symmetric twin
    scales: np.ndarray  # float32[n_terms] dequant scale = maxabs/127
    tile_max: np.ndarray  # float32[n_tiles] max fp32 weight in tile
    tile_qmax: np.ndarray  # float32[n_tiles] max dequantized weight
    exists: np.ndarray  # bool[N]
    pruned: int = 0  # postings dropped by static pruning at build
    _term_index: Optional[Dict[str, int]] = None

    def term_id(self, term: str) -> int:
        if self._term_index is None:
            self._term_index = {t: i for i, t in enumerate(self.terms)}
        return self._term_index.get(term, -1)

    def term_ids(self, terms: Sequence[str]) -> np.ndarray:
        """int64[len(terms)]: `term_id` of each, in one pass."""
        if self._term_index is None:
            self.term_id("")
        return np.fromiter(
            map(self._term_index.get, terms, itertools.repeat(-1)),
            np.int64,
            len(terms),
        )

    @property
    def n_tiles(self) -> int:
        return self.doc_ids.shape[0]

    def term_postings(self, tid: int) -> Tuple[np.ndarray, np.ndarray]:
        """Compact (unpadded) impact-ordered (docs, fp32 weights)."""
        start = int(self.term_tile_start[tid])
        count = int(self.term_tile_count[tid])
        df = int(self.term_df[tid])
        return (
            self.doc_ids[start : start + count].ravel()[:df],
            self.weights[start : start + count].ravel()[:df],
        )


def sparse_plan(inv: Dict[str, Dict[int, float]], pruning_ratio: float) -> dict:
    """Host-side layout plan for one sparse_vector column, shared by the
    host build AND the device build (ops/index_build.sparse_planes_device):
    sorted term dictionary, impact ordering (weight desc, doc asc
    tie-break), static pruning of the lowest-impact tail, and flat scatter
    destinations. All layout decisions happen exactly once here, so the
    two materializers stay bit-identical by construction — the device
    kernels only scatter, reduce with exact max, and quantize."""
    terms = sorted(inv)
    n_terms = len(terms)
    term_df = np.zeros(n_terms, np.int32)
    term_tile_start = np.zeros(n_terms, np.int32)
    term_tile_count = np.zeros(n_terms, np.int32)
    docs_parts: List[np.ndarray] = []
    w_parts: List[np.ndarray] = []
    dest_parts: List[np.ndarray] = []
    next_tile = 0
    pruned = 0
    for tid, term in enumerate(terms):
        plist = inv[term]
        d_arr = np.fromiter(sorted(plist), count=len(plist), dtype=np.int32)
        w_arr = np.asarray([plist[int(d)] for d in d_arr], dtype=np.float32)
        order = np.lexsort((d_arr, -w_arr))
        d_arr, w_arr = d_arr[order], w_arr[order]
        if pruning_ratio > 0.0 and len(d_arr) > 1:
            keep = max(1, math.ceil((1.0 - pruning_ratio) * len(d_arr)))
            pruned += len(d_arr) - keep
            d_arr, w_arr = d_arr[:keep], w_arr[:keep]
        df = len(d_arr)
        term_df[tid] = df
        nt = (df + TILE - 1) // TILE
        term_tile_start[tid] = next_tile
        term_tile_count[tid] = nt
        dest_parts.append(next_tile * TILE + np.arange(df, dtype=np.int64))
        docs_parts.append(d_arr)
        w_parts.append(w_arr)
        next_tile += nt
    return {
        "terms": terms,
        "term_df": term_df,
        "term_tile_start": term_tile_start,
        "term_tile_count": term_tile_count,
        "n_tiles": next_tile,
        "pruned": pruned,
        "docs": (
            np.concatenate(docs_parts) if docs_parts else np.zeros(0, np.int32)
        ),
        "weights": (
            np.concatenate(w_parts) if w_parts else np.zeros(0, np.float32)
        ),
        "dest": (
            np.concatenate(dest_parts) if dest_parts else np.zeros(0, np.int64)
        ),
        "tile_term": np.repeat(
            np.arange(n_terms, dtype=np.int32), term_tile_count
        ),
    }


def sparse_from_plan(plan: dict, n: int, exists: np.ndarray) -> SparseField:
    """Host materializer: scatter the planned postings into padded tile
    planes and derive the quantized twin + block-max sidecars. Mirrors
    ops/index_build.sparse_planes_device formula-for-formula (scatter,
    exact max reductions, f32 divides, rint) for bit-parity."""
    n_tiles = int(plan["n_tiles"])
    n_terms = len(plan["terms"])
    doc_plane = np.full(n_tiles * TILE, INVALID_DOC, np.int32)
    w_plane = np.zeros(n_tiles * TILE, np.float32)
    doc_plane[plan["dest"]] = plan["docs"]
    w_plane[plan["dest"]] = plan["weights"]
    doc_ids = doc_plane.reshape(n_tiles, TILE)
    weights = w_plane.reshape(n_tiles, TILE)
    tile_term = plan["tile_term"]
    if n_tiles:
        tile_max = weights.max(axis=1).astype(np.float32)
    else:
        tile_max = np.zeros(0, np.float32)
    scales = np.zeros(n_terms, np.float32)
    if n_terms:
        # impact ordering puts every term's global max in its first tile
        first = plan["term_tile_start"].astype(np.int64)
        scales = (tile_max[first] / np.float32(127.0)).astype(np.float32)
    if n_tiles:
        slot_scale = scales[tile_term]
        safe = np.where(
            slot_scale == 0.0, np.float32(1.0), slot_scale
        ).astype(np.float32)
        qweights = np.clip(
            np.rint(weights / safe[:, None]), -127, 127
        ).astype(np.int8)
        tile_qmax = (
            qweights.max(axis=1).astype(np.float32) * slot_scale
        ).astype(np.float32)
    else:
        qweights = np.zeros((0, TILE), np.int8)
        tile_qmax = np.zeros(0, np.float32)
    return SparseField(
        terms=plan["terms"],
        term_df=plan["term_df"],
        term_tile_start=plan["term_tile_start"],
        term_tile_count=plan["term_tile_count"],
        doc_ids=doc_ids,
        weights=weights,
        qweights=qweights,
        scales=scales,
        tile_max=tile_max,
        tile_qmax=tile_qmax,
        exists=exists,
        pruned=int(plan["pruned"]),
    )


def attach_impacts(pf: PostingsField, inv_norm_cache: np.ndarray) -> None:
    """Fold the BM25 tf/norm factor into per-posting int8 impacts (BM25S
    eager scoring): impact = 1 - 1/(1 + tf * inv_norm[norm_byte]) with
    the SEGMENT-local avgdl baked into `inv_norm_cache` (256-entry f32
    table, computed once on host and shared with the device build path
    so both produce identical bits). Query-time scoring of term t is
    then idf(t) * impact — pure gather+sum."""
    n_terms = len(pf.terms)
    if pf.n_tiles == 0:
        pf.impacts = np.zeros((0, TILE), np.int8)
        pf.impact_scales = np.zeros(n_terms, np.float32)
        return
    valid = pf.doc_ids >= 0
    n = len(pf.norms)
    nb = pf.norms[np.clip(pf.doc_ids, 0, n - 1 if n else 0)]
    one = np.float32(1.0)
    inv = inv_norm_cache[nb.astype(np.int64)]
    imp = (one - one / (one + pf.tfs.astype(np.float32) * inv)).astype(
        np.float32
    )
    imp = np.where(valid, imp, np.float32(0.0))
    tile_imax = imp.max(axis=1).astype(np.float32)
    starts = pf.term_tile_start.astype(np.int64)
    term_max = np.maximum.reduceat(tile_imax, starts).astype(np.float32)
    scales = (term_max / np.float32(127.0)).astype(np.float32)
    tile_term = np.repeat(
        np.arange(n_terms, dtype=np.int64), pf.term_tile_count
    )
    slot_scale = scales[tile_term]
    safe = np.where(slot_scale == 0.0, np.float32(1.0), slot_scale).astype(
        np.float32
    )
    pf.impacts = np.clip(np.rint(imp / safe[:, None]), -127, 127).astype(
        np.int8
    )
    pf.impact_scales = scales


class Segment:
    """An immutable searchable segment of N documents (local ids 0..N-1)."""

    def __init__(
        self,
        num_docs: int,
        doc_ids: List[str],
        sources: List[Optional[dict]],
        postings: Dict[str, PostingsField],
        numerics: Dict[str, NumericField],
        ordinals: Dict[str, OrdinalField],
        vectors: Dict[str, VectorField],
        generation: int = 0,
        multi_vectors: Optional[Dict[str, MultiVectorField]] = None,
        sparse: Optional[Dict[str, SparseField]] = None,
    ):
        self.num_docs = num_docs
        self.doc_ids = doc_ids  # _id per local doc
        self.sources = sources  # _source per local doc
        self.postings = postings
        self.numerics = numerics
        self.ordinals = ordinals
        self.vectors = vectors
        self.multi_vectors = multi_vectors or {}
        self.sparse = sparse or {}
        self.generation = generation

    # ---------- persistence ----------

    def save(self, path: str, codec: str = "default") -> None:
        os.makedirs(path, exist_ok=True)
        compress = codec == "best_compression"
        manifest: dict = {
            "format_version": 1,
            "num_docs": self.num_docs,
            "generation": self.generation,
            "codec": codec,
            "postings": {},
            "numerics": sorted(self.numerics),
            "ordinals": sorted(self.ordinals),
            "vectors": {},
            "multi_vectors": {},
            "sparse": {},
        }
        arrays: Dict[str, np.ndarray] = {}

        def put(name: str, arr: np.ndarray):
            arrays[name] = np.ascontiguousarray(arr)

        for fname, pf in self.postings.items():
            key = _fkey(fname)
            manifest["postings"][fname] = {
                "key": key,
                "n_terms": len(pf.terms),
                "stats": vars(pf.stats),
            }
            blob, offsets = _encode_terms(pf.terms)
            arrays[f"{key}.terms_blob"] = blob
            put(f"{key}.term_offsets", offsets)
            put(f"{key}.term_df", pf.term_df)
            put(f"{key}.term_total_tf", pf.term_total_tf)
            put(f"{key}.term_tile_start", pf.term_tile_start)
            put(f"{key}.term_tile_count", pf.term_tile_count)
            if compress:
                # best_compression: posting tiles go to disk delta+varint
                # encoded (the native codec — ForUtil's on-disk role);
                # decoded once at load into the dense HBM-upload form
                from ..native import tiles_encode, vb_encode

                manifest["postings"][fname]["tiles_vb"] = list(
                    pf.doc_ids.shape
                )
                arrays[f"{key}.doc_ids_vb"] = np.frombuffer(
                    tiles_encode(pf.doc_ids), np.uint8
                )
                arrays[f"{key}.tfs_vb"] = np.frombuffer(
                    vb_encode(pf.tfs.ravel()), np.uint8
                )
            else:
                put(f"{key}.doc_ids", pf.doc_ids)
                put(f"{key}.tfs", pf.tfs)
            put(f"{key}.tile_max_tf", pf.tile_max_tf)
            put(f"{key}.tile_min_norm", pf.tile_min_norm)
            put(f"{key}.norms", pf.norms)
            if pf.has_positions:
                manifest["postings"][fname]["positions"] = True
                put(f"{key}.term_pos_start", pf.term_pos_start)
                put(f"{key}.pos_offsets", pf.pos_offsets)
                put(f"{key}.pos_data", pf.pos_data)
            if pf.impacts is not None:
                manifest["postings"][fname]["impacts"] = True
                put(f"{key}.impacts", pf.impacts)
                put(f"{key}.impact_scales", pf.impact_scales)
        for fname, nf in self.numerics.items():
            key = _fkey(fname)
            put(f"num.{key}.values", nf.values)
            put(f"num.{key}.exists", nf.exists)
        for fname, of in self.ordinals.items():
            key = _fkey(fname)
            blob, offsets = _encode_terms(of.ord_terms)
            arrays[f"ord.{key}.terms_blob"] = blob
            put(f"ord.{key}.term_offsets", offsets)
            put(f"ord.{key}.ords", of.ords)
            put(f"ord.{key}.mv_ords", of.mv_ords)
            put(f"ord.{key}.mv_offsets", of.mv_offsets)
        for fname, vf in self.vectors.items():
            key = _fkey(fname)
            manifest["vectors"][fname] = {"key": key, "similarity": vf.similarity}
            put(f"vec.{key}.vectors", vf.vectors)
            put(f"vec.{key}.exists", vf.exists)
        for fname, mvf in self.multi_vectors.items():
            key = _fkey(fname)
            manifest["multi_vectors"][fname] = {
                "key": key,
                "similarity": mvf.similarity,
            }
            put(f"mvec.{key}.tok_vectors", mvf.tok_vectors)
            put(f"mvec.{key}.tok_offsets", mvf.tok_offsets)
            put(f"mvec.{key}.exists", mvf.exists)
        for fname, sf in self.sparse.items():
            key = _fkey(fname)
            manifest["sparse"][fname] = {
                "key": key,
                "n_terms": len(sf.terms),
                "pruned": sf.pruned,
            }
            blob, offsets = _encode_terms(sf.terms)
            arrays[f"sp.{key}.terms_blob"] = blob
            put(f"sp.{key}.term_offsets", offsets)
            put(f"sp.{key}.term_df", sf.term_df)
            put(f"sp.{key}.term_tile_start", sf.term_tile_start)
            put(f"sp.{key}.term_tile_count", sf.term_tile_count)
            put(f"sp.{key}.doc_ids", sf.doc_ids)
            put(f"sp.{key}.weights", sf.weights)
            put(f"sp.{key}.qweights", sf.qweights)
            put(f"sp.{key}.scales", sf.scales)
            put(f"sp.{key}.tile_max", sf.tile_max)
            put(f"sp.{key}.tile_qmax", sf.tile_qmax)
            put(f"sp.{key}.exists", sf.exists)

        np.savez(os.path.join(path, "arrays.npz"), **arrays)
        fsync_path(os.path.join(path, "arrays.npz"))
        if compress:
            # stored fields ride DEFLATE (the reference's
            # best_compression stored-fields codec)
            import gzip

            with gzip.open(
                os.path.join(path, "docs.json.gz"), "wt", encoding="utf-8"
            ) as f:
                json.dump(
                    {"doc_ids": self.doc_ids, "sources": self.sources}, f
                )
            fsync_path(os.path.join(path, "docs.json.gz"))
        else:
            with open(os.path.join(path, "docs.json"), "w") as f:
                json.dump(
                    {"doc_ids": self.doc_ids, "sources": self.sources}, f
                )
                f.flush()
                os.fsync(f.fileno())
        tmp = os.path.join(path, "segment.json.tmp")
        with open(tmp, "w") as f:
            json.dump(manifest, f)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, os.path.join(path, "segment.json"))
        fsync_dir(path)

    @classmethod
    def load(cls, path: str) -> "Segment":
        with open(os.path.join(path, "segment.json")) as f:
            manifest = json.load(f)
        gz = os.path.join(path, "docs.json.gz")
        if os.path.exists(gz):
            import gzip

            with gzip.open(gz, "rt", encoding="utf-8") as f:
                docs = json.load(f)
        else:
            with open(os.path.join(path, "docs.json")) as f:
                docs = json.load(f)
        data = np.load(os.path.join(path, "arrays.npz"), allow_pickle=False)
        postings: Dict[str, PostingsField] = {}
        for fname, meta in manifest["postings"].items():
            key = meta["key"]
            terms = _decode_terms(data[f"{key}.terms_blob"], data[f"{key}.term_offsets"])
            if meta.get("tiles_vb"):
                # best_compression: one-time native decode into the
                # dense HBM-upload form (the ForUtil decode moment)
                from ..native import tiles_decode, vb_decode

                n_tiles, width = meta["tiles_vb"]
                doc_ids = tiles_decode(
                    data[f"{key}.doc_ids_vb"].tobytes(), n_tiles, width
                )
                tfs = vb_decode(
                    data[f"{key}.tfs_vb"].tobytes(), n_tiles * width
                ).reshape(n_tiles, width)
            else:
                doc_ids = data[f"{key}.doc_ids"]
                tfs = data[f"{key}.tfs"]
            postings[fname] = PostingsField(
                terms=terms,
                term_df=data[f"{key}.term_df"],
                term_total_tf=data[f"{key}.term_total_tf"],
                term_tile_start=data[f"{key}.term_tile_start"],
                term_tile_count=data[f"{key}.term_tile_count"],
                doc_ids=doc_ids,
                tfs=tfs,
                tile_max_tf=data[f"{key}.tile_max_tf"],
                tile_min_norm=data[f"{key}.tile_min_norm"],
                norms=data[f"{key}.norms"],
                stats=FieldStats(**meta["stats"]),
                term_pos_start=(
                    data[f"{key}.term_pos_start"] if meta.get("positions") else None
                ),
                pos_offsets=(
                    data[f"{key}.pos_offsets"] if meta.get("positions") else None
                ),
                pos_data=(
                    data[f"{key}.pos_data"] if meta.get("positions") else None
                ),
                impacts=(
                    data[f"{key}.impacts"] if meta.get("impacts") else None
                ),
                impact_scales=(
                    data[f"{key}.impact_scales"]
                    if meta.get("impacts")
                    else None
                ),
            )
        numerics = {
            fname: NumericField(
                values=data[f"num.{_fkey(fname)}.values"],
                exists=data[f"num.{_fkey(fname)}.exists"],
            )
            for fname in manifest["numerics"]
        }
        ordinals = {}
        for fname in manifest["ordinals"]:
            key = _fkey(fname)
            ordinals[fname] = OrdinalField(
                ord_terms=_decode_terms(
                    data[f"ord.{key}.terms_blob"], data[f"ord.{key}.term_offsets"]
                ),
                ords=data[f"ord.{key}.ords"],
                mv_ords=data[f"ord.{key}.mv_ords"],
                mv_offsets=data[f"ord.{key}.mv_offsets"],
            )
        vectors = {}
        for fname, meta in manifest["vectors"].items():
            key = meta["key"]
            vf = VectorField(
                vectors=data[f"vec.{key}.vectors"],
                exists=data[f"vec.{key}.exists"],
                similarity=meta["similarity"],
            )
            if vf.similarity == "cosine":
                vf.unit_vectors = _unit_normalize(vf.vectors)
            vectors[fname] = vf
        multi_vectors = {}
        for fname, meta in manifest.get("multi_vectors", {}).items():
            key = meta["key"]
            multi_vectors[fname] = MultiVectorField(
                tok_vectors=data[f"mvec.{key}.tok_vectors"],
                tok_offsets=data[f"mvec.{key}.tok_offsets"],
                exists=data[f"mvec.{key}.exists"],
                similarity=meta["similarity"],
            )
        sparse = {}
        for fname, meta in manifest.get("sparse", {}).items():
            key = meta["key"]
            sparse[fname] = SparseField(
                terms=_decode_terms(
                    data[f"sp.{key}.terms_blob"],
                    data[f"sp.{key}.term_offsets"],
                ),
                term_df=data[f"sp.{key}.term_df"],
                term_tile_start=data[f"sp.{key}.term_tile_start"],
                term_tile_count=data[f"sp.{key}.term_tile_count"],
                doc_ids=data[f"sp.{key}.doc_ids"],
                weights=data[f"sp.{key}.weights"],
                qweights=data[f"sp.{key}.qweights"],
                scales=data[f"sp.{key}.scales"],
                tile_max=data[f"sp.{key}.tile_max"],
                tile_qmax=data[f"sp.{key}.tile_qmax"],
                exists=data[f"sp.{key}.exists"],
                pruned=int(meta.get("pruned", 0)),
            )
        return cls(
            num_docs=manifest["num_docs"],
            doc_ids=docs["doc_ids"],
            sources=docs["sources"],
            postings=postings,
            numerics=numerics,
            ordinals=ordinals,
            vectors=vectors,
            generation=manifest.get("generation", 0),
            multi_vectors=multi_vectors,
            sparse=sparse,
        )


def fsync_path(path: str) -> None:
    """fsync an already-written file by path (durability before commit)."""
    fd = os.open(path, os.O_RDONLY)
    try:
        os.fsync(fd)
    finally:
        os.close(fd)


def fsync_dir(path: str) -> None:
    """fsync a directory so its entries (renames, new files) are durable."""
    fd = os.open(path, os.O_RDONLY)
    try:
        os.fsync(fd)
    finally:
        os.close(fd)


def _fkey(fname: str) -> str:
    return fname.replace("/", "_")


def _encode_terms(terms: Sequence[str]) -> Tuple[np.ndarray, np.ndarray]:
    encoded = [t.encode("utf-8") for t in terms]
    blob = np.frombuffer(b"".join(encoded), dtype=np.uint8)
    offsets = np.zeros(len(terms) + 1, dtype=np.int64)
    np.cumsum([len(e) for e in encoded], out=offsets[1:])
    return blob, offsets


def _decode_terms(blob: np.ndarray, offsets: np.ndarray) -> List[str]:
    raw = blob.tobytes()
    return [
        raw[offsets[i] : offsets[i + 1]].decode("utf-8")
        for i in range(len(offsets) - 1)
    ]


def _unit_normalize(vectors: np.ndarray) -> np.ndarray:
    norms = np.linalg.norm(vectors, axis=1, keepdims=True)
    return (vectors / np.where(norms == 0, 1.0, norms)).astype(np.float32)


class SegmentBuilder:
    """Builds an immutable Segment from parsed documents (the analog of
    Lucene's DefaultIndexingChain flush)."""

    def __init__(self, mappings: Mappings, generation: int = 0):
        self.mappings = mappings
        self.generation = generation
        self._docs: List[ParsedDocument] = []

    def add(self, doc: ParsedDocument) -> int:
        self._docs.append(doc)
        return len(self._docs) - 1

    def __len__(self) -> int:
        return len(self._docs)

    def build(self) -> Segment:
        docs = self._docs
        n = len(docs)
        postings: Dict[str, PostingsField] = {}
        numerics: Dict[str, NumericField] = {}
        ordinals: Dict[str, OrdinalField] = {}
        vectors: Dict[str, VectorField] = {}

        # ---- indexed text fields → tiled postings with tf + positions ----
        text_fields = sorted({f for d in docs for f in d.text_terms})
        for fname in text_fields:
            inv_pos: Dict[str, Dict[int, List[int]]] = {}
            lengths = np.zeros(n, dtype=np.int64)
            doc_count = 0
            for local_id, d in enumerate(docs):
                terms = d.text_terms.get(fname)
                if not terms:
                    continue
                doc_count += 1
                lengths[local_id] = d.field_lengths.get(fname, len(terms))
                for term, pos in terms:
                    inv_pos.setdefault(term, {}).setdefault(local_id, []).append(
                        pos
                    )
            inv = {
                t: {d: len(ps) for d, ps in pl.items()}
                for t, pl in inv_pos.items()
            }
            pf = self._build_postings(inv, lengths, n, doc_count)
            self._attach_positions(pf, inv_pos)
            mf = self.mappings.get(fname)
            if mf is None or mf.type == TEXT:
                from ..models import bm25

                attach_impacts(
                    pf,
                    bm25.norm_inverse_cache(
                        bm25.avg_field_length(
                            pf.stats.sum_total_term_freq, pf.stats.doc_count
                        )
                    ),
                )
            postings[fname] = pf

        # ---- keyword fields → postings (tf=1) + ordinals ----
        kw_fields = sorted({f for d in docs for f in d.keyword_terms})
        for fname in kw_fields:
            inv = {}
            lengths = np.zeros(n, dtype=np.int64)
            doc_count = 0
            all_vals: List[List[str]] = []
            for local_id, d in enumerate(docs):
                vals = d.keyword_terms.get(fname) or []
                all_vals.append(vals)
                if vals:
                    doc_count += 1
                    lengths[local_id] = len(vals)
                for v in set(vals):
                    inv.setdefault(v, {})[local_id] = 1
            postings[fname] = self._build_postings(inv, lengths, n, doc_count)
            ordinals[fname] = self._build_ordinals(all_vals, n)

        # ---- numeric/date/boolean doc values ----
        num_fields = sorted({f for d in docs for f in d.numeric_values})
        for fname in num_fields:
            values = np.zeros(n, dtype=np.float64)
            exists = np.zeros(n, dtype=bool)
            for local_id, d in enumerate(docs):
                vals = d.numeric_values.get(fname)
                if vals:
                    values[local_id] = vals[0]
                    exists[local_id] = True
            numerics[fname] = NumericField(values=values, exists=exists)

        # ---- dense vectors ----
        vec_fields = sorted({f for d in docs for f in d.vectors})
        for fname in vec_fields:
            mf = self.mappings.get(fname)
            dims = mf.dims if mf else len(next(v for d in docs for f2, v in d.vectors.items() if f2 == fname))
            mat = np.zeros((n, dims), dtype=np.float32)
            exists = np.zeros(n, dtype=bool)
            for local_id, d in enumerate(docs):
                v = d.vectors.get(fname)
                if v is not None:
                    mat[local_id] = np.asarray(v, dtype=np.float32)
                    exists[local_id] = True
            sim = mf.similarity if mf else "cosine"
            vf = VectorField(
                vectors=stored_rows(mat, mf), exists=exists, similarity=sim
            )
            if sim == "cosine":
                vf.unit_vectors = _unit_normalize(mat)
            vectors[fname] = vf

        # ---- rank_vectors: per-doc token matrices, flat CSR layout ----
        multi_vectors: Dict[str, MultiVectorField] = {}
        mv_fields = sorted({f for d in docs for f in d.multi_vectors})
        for fname in mv_fields:
            mf = self.mappings.get(fname)
            dims = (
                mf.dims
                if mf and mf.dims
                else len(
                    next(
                        row
                        for d in docs
                        for m in (d.multi_vectors.get(fname),)
                        if m
                        for row in m[:1]
                    )
                )
            )
            sim = mf.similarity if mf else "cosine"
            offsets = np.zeros(n + 1, dtype=np.int32)
            chunks: List[np.ndarray] = []
            exists = np.zeros(n, dtype=bool)
            total = 0
            for local_id, d in enumerate(docs):
                mat = d.multi_vectors.get(fname)
                if mat:
                    arr = stored_token_rows(mat, mf, sim)
                    chunks.append(arr)
                    total += len(arr)
                    exists[local_id] = True
                offsets[local_id + 1] = total
            tok = (
                np.concatenate(chunks, axis=0)
                if chunks
                else stored_token_rows(np.zeros((0, dims)), mf, sim)
            )
            multi_vectors[fname] = MultiVectorField(
                tok_vectors=tok,
                tok_offsets=offsets,
                exists=exists,
                similarity=sim,
            )

        # ---- sparse_vector: impact-ordered quantized postings ----
        sparse: Dict[str, SparseField] = {}
        sp_fields = sorted({f for d in docs for f in d.sparse_vectors})
        for fname in sp_fields:
            mf = self.mappings.get(fname)
            ratio = mf.pruning_ratio if mf else 0.0
            inv_w: Dict[str, Dict[int, float]] = {}
            exists = np.zeros(n, dtype=bool)
            for local_id, d in enumerate(docs):
                wmap = d.sparse_vectors.get(fname)
                if not wmap:
                    continue
                exists[local_id] = True
                for term, w in wmap.items():
                    inv_w.setdefault(term, {})[local_id] = float(w)
            plan = sparse_plan(inv_w, ratio)
            sparse[fname] = sparse_from_plan(plan, n, exists)

        return Segment(
            num_docs=n,
            doc_ids=[d.doc_id for d in docs],
            sources=[d.source for d in docs],
            postings=postings,
            numerics=numerics,
            ordinals=ordinals,
            vectors=vectors,
            generation=self.generation,
            multi_vectors=multi_vectors,
            sparse=sparse,
        )

    @staticmethod
    def _build_postings(
        inv: Dict[str, Dict[int, int]], lengths: np.ndarray, n: int, doc_count: int
    ) -> PostingsField:
        terms = sorted(inv)
        n_terms = len(terms)
        term_df = np.zeros(n_terms, dtype=np.int32)
        term_total_tf = np.zeros(n_terms, dtype=np.int64)
        term_tile_start = np.zeros(n_terms, dtype=np.int32)
        term_tile_count = np.zeros(n_terms, dtype=np.int32)

        # norms: SmallFloat-encoded field length per doc (0 where absent)
        norms = encode_norms(lengths)

        tile_rows_doc: List[np.ndarray] = []
        tile_rows_tf: List[np.ndarray] = []
        next_tile = 0
        for tid, term in enumerate(terms):
            plist = inv[term]
            df = len(plist)
            term_df[tid] = df
            term_total_tf[tid] = sum(plist.values())
            d_arr = np.fromiter(sorted(plist), count=df, dtype=np.int32)
            t_arr = np.fromiter((plist[d] for d in d_arr), count=df, dtype=np.int32)
            n_tiles = (df + TILE - 1) // TILE
            pad = n_tiles * TILE - df
            if pad:
                d_arr = np.concatenate([d_arr, np.full(pad, INVALID_DOC, np.int32)])
                t_arr = np.concatenate([t_arr, np.zeros(pad, np.int32)])
            tile_rows_doc.append(d_arr.reshape(n_tiles, TILE))
            tile_rows_tf.append(t_arr.reshape(n_tiles, TILE))
            term_tile_start[tid] = next_tile
            term_tile_count[tid] = n_tiles
            next_tile += n_tiles

        if tile_rows_doc:
            doc_ids = np.concatenate(tile_rows_doc, axis=0)
            tfs = np.concatenate(tile_rows_tf, axis=0)
        else:
            doc_ids = np.full((0, TILE), INVALID_DOC, np.int32)
            tfs = np.zeros((0, TILE), np.int32)

        tile_max_tf = tfs.max(axis=1).astype(np.int32) if len(tfs) else np.zeros(0, np.int32)
        # min norm byte over *valid* postings per tile (255 where padded-only)
        if len(doc_ids):
            valid = doc_ids >= 0
            tile_norms = np.where(valid, norms[np.clip(doc_ids, 0, n - 1 if n else 0)], 255)
            tile_min_norm = tile_norms.min(axis=1).astype(np.uint8)
        else:
            tile_min_norm = np.zeros(0, np.uint8)

        stats = FieldStats(
            doc_count=doc_count,
            sum_total_term_freq=int(term_total_tf.sum()),
            sum_doc_freq=int(term_df.sum()),
        )
        return PostingsField(
            terms=terms,
            term_df=term_df,
            term_total_tf=term_total_tf,
            term_tile_start=term_tile_start,
            term_tile_count=term_tile_count,
            doc_ids=doc_ids,
            tfs=tfs,
            tile_max_tf=tile_max_tf,
            tile_min_norm=tile_min_norm,
            norms=norms,
            stats=stats,
        )

    @staticmethod
    def _attach_positions(
        pf: PostingsField, inv_pos: Dict[str, Dict[int, List[int]]]
    ) -> None:
        """Builds the compact-CSR position arrays aligned with posting
        order: term t's posting k (k-th doc in sorted doc order) owns the
        slice pos_offsets[term_pos_start[t]+k : +1] of pos_data."""
        n_terms = len(pf.terms)
        term_pos_start = np.zeros(n_terms, dtype=np.int64)
        if n_terms > 1:
            np.cumsum(pf.term_df[:-1].astype(np.int64), out=term_pos_start[1:])
        total_postings = int(pf.term_df.sum())
        pos_offsets = np.zeros(total_postings + 1, dtype=np.int64)
        chunks: List[List[int]] = []
        p = 0
        for tid, term in enumerate(pf.terms):
            plist = inv_pos[term]
            for d in sorted(plist):
                ps = sorted(plist[d])
                chunks.append(ps)
                pos_offsets[p + 1] = pos_offsets[p] + len(ps)
                p += 1
        pf.term_pos_start = term_pos_start
        pf.pos_offsets = pos_offsets
        pf.pos_data = (
            np.concatenate([np.asarray(c, np.int32) for c in chunks])
            if chunks
            else np.zeros(0, np.int32)
        )

    @staticmethod
    def _build_ordinals(all_vals: List[List[str]], n: int) -> OrdinalField:
        uniq = sorted({v for vals in all_vals for v in vals})
        ord_of = {v: i for i, v in enumerate(uniq)}
        ords = np.full(n, -1, dtype=np.int32)
        mv_offsets = np.zeros(n + 1, dtype=np.int32)
        mv: List[int] = []
        for i, vals in enumerate(all_vals):
            sorted_ords = sorted(ord_of[v] for v in set(vals))
            if sorted_ords:
                ords[i] = sorted_ords[0]
            mv.extend(sorted_ords)
            mv_offsets[i + 1] = len(mv)
        return OrdinalField(
            ord_terms=uniq,
            ords=ords,
            mv_ords=np.asarray(mv, dtype=np.int32),
            mv_offsets=mv_offsets,
        )
