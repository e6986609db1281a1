"""Learned-sparse routing + observability for the `sparse_vector` path.

The decision layer between the DSL and the impact kernels
(ops/impact.py): an index picks its impact storage via
`index.sparse.quantization` (`int8` — the default, 4x smaller postings
with per-term symmetric scales — or `none` for full-fidelity fp32); a
request opts into the fp32 column regardless via a body-level
`"exact": true` (the same escape hatch the ANN tier honors). Pruning
is always the exact impact-ordered block-max pass — it never changes
the returned hits, only how many tiles get scored — and it never
changes `hits.total` either: a job drops tiles only where the total
Elasticsearch would report is already proved (search/batcher
`_dispatch_sparse_group`: exact up to `track_total_hits`, then a `gte`
bound). So there is no recall knob to resolve here; the only lossy
choice is int8 storage, and even that is gated by a recall@10 ≥ 0.95
floor in tier-1.

The dense host oracle (NumpyExecutor's term-at-a-time fp32 scorer) is
never removed: every device-path failure (injected `sparse.score`
fault, HBM budget breach, missing column) deterministically falls back
to it.
"""

from __future__ import annotations

import threading
from dataclasses import dataclass
from typing import Optional


@dataclass(frozen=True)
class SparseSpec:
    """Resolved per-request sparse serving parameters. Frozen/hashable
    so it can ride the batcher's group key (int8 and fp32 servings of
    the same field never share a launch) and key the executor's
    per-generation column cache."""

    quantized: bool


def resolve(settings, body_exact: bool) -> SparseSpec:
    """SparseSpec for one sparse_vector query under one index's
    settings. Unlike ANN there is no exact-vs-approximate fork in the
    *plan* — only the storage column changes."""
    quant = str(settings.get("sparse.quantization", "int8")) == "int8"
    if body_exact and quant:
        note("exact_searches")
        quant = False
    return SparseSpec(quantized=quant)


# ---------------------------------------------------------------------------
# observability: the `sparse` block of `_nodes/stats`
# ---------------------------------------------------------------------------

_STATS_LOCK = threading.Lock()
SPARSE_STATS = {
    "searches": 0,  # (job × segment) scorings served from impact tiles
    "quantized_searches": 0,  # of those, served from the int8 column
    "exact_searches": 0,  # body-level exact:true escape-hatch routings
    "fallbacks": 0,  # device-path failures → host dense oracle
    "tiles_scored": 0,  # Σ tiles actually launched
    "tiles_pruned": 0,  # Σ tail tiles dropped by block-max bounds
    "pruned_searches": 0,  # scorings where at least one tile dropped
    "chunk_launches": 0,  # Σ `_impact_chunk_add` launches
    "tile_trips": 0,  # Σ trips of impact.TILE_STEP tiles their loops ran
    # hot terms of an int8 column hold a dense row on the device (one
    # int8 a document, -128 = no posting; ops/impact.ImpactRows: a term
    # wants one from df >= max(1024, n / 128) on the segment, rows are
    # held by df rank inside the text family's HBM budget). A scoring
    # adds its query's rows first (`_impact_dense_add`), then the other
    # terms' tiles in term order; `tiles_scored` / `chunk_launches`
    # above count the tile pass alone.
    "dense_rows_scored": 0,  # Σ row slots the scorings used
    "tiles_dense": 0,  # Σ tiles those terms' postings would have been
    "dense_launches": 0,  # Σ `_impact_dense_add` launches
    "theta_host": 0,  # thetas the host computed (a prunable job × segment)
    # bytes of the impact VALUE planes actually uploaded vs what the
    # same planes would cost at fp32 — the headline int8 compression
    # ratio (4x per plane; ≥2x smaller gated in tier-1). The doc-id
    # planes are identical in both modes and are counted in
    # `ledger_bytes` with the rest of the upload.
    "impact_bytes": 0,
    "impact_fp32_equivalent_bytes": 0,
}


def note(key: str, n: int = 1) -> None:
    with _STATS_LOCK:
        SPARSE_STATS[key] += n


def note_search(
    jobs: int, quantized: bool, tiles_scored: int, tiles_pruned: int,
    chunk_launches: int = 0, theta_host: int = 0,
    dense_rows: int = 0, tiles_dense: int = 0, dense_launches: int = 0,
    tile_trips: int = 0,
) -> None:
    """One impact scoring of `jobs` queries against one segment:
    `tiles_scored`, `chunk_launches` and `tile_trips` are its tile
    pass's (one launch a scoring whose longest row holds at most
    impact.TILE_CAP tiles, looping over the trips its plan uses),
    `theta_host` the jobs among them whose threshold the host computed
    beforehand from the query terms' first tiles (how often the
    block-max mechanism engages; `tiles_pruned` says how often it
    pays); `dense_rows` the row slots its row pass used, `tiles_dense`
    the tiles those terms hold (what the rows took off the tile pass),
    `dense_launches` that pass's launches (how often rows engage)."""
    with _STATS_LOCK:
        SPARSE_STATS["searches"] += jobs
        if quantized:
            SPARSE_STATS["quantized_searches"] += jobs
        SPARSE_STATS["tiles_scored"] += tiles_scored
        SPARSE_STATS["tiles_pruned"] += tiles_pruned
        if tiles_pruned:
            SPARSE_STATS["pruned_searches"] += jobs
        SPARSE_STATS["chunk_launches"] += chunk_launches
        SPARSE_STATS["tile_trips"] += tile_trips
        SPARSE_STATS["theta_host"] += theta_host
        SPARSE_STATS["dense_rows_scored"] += dense_rows
        SPARSE_STATS["tiles_dense"] += tiles_dense
        SPARSE_STATS["dense_launches"] += dense_launches


def stats_snapshot() -> dict:
    """The `sparse` stats block (ledger bytes from the `impacts` HBM
    category joined in)."""
    from ..common.memory import hbm_ledger

    with _STATS_LOCK:
        out = dict(SPARSE_STATS)
    out["ledger_bytes"] = int(
        hbm_ledger.stats()["by_category"].get("impacts", 0)
    )
    return out


def reset_stats() -> None:
    """Test hook: zero the counters."""
    with _STATS_LOCK:
        for k in SPARSE_STATS:
            SPARSE_STATS[k] = 0
