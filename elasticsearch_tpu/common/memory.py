"""Device-memory accounting: the HBM ledger + circuit breaker.

Reference analogs: HierarchyCircuitBreakerService (parent + child
breakers; CircuitBreakingException → HTTP 429) and the fielddata /
request breakers (SURVEY.md §2.1 Memory management row). The TPU-native
resource is HBM: device-resident postings tiles, doc-value columns,
vectors, norm caches, and dense hot-term rows all charge the ledger at
upload. When a WOULD-BE upload cannot fit, the allocator either
degrades (dense hot rows are an optimization — the chunked scorer path
covers correctness without them) or trips the breaker.

Categories in use: `postings`/`doc_values`/`vectors`/`norms`/`dense`
(index-resident uploads), `query_cache` (device filter bitsets, own
LRU budget), `serving` — the serving pipeline's persistent padded
staging slabs (executor_jax.staging_slab: fixed-size rings of reusable
query-operand buffers, sized to workers × (pipeline_depth + 1), charged
once at first use and released with the executor) — and `mesh`, the
mesh-parallel serving stacks (parallel/mesh_executor.py: per-snapshot
device views of an index's live (shard, segment) entries, charged at
build and released on generation rebuild/close; a stack that cannot fit
DEGRADES the request to the single-device path instead of tripping the
breaker). `rerank` holds the second-stage reranker's shard-level
`rank_vectors` token columns (search/rescorer.py; a column that cannot
fit DEGRADES TO SKIP — the request keeps its first-stage ranking).
`impacts` holds the learned-sparse impact-tile columns
(executor_jax.impact_scorer: per-(segment, field, storage-mode) uploads
of the impact-ordered doc/value planes, int8 or fp32; a column that
cannot fit DEGRADES to the dense fp32 host oracle — exact answers,
just not device-served). `positions` holds the text fields' positions
planes (executor_jax.DeviceSegment.positions: the position-major term-id
matrices the phrase kernel reads, uploaded when a field is first asked a
bare phrase; an upload that cannot fit is REFUSED and the segment's
phrases are served per job by the unbatched executor's `_exec_phrase`).
Per-category bytes surface as child breakers
in `_nodes/stats` (child_breakers())."""

from __future__ import annotations

import os
import threading
from typing import Dict


class CircuitBreakingException(Exception):
    """es analog: circuit_breaking_exception, HTTP 429."""

    def __init__(self, reason: str, bytes_wanted: int, limit: int):
        super().__init__(reason)
        self.reason = reason
        self.bytes_wanted = bytes_wanted
        self.limit = limit
        self.status = 429
        self.err_type = "circuit_breaking_exception"


def _default_budget() -> int:
    # v5e has 16 GiB HBM; leave headroom for XLA scratch + accumulators.
    # Overridable for tests and other parts.
    env = os.environ.get("ES_TPU_HBM_BUDGET_BYTES")
    if env:
        return int(env)
    return 12 * 1024**3


class HbmLedger:
    """Byte accounting per category with a hard budget.

    Not a malloc hook — JAX owns real allocation. This tracks the
    framework's OWN resident uploads (the analog of ES accounting its
    own BigArrays rather than the JVM heap) so admission control can
    refuse or degrade before the device OOMs.
    """

    def __init__(self, budget: int | None = None):
        self.budget = budget if budget is not None else _default_budget()
        self._lock = threading.Lock()
        self._by_category: Dict[str, int] = {}
        self.stats_counters = {"tripped": 0, "degraded": 0}

    @property
    def used(self) -> int:
        with self._lock:
            return sum(self._by_category.values())

    def would_fit(self, nbytes: int) -> bool:
        return self.used + nbytes <= self.budget

    def add(self, category: str, nbytes: int, breaker: bool = True) -> None:
        """Charges the ledger; raises CircuitBreakingException when the
        budget would be exceeded and `breaker` is set (non-breaker adds
        record overage instead — better a tracked overage than a lying
        ledger)."""
        with self._lock:
            used = sum(self._by_category.values())
            if breaker and used + nbytes > self.budget:
                self.stats_counters["tripped"] += 1
                raise CircuitBreakingException(
                    f"[hbm] Data too large: would use "
                    f"{used + nbytes} bytes, limit {self.budget}",
                    bytes_wanted=nbytes,
                    limit=self.budget,
                )
            self._by_category[category] = (
                self._by_category.get(category, 0) + nbytes
            )

    def release(self, category: str, nbytes: int) -> None:
        with self._lock:
            left = self._by_category.get(category, 0) - nbytes
            if left <= 0:
                self._by_category.pop(category, None)
            else:
                self._by_category[category] = left

    def note_degraded(self) -> None:
        with self._lock:
            self.stats_counters["degraded"] += 1

    def stats(self) -> dict:
        with self._lock:
            used = sum(self._by_category.values())
            return {
                "limit_size_in_bytes": self.budget,
                "estimated_size_in_bytes": used,
                "by_category": dict(self._by_category),
                "tripped": self.stats_counters["tripped"],
                "degraded_allocations": self.stats_counters["degraded"],
            }

    def child_breakers(self) -> Dict[str, dict]:
        """ES-style child-breaker entries, one per ledger category
        (postings tiles, norms, dense rows, query_cache bitsets, …) —
        the per-category byte usage the `_nodes/stats` breakers section
        surfaces next to the `hbm` parent."""
        with self._lock:
            return {
                f"hbm.{cat}": {
                    "limit_size_in_bytes": self.budget,
                    "estimated_size_in_bytes": nbytes,
                }
                for cat, nbytes in sorted(self._by_category.items())
            }


# process-wide ledger (one device per process in this deployment shape)
hbm_ledger = HbmLedger()


def array_nbytes(a) -> int:
    try:
        return int(a.nbytes)
    except AttributeError:
        return 0
