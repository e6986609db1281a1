"""`_nodes/stats` schema stability gate.

Every observability PR bolts counters onto `_nodes/stats`; dashboards
and the bench driver read them by key. This test freezes the top-level
node blocks and each block's required keys so a refactor that renames
or drops one fails loudly here instead of silently zeroing a chart.
Blocks may GROW (new keys are fine) — they may not lose keys.
"""

import glob
import json
import os
import sys

import numpy as np
import pytest

from elasticsearch_tpu.cluster import ClusterService
from elasticsearch_tpu.cluster.indices import IndexService
from elasticsearch_tpu.rest.actions import RestActions

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.join(os.path.dirname(HERE), "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from run import node_numbers  # noqa: E402

REQUIRED = {
    "pipeline": {"depth", "batching", "mesh", "rrf"},
    "pipeline.batching": {
        "buckets", "launches_by_bucket", "occupancy_jobs",
        "occupancy_slots", "express_lane_hits", "avg_occupancy",
        "warmup_failures", "worker_compile_ms", "worker_compiles",
        "groups_launched_together", "unplanned_queries",
    },
    "pipeline.mesh": {
        "routed", "launches", "jobs", "rebuilds", "degraded",
        "fallbacks",
    },
    "pipeline.rrf": {
        "searches", "device_fused", "host_fused", "fuse_ms",
        "bm25_leg_ms", "knn_leg_ms", "sparse_leg_ms",
    },
    "admission": {
        "enabled", "limit", "inflight", "queued", "pressure",
        "pressure_tier", "pressure_mode", "retry_after_s",
        "tier_grants", "tenants", "admitted", "shed_rejected",
        "brownouts", "retries_granted", "retries_denied",
        "profiles_shed",
    },
    "aggs": {"batched_jobs"},
    "knn.ann": set(),  # block presence is the contract
    # the benchmark's `rerank_*`, `window_ties_refilled_share` and
    # `maxsim_gather_roofline` read these by dotted path
    "rescore": {
        "batched_jobs", "device_rescores", "host_rescores", "skipped",
        "fallbacks", "requests", "first_stage_kept", "columns_refused",
        "launches", "windows_docs", "tokens_scored", "slots_gathered",
        "slots_padded", "least_bytes", "window_ties_refilled",
        "window_block_selected", "hits_built",
    },
    # the benchmark's `fuzzy_*` metrics read these by dotted path
    "fuzzy": {
        "requests", "words", "words_expanded", "terms_kept",
        "words_saturated", "hot_terms", "tiles", "overflows", "fallbacks",
        "launches", "blocked_launches", "score_launches", "least_bytes",
        "least_cells",
    },
    "sparse": {"batched_jobs"},
    "translog": {
        "uncommitted_ops", "uncommitted_bytes", "pending_unsynced_ops",
        "fsyncs", "appended_ops", "torn_tails_truncated",
    },
    "recovery": {
        "replayed_ops", "tail_replays", "quarantined_segments", "peer",
    },
    "ingest": {"refreshers_running"},
    "breakers": {"hbm"},
    "thread_pool": {"search"},
    "thread_pool.search": {
        "queue_capacity", "completed", "rejected", "launches",
        "serve_fallback_jobs", "serve_clauses", "serve_multi_term_clauses",
        "fan_out",
    },
    "thread_pool.search.fan_out": {"inline", "pooled"},
    "transfer.scoring": {
        "h2d_count", "h2d_bytes", "d2h_count", "d2h_bytes",
    },
    # the benchmark's `bool_filter_*` / `bool_excluded_tiles_per_req` /
    # `bool_filtered_fallback_share` read these by dotted path
    "serve_filtered": {
        "searches", "mask_launches", "filter_terms", "bitset_terms",
        "filter_tiles", "rows_scanned", "rows_passed", "excluded_terms",
        "excluded_tiles", "fallbacks",
    },
}


def test_nodes_stats_blocks_stable():
    cluster = ClusterService()
    try:
        cluster.create_index("ns", {"settings": {"number_of_shards": 1}})
        idx = cluster.indices["ns"]
        idx.index_doc("1", {"body": "hello"})
        idx.refresh()
        idx.search({"query": {"match": {"body": "hello"}}})
        actions = RestActions(cluster)
        status, body = actions.nodes_stats(None, {}, {})
        assert status == 200
        node = body["nodes"]["node-0"]
        for path, keys in REQUIRED.items():
            cur = node
            for part in path.split("."):
                assert part in cur, f"missing block [{path}]"
                cur = cur[part]
            missing = keys - set(cur)
            assert not missing, f"block [{path}] lost keys {sorted(missing)}"
        # the search thread_pool keeps its queue/rejection counters
        tp = node["thread_pool"]["search"]
        for key in ("queue_capacity", "completed", "rejected", "launches"):
            assert key in tp
    finally:
        cluster.close()


# ---- the seam: a layer declares its counters, the handler folds them ------
#
# `REQUIRED` above is the frozen floor. What follows holds the handler to
# the declarations of the layers under it (search/batcher.NODE_STATS,
# IndexService.node_stats, ...): nothing the parent commit reported may go,
# a node with no index reports the same counters at zero, every dotted path
# a benchmark metric reads is there, and two indices fold as one index did.

# the dotted numeric paths of the served node below at the commit before
# the handler became a fold (PR 56), written by `numeric_paths` then, and
# what a layer has declared since (PR 58: `rescore.hits_built`)
PARENT_PATHS = os.path.join(HERE, "nodes_stats_paths_pr56.json")
LAYER_METRICS = sorted(glob.glob(os.path.join(
    BENCH, "layer_metrics", "*.json")))

DIMS = 8
MAPPINGS = {"properties": {
    "body": {"type": "text"}, "title": {"type": "text"},
    "tag": {"type": "keyword"},
    "vec": {"type": "dense_vector", "dims": DIMS, "similarity": "cosine"},
    "ml": {"type": "sparse_vector"},
    "toks": {"type": "rank_vectors", "dims": DIMS,
             "similarity": "dot_product"},
}}
WORDS = ["alpha", "beta", "gamma", "delta", "epsilon"]


def fill(idx, n=40, seed=3):
    rng = np.random.default_rng(seed)
    for i in range(n):
        idx.index_doc(str(i), {
            "body": " ".join(WORDS[(i + j) % 5] for j in range(3)),
            "title": WORDS[i % 5],
            "tag": "even" if i % 2 == 0 else "odd",
            "vec": rng.standard_normal(DIMS).tolist(),
            "ml": {WORDS[i % 5]: 1.0 + i % 3, WORDS[(i + 1) % 5]: 0.5},
            "toks": rng.normal(size=(1 + i % 3, DIMS)).round(3).tolist(),
        })
    idx.refresh()


def one_search_a_family() -> dict:
    """One body of each family tier-1 can build on a small jax index."""
    rng = np.random.default_rng(0)
    knn = {"field": "vec", "query_vector": rng.standard_normal(DIMS).tolist(),
           "k": 5, "num_candidates": 20}
    return {
        "match": {"query": {"match": {"body": "alpha gamma"}}},
        "bool": {"query": {"bool": {
            "must": [{"match": {"body": "alpha"}}],
            "should": [{"match": {"body": "beta"}}]}}},
        "multi_match": {"query": {"multi_match": {
            "query": "alpha", "fields": ["body", "title"]}}},
        "bool_filter": {"query": {"bool": {
            "must": [{"match": {"body": "alpha"}}],
            "filter": [{"term": {"tag": "even"}}],
            "must_not": [{"match": {"body": "delta"}}]}}},
        "knn": {"knn": knn},
        "knn_filter": {"knn": dict(knn, filter={"term": {"tag": "odd"}})},
        "phrase": {"query": {"match_phrase": {"body": "alpha beta"}}},
        "fuzzy": {"query": {"match": {
            "body": {"query": "alpah", "fuzziness": "AUTO"}}}},
        "sparse": {"query": {"sparse_vector": {
            "field": "ml", "query_vector": {"alpha": 1.0, "beta": 0.5}}}},
        "rescore": {
            "query": {"match": {"body": "alpha"}},
            "rescore": {"window_size": 10, "query": {"rescore_query": {
                "rank_vectors": {"field": "toks", "query_vectors": rng.normal(
                    size=(2, DIMS)).round(3).tolist()}}}}},
        "rrf": {"retriever": {"rrf": {"retrievers": [
            {"standard": {"query": {"match": {"body": "alpha"}}}},
            {"knn": knn}]}}},
        "aggs": {"size": 0, "aggs": {"tags": {"terms": {"field": "tag"}}}},
    }


def node_of(cluster) -> dict:
    status, body = RestActions(cluster).nodes_stats(None, {}, {})
    assert status == 200
    return body["nodes"]["node-0"]


class _Canned:
    """`benchmarks/run.py`'s `Http`, answering `GET /_nodes/stats` with a
    node's document."""

    def __init__(self, node: dict):
        self.node = node

    def call(self, method, path):
        assert (method, path) == ("GET", "/_nodes/stats")
        return {"nodes": {"node-0": self.node}}


def numeric_paths(node: dict) -> dict:
    """Every number of the node's document under its dotted path, by the
    benchmark's own walk (`node_numbers`: what a metric's file names)."""
    return node_numbers(_Canned(node))


# keys that follow the traffic and not a declaration: a histogram's bins,
# the tenants seen, the HBM ledger's categories (a category and its child
# breaker appear with its first allocation), a refresh's lag percentiles
TRAFFIC_KEYED = (
    "pipeline.batching.launches_by_bucket.",
    "pipeline.batching.fused_hot_slots.",
    "pipeline.batching.serve_hot_slots.",
    "rescore.windows.", "admission.tenants.",
    "breakers.hbm.by_category.", "ingest.refresh_lag.",
)


def declared(paths) -> set:
    return {p for p in paths
            if not p.startswith(TRAFFIC_KEYED)
            # `breakers."hbm.<category>".*`: a child breaker
            and not (p.startswith("breakers.hbm.") and p.count(".") == 3)}


@pytest.fixture(scope="module")
def served():
    """(the numeric paths of a node with no index, those of the same node
    after one search of each family) -> {path: number} each."""
    cluster = ClusterService()
    try:
        empty = numeric_paths(node_of(cluster))
        cluster.create_index("served", {
            "settings": {"number_of_shards": 1, "search.backend": "jax"},
            "mappings": MAPPINGS})
        idx = cluster.indices["served"]
        fill(idx)
        for body in one_search_a_family().values():
            idx.search(body)
        yield empty, numeric_paths(node_of(cluster))
    finally:
        cluster.close()


def test_served_node_ran_every_family(served):
    """The fixture's premise: each family's job reached the batcher."""
    _, node = served
    for path in ("thread_pool.search.fused_jobs", "knn_filtered.searches",
                 "phrase.searches", "sparse.batched_jobs",
                 "rescore.batched_jobs", "aggs.batched_jobs",
                 "pipeline.rrf.searches",
                 "thread_pool.search.serve_fallback_jobs"):
        assert node[path] > 0, path
    assert node["fuzzy.requests"] + node["fuzzy.fallbacks"] > 0
    assert (node["serve_filtered.searches"]
            + node["serve_filtered.fallbacks"]) > 0
    assert node["pipeline.batching.unplanned_queries"] == 0


def test_no_path_of_the_parent_is_lost(served):
    with open(PARENT_PATHS) as f:
        parent = set(json.load(f))
    _, node = served
    assert not sorted(parent - set(node))


def test_node_without_an_index_reports_every_counter_at_zero(served):
    empty, node = served
    assert not sorted(declared(node) - set(empty))
    # at zero: what the layers under the handler declare, but a leaf whose
    # declaration gives a node with no batcher a setting's value
    zeros, fold, _ = IndexService.node_stats_schema()
    for path, zero in numeric_paths(zeros).items():
        assert empty[path] == zero, path
        assert zero == 0 or path in fold, path


def _dotted(value):
    if isinstance(value, str):
        if "." in value:
            yield value
    elif isinstance(value, dict):
        for v in value.values():
            yield from _dotted(v)
    elif isinstance(value, list):
        for v in value:
            yield from _dotted(v)


def _metric_paths(path: str) -> list:
    with open(path) as f:
        return sorted(_dotted(json.load(f).get("args")))


@pytest.mark.parametrize(
    "metric", [p for p in LAYER_METRICS if _metric_paths(p)],
    ids=lambda p: os.path.basename(p)[:-len(".json")])
def test_layer_metric_reads_a_path_the_node_reports(served, metric):
    """`benchmarks/run.py` `node_numbers` reads these by dotted path (a
    path ending in `.` as a prefix): one the node lacks reads `null`."""
    _, node = served
    for path in _metric_paths(metric):
        if path.endswith("."):
            assert any(p.startswith(path) for p in node), path
        else:
            assert path in node, path


def test_two_indices_fold():
    """Sums add; `pipeline.depth` and `queue_capacity` are the maxima
    over the batchers, `buckets` the longer ladder, `avg_occupancy` the
    quotient of the summed occupancy."""
    cluster = ClusterService()
    try:
        for name in ("a", "b"):
            cluster.create_index(name, {"settings": {"number_of_shards": 1}})
        a, b = (cluster.indices[n]._batcher for n in ("a", "b"))
        a.pipeline_depth, b.pipeline_depth = 1, 3
        a._queue.maxsize, b._queue.maxsize = 4096, 16
        a.buckets, b.buckets = (1, 32), (1, 2, 4)
        a._record_bucket(32, 8)
        b._record_bucket(4, 4)
        b._record_bucket(4, 3)
        a.stats["launches"], b.stats["launches"] = 5, 7
        a.stats["jobs"], b.stats["jobs"] = 11, 13
        a.fuzzy["words"], b.fuzzy["words"] = 2, 3
        a.stats["sparse_jobs"], b.stats["agg_jobs"] = 4, 6
        node = node_of(cluster)
        tp, batching = node["thread_pool"]["search"], node["pipeline"]["batching"]
        assert node["pipeline"]["depth"] == 3
        assert tp["queue_capacity"] == 4096
        assert (tp["launches"], tp["completed"]) == (12, 24)
        assert node["fuzzy"]["words"] == 5
        assert node["sparse"]["batched_jobs"] == 4
        assert node["aggs"]["batched_jobs"] == 6
        assert batching["buckets"] == [1, 2, 4]
        assert batching["launches_by_bucket"] == {"32": 1, "4": 2}
        assert (batching["occupancy_jobs"], batching["occupancy_slots"]) == (15, 40)
        assert batching["avg_occupancy"] == round(15 / 40, 4)
        for idx in cluster.indices.values():
            idx.index_doc("1", {"body": "hello"})
        assert node_of(cluster)["translog"]["uncommitted_ops"] == 2
    finally:
        cluster.close()
