"""`_nodes/stats` schema stability gate.

Every observability PR bolts counters onto `_nodes/stats`; dashboards
and the bench driver read them by key. This test freezes the top-level
node blocks and each block's required keys so a refactor that renames
or drops one fails loudly here instead of silently zeroing a chart.
Blocks may GROW (new keys are fine) — they may not lose keys.
"""

from elasticsearch_tpu.cluster import ClusterService
from elasticsearch_tpu.rest.actions import RestActions

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
        "window_block_selected",
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
