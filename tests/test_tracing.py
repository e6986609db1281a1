"""Per-request span-tree tracing + X-Opaque-Id propagation.

Contract under test:
  * a search request arms a `Trace`; shard/coordinator seams add spans
    with monotonic clocks and parent/child ids; the completed trace
    lands in the bounded ring queryable via GET /_internal/traces;
  * `X-Opaque-Id` propagates from the HTTP header into the task
    description, the trace, and (via OPAQUE_ID_CTX) slow-log records;
  * the ring is bounded (`ES_TPU_TRACE_RING`) and a single trace caps
    at MAX_SPANS with an explicit dropped counter;
  * `ES_TPU_TRACING=off` disables arming entirely;
  * a batcher job's life is four spans of the submitting request's
    trace (queue_wait | dispatch | inflight | collect) that tile it
    exactly, children of the `shard_search` (or leg) span that
    submitted it, whose parent is the coordinator's `fan_out`; the
    request thread's own `plan` in front of them and `wake` behind them
    tile the rest of `shard_search` up to `fetch`;
  * over HTTP the trace's root is `http`, from the request line to the
    response's last byte: the handler's `http_read`, `request_parse`
    and `respond`, and `admission_wait` and `coordinator`, are its
    children, and the trace reaches the ring after the response;
  * `dispatch` and `collect` hold what they are made of: a `launch`
    span a jitted call (its host operands and their bytes), a
    `download` span a blocking download, `unpack` from the last download
    to the job's completion mark; the same phases are annotations on
    the profiler's clock; a job's spans are one write;
  * `GET /_internal/traces` answers the ring's document from one
    encoding a trace, and `_nodes/stats` `tracing` counts the exports;
  * the query path's host<->device transfers are counted exactly
    (`_nodes/stats` `transfer.scoring`).
"""

import contextvars
import json
import threading
import time
import urllib.request

import numpy as np
import pytest

from elasticsearch_tpu.common import tracing
from elasticsearch_tpu.ops import scoring


@pytest.fixture(autouse=True)
def _clean_ring():
    tracing.clear()
    yield
    tracing.clear()


class TestTraceCore:
    def test_span_tree_parents_and_clocks(self):
        tr = tracing.Trace("t")
        t0 = time.perf_counter_ns()
        root = tr.add_span("coordinator", t0, t0 + 1000, shards=2)
        child = tr.add_span("fan_out", t0 + 100, t0 + 900, parent_id=root)
        tr.finish()
        d = tr.to_dict()
        assert d["span_count"] == 2
        by_id = {s["id"]: s for s in d["spans"]}
        assert by_id[child]["parent_id"] == root
        assert by_id[root]["parent_id"] is None
        assert by_id[root]["duration_ns"] == 1000
        assert by_id[root]["tags"] == {"shards": 2}

    def test_reserved_span_parents_what_runs_under_it(self):
        tr = tracing.Trace("t")
        outer = tr.reserve_span()
        with tracing.under(outer):
            inner = tr.add_span("inner", 10, 20)
            assert tracing.PARENT_CTX.get() == outer
            # copied contexts (the fan-out pools) carry the parent along
            assert contextvars.copy_context().run(
                tracing.PARENT_CTX.get) == outer
        assert tracing.PARENT_CTX.get() is None
        assert tr.add_span("outer", 0, 30, span_id=outer) == outer
        spans = {s["name"]: s for s in tr.to_dict()["spans"]}
        assert spans["inner"]["id"] == inner != outer
        assert spans["inner"]["parent_id"] == outer
        assert spans["outer"]["parent_id"] is None

    def test_max_spans_cap_counts_drops(self):
        tr = tracing.Trace("t")
        for i in range(tracing.MAX_SPANS + 10):
            tr.add_span(f"s{i}", 0, 1)
        tr.finish()
        d = tr.to_dict()
        assert d["span_count"] == tracing.MAX_SPANS
        assert d["dropped_spans"] == 10

    def test_ring_is_bounded_and_newest_first(self):
        for i in range(5):
            tr = tracing.Trace(f"t{i}")
            tr.finish()
        out = tracing.recent(3)
        assert len(out) == 3
        assert out[0]["name"] == "t4"  # newest first

    def test_finish_publishes_once(self):
        tr = tracing.Trace("once")
        tr.finish()
        tr.finish()
        assert len(tracing.recent(50)) == 1

    def test_begin_end_arm_the_contextvar(self):
        handle = tracing.begin("req", index="i")
        assert tracing.current() is not None
        tracing.end(handle)
        assert tracing.current() is None
        assert tracing.recent(1)[0]["name"] == "req"

    def test_disabled_via_env(self, monkeypatch):
        monkeypatch.setenv("ES_TPU_TRACING", "off")
        assert tracing.begin("req") is None
        tracing.end(None)  # no-op
        assert tracing.recent(5) == []


class TestSearchTracing:
    def test_search_records_coordinator_and_shard_spans(self):
        from elasticsearch_tpu.cluster.indices import IndexService

        # numpy backend pins the per-shard coordinator path (the jax
        # multi-shard default can ride the SPMD mesh on the forced
        # 8-device platform, which records a single mesh_search span)
        idx = IndexService("tr-idx", settings={
            "number_of_shards": 2, "search.backend": "numpy",
        })
        try:
            for i in range(6):
                idx.index_doc(str(i), {"body": f"hello {i}"})
            idx.refresh()
            handle = tracing.begin("search", index="tr-idx")
            idx.search({"query": {"match": {"body": "hello"}}})
            tracing.end(handle)
            d = tracing.recent(1)[0]
            names = {s["name"] for s in d["spans"]}
            assert "coordinator" in names
            assert "shard_search" in names
            # per-shard spans from BOTH fan-out workers landed in the
            # same trace (copied contexts share the Trace object)
            shard_spans = [s for s in d["spans"]
                           if s["name"] == "shard_search"]
            assert len(shard_spans) == 2
            assert {s["tags"]["shard"] for s in shard_spans} == {0, 1}
            # coordinator phase children parent onto the root span
            root = next(s for s in d["spans"]
                        if s["name"] == "coordinator")
            phases = [s for s in d["spans"]
                      if s["parent_id"] == root["id"]]
            assert {s["name"] for s in phases} >= {
                "parse", "can_match", "dfs", "fan_out", "reduce",
            }
        finally:
            idx.close()



# ---------------------------------------------------------------------
# the batcher's job lifecycle as spans of the submitting request's trace
# ---------------------------------------------------------------------

JOB_SPANS = ("queue_wait", "dispatch", "inflight", "collect")
# what `dispatch` and `collect` hold: every jitted call, every blocking
# download, and the host's work on what came down
PHASE_SPANS = ("launch", "download", "unpack")
DIMS = 8
WORDS = ["alpha", "beta", "gamma", "delta", "epsilon", "zeta", "eta",
         "theta", "iota", "kappa"]
MATCH = {"query": {"match": {"body": "alpha beta"}}, "size": 10}
# six terms. The fused kernel overflows when a query holds more HOT
# terms than its dense slots (scoring.FUSED_H), and terms are hot from
# 1,024 postings up, which this small index has not. So the test below
# applies a slot rule of its own to the term count (more than 4 terms:
# the number is this test's, not the kernel's budget) and the group
# overflows to the chunked block-max path as a real one does;
# tests/test_fused_slots.py overflows the real budget with hot terms
OVERFLOW = {"query": {"match": {
    "body": "alpha beta gamma delta epsilon zeta"}}, "size": 10}
KNN = {"knn": {"field": "vec", "query_vector": [1.0] + [0.0] * (DIMS - 1),
               "k": 10, "num_candidates": 10}, "size": 10}


@pytest.fixture(scope="module")
def fused_service():
    """One 300-doc segment on the jax backend with the fused kernel
    forced on (it is normally gated to large segments)."""
    from elasticsearch_tpu.cluster.indices import IndexService
    from elasticsearch_tpu.search import executor_jax

    orig = executor_jax.FUSED_MIN_DOCS
    executor_jax.FUSED_MIN_DOCS = 10
    rng = np.random.default_rng(5)
    svc = IndexService(
        "tr-fused",
        settings={"number_of_shards": 1, "search.backend": "jax"},
        mappings_json={"properties": {
            "body": {"type": "text"},
            "vec": {"type": "dense_vector", "dims": DIMS,
                    "similarity": "cosine"},
        }},
    )
    for i in range(300):
        svc.index_doc(str(i), {
            "body": " ".join(rng.choice(WORDS, int(rng.integers(3, 9)))),
            "vec": [float(x) for x in rng.normal(size=DIMS)],
        })
    svc.refresh()
    yield svc
    svc.close()
    executor_jax.FUSED_MIN_DOCS = orig


def traced_search(svc, body):
    """-> the spans of one search under an armed trace, by name."""
    tracing.clear()
    handle = tracing.begin("search", index=svc.name)
    svc.search(json.loads(json.dumps(body)))
    tracing.end(handle)
    spans = tracing.recent(1)[0]["spans"]
    return spans, {s["name"]: s for s in spans}


def end_ns(span):
    return span["start_ns"] + span["duration_ns"]


class TestBatcherJobSpans:
    @pytest.mark.parametrize("body,family,overflow", [
        (MATCH, "match", False),
        (OVERFLOW, "match", True),
        (KNN, "knn", False),
    ], ids=["fused_match", "chunked_overflow_match", "exact_knn"])
    def test_job_spans_tile_submit_to_wakeup(
        self, fused_service, monkeypatch, body, family, overflow
    ):
        ex = fused_service._executor(fused_service.shards[0])
        fits = ex.fused_plan_field
        monkeypatch.setattr(
            ex, "fused_plan_field",
            lambda si, field, parts, terms, boost: None
            if len(terms) > 4 else fits(si, field, parts, terms, boost))
        fused_service.search(json.loads(json.dumps(body)))  # compile
        t_before = time.perf_counter_ns()
        _, by = traced_search(fused_service, body)
        t_after = time.perf_counter_ns()
        q, d, i, c = (by[n] for n in JOB_SPANS)
        # consecutive marks: each span starts where the last one ended,
        # so the four durations add up to the job's life exactly
        assert end_ns(q) == d["start_ns"]
        assert end_ns(d) == i["start_ns"]
        assert end_ns(i) == c["start_ns"]
        assert sum(by[n]["duration_ns"] for n in JOB_SPANS) == (
            end_ns(c) - q["start_ns"]
        )
        # the job lies inside its shard_search span, inside the request,
        # between the request thread's own two spans: `plan` ends at the
        # job's submit mark, `wake` starts at its completion mark
        sh = by["shard_search"]
        assert t_before <= sh["start_ns"] <= by["plan"]["start_ns"]
        assert end_ns(by["plan"]) == q["start_ns"]
        assert by["plan"]["tags"] == {"family": family, "planned": True}
        assert by["wake"]["start_ns"] == end_ns(c)
        assert by["wake"]["tags"] == {}
        assert end_ns(by["wake"]) <= by["fetch"]["start_ns"]
        assert end_ns(by["fetch"]) <= end_ns(sh) <= t_after
        assert q["tags"] == {"family": family, "cold_ms": 0.0}
        # a fused match group also says how many tile slots it carried
        fused = family == "match" and not overflow
        assert d["tags"] == {
            "family": family, "jobs": 1, "rows": 1,
            "launches": d["tags"]["launches"], "express": True,
            "overflow": overflow,
            **({"rare_tiles": d["tags"]["rare_tiles"]} if fused else {}),
        }
        assert not fused or d["tags"]["rare_tiles"] >= 1
        assert d["tags"]["launches"] >= 1
        assert c["tags"]["d2h_bytes"] > 0
        # every family is a dispatch / collect pair: the group is in
        # flight from its last launch to the worker's return
        assert i["duration_ns"] > 0

    def test_express_lane_job_is_tagged_and_counted(self, fused_service):
        b = fused_service._batcher
        before = b.stats["express_lane_hits"]
        _, by = traced_search(fused_service, MATCH)
        assert b.stats["express_lane_hits"] == before + 1
        assert by["dispatch"]["tags"]["express"] is True
        assert by["dispatch"]["tags"]["rows"] == 1

    def test_parents_resolve_to_shard_search_under_fan_out(
        self, fused_service
    ):
        spans, by = traced_search(fused_service, KNN)
        ids = {s["id"]: s for s in spans}
        kids = [s["name"] for s in sorted(spans, key=lambda s: s["start_ns"])
                if s["parent_id"] == by["shard_search"]["id"]]
        assert kids == ["plan", *JOB_SPANS, "wake", "fetch"]
        assert ids[by["shard_search"]["parent_id"]]["name"] == "fan_out"
        assert ids[by["fan_out"]["parent_id"]]["name"] == "coordinator"
        # a library call has no handler above it, so no `http`: the only
        # roots are the coordinator and the wait before it (over HTTP
        # both hang off `http`: TestHttpRoot)
        assert {s["name"] for s in spans if s["parent_id"] is None} == {
            "coordinator", "admission_wait",
        }
        assert by["admission_wait"]["tags"].keys() == {"tier", "limit"}
        assert end_ns(by["admission_wait"]) <= by["coordinator"]["start_ns"]
        # self time (choosing-metrics section 4): what shard_search does
        # itself is its duration less what its children cover
        covered = sum(s["duration_ns"] for s in spans
                      if s["parent_id"] == by["shard_search"]["id"])
        assert 0 <= by["shard_search"]["duration_ns"] - covered

    def test_two_requests_sharing_a_launch_get_their_own_spans(
        self, fused_service, monkeypatch
    ):
        from elasticsearch_tpu.search import dsl
        from elasticsearch_tpu.search.batcher import (
            QueryBatcher,
            extract_match_plan,
        )

        ex = fused_service._executor(fused_service.shards[0])
        b = QueryBatcher(workers=1)
        start_workers = b._ensure_thread
        monkeypatch.setattr(b, "_ensure_thread", lambda: None)
        traces, jobs = [], []
        try:
            for text in ("alpha beta", "gamma delta"):
                q = dsl.parse_query({"match": {"body": text}})
                plan = extract_match_plan(
                    q, fused_service.mappings, fused_service.analysis,
                    10_000)

                def submit():
                    tr = tracing.Trace(text)
                    tracing.TRACE_CTX.set(tr)
                    parent = tr.reserve_span()
                    with tracing.under(parent):
                        job = b.submit_nowait(ex, plan, 10, query=q)
                    return tr, parent, job

                tr, parent, job = contextvars.copy_context().run(submit)
                traces.append((tr, parent))
                jobs.append(job)
            start_workers()  # the one worker drains both into one group
            for job in jobs:
                QueryBatcher.wait(job, timeout=60)
        finally:
            b.close()
        seen = []
        for tr, parent in traces:
            # (the four-row shape is new to this process: it compiles,
            # and the compile spans hang off the job's spans, as their
            # launches, download and unpack do: TestPhaseSpans)
            spans = {s["name"]: s for s in tr.to_dict()["spans"]
                     if s["name"] not in ("compile", *PHASE_SPANS)}
            assert set(spans) == set(JOB_SPANS)
            assert all(s["parent_id"] == parent for s in spans.values())
            seen.append(spans)
        a, z = seen
        assert a["dispatch"]["tags"] == z["dispatch"]["tags"]
        assert a["dispatch"]["tags"]["jobs"] == 2
        assert a["dispatch"]["tags"]["rows"] == 4  # the bucket over 2
        assert a["dispatch"]["tags"]["express"] is False
        # one launch, one set of marks: the shared phases coincide
        for name in ("dispatch", "inflight"):
            assert a[name]["start_ns"] == z[name]["start_ns"]
            assert a[name]["duration_ns"] == z[name]["duration_ns"]
        # each job has its own submit and its own wake-up
        assert a["queue_wait"]["start_ns"] < z["queue_wait"]["start_ns"]
        assert end_ns(a["collect"]) <= end_ns(z["collect"])

    def test_untraced_job_records_nothing_and_runs_the_same_path(
        self, fused_service
    ):
        b = fused_service._batcher
        tracing.clear()
        before = dict(b.stats)
        plain = fused_service.search(json.loads(json.dumps(MATCH)))
        assert tracing.recent(5) == []
        assert tracing.current() is None
        mid = dict(b.stats)
        _, by = traced_search(fused_service, MATCH)
        after = dict(b.stats)
        # the same launches, jobs and lane with the trace armed or not
        for key in ("launches", "jobs", "fused_jobs", "express_lane_hits"):
            assert mid[key] - before[key] == after[key] - mid[key] == 1, key
        traced = fused_service.search(json.loads(json.dumps(MATCH)))
        assert traced["hits"] == plain["hits"]

    def test_compile_span_on_first_use_of_a_shape_only(self, fused_service):
        # a page size no other test of this module asks for: its top-k
        # bucket (64) makes a new program of the fused kernel, the only
        # program of a fused request on a one-segment shard (its packed
        # row is downloaded as it is: no merge program to build)
        body = {**MATCH, "size": 40}
        spans, by = traced_search(fused_service, body)
        compiles = [s for s in spans if s["name"] == "compile"]
        assert {s["tags"]["program"] for s in compiles} == {"_fused_query_mf"}
        for s in compiles:
            assert s["tags"]["seconds"] > 0
            assert s["parent_id"] == by["dispatch"]["id"]
            assert by["dispatch"]["start_ns"] <= s["start_ns"]
            assert end_ns(s) <= end_ns(by["dispatch"])
        stats = fused_service._batcher.batching_stats()
        assert stats["worker_compiles"] >= len(compiles)
        assert stats["worker_compile_ms"] > 0
        spans, _ = traced_search(fused_service, body)
        assert [s for s in spans if s["name"] == "compile"] == []

    def test_rrf_legs_parent_their_jobs(self, fused_service):
        body = {"retriever": {"rrf": {"retrievers": [
            {"standard": {"query": MATCH["query"]}},
            {"knn": KNN["knn"]},
        ]}}, "size": 5}
        spans, by = traced_search(fused_service, body)
        ids = {s["id"]: s for s in spans}
        legs = {s["id"]: s["name"] for s in spans
                if s["name"].startswith("leg:")}
        assert sorted(legs.values()) == ["leg:bm25", "leg:knn"]
        families = {}
        for s in spans:
            if s["name"] == "dispatch":
                families[s["tags"]["family"]] = legs[s["parent_id"]]
        assert families == {"match": "leg:bm25", "knn": "leg:knn"}
        assert ids[by["leg:knn"]["parent_id"]]["name"] == "rrf"
        # the request thread's own spans hang off the node, not a leg
        assert ids[by["plan_legs"]["parent_id"]]["name"] == "rrf"
        assert ids[by["wake"]["parent_id"]]["name"] == "rrf"
        assert by["plan_legs"]["tags"].keys() == {"legs", "bm25_ms", "knn_ms"}
        assert by["wake"]["start_ns"] == max(
            end_ns(by["leg:bm25"]), end_ns(by["leg:knn"]))
        assert end_ns(by["wake"]) == by["fuse"]["start_ns"]
        assert ids[by["rrf"]["parent_id"]]["name"] == "retriever"
        assert ids[by["retriever"]["parent_id"]]["name"] == "coordinator"

    def test_annotations_are_inert_without_a_profiler_session(self):
        from elasticsearch_tpu.search.batcher import _Group

        g = _Group("match", 1, 1)
        with g.phase("es.dispatch"):
            g.dispatched()
        with g.phase("es.collect"):
            g.collecting()
        assert g.t_start <= g.t_dispatched <= g.t_collect
        # the request thread's two (rest/server.py, rest/actions.py)
        from jax.profiler import TraceAnnotation

        with TraceAnnotation("es.http"):
            with TraceAnnotation("es.search", route="_search"):
                pass


class TestTransferCounters:
    """`transfer.scoring`: exact, so the same on any backend."""

    def delta(self, svc, body):
        svc.search(json.loads(json.dumps(body)))  # nothing left to build
        before = tracing.transfer_stats()
        svc.search(json.loads(json.dumps(body)))
        after = tracing.transfer_stats()
        return {k: after[k] - before[k] for k in after}

    def test_one_row_knn_request(self, fused_service):
        # up: the query row f32[1, 8] and its validity bool[1] (the scan),
        # the segment of each of 16 candidate slots i32[16] and the
        # candidate cut bool[1, 16] (the merge); down: one packed
        # i32[1, 3 * 10 + 1]
        assert self.delta(fused_service, KNN) == {
            "h2d_count": 4, "h2d_bytes": 32 + 1 + 64 + 16,
            "d2h_count": 1, "d2h_bytes": 124,
        }

    def test_one_fused_match_request(self, fused_service):
        # up: the packed plan i32[1, 2 * 256 + 2 * FUSED_H + 1] alone;
        # down: the kernel's own packed i32[1, 2 * 16 + 1], as it is (one
        # scoring segment: no merge program, so no i32[16] goes up for
        # it and no segment column comes down)
        plan_bytes = 4 * (2 * scoring.FUSED_T_RARE + 2 * scoring.FUSED_H + 1)
        assert self.delta(fused_service, MATCH) == {
            "h2d_count": 1, "h2d_bytes": plan_bytes,
            "d2h_count": 1, "d2h_bytes": 132,
        }

    def test_collect_span_carries_the_groups_download(self, fused_service):
        _, by = traced_search(fused_service, KNN)
        assert by["collect"]["tags"] == {"d2h_bytes": 124}


# ---------------------------------------------------------------------
# inside `dispatch` and `collect`: launch, download, unpack
# ---------------------------------------------------------------------

VEC = [1.0] + [0.0] * (DIMS - 1)
BM25_LEG = {"query": {"match": {"body": "alpha beta"}}}
KNN_LEG = {"field": "vec", "query_vector": VEC, "k": 10,
           "num_candidates": 10}
# one body a family of the batcher, and the programs its request launches
# (dispatch first, then collect)
FAMILIES = {
    "match": (BM25_LEG, ["_fused_query_mf"], []),
    "serve": ({"query": {"multi_match": {
        "query": "alpha beta", "fields": ["body", "title"]}}},
        ["_fused_query_mf"], []),
    "filtered_serve": ({"query": {"bool": {
        "must": [{"match": {"body": "alpha"}}],
        "filter": [{"term": {"tag": "even"}}]}}},
        ["_fused_query_mf"], []),
    "knn": ({"knn": KNN_LEG}, ["knn_topk_batch"], ["_knn_merge_segments"]),
    "filtered_knn_scan": (
        {"knn": {**KNN_LEG, "filter": {"term": {"tag": "even"}}}},
        ["knn_filter_mask", "knn_topk_filtered"], ["_knn_merge_segments"]),
    "filtered_knn_lead": (
        {"knn": {**KNN_LEG, "filter": {"term": {"tag": "rare"}}}},
        ["knn_topk_lead"], ["_knn_merge_segments"]),
    "phrase": ({"query": {"match_phrase": {"body": "alpha beta"}}},
               ["phrase_topk"], []),
    "sparse": ({"query": {"sparse_vector": {
        "field": "ml", "query_vector": {"alpha": 1.0, "beta": 0.5}}}},
        ["_impact_zeros", "_impact_chunk_add", "_finalize"],
        ["_merge_segments"]),
    "rrf_leg": ({"retriever": {"rrf": {"retrievers": [
        {"standard": BM25_LEG}, {"knn": KNN_LEG}]}}, "size": 5},
        ["_fused_query_mf", "knn_topk_batch"], ["_knn_merge_segments"]),
}


@pytest.fixture(scope="module")
def family_service():
    """One 300-doc segment every family of the batcher can serve: two
    text fields, a keyword, a vector and a sparse vector, the fused
    kernel forced on."""
    from elasticsearch_tpu.cluster.indices import IndexService
    from elasticsearch_tpu.search import executor_jax

    orig = executor_jax.FUSED_MIN_DOCS
    executor_jax.FUSED_MIN_DOCS = 10
    rng = np.random.default_rng(7)
    svc = IndexService(
        "tr-families",
        settings={"number_of_shards": 1, "search.backend": "jax"},
        mappings_json={"properties": {
            "body": {"type": "text"}, "title": {"type": "text"},
            "tag": {"type": "keyword"},
            "vec": {"type": "dense_vector", "dims": DIMS,
                    "similarity": "cosine"},
            "ml": {"type": "sparse_vector"},
        }},
    )
    for i in range(300):
        svc.index_doc(str(i), {
            "body": " ".join(rng.choice(WORDS, int(rng.integers(3, 9)))),
            "title": " ".join(rng.choice(WORDS, 2)),
            "tag": ["even" if i % 2 == 0 else "odd"]
            + (["rare"] if i % 50 == 0 else []),
            "vec": [float(x) for x in rng.normal(size=DIMS)],
            "ml": {w: float(rng.random()) + 0.1
                   for w in rng.choice(WORDS, 3, replace=False)},
        })
    svc.refresh()
    yield svc
    svc.close()
    executor_jax.FUSED_MIN_DOCS = orig


class TestPhaseSpans:
    @pytest.mark.parametrize("family", list(FAMILIES))
    def test_dispatch_and_collect_hold_their_launches_and_downloads(
        self, family_service, monkeypatch, family
    ):
        body, in_dispatch, in_collect = FAMILIES[family]
        if family == "filtered_knn_lead":
            # a lead slot need stand for one row only: a 300-row segment
            # leads by a tag of six documents
            monkeypatch.setattr(scoring, "KNN_LEAD_SCAN_ROWS", 1)
        family_service.search(json.loads(json.dumps(body)))  # compile
        syncs0 = tracing.transfer_stats()
        spans, _ = traced_search(family_service, body)
        moved = {k: v - syncs0[k]
                 for k, v in tracing.transfer_stats().items()}
        ids = {s["id"]: s for s in spans}
        phase = [s for s in spans if s["name"] in PHASE_SPANS]
        # every one lies inside the span of the group that made it
        for s in phase:
            parent = ids[s["parent_id"]]
            assert parent["name"] in ("dispatch", "collect"), s
            assert parent["start_ns"] <= s["start_ns"], s
            assert end_ns(s) <= end_ns(parent), s
        launched = {"dispatch": [], "collect": []}
        for s in sorted(phase, key=lambda s: s["start_ns"]):
            if s["name"] == "launch":
                launched[ids[s["parent_id"]]["name"]].append(
                    s["tags"]["program"])
        assert launched == {"dispatch": in_dispatch, "collect": in_collect}
        # the host operands the launches were handed are the uploads the
        # request noted, to the byte
        launches = [s for s in phase if s["name"] == "launch"]
        assert all(s["tags"].keys() == {
            "program", "host_operands", "h2d_bytes"} for s in launches)
        assert sum(s["tags"]["host_operands"] for s in launches) == (
            moved["h2d_count"])
        assert sum(s["tags"]["h2d_bytes"] for s in launches) == (
            moved["h2d_bytes"])
        downloads = [s for s in phase if s["name"] == "download"]
        assert len(downloads) == moved["d2h_count"]  # one a `_to_host`
        for disp in (s for s in spans if s["name"] == "dispatch"):
            kids = children_of(spans, disp)
            assert disp["tags"]["launches"] == sum(
                s["name"] == "launch" for s in kids) >= 1
        collects = [s for s in spans if s["name"] == "collect"]
        assert len(collects) == (2 if family == "rrf_leg" else 1)
        for coll in collects:
            # launch* | download+ | unpack, in this order, none
            # overlapping; what is left is the hand-over between marks
            kids = children_of(spans, coll)
            names = [s["name"] for s in kids]
            n_launch = names.count("launch")
            assert names == (["launch"] * n_launch + ["download"] * (
                len(names) - n_launch - 1) + ["unpack"]), names
            assert len(names) - n_launch - 1 >= 1
            for a, b in zip(kids, kids[1:]):
                assert end_ns(a) <= b["start_ns"], (a["name"], b["name"])
            # `unpack` starts where the last download ended and ends at
            # the job's completion mark, the span's own end
            assert kids[-1]["start_ns"] == end_ns(kids[-2])
            assert end_ns(kids[-1]) == end_ns(coll)
            assert kids[-1]["tags"] == {}
            assert sum(s["tags"]["bytes"] for s in kids
                       if s["name"] == "download") == (
                coll["tags"]["d2h_bytes"]) > 0
        assert sum(c["tags"]["d2h_bytes"] for c in collects) == (
            moved["d2h_bytes"])

    def test_fused_launch_carries_what_note_transfer_noted(
        self, family_service
    ):
        """The filtered serve launch: the packed plan, the tie breaker
        and the rows' filter plan, three host operands of one call."""
        body = FAMILIES["filtered_serve"][0]
        family_service.search(json.loads(json.dumps(body)))
        before = tracing.transfer_stats()
        _, by = traced_search(family_service, body)
        after = tracing.transfer_stats()
        assert by["launch"]["tags"] == {
            "program": "_fused_query_mf",
            "host_operands": after["h2d_count"] - before["h2d_count"],
            "h2d_bytes": after["h2d_bytes"] - before["h2d_bytes"],
        }
        assert by["launch"]["tags"]["host_operands"] == 3
        # the mask's host part ends before the launch that builds it
        assert end_ns(by["filter_mask"]) <= by["launch"]["start_ns"]

    def test_a_dispatch_that_blocks_holds_its_download(
        self, fused_service, monkeypatch
    ):
        """The chunked path's finalize is a launch of the dispatch; a
        download made before the group is collected is the dispatch's
        child, and no `unpack` follows it there."""
        from elasticsearch_tpu.search.batcher import _Group

        g = _Group("match", 1, 1)
        tracing.set_worker_group(g)
        try:
            with g.phase("es.dispatch"):
                with tracing.launch("_threshold"):
                    pass
                scoring._to_host(np.zeros(4, np.float32))
            g.dispatched()
            g.collecting()
            with g.phase("es.collect"):
                scoring._to_host(np.zeros(2, np.float32))
                scoring._to_host(np.zeros(2, np.float32))
                assert g.unpacking is not None
                g.unpacked()
            assert g.unpacking is None
        finally:
            tracing.set_worker_group(None)
        assert g.launches == 1
        assert [(n, t.get("bytes")) for n, _s, _e, t in g.sub_spans] == [
            ("launch", None), ("download", 16), ("download", 8),
            ("download", 8)]
        assert g.sub_spans[1][2] <= g.t_dispatched  # the dispatch's
        assert g.t_unpack == g.sub_spans[-1][2] >= g.t_collect
        # off a worker the brackets are inert and nothing is kept
        with tracing.launch("_fused_query_mf", 1, 8):
            scoring._to_host(np.zeros(1, np.float32))
        assert len(g.sub_spans) == 4

    def test_one_write_a_job(self, fused_service, monkeypatch):
        """The worker writes a job's spans in one `add_spans` call."""
        calls = []
        inner = tracing.Trace.add_spans

        def spy(self, spans):
            spans = list(spans)
            calls.append([s[0] for s in spans])
            return inner(self, spans)

        monkeypatch.setattr(tracing.Trace, "add_spans", spy)
        monkeypatch.setattr(
            tracing.Trace, "add_span",
            lambda self, name, *a, **kw: calls.append(name) or None)
        traced_search(fused_service, MATCH)
        jobs = [c for c in calls if isinstance(c, list) and "dispatch" in c]
        assert jobs == [[*JOB_SPANS, "launch", "download", "unpack"]]

    def test_phase_annotations_nest_on_the_profilers_clock(
        self, fused_service, http_server, tmp_path
    ):
        """Inside a profiler session (here the CPU's) the host plane
        holds `es.launch` inside `es.dispatch`, `es.download` and
        `es.unpack` inside `es.collect`, and `es.trace_export`."""
        import glob

        import jax
        from jax.profiler import ProfileData

        fused_service.search(json.loads(json.dumps(MATCH)))
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            fused_service.search(json.loads(json.dumps(MATCH)))
            http_call(http_server, "GET", "/_internal/traces?n=4")
            # the connection's thread leaves `es.http` after the
            # response's last byte, which the client may read first: an
            # annotation still open when the session stops is not written
            time.sleep(0.05)
        finally:
            jax.profiler.stop_trace()
        (path,) = glob.glob(str(
            tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
        found = {}
        for plane in ProfileData.from_file(path).planes:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("es."):
                        found.setdefault(e.name, []).append((
                            line.name, e.start_ns,
                            e.start_ns + e.duration_ns, dict(e.stats)))
        assert found.keys() >= {
            "es.dispatch", "es.collect", "es.launch", "es.download",
            "es.unpack", "es.trace_export"}

        def inside(inner, outer):
            return all(any(
                line == o_line and o0 <= s0 and e0 <= o1
                for o_line, o0, o1, _ in found[outer])
                for line, s0, e0, _ in found[inner])

        assert inside("es.launch", "es.dispatch")
        assert inside("es.download", "es.collect")
        assert inside("es.unpack", "es.collect")
        assert inside("es.trace_export", "es.http")
        assert [st["program"] for *_, st in found["es.launch"]] == [
            "_fused_query_mf"]
        # download | unpack on the worker's line, in this order
        (_, _, down_end, _), = found["es.download"]
        (_, up_start, _, _), = found["es.unpack"]
        assert down_end <= up_start


class TestRestSurface:
    @pytest.fixture
    def server(self):
        from elasticsearch_tpu.rest.server import ElasticsearchTpuServer

        srv = ElasticsearchTpuServer(port=0)
        srv.start_background()
        yield srv
        srv.close()

    def _call(self, server, method, path, body=None, headers=None):
        status, _, payload = http_call(server, method, path, body, headers)
        assert status < 400, (status, payload)
        return status, payload

    def test_traces_endpoint_and_opaque_id(self, server):
        self._call(server, "PUT", "/tr-rest", {
            "settings": {"number_of_shards": 1},
        })
        self._call(server, "POST", "/tr-rest/_doc/1?refresh=true",
                   {"body": "hello"})
        status, _ = self._call(
            server, "POST", "/tr-rest/_search",
            {"query": {"match": {"body": "hello"}}},
            headers={"X-Opaque-Id": "caller-42"},
        )
        assert status == 200
        # the trace is published after its response's last byte, so a
        # client that has the answer may have to look twice
        deadline = time.monotonic() + 10.0
        while True:
            status, out = self._call(server, "GET", "/_internal/traces?n=5")
            if out["traces"] or time.monotonic() > deadline:
                break
        assert status == 200
        assert out["enabled"] is True
        search_traces = [t for t in out["traces"] if t["name"] == "search"]
        assert search_traces, f"no search trace in {out['traces']}"
        tr = search_traces[0]
        assert tr["opaque_id"] == "caller-42"
        assert tr["tags"]["index"] == "tr-rest"
        assert any(s["name"] == "coordinator" for s in tr["spans"])
        # a tree: http > coordinator > fan_out > shard_search > the
        # job's spans and the request thread's own around them
        ids = {s["id"]: s for s in tr["spans"]}
        for name in ("plan",) + JOB_SPANS + ("wake", "fetch"):
            span = next(s for s in tr["spans"] if s["name"] == name)
            chain = []
            while span["parent_id"] is not None:
                span = ids[span["parent_id"]]
                chain.append(span["name"])
            assert chain == [
                "shard_search", "fan_out", "coordinator", "http"], name
        # the node's transfer counters (whether this search compiled
        # depends on what the process built before it: the compile count
        # is test_compile_span_on_first_use_of_a_shape_only's)
        _, stats = self._call(server, "GET", "/_nodes/stats")
        node = next(iter(stats["nodes"].values()))
        assert node["transfer"]["scoring"].keys() == {
            "h2d_count", "h2d_bytes", "d2h_count", "d2h_bytes",
        }
        assert node["transfer"]["scoring"]["d2h_count"] >= 1
        # DELETE clears the ring
        status, _ = self._call(server, "DELETE", "/_internal/traces")
        assert status == 200
        _, out = self._call(server, "GET", "/_internal/traces")
        assert out["count"] == 0

    def test_export_answers_the_ring_as_before_and_counts_itself(
        self, server, monkeypatch
    ):
        """`GET /_internal/traces` is `{"enabled", "count", "traces":
        recent(n)}` assembled from each trace's own encoding; a second
        export of the same ring equals the first; `_nodes/stats`
        `tracing.*` counts exports, traces and the ones the ring dropped
        before any export read them."""
        def tracing_stats():
            _, stats = self._call(server, "GET", "/_nodes/stats")
            return next(iter(stats["nodes"].values()))["tracing"]

        ring = tracing._ring.__class__(maxlen=4)
        monkeypatch.setattr(tracing, "_ring", ring)
        for i in range(3):
            tr = tracing.Trace(f"t{i}", opaque_id=f"o{i}", index="i")
            root = tr.add_span("coordinator", 10, 90, shards=1)
            tr.add_span("fan_out", 20, 80, parent_id=root, inline=True)
            tr.finish()
        s0 = tracing_stats()
        assert s0.keys() == {"exports", "exported_traces", "export_ms",
                             "ring_overwritten"}
        for n, want in ((2, 2), (50, 3), (0, 0)):
            _, out = self._call(server, "GET", f"/_internal/traces?n={n}")
            assert out == {"enabled": True, "count": want,
                           "traces": tracing.recent(n)}
            assert [t["name"] for t in out["traces"]] == [
                "t2", "t1", "t0"][:want]
        _, first = self._call(server, "GET", "/_internal/traces")
        _, again = self._call(server, "GET", "/_internal/traces")
        assert first == again and first["count"] == 3
        s1 = tracing_stats()
        assert s1["exports"] == s0["exports"] + 5
        assert s1["exported_traces"] == s0["exported_traces"] + 2 + 3 + 3 + 3
        assert s1["export_ms"] > s0["export_ms"]
        assert s1["ring_overwritten"] == s0["ring_overwritten"]
        # the ring holds four: three more traces push out two that were
        # read and none unread; three after that push out the unread
        for i in range(3, 6):
            tracing.Trace(f"t{i}").finish()
        assert tracing_stats()["ring_overwritten"] == s0["ring_overwritten"]
        for i in range(6, 9):
            tracing.Trace(f"t{i}").finish()
        # t2 had been read; t3 and t4 had not
        assert tracing_stats()["ring_overwritten"] == (
            s0["ring_overwritten"] + 2)
        # a span written after `finish` (a straggler of an abandoned
        # fan-out) is in the next export all the same
        late = ring[-1]
        assert json.loads(late.encoded())["span_count"] == 0
        late.add_span("straggler", 1, 2)
        _, out = self._call(server, "GET", "/_internal/traces?n=1")
        assert [s["name"] for s in out["traces"][0]["spans"]] == [
            "straggler"]
        # tracing off: the ring still answers, and says so
        monkeypatch.setenv("ES_TPU_TRACING", "off")
        _, out = self._call(server, "GET", "/_internal/traces?n=1")
        assert out["enabled"] is False and out["count"] == 1


    def test_opaque_id_in_slowlog_record(self, server):
        import logging

        class Cap(logging.Handler):
            def __init__(self):
                super().__init__()
                self.records = []

            def emit(self, record):
                self.records.append(record.getMessage())

        cap = Cap()
        root = logging.getLogger("index.search.slowlog")
        root.addHandler(cap)
        root.setLevel(logging.DEBUG)
        try:
            self._call(server, "PUT", "/tr-slow", {
                "settings": {
                    "number_of_shards": 1,
                    "index.search.slowlog.threshold.query.warn": "0",
                },
            })
            self._call(server, "POST", "/tr-slow/_doc/1?refresh=true",
                       {"body": "hello"})
            self._call(
                server, "POST", "/tr-slow/_search",
                {"query": {"match_all": {}}},
                headers={"X-Opaque-Id": "tenant-7"},
            )
            recs = [json.loads(r) for r in cap.records]
            assert any(r.get("opaque_id") == "tenant-7" for r in recs), recs
        finally:
            root.removeHandler(cap)


# ---------------------------------------------------------------------
# the request thread's spans: `http` is the root of a search's trace
# ---------------------------------------------------------------------

RRF = {"retriever": {"rrf": {"retrievers": [
    {"standard": {"query": MATCH["query"]}},
    {"knn": KNN["knn"]},
]}}, "size": 5}
HTTP_CHILDREN = ["http_read", "request_parse", "admission_wait",
                 "coordinator", "respond"]


@pytest.fixture(scope="module")
def http_server():
    """A real server on the CPU backend holding one small index with a
    text and a vector field."""
    from elasticsearch_tpu.rest.server import ElasticsearchTpuServer

    srv = ElasticsearchTpuServer(port=0)
    srv.start_background()
    rng = np.random.default_rng(11)
    http_call(srv, "PUT", "/tr-http", {
        "settings": {"number_of_shards": 1},
        "mappings": {"properties": {
            "body": {"type": "text"},
            "vec": {"type": "dense_vector", "dims": DIMS,
                    "similarity": "cosine"},
        }},
    })
    for i in range(40):
        http_call(srv, "POST", f"/tr-http/_doc/{i}", {
            "body": " ".join(rng.choice(WORDS, int(rng.integers(3, 9)))),
            "vec": [float(x) for x in rng.normal(size=DIMS)],
        })
    http_call(srv, "POST", "/tr-http/_refresh")
    yield srv
    srv.close()


def http_call(srv, method, path, body=None, headers=None):
    """-> (status, headers, payload); a 4xx or 429 answer is returned,
    not raised."""
    import urllib.error

    req = urllib.request.Request(
        f"http://127.0.0.1:{srv.port}{path}", method=method,
        data=None if body is None else json.dumps(body).encode(),
        headers={"Content-Type": "application/json", **(headers or {})},
    )
    try:
        with urllib.request.urlopen(req) as resp:
            return resp.status, resp.headers, json.loads(resp.read() or b"null")
    except urllib.error.HTTPError as err:
        return err.code, err.headers, json.loads(err.read())


def ring_after(n: int = 1) -> list:
    """The ring once it holds `n` traces: a trace is published after its
    response is written, so the client may read the answer first."""
    deadline = time.monotonic() + 10.0
    while len(tracing.recent(50)) < n and time.monotonic() < deadline:
        time.sleep(0.002)
    return tracing.recent(50)


def children_of(spans, parent):
    return sorted((s for s in spans if s["parent_id"] == parent["id"]),
                  key=lambda s: s["start_ns"])


class TestHttpRoot:
    @pytest.mark.parametrize("body", [MATCH, KNN, RRF],
                             ids=["match", "knn", "rrf"])
    def test_one_trace_a_request_rooted_at_http(self, http_server, body):
        raw = json.dumps(body).encode()
        t_sent = time.perf_counter_ns()
        status, _, answer = http_call(http_server, "POST",
                                      "/tr-http/_search", body)
        t_answered = time.perf_counter_ns()
        assert status == 200 and answer["hits"]["hits"]
        traces = ring_after(1)
        assert len(traces) == 1
        tr = traces[0]
        spans = tr["spans"]
        roots = [s for s in spans if s["parent_id"] is None]
        assert [s["name"] for s in roots] == ["http"]
        http = roots[0]
        assert http["tags"] == {
            "method": "POST", "status": 200, "request_bytes": len(raw),
            "response_bytes": http["tags"]["response_bytes"],
        }
        # the handler's three spans and the two that were roots are its
        # children, in this order, none overlapping, all inside it
        kids = children_of(spans, http)
        assert [s["name"] for s in kids] == HTTP_CHILDREN
        assert kids[0]["start_ns"] == http["start_ns"]
        assert end_ns(kids[0]) == kids[1]["start_ns"]  # read | parse
        for a, b in zip(kids, kids[1:]):
            assert end_ns(a) <= b["start_ns"], (a["name"], b["name"])
        assert end_ns(kids[-1]) == end_ns(http)
        by = {s["name"]: s for s in kids}
        assert by["request_parse"]["tags"] == {"bytes": len(raw)}
        assert by["respond"]["tags"].keys() == {"bytes", "dumps_ms"}
        assert by["respond"]["tags"]["bytes"] == (
            http["tags"]["response_bytes"]) > 0
        assert 0 <= by["respond"]["tags"]["dumps_ms"] * 1e6 <= (
            by["respond"]["duration_ns"])
        # the trace IS the http span, and it was published after the
        # response was written: its length covers `respond`
        assert tr["duration_ns"] == http["duration_ns"]
        assert end_ns(by["respond"]) == http["start_ns"] + tr["duration_ns"]
        # `http` ends at a mark the handler thread takes once its last
        # socket write has returned, so the client may hold the answer
        # first: the span starts after the request left and ends within
        # a second of the answer (a thread's turn under six workers)
        assert t_sent <= http["start_ns"]
        assert end_ns(http) <= t_answered + 1_000_000_000

    def test_request_thread_spans_tile_shard_search(self, http_server):
        for body in (MATCH, KNN):
            tracing.clear()
            http_call(http_server, "POST", "/tr-http/_search", body)
            spans = ring_after(1)[0]["spans"]
            sh = next(s for s in spans if s["name"] == "shard_search")
            kids = [s for s in children_of(spans, sh)
                    if s["name"] != "compile"]
            assert [s["name"] for s in kids] == [
                "plan", *JOB_SPANS, "wake", "fetch"]
            # consecutive marks from `plan`'s end to `wake`'s start
            for a, b in zip(kids[:6], kids[1:6]):
                assert end_ns(a) == b["start_ns"], (a["name"], b["name"])
            assert sh["start_ns"] <= kids[0]["start_ns"]
            assert end_ns(kids[5]) <= kids[6]["start_ns"]
            assert end_ns(kids[6]) <= end_ns(sh)

    def test_rrf_node_holds_plan_legs_and_wake(self, http_server):
        http_call(http_server, "POST", "/tr-http/_search", RRF)
        spans = ring_after(1)[0]["spans"]
        by = {s["name"]: s for s in spans if s["name"] not in JOB_SPANS}
        kids = [s["name"] for s in children_of(spans, by["rrf"])]
        assert sorted(kids) == sorted(
            ["plan_legs", "leg:bm25", "leg:knn", "wake", "fuse"])
        assert by["plan_legs"]["start_ns"] == by["rrf"]["start_ns"]
        assert by["plan_legs"]["tags"]["legs"] == 2
        # it lies inside every leg: a leg cannot end before it is planned
        for leg in ("leg:bm25", "leg:knn"):
            assert end_ns(by["plan_legs"]) <= end_ns(by[leg])
            assert [s["name"] for s in children_of(spans, by[leg])
                    if s["name"] != "compile"] == list(JOB_SPANS)
        assert by["wake"]["start_ns"] == max(
            end_ns(by["leg:bm25"]), end_ns(by["leg:knn"]))
        assert end_ns(by["wake"]) == by["fuse"]["start_ns"]
        assert by["fuse"]["tags"].keys() == {"window"}
        assert by["rrf"]["tags"].keys() == {"index", "legs"}

    @pytest.mark.parametrize("case", ["malformed_query_400",
                                      "overloaded_429"])
    def test_refused_request_still_finishes_its_trace(
        self, http_server, case
    ):
        from elasticsearch_tpu.search.admission import admission

        if case == "overloaded_429":
            admission.configure(enabled=True, target_delay_ms=10)
            for _ in range(60):
                admission.observe_queue_delay(0.5)  # tier 4: reject
            want, body = 429, MATCH
        else:
            want, body = 400, {"query": {"no_such_query": {}}}
        try:
            status, headers, _ = http_call(
                http_server, "POST", "/tr-http/_search", body)
        finally:
            admission.reset()
        assert status == want
        assert want != 429 or int(headers["Retry-After"]) >= 1
        traces = ring_after(1)
        assert len(traces) == 1
        spans = traces[0]["spans"]
        http = next(s for s in spans if s["parent_id"] is None)
        assert http["name"] == "http" and http["tags"]["status"] == want
        names = [s["name"] for s in children_of(spans, http)]
        assert names[:2] == ["http_read", "request_parse"]
        assert names[-1] == "respond" and "coordinator" not in names
        assert traces[0]["duration_ns"] == http["duration_ns"]

    def test_route_that_arms_no_trace_leaves_the_ring_alone(
        self, http_server
    ):
        http_call(http_server, "POST", "/tr-http/_search", MATCH)
        before = [t["trace_id"] for t in ring_after(1)]
        for method, path, body in (
            ("GET", "/tr-http/_doc/1", None),
            ("POST", "/tr-http/_count", {"query": MATCH["query"]}),
            ("GET", "/_nodes/stats", None),
            ("GET", "/no/such/route/at/all", None),
        ):
            http_call(http_server, method, path, body)
        # a later search's trace is the next one: nothing was armed,
        # held over or published in between
        http_call(http_server, "POST", "/tr-http/_search", MATCH)
        after = [t["trace_id"] for t in ring_after(2)]
        assert len(after) == 2 and after[1:] == before
        assert int(after[0].split("-")[1]) == int(before[0].split("-")[1]) + 1

    def test_tracing_off_records_nothing_and_answers_the_same(
        self, http_server, monkeypatch
    ):
        _, _, traced = http_call(http_server, "POST", "/tr-http/_search",
                                 MATCH)
        ring_after(1)
        tracing.clear()
        monkeypatch.setenv("ES_TPU_TRACING", "off")
        answers = [http_call(http_server, "POST", "/tr-http/_search", b)
                   for b in (MATCH, RRF)]
        http_call(http_server, "GET", "/_nodes/stats")  # the thread is idle
        assert tracing.recent(50) == []
        assert [a[0] for a in answers] == [200, 200]
        assert answers[0][2]["hits"] == traced["hits"]

    def test_search_called_as_a_library_finishes_its_own_trace(
        self, http_server
    ):
        """No handler above the action: `end()` publishes the trace, as
        it did, and nothing is left armed on the calling thread."""
        status, _ = http_server.actions.search(
            dict(MATCH), {"index": "tr-http"}, {})
        assert status == 200
        assert tracing.current() is None
        assert tracing.PARENT_CTX.get() is None
        roots = {s["name"] for s in tracing.recent(1)[0]["spans"]
                 if s["parent_id"] is None}
        assert roots == {"admission_wait", "coordinator"}


class TestAddSpans:
    def test_one_call_writes_roots_children_and_reserved_ids(self):
        tr = tracing.Trace("t", start_ns=1_000)
        root = tr.reserve_span()
        with tracing.under(99):  # explicit parents: the var is not read
            tr.add_spans((
                ("http", 1_000, 9_000, None, root, {"status": 200}),
                ("respond", 8_000, 9_000, root, None, {}),
            ))
        tr.finish(9_000)
        d = tr.to_dict()
        assert d["duration_ns"] == 8_000
        by = {s["name"]: s for s in d["spans"]}
        assert by["http"]["id"] == root and by["http"]["parent_id"] is None
        assert by["respond"]["parent_id"] == root != by["respond"]["id"]

    def test_cap_counts_what_it_drops(self):
        tr = tracing.Trace("t")
        tr.add_spans(
            (f"s{i}", 0, 1, None, None, {})
            for i in range(tracing.MAX_SPANS + 3))
        d = tr.to_dict()
        assert d["span_count"] == tracing.MAX_SPANS
        assert d["dropped_spans"] == 3


# ---------------------------------------------------------------------
# a one-shard fan-out on the request thread (`fan_out` [inline])
# ---------------------------------------------------------------------


def fan_out_counts(srv) -> dict:
    _, _, stats = http_call(srv, "GET", "/_nodes/stats")
    node = next(iter(stats["nodes"].values()))
    return dict(node["thread_pool"]["search"]["fan_out"])


def tree_shape(spans) -> set:
    """{(name, parent's name, tag names)}: what two runs of one request
    over different fan-outs must agree on."""
    ids = {s["id"]: s["name"] for s in spans}
    return {(s["name"], ids.get(s["parent_id"]), tuple(sorted(s["tags"])))
            for s in spans if s["name"] != "compile"}


@pytest.fixture()
def shard_threads(monkeypatch):
    """The names of the threads `shard_search_local` ran on, and what
    the context variables read there."""
    from elasticsearch_tpu.cluster.indices import IndexService

    seen = []
    inner = IndexService.shard_search_local

    def spy(self, *args, **kwargs):
        seen.append({
            "thread": threading.current_thread().name,
            "trace": tracing.TRACE_CTX.get(),
            "parent": tracing.PARENT_CTX.get(),
            "opaque": tracing.OPAQUE_ID_CTX.get(),
        })
        LEAK.set("set inside the shard call")
        return inner(self, *args, **kwargs)

    monkeypatch.setattr(IndexService, "shard_search_local", spy)
    return seen


LEAK: contextvars.ContextVar = contextvars.ContextVar("leak", default=None)


def cancellable_task():
    from elasticsearch_tpu.tasks import TaskManager

    return TaskManager("n").register(
        "indices:data/read/search", "t", cancellable=True)


class TestInlineFanOut:
    def test_rest_search_of_one_shard_stays_on_the_request_thread(
        self, http_server, shard_threads, monkeypatch
    ):
        """(a) every REST search is a cancellable task; on a one-shard
        jax index its shard runs on the handler's thread, and the trace
        is the one a pooled two-shard fan-out writes."""
        before = fan_out_counts(http_server)
        http_call(http_server, "POST", "/tr-http/_search", MATCH,
                  headers={"X-Opaque-Id": "caller-7"})
        one = ring_after(1)[0]
        assert fan_out_counts(http_server) == {
            "inline": before["inline"] + 1, "pooled": before["pooled"]}
        by = {s["name"]: s for s in one["spans"]}
        assert by["fan_out"]["tags"] == {"inline": True}
        (ran,) = shard_threads
        assert not ran["thread"].startswith("search-fanout")
        # the shard read the request's own trace, parent and header
        assert ran["trace"].trace_id == one["trace_id"]
        assert ran["parent"] == by["fan_out"]["id"]
        assert ran["opaque"] == "caller-7"
        # two shards on the shard path (the mesh twin would take them)
        monkeypatch.setenv("ES_TPU_MESH", "off")
        http_call(http_server, "PUT", "/tr-http2", {
            "settings": {"number_of_shards": 2},
            "mappings": {"properties": {"body": {"type": "text"}}},
        })
        try:
            # (as many documents a shard as `tr-http` holds, so both
            # sides of the fused kernel's size gate agree)
            for i in range(80):
                http_call(http_server, "POST", f"/tr-http2/_doc/{i}",
                          {"body": "alpha beta"})
            http_call(http_server, "POST", "/tr-http2/_refresh")
            del shard_threads[:]
            tracing.clear()
            http_call(http_server, "POST", "/tr-http2/_search", MATCH)
            two = ring_after(1)[0]
            # (the node sums the counters of the indices it holds)
            assert fan_out_counts(http_server) == {
                "inline": before["inline"] + 1,
                "pooled": before["pooled"] + 1,
            }
        finally:
            http_call(http_server, "DELETE", "/tr-http2")
        assert [s["tags"] for s in two["spans"]
                if s["name"] == "fan_out"] == [{"inline": False}]
        assert len(shard_threads) == 2 and all(
            r["thread"].startswith("search-fanout") for r in shard_threads)
        assert tree_shape(one["spans"]) == tree_shape(two["spans"])

    def test_nothing_set_inside_the_shard_call_leaks(
        self, fused_service, shard_threads
    ):
        """(d) the shard runs under a copy of the request's context on
        either branch."""
        inline0 = fused_service.fan_out_stats["inline"]
        fused_service.search(json.loads(json.dumps(MATCH)),
                             task=cancellable_task())
        assert fused_service.fan_out_stats["inline"] == inline0 + 1
        (ran,) = shard_threads
        assert ran["thread"] == threading.current_thread().name
        assert LEAK.get() is None
        assert tracing.PARENT_CTX.get() is None

    @pytest.mark.parametrize("case", [
        "timeout", "pinned_reader", "remote_copy", "unplanned_query"])
    def test_what_keeps_the_pool_is_abandoned_as_before(
        self, fused_service, shard_threads, monkeypatch, case
    ):
        """(c) a deadline, a pinned or remote copy and a query no planner
        takes run in the pool: the request thread stays free to give up
        at the deadline or within a poll step of a cancel."""
        from elasticsearch_tpu.tasks import TaskCancelledException

        svc = fused_service
        task = cancellable_task()
        body = json.loads(json.dumps(MATCH))
        held = 1.5  # seconds the shard's work takes

        def slow(*args, **kwargs):
            time.sleep(held)
            return {"total": 0, "relation": "eq", "max_score": None,
                    "hits": []}

        pins = None
        direct = {}
        if case == "timeout":
            body["timeout"] = "150ms"
            monkeypatch.setattr(
                type(svc), "_shard_search", lambda *a, **k: slow())
        elif case == "pinned_reader":
            pins = [{"node": "elsewhere", "ctx": "c0"}]
            monkeypatch.setattr(svc, "remote_call", slow, raising=False)
        elif case == "remote_copy":
            direct = {"owners": {0: "elsewhere"}}
            monkeypatch.setattr(svc, "remote_call", slow, raising=False)
        else:
            # a range query has no plan: the unbatched executor runs it
            body["query"] = {"range": {"body": {"gte": "a"}}}
            ex = svc._executor(svc.shards[0])
            monkeypatch.setattr(
                ex, "execute", lambda *a, **k: time.sleep(held))
        unplanned0 = svc._batcher.stats["unplanned_queries"]
        pooled0 = svc.fan_out_stats["pooled"]
        timer = threading.Timer(0.1, task.cancel)
        if case != "timeout":
            timer.start()
        t0 = time.monotonic()
        try:
            if direct:
                with pytest.raises(TaskCancelledException):
                    svc._fan_out(body, task=task, **direct)
            elif case == "timeout":
                handle = tracing.begin("search", index=svc.name)
                resp = svc.search(body, task=task)
                tracing.end(handle)
                assert resp["timed_out"] is True
                assert resp["_shards"]["failures"][0]["reason"]["type"] == (
                    "timeout_exception")
                assert [s["tags"] for s in tracing.recent(1)[0]["spans"]
                        if s["name"] == "fan_out"] == [{"inline": False}]
            else:
                with pytest.raises(TaskCancelledException):
                    svc.search(body, pinned_executors=pins, task=task)
        finally:
            timer.cancel()
        # given up long before the shard's work ends
        assert time.monotonic() - t0 < 0.5 * held
        assert svc.fan_out_stats["pooled"] == pooled0 + 1
        if case in ("pinned_reader", "remote_copy"):
            assert shard_threads == []  # no local shard call at all
        elif case == "unplanned_query":
            # asked once on the request thread (not counted, no span),
            # run once in the pool
            assert [r["thread"].startswith("search-fanout")
                    for r in shard_threads] == [False, True]
            assert svc._batcher.stats["unplanned_queries"] == unplanned0 + 1
