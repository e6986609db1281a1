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
    submitted it, whose parent is the coordinator's `fan_out`;
  * the query path's host<->device transfers are counted exactly
    (`_nodes/stats` `transfer.scoring`).
"""

import contextvars
import json
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
        fits = ex.fused_plan
        monkeypatch.setattr(
            ex, "fused_plan",
            lambda fs, si, field, terms, boost, msm: None
            if len(terms) > 4 else fits(fs, si, field, terms, boost, msm))
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
        # the job lies inside its shard_search span, inside the request
        sh = by["shard_search"]
        assert t_before <= sh["start_ns"] <= q["start_ns"]
        assert end_ns(c) <= by["fetch"]["start_ns"]
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
        for name in JOB_SPANS + ("fetch",):
            assert ids[by[name]["parent_id"]]["name"] == "shard_search", name
        assert ids[by["shard_search"]["parent_id"]]["name"] == "fan_out"
        assert ids[by["fan_out"]["parent_id"]]["name"] == "coordinator"
        # the only roots: the coordinator and the wait before it
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
            # and the compile spans hang off the job's spans)
            spans = {s["name"]: s for s in tr.to_dict()["spans"]
                     if s["name"] != "compile"}
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
        assert {s["tags"]["program"] for s in compiles} == {"_fused_query"}
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


class TestRestSurface:
    @pytest.fixture
    def server(self):
        from elasticsearch_tpu.rest.server import ElasticsearchTpuServer

        srv = ElasticsearchTpuServer(port=0)
        srv.start_background()
        yield srv
        srv.close()

    def _call(self, server, method, path, body=None, headers=None):
        url = f"http://127.0.0.1:{server.port}{path}"
        data = json.dumps(body).encode() if body is not None else None
        req = urllib.request.Request(
            url, data=data, method=method,
            headers={"Content-Type": "application/json", **(headers or {})},
        )
        with urllib.request.urlopen(req) as resp:
            return resp.status, json.loads(resp.read() or b"null")

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
        status, out = self._call(server, "GET", "/_internal/traces?n=5")
        assert status == 200
        assert out["enabled"] is True
        search_traces = [t for t in out["traces"] if t["name"] == "search"]
        assert search_traces, f"no search trace in {out['traces']}"
        tr = search_traces[0]
        assert tr["opaque_id"] == "caller-42"
        assert tr["tags"]["index"] == "tr-rest"
        assert any(s["name"] == "coordinator" for s in tr["spans"])
        # a tree: coordinator > fan_out > shard_search > the job's spans
        ids = {s["id"]: s for s in tr["spans"]}
        for name in JOB_SPANS + ("fetch",):
            span = next(s for s in tr["spans"] if s["name"] == name)
            chain = []
            while span["parent_id"] is not None:
                span = ids[span["parent_id"]]
                chain.append(span["name"])
            assert chain == ["shard_search", "fan_out", "coordinator"], name
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
