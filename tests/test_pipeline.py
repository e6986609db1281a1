"""Zero-sync serving pipeline (round 6): double-buffered batch dispatch
(`ES_TPU_PIPELINE_DEPTH`), device-side cross-segment top-k merge, and
MFU/roofline accounting.

Contracts under test:
  * depth=2 and depth=1 produce FLOAT-EXACT identical results (same doc
    ids, same scores bit-for-bit, same totals) under randomized
    interleaved match/serve/knn submission — pipelining is scheduling
    only, never semantics;
  * the device merge is hit-for-hit identical to the unbatched executor
    path across multiple segments;
  * 429 overflow still fires at exactly the same queue bound;
  * close() during in-flight batches fails waiters instead of hanging;
  * pipeline roofline stats surface in `_nodes/stats`.
"""

import threading

import numpy as np
import pytest

from elasticsearch_tpu.cluster.indices import IndexService
from elasticsearch_tpu.ops import scoring
from elasticsearch_tpu.search.batcher import (
    EsRejectedExecutionError,
    QueryBatcher,
)

WORDS = [
    "alpha", "beta", "gamma", "delta", "epsilon", "zeta", "eta", "theta",
    "iota", "kappa", "lam", "mu", "nu", "xi", "omicron", "pi",
]

DIMS = 8


def _zipf(n):
    w = 1.0 / np.arange(1, n + 1)
    return w / w.sum()


def make_service(n_docs=240, n_shards=1, seed=0, waves=3):
    """`waves` refresh points → multiple segments, so the cross-segment
    device merge actually merges."""
    rng = np.random.default_rng(seed)
    svc = IndexService(
        "pl",
        settings={"number_of_shards": n_shards, "search.backend": "jax"},
        mappings_json={
            "properties": {
                "title": {"type": "text"},
                "body": {"type": "text"},
                "vec": {"type": "dense_vector", "dims": DIMS,
                        "similarity": "cosine"},
            }
        },
    )
    per_wave = max(1, n_docs // waves)
    for i in range(n_docs):
        kt = int(rng.integers(1, 4))
        kb = int(rng.integers(3, 12))
        svc.index_doc(
            str(i),
            {
                "title": " ".join(rng.choice(WORDS, kt, p=_zipf(len(WORDS)))),
                "body": " ".join(rng.choice(WORDS, kb, p=_zipf(len(WORDS)))),
                "vec": [float(x) for x in rng.normal(size=DIMS)],
            },
        )
        if (i + 1) % per_wave == 0:
            svc.refresh()
    svc.refresh()
    return svc


@pytest.fixture(scope="module")
def service():
    svc = make_service()
    yield svc
    svc.close()


def mixed_bodies(rng):
    """A randomized interleaving of every plan family the batcher
    serves (match / serve / knn, two k buckets, a pruned-totals
    variant)."""
    bodies = []
    for i in range(48):
        w = WORDS[int(rng.integers(0, 8))]
        w2 = WORDS[int(rng.integers(0, len(WORDS)))]
        kind = i % 6
        if kind == 0:
            bodies.append({"query": {"match": {"body": f"{w} {w2}"}},
                          "size": 7})
        elif kind == 1:
            bodies.append({
                "query": {"match": {"body": {"query": f"{w} {w2}",
                                             "operator": "and"}}},
                "size": 20,
            })
        elif kind == 2:
            bodies.append({
                "query": {"bool": {
                    "must": [{"term": {"body": w}}],
                    "should": [{"match": {"title": w2}}],
                }},
                "size": 7,
            })
        elif kind == 3:
            bodies.append({
                "query": {"multi_match": {
                    "query": f"{w} {w2}", "fields": ["title", "body"],
                    "tie_breaker": 0.3,
                }},
                "size": 7,
            })
        elif kind == 4:
            v = [float(x) for x in rng.normal(size=DIMS)]
            bodies.append({
                "knn": {"field": "vec", "query_vector": v, "k": 5,
                        "num_candidates": int(rng.choice([7, 50]))},
                "size": 5,
            })
        else:
            bodies.append({"query": {"match": {"body": f"{w} {w2}"}},
                          "size": 7, "track_total_hits": False})
    order = rng.permutation(len(bodies))
    return [bodies[int(i)] for i in order]


def run_concurrent(svc, bodies, threads=12):
    results = [None] * len(bodies)
    errs = []
    cursor = [0]
    lock = threading.Lock()

    def worker():
        while True:
            with lock:
                i = cursor[0]
                if i >= len(bodies):
                    return
                cursor[0] += 1
            try:
                results[i] = svc.search(bodies[i])
            except Exception as e:  # pragma: no cover
                errs.append(e)
                return

    ts = [threading.Thread(target=worker) for _ in range(threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert not errs, errs
    return results


def fingerprint(resp):
    """Exact (unrounded) result identity: ids, float-exact scores,
    totals/relation when present."""
    hits = [(h["_id"], h["_score"]) for h in resp["hits"]["hits"]]
    total = resp["hits"].get("total")
    return (hits, (total["value"], total["relation"]) if total else None)


class TestDepthParity:
    def test_depth2_vs_depth1_float_exact(self, service):
        """Float-exact for the text families; kNN within the stated
        tolerance (see below)."""
        rng = np.random.default_rng(3)
        bodies = mixed_bodies(rng)
        b = service._batcher
        # warm compiles so both passes measure the same code paths
        run_concurrent(service, bodies[:8], threads=4)
        old = b.pipeline_depth
        try:
            b.pipeline_depth = 1
            r1 = run_concurrent(service, bodies)
            b.pipeline_depth = 2
            r2 = run_concurrent(service, bodies)
        finally:
            b.pipeline_depth = old
        for i, (a, c) in enumerate(zip(r1, r2)):
            if "knn" not in bodies[i]:
                assert fingerprint(a) == fingerprint(c), bodies[i]
                continue
            # kNN scores are not bit-identical across launch shapes
            # (ROADMAP D7): depth changes how requests group, the group's
            # row count picks the matmul's internal tiling, and the same
            # fp32 dot product is summed in another order — last-ulp,
            # bounded by d * 2^-24. Same ids, same order, scores within
            # scoring.KNN_SCORE_RTOL (chip_smoke.py uses the same bound).
            (ha, ta), (hc, tc) = fingerprint(a), fingerprint(c)
            assert [d for d, _ in ha] == [d for d, _ in hc], bodies[i]
            assert ta == tc, bodies[i]
            np.testing.assert_allclose(
                [s for _, s in ha], [s for _, s in hc],
                rtol=scoring.KNN_SCORE_RTOL, atol=0.0,
            )

    def test_pipelining_actually_engages(self, service):
        # with depth=2 and a flood of submissions, jobs/launches stats
        # keep ticking and every request completes
        b = service._batcher
        before = b.stats["jobs"]
        rng = np.random.default_rng(5)
        bodies = mixed_bodies(rng)
        run_concurrent(service, bodies, threads=16)
        assert b.stats["jobs"] - before == len(bodies)


class TestCrossSegmentMerge:
    def test_multi_segment_parity_with_unbatched(self, service):
        # the service has >= 3 segments; the batched path must match
        # the unbatched executor path hit-for-hit across all of them
        assert len(service.shards[0].segments) >= 2
        cases = [
            {"query": {"match": {"body": "alpha gamma"}}, "size": 10},
            {"query": {"match": {"body": {"query": "alpha beta",
                                          "operator": "and"}}}, "size": 10},
            {"query": {"bool": {"must": [{"term": {"body": "alpha"}}],
                                "should": [{"match": {"title": "beta"}}]}},
             "size": 10},
            {"query": {"multi_match": {"query": "gamma delta",
                                       "fields": ["title^2", "body"]}},
             "size": 10},
        ]
        for body in cases:
            batched = service.search(body)
            unbatched = service.search({**body, "min_score": 0})
            assert [
                (h["_id"], round(h["_score"], 4))
                for h in batched["hits"]["hits"]
            ] == [
                (h["_id"], round(h["_score"], 4))
                for h in unbatched["hits"]["hits"]
            ], body
            assert (
                batched["hits"]["total"]["value"]
                == unbatched["hits"]["total"]["value"]
            )

    def test_knn_multi_segment_parity(self, service):
        # nc == k exercises the per-segment candidate rank cut (each
        # segment can contribute at most nc, fewer than k x segments);
        # nc < k is now a request-scoped 400 (KnnSearchBuilder parity)
        rng = np.random.default_rng(11)
        for nc in (8, 100):
            v = [float(x) for x in rng.normal(size=DIMS)]
            body = {
                "knn": {"field": "vec", "query_vector": v, "k": 8,
                        "num_candidates": nc},
                "size": 8,
            }
            batched = service.search(body)
            unbatched = service.search({**body, "min_score": 0})
            # the unbatched path reports total differently (mask count);
            # compare the ranked hit list only
            assert [
                (h["_id"], round(h["_score"], 5))
                for h in batched["hits"]["hits"]
            ] == [
                (h["_id"], round(h["_score"], 5))
                for h in unbatched["hits"]["hits"]
            ], nc

    def test_wand_pruned_path_same_topk(self, service):
        body = {
            "query": {"match": {"body": "alpha gamma epsilon"}},
            "size": 10,
            "track_total_hits": False,
        }
        wand = service.search(body)
        exact = service.search({**body, "track_total_hits": True})
        assert [h["_id"] for h in wand["hits"]["hits"]] == [
            h["_id"] for h in exact["hits"]["hits"]
        ]


class TestBackpressure:
    def test_429_fires_at_same_queue_bound(self, service, monkeypatch):
        """The pipeline must not change the admission bound: with no
        worker draining, EXACTLY queue_capacity jobs are admitted and
        every overflow raises 429, at any depth."""
        ex = service._executor(service.shards[0])
        from elasticsearch_tpu.search import dsl
        from elasticsearch_tpu.search.batcher import extract_match_plan

        plan = extract_match_plan(
            dsl.parse_query({"match": {"body": "alpha"}}),
            service.mappings, service.analysis, False,
        )
        for depth in (1, 2):
            tiny = QueryBatcher(
                workers=1, queue_capacity=4, pipeline_depth=depth
            )
            monkeypatch.setattr(tiny, "_ensure_thread", lambda: None)
            rejected = 0
            for _ in range(10):
                try:
                    tiny.submit_nowait(ex, plan, 5)
                except EsRejectedExecutionError:
                    rejected += 1
            assert rejected == 6  # 10 submits - capacity 4
            assert tiny.stats["rejected"] == 6
            tiny.close()  # queued waiters must fail, not hang

    def test_flood_completes_under_depth2(self, service):
        ex = service._executor(service.shards[0])
        from elasticsearch_tpu.search import dsl
        from elasticsearch_tpu.search.batcher import extract_match_plan

        plan = extract_match_plan(
            dsl.parse_query({"match": {"body": "alpha"}}),
            service.mappings, service.analysis, False,
        )
        tiny = QueryBatcher(workers=2, queue_capacity=8, pipeline_depth=2)
        jobs = []
        rejected = 0
        for _ in range(64):
            try:
                jobs.append(tiny.submit_nowait(ex, plan, 5))
            except EsRejectedExecutionError:
                rejected += 1
        for j in jobs:
            td = QueryBatcher.wait(j, timeout=30)
            assert td is not None
        tiny.close()


class _GatedCollect(QueryBatcher):
    """Collect stage blocks on a gate — simulates a batch whose device
    results are still in flight when close() lands."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.gate = threading.Event()
        self.collects = 0

    def _collect_batch(self, ctx):
        self.collects += 1
        self.gate.wait(15)
        super()._collect_batch(ctx)


class TestCloseInFlight:
    def test_close_fails_waiters_instead_of_hanging(self, service):
        ex = service._executor(service.shards[0])
        from elasticsearch_tpu.search import dsl
        from elasticsearch_tpu.search.batcher import extract_serve_plan

        plan = extract_serve_plan(
            dsl.parse_query({"bool": {"should": [
                {"match": {"body": "alpha"}}]}}),
            service.mappings, service.analysis,
        )
        assert plan is not None
        gated = _GatedCollect(workers=1, pipeline_depth=2)
        j1 = gated.submit_nowait(ex, plan, 5, kind="serve",
                                 query=dsl.parse_query(
                                     {"match": {"body": "alpha"}}))
        # wait until the worker is inside the gated collect, then queue
        # a second job it will never get to collect
        for _ in range(200):
            if gated.collects:
                break
            threading.Event().wait(0.02)
        assert gated.collects == 1
        j2 = gated.submit_nowait(ex, plan, 5, kind="serve",
                                 query=dsl.parse_query(
                                     {"match": {"body": "alpha"}}))
        gated.close()
        gated.gate.set()
        # neither waiter may hang: j1 completes (its collect finishes),
        # j2 fails fast with the closed error
        assert j1.event.wait(20)
        assert j2.event.wait(20)
        assert j2.error is not None
        with pytest.raises(RuntimeError):
            QueryBatcher.wait(j2, timeout=1)
        for t in gated._threads:
            t.join(timeout=10)
            assert not t.is_alive()


class TestPipelineStats:
    def test_nodes_stats_pipeline_block(self):
        from elasticsearch_tpu.cluster.service import ClusterService
        from elasticsearch_tpu.rest.actions import RestActions

        c = ClusterService()
        try:
            c.create_index("ps", {
                "settings": {"search.backend": "jax"},
                "mappings": {"properties": {"body": {"type": "text"}}},
            })
            idx = c.indices["ps"]
            for i in range(20):
                idx.index_doc(str(i), {"body": f"alpha beta {i}"})
            idx.refresh()
            idx.search({"query": {"match": {"body": "alpha"}}})
            actions = RestActions(c)
            _, resp = actions.nodes_stats(None, {}, {})
            pipe = resp["nodes"]["node-0"]["pipeline"]
            # device time is the profiler's to measure (PERF.md §3)
            assert set(pipe) == {"depth", "batching", "mesh", "rrf"}
            assert pipe["depth"] >= 1
            assert pipe["batching"]["occupancy_jobs"] >= 1
        finally:
            c.close()


class TestStagingSlabs:
    def test_ring_rotation_and_ledger_charge(self, service):
        from elasticsearch_tpu.common.memory import hbm_ledger

        ex = service._executor(service.shards[0])
        a = ex.staging_slab("t_probe", (4, 8), np.int32)
        b = ex.staging_slab("t_probe", (4, 8), np.int32)
        assert a is not b  # ring hands out distinct buffers
        seen = {id(a), id(b)}
        for _ in range(64):
            seen.add(id(ex.staging_slab("t_probe", (4, 8), np.int32)))
        assert id(a) in seen  # ...and cycles back around
        assert hbm_ledger.stats()["by_category"].get("serving", 0) > 0
