"""Concurrent hybrid retrieval: async batcher futures + RRF fusion.

Covers the tentpole contract of the hybrid pipeline:
  * the served fuse (ops/fusion.rrf_fuse_ranked: the host's dictionary
    over the hits the legs returned) is hit-for-hit with the NumPy
    oracle — ranks, scores, exact-doc dedup, and the ascending doc-id
    tie-break;
  * both hybrid legs are genuinely in flight at the same time
    (instrumented batcher counters);
  * the async submission path (`submit_nowait`) keeps the dispatcher's
    429 backpressure;
  * the rrf retriever and the top-level `rank: {rrf: ...}` hybrid API
    produce identical results over the same legs.
"""

import threading
import time

import numpy as np
import pytest

from elasticsearch_tpu.cluster.indices import IndexService
from elasticsearch_tpu.ops.fusion import (
    rrf_fuse_host,
    rrf_fuse_ranked,
)
from elasticsearch_tpu.search.batcher import (
    EsRejectedExecutionError,
    QueryBatcher,
    extract_match_plan,
)
from elasticsearch_tpu.search import dsl
from elasticsearch_tpu.search.executor_jax import JaxExecutor

WORDS = [
    "alpha", "beta", "gamma", "delta", "epsilon", "zeta", "eta", "theta",
    "iota", "kappa", "lam", "mu",
]
DIMS = 8


def make_service(backend="jax", n_docs=250, seed=0):
    rng = np.random.default_rng(seed)
    svc = IndexService(
        f"hy-{backend}",
        settings={"number_of_shards": 1, "search.backend": backend},
        mappings_json={
            "properties": {
                "body": {"type": "text"},
                "vec": {
                    "type": "dense_vector", "dims": DIMS,
                    "similarity": "cosine",
                },
            }
        },
    )
    for i in range(n_docs):
        k = int(rng.integers(3, 9))
        svc.index_doc(
            str(i),
            {
                "body": " ".join(rng.choice(WORDS, size=k)),
                "vec": rng.standard_normal(DIMS).tolist(),
            },
        )
    svc.refresh()
    return svc


def hybrid_body(seed=0, size=10, rank_constant=60):
    qv = np.random.default_rng(seed).standard_normal(DIMS).tolist()
    return {
        "retriever": {
            "rrf": {
                "retrievers": [
                    {"standard": {"query": {"match": {"body": "alpha gamma"}}}},
                    {
                        "knn": {
                            "field": "vec", "query_vector": qv,
                            "k": 20, "num_candidates": 50,
                        }
                    },
                ],
                "rank_constant": rank_constant,
            }
        },
        "size": size,
        "_source": False,
    }


@pytest.fixture(scope="module")
def service():
    svc = make_service()
    yield svc
    svc.close()


def _random_legs(seed, n_legs, universe, width):
    rng = np.random.default_rng(seed)
    return [rng.permutation(universe)[:width].tolist() for _ in range(n_legs)]


# one query's legs, each its docs in rank order, and the cut
SERVED_FUSE_CASES = {
    # overlapping universes force cross-leg accumulation
    "random_legs_with_shared_documents": (_random_legs(11, 2, 30, 12), 24),
    # leg a's rank i ties leg b's rank i: every score is a tie group of two
    "two_legs_tied_at_every_rank": (
        [[40, 7, 33, 2, 19, 28], [5, 41, 3, 30, 20, 11]], 12),
    "short_leg": (_random_legs(12, 1, 30, 12) + [[4, 17]], 14),
    "empty_leg": (_random_legs(13, 1, 30, 10) + [[]], 10),
    "cut_smaller_than_the_union": (_random_legs(14, 2, 30, 12), 5),
    "three_legs": (_random_legs(15, 3, 20, 8), 24),
}


@pytest.mark.parametrize("case", sorted(SERVED_FUSE_CASES))
def test_served_host_fuse_matches_oracle(case):
    """What the serving path runs (`rrf_fuse_ranked`, Python floats over
    the keys the legs returned) against the NumPy oracle, fed the same
    legs as the rows of one -1-padded array: same documents in the same
    order, scores within float32's rounding of the float64 sums."""
    legs, k = SERVED_FUSE_CASES[case]
    served = rrf_fuse_ranked(legs, k, 60)
    width = max(len(leg) for leg in legs)
    padded = np.full((len(legs), width), -1, np.int32)
    for i, leg in enumerate(legs):
        padded[i, :len(leg)] = leg
    rows = tuple(padded[i][None, :] for i in range(len(legs)))
    union = set().union(*legs)
    assert len(served) == min(k, len(union))
    assert len({doc for doc, _ in served}) == len(served)
    s, d = (a[0] for a in rrf_fuse_host(rows, k, 60))
    assert d[:len(served)].tolist() == [doc for doc, _ in served]
    assert (d[len(served):] == -1).all()
    np.testing.assert_allclose(
        s[:len(served)], [sc for _, sc in served], rtol=1e-6)
    if case == "two_legs_tied_at_every_rank":
        # each tie group comes out lower doc first
        a, b = legs
        assert [doc for doc, _ in served] == [
            doc for pair in zip(a, b) for doc in sorted(pair)]
    # string keys (legs without integer identity) fuse by the same rule
    by_id = rrf_fuse_ranked(
        [[f"{doc:04d}" for doc in leg] for leg in legs], k, 60)
    assert by_id == [(f"{doc:04d}", sc) for doc, sc in served]


class TestHybridServing:
    def test_host_fused_path_engaged(self, service):
        before = dict(service.rrf_stats)
        r = service.search(hybrid_body(seed=1))
        assert r["hits"]["hits"], "hybrid search returned no hits"
        assert service.rrf_stats["host_fused"] == before["host_fused"] + 1
        assert service.rrf_stats["device_fused"] == before["device_fused"] == 0
        # per-leg breakdown recorded for bench reporting
        assert service.rrf_stats["bm25_leg_ms"] > 0
        assert service.rrf_stats["knn_leg_ms"] > 0

    def test_same_members_as_host_fallback_backend(self, service):
        svc_np = make_service(backend="numpy", seed=0)
        try:
            body = hybrid_body(seed=2, size=10)
            rj = service.search(body)
            rn = svc_np.search(body)
            jd = {h["_id"]: round(h["_score"], 6) for h in rj["hits"]["hits"]}
            nd = {h["_id"]: round(h["_score"], 6) for h in rn["hits"]["hits"]}
            # same fused scores per doc; ordering may differ only on
            # exact ties (the batcher legs' ties break on (segment, doc),
            # the NumPy backend's thread-pool legs' on the _id string)
            assert jd == nd
        finally:
            svc_np.close()

    def test_rank_rrf_top_level_api_matches_retriever(self, service):
        body = hybrid_body(seed=3)
        rrf = body["retriever"]["rrf"]
        std, knn = rrf["retrievers"]
        rank_body = {
            "query": std["standard"]["query"],
            "knn": knn["knn"],
            "rank": {"rrf": {"rank_constant": rrf["rank_constant"]}},
            "size": 10,
            "_source": False,
        }
        r1 = service.search(body)
        r2 = service.search(rank_body)
        assert [h["_id"] for h in r1["hits"]["hits"]] == [
            h["_id"] for h in r2["hits"]["hits"]
        ]

    def test_legs_overlap_in_flight(self, service, monkeypatch):
        """Both hybrid legs are launched before either is collected: a
        worker that finds both queued drains them as one batch of two
        groups, and the counter reads both. The one worker is held at
        its dequeue until the request thread has submitted the second
        leg, so the batch is deterministic."""
        want = [
            h["_id"]
            for h in service.search(hybrid_body(seed=10))["hits"]["hits"]
        ]
        b = QueryBatcher(workers=1)
        monkeypatch.setattr(service, "_batcher", b)
        admit = b._admit_job

        def slow_admit(job):
            time.sleep(0.05)
            return admit(job)

        monkeypatch.setattr(b, "_admit_job", slow_admit)
        try:
            got = service.search(hybrid_body(seed=10))
            assert [h["_id"] for h in got["hits"]["hits"]] == want
            assert b.stats["max_batch_seen"] == 2
            assert b.stats["groups_launched_together"] == 2
            # the worker leaves a group's class after it has woken the
            # group's waiter, and the host fuse no longer keeps the
            # request ~1 ms behind that: give the worker its moment
            deadline = time.monotonic() + 5.0
            while any(b._inflight.values()) and time.monotonic() < deadline:
                time.sleep(0.005)
            assert all(n == 0 for n in b._inflight.values())
        finally:
            b.close()


class TestAsyncSubmission:
    def test_submit_nowait_multiple_in_flight(self, service):
        ex = service._executor(service.local_shard(0))
        assert isinstance(ex, JaxExecutor)
        q = dsl.parse_query({"match": {"body": "alpha beta"}})
        plan = extract_match_plan(q, service.mappings, service.analysis, 10_000)
        jobs = [
            service._batcher.submit_nowait(ex, plan, 5, query=q)
            for _ in range(4)
        ]
        results = [QueryBatcher.wait(j) for j in jobs]
        assert all(j.done() for j in jobs)
        first = [(h.doc_id, h.score) for h in results[0].hits]
        for td in results[1:]:
            assert [(h.doc_id, h.score) for h in td.hits] == first

    def test_submit_nowait_overflow_is_429(self, service):
        ex = service._executor(service.local_shard(0))
        q = dsl.parse_query({"match": {"body": "alpha"}})
        plan = extract_match_plan(q, service.mappings, service.analysis, 10_000)
        tiny = QueryBatcher(workers=1, queue_capacity=2)
        try:
            jobs, rejected = [], 0
            for _ in range(300):
                try:
                    jobs.append(tiny.submit_nowait(ex, plan, 5, query=q))
                except EsRejectedExecutionError as e:
                    rejected += 1
                    assert e.status == 429
            assert rejected > 0
            assert tiny.stats["rejected"] == rejected
            for j in jobs:  # accepted jobs still complete
                QueryBatcher.wait(j, timeout=30)
        finally:
            tiny.close()
