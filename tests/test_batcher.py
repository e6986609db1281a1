"""Cross-request micro-batching dispatcher (search/batcher.py).

The north-star serving idea: concurrent _search requests that reduce to
flat weighted-term plans share ONE [B, T, 128] kernel launch. These
tests check (a) batched results are hit-for-hit identical to the
unbatched executor path, (b) concurrent submissions actually coalesce,
(c) the WAND group (track_total_hits: false) returns the same top-k.
"""

import threading

import numpy as np
import pytest

from elasticsearch_tpu.analysis import AnalysisRegistry
from elasticsearch_tpu.cluster.indices import IndexService
from elasticsearch_tpu.index.engine import ShardEngine
from elasticsearch_tpu.index.mapping import Mappings
from elasticsearch_tpu.search import dsl
from elasticsearch_tpu.search.batcher import QueryBatcher, extract_match_plan
from elasticsearch_tpu.search.executor_jax import JaxExecutor

WORDS = [
    "alpha", "beta", "gamma", "delta", "epsilon", "zeta", "eta", "theta",
    "iota", "kappa", "lam", "mu", "nu", "xi", "omicron", "pi",
]


def make_service(n_docs=300, n_shards=1, seed=0):
    rng = np.random.default_rng(seed)
    svc = IndexService(
        "b1",
        settings={"number_of_shards": n_shards, "search.backend": "jax"},
        mappings_json={"properties": {"body": {"type": "text"}}},
    )
    for i in range(n_docs):
        k = int(rng.integers(3, 12))
        words = rng.choice(WORDS, size=k, p=_zipf(len(WORDS)))
        svc.index_doc(str(i), {"body": " ".join(words)})
    svc.refresh()
    return svc


def _zipf(n):
    w = 1.0 / np.arange(1, n + 1)
    return w / w.sum()


@pytest.fixture(scope="module")
def service():
    return make_service()


class TestPlanExtraction:
    def test_match_query_plan(self, service):
        q = dsl.parse_query({"match": {"body": "alpha beta"}})
        plan = extract_match_plan(q, service.mappings, service.analysis, False)
        assert plan is not None
        assert plan.terms == ("alpha", "beta") and plan.msm == 1

    def test_and_operator_msm(self, service):
        q = dsl.parse_query(
            {"match": {"body": {"query": "alpha beta", "operator": "and"}}}
        )
        plan = extract_match_plan(q, service.mappings, service.analysis, False)
        assert plan.msm == 2

    def test_non_match_not_planned(self, service):
        q = dsl.parse_query({"bool": {"must": [{"match": {"body": "alpha"}}]}})
        assert (
            extract_match_plan(q, service.mappings, service.analysis, False) is None
        )

    def test_wand_eligibility(self, service):
        q = dsl.parse_query({"match": {"body": "alpha beta"}})
        # exact totals requested → no pruning
        assert not extract_match_plan(
            q, service.mappings, service.analysis, True
        ).wand_ok
        # uncounted and capped (the ES default of 10_000) → pruning ok
        assert extract_match_plan(
            q, service.mappings, service.analysis, False
        ).wand_ok
        assert extract_match_plan(
            q, service.mappings, service.analysis, 10_000
        ).wand_ok
        qa = dsl.parse_query(
            {"match": {"body": {"query": "alpha beta", "operator": "and"}}}
        )
        # conjunctions need match counts → no pruning
        assert not extract_match_plan(
            qa, service.mappings, service.analysis, False
        ).wand_ok


class TestBatchedParity:
    def test_single_request_matches_executor_path(self, service):
        body = {"query": {"match": {"body": "alpha gamma"}}, "size": 7}
        batched = service.search(body)
        # force the unbatched path by adding min_score=0 (not batchable)
        unbatched = service.search({**body, "min_score": 0})
        bh = [(h["_id"], round(h["_score"], 4)) for h in batched["hits"]["hits"]]
        uh = [(h["_id"], round(h["_score"], 4)) for h in unbatched["hits"]["hits"]]
        assert bh == uh
        assert (
            batched["hits"]["total"]["value"] == unbatched["hits"]["total"]["value"]
        )

    def test_and_operator_parity(self, service):
        body = {
            "query": {"match": {"body": {"query": "alpha beta", "operator": "and"}}},
            "size": 5,
        }
        batched = service.search(body)
        unbatched = service.search({**body, "min_score": 0})
        assert [h["_id"] for h in batched["hits"]["hits"]] == [
            h["_id"] for h in unbatched["hits"]["hits"]
        ]

    def test_multi_shard_merge(self):
        svc = make_service(n_docs=200, n_shards=3, seed=1)
        body = {"query": {"match": {"body": "alpha"}}, "size": 10}
        batched = svc.search(body)
        unbatched = svc.search({**body, "min_score": 0})
        assert [h["_id"] for h in batched["hits"]["hits"]] == [
            h["_id"] for h in unbatched["hits"]["hits"]
        ]

    def test_wand_group_same_topk(self, service):
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
        assert "total" not in wand["hits"]

    def test_deleted_docs_respected(self):
        svc = make_service(n_docs=50, seed=2)
        top = svc.search({"query": {"match": {"body": "alpha"}}, "size": 1})
        victim = top["hits"]["hits"][0]["_id"]
        svc.delete_doc(victim)
        svc.refresh()
        after = svc.search({"query": {"match": {"body": "alpha"}}, "size": 50})
        assert victim not in [h["_id"] for h in after["hits"]["hits"]]


class TestConcurrentCoalescing:
    def test_concurrent_requests_share_launches(self, service):
        # warm the compile caches first so the batch window isn't skewed
        service.search({"query": {"match": {"body": "alpha"}}, "size": 5})
        batcher = service._batcher
        assert batcher is not None
        errs = []

        def one(results, i):
            try:
                results[i] = service.search(
                    {"query": {"match": {"body": WORDS[i % 8]}}, "size": 5}
                )
            except Exception as e:  # pragma: no cover
                errs.append(e)

        # whether two of a burst's jobs meet in the queue is the
        # scheduler's to say (on a loaded machine the threads start one
        # by one and six workers take a job each): a few bursts, until
        # one launch has carried more than one job
        for _ in range(8):
            base_jobs = batcher.stats["jobs"]
            results = {}
            threads = [
                threading.Thread(target=one, args=(results, i))
                for i in range(24)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            assert not errs
            assert len(results) == 24
            assert batcher.stats["jobs"] - base_jobs == 24
            if batcher.stats["max_batch_seen"] > 1:
                break
        assert batcher.stats["max_batch_seen"] > 1


class TestDirectBatcher:
    def test_batch_of_plans_matches_individual(self, service):
        ex = service._executor(service.shards[0])
        assert isinstance(ex, JaxExecutor)
        batcher = QueryBatcher()
        plans = [
            extract_match_plan(
                dsl.parse_query({"match": {"body": w}}),
                service.mappings,
                service.analysis,
                False,
            )
            for w in WORDS[:6]
        ]
        jobs = [batcher.submit(ex, p, 10) for p in plans]
        tds = [QueryBatcher.wait(j) for j in jobs]
        for p, td in zip(plans, tds):
            ref = ex.search(
                dsl.MatchQuery(field="body", query=p.terms[0]), size=10
            )
            assert [(h.doc_id, round(h.score, 4)) for h in td.hits] == [
                (h.doc_id, round(h.score, 4)) for h in ref.hits
            ]
            assert td.total == ref.total
        batcher.close()


class TestFusedPath:
    def test_zero_boost_match_leaves_the_fused_kernel(self, monkeypatch):
        """The fused plan's weights carry the count flag in their sign,
        so a `match` whose boost is 0 is scored by the chunked path: an
        `and` of zero-weight terms still matches by count, with the
        unbatched executor's page, scores of exactly 0.0 and total."""
        from elasticsearch_tpu.search import executor_jax

        monkeypatch.setattr(executor_jax, "FUSED_MIN_DOCS", 10)
        svc = make_service(n_docs=200, seed=3)
        try:
            body = {"query": {"match": {"body": {
                "query": "alpha beta", "operator": "and", "boost": 0}}},
                "size": 10, "track_total_hits": True}
            stats = svc._batcher.stats
            before = dict(stats)
            served = svc.search(body)
            assert stats["fused_jobs"] == before["fused_jobs"]
            assert stats["fused_overflow_jobs"] == (
                before["fused_overflow_jobs"] + 1)
            unbatched = svc.search({**body, "min_score": -1})
            assert served["hits"]["total"]["value"] > 0
            assert served["hits"]["total"] == unbatched["hits"]["total"]
            assert [(h["_id"], h["_score"]) for h in served["hits"]["hits"]
                    ] == [(h["_id"], 0.0)
                          for h in unbatched["hits"]["hits"]]
        finally:
            svc.close()

    def test_fused_parity_with_unbatched(self, monkeypatch):
        """Force the fused single-round-trip scorer (normally gated to
        large segments) and check hit-for-hit parity + exact totals."""
        from elasticsearch_tpu.search import executor_jax

        monkeypatch.setattr(executor_jax, "FUSED_MIN_DOCS", 10)
        svc = make_service(n_docs=400, seed=7)
        try:
            for text in ["alpha", "alpha beta", "gamma delta epsilon", "mu nu"]:
                body = {"query": {"match": {"body": text}}, "size": 10}
                fused = svc.search(body)
                unbatched = svc.search({**body, "min_score": 0})
                assert [
                    (h["_id"], round(h["_score"], 4))
                    for h in fused["hits"]["hits"]
                ] == [
                    (h["_id"], round(h["_score"], 4))
                    for h in unbatched["hits"]["hits"]
                ], text
                assert (
                    fused["hits"]["total"]["value"]
                    == unbatched["hits"]["total"]["value"]
                )
            assert svc._batcher.stats["fused_jobs"] > 0
            # operator=and goes through the with_cnt variant
            body = {
                "query": {
                    "match": {"body": {"query": "alpha beta", "operator": "and"}}
                },
                "size": 10,
            }
            fused = svc.search(body)
            unbatched = svc.search({**body, "min_score": 0})
            assert [h["_id"] for h in fused["hits"]["hits"]] == [
                h["_id"] for h in unbatched["hits"]["hits"]
            ]
            # deletes respected through the fused live mask
            top = svc.search({"query": {"match": {"body": "alpha"}}, "size": 1})
            victim = top["hits"]["hits"][0]["_id"]
            svc.delete_doc(victim)
            svc.refresh()
            after = svc.search({"query": {"match": {"body": "alpha"}}, "size": 400})
            assert victim not in [h["_id"] for h in after["hits"]["hits"]]
        finally:
            svc.close()
