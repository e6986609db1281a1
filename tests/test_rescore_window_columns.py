"""A rescore window travels as columns from the first stage's download
to the page (PR 58): `TopDocs.of_columns` at `_collect_match_group`,
`rescorer.build_plan` / `apply_perm_to_topdocs` over the arrays, and
`TopDocs.head` making `Hit`s for the page alone.

What is held here, on the CPU: the page a columnar window answers is
the page the PARENT's `Hit` path answered (a `Hit` a candidate at the
collect, the permutation applied `Hit` by `Hit`, the page sliced from
the list: kept below as the reference), bit for bit; the three ways out
that keep the first stage return its first page; and `Hit`s are built
for the page, counted in `rescore.hits_built`."""

import numpy as np
import pytest

from elasticsearch_tpu.cluster.indices import IndexService
from elasticsearch_tpu.common.faults import faults
from elasticsearch_tpu.models import rerank as rerank_model
from elasticsearch_tpu.search import batcher, executor_jax, rescorer
from elasticsearch_tpu.search.executor import Hit, TopDocs

# the small byte-token index of the deployment's own tests: four bodies,
# so a question's BM25 scores tie in groups of a quarter
from test_colbert_rescore_deployment import DIMS, MAPPINGS, WORDS, rescore


def make_service(name, docs, batches=1):
    """One jax shard over `docs` [(body, token rows)], a segment a batch."""
    svc = IndexService(
        name, settings={"number_of_shards": 1, "search.backend": "jax"},
        mappings_json=MAPPINGS)
    per = -(-len(docs) // batches)
    for i, (body, tok) in enumerate(docs):
        svc.index_doc(f"{i:03d}", {"body": body, "tok": tok})
        if (i + 1) % per == 0 or i + 1 == len(docs):
            svc.refresh()
    return svc


def mixed_docs(n=200, seed=5):
    rng = np.random.default_rng(seed)
    return [(WORDS[i % 4],
             rng.integers(-127, 128, size=(1 + i % 5, DIMS)).tolist())
            for i in range(n)]


def tied_docs(n=120):
    """One body (every BM25 score ties exactly); the best token rows
    belong to the passages of the HIGHEST ids."""
    return [("alpha beta", [[i] * DIMS]) for i in range(n)]


QV = np.random.default_rng(58).normal(size=(3, DIMS)).round(3).tolist()
ONES = [[1.0] * DIMS]

# name -> (docs, segments, body, asked at the shard's own entry)
CASES = {
    # groups of 50 tied first-stage scores inside a window of 100
    "interior_ties": (mixed_docs, 1, {
        "query": {"match": {"body": "alpha"}}, "size": 10,
        "rescore": rescore(QV, 100, qw=1.0, rw=0.0)}, False),
    # 120 tied passages, a window of 40: the group runs past the fetch
    "tie_group_split_by_the_cut": (tied_docs, 1, {
        "query": {"match": {"body": "alpha"}}, "size": 5,
        "rescore": rescore(ONES, 40)}, False),
    # 50 passages hold `delta`
    "fewer_matches_than_the_window": (mixed_docs, 1, {
        "query": {"match": {"body": "delta"}}, "size": 10,
        "rescore": rescore(QV, 100)}, False),
    # maxsim 8 x id times -2e36 passes float32's range from id 22 on:
    # the ranking ends at the first score that is not finite
    "a_rescored_score_of_minus_infinity": (tied_docs, 1, {
        "query": {"match": {"body": "alpha"}}, "size": 30,
        "rescore": rescore(ONES, 40, rw=-2e36)}, False),
    # a `bool` first stage (`Hit`s, turned into columns once) of 60
    # candidates under a window of 40: the tail keeps its order
    "window_below_the_candidates": (mixed_docs, 1, {
        "query": {"bool": {"should": [{"match": {"body": "alpha"}},
                                      {"match": {"body": "gamma"}}]}},
        "size": 60, "rescore": rescore(QV, 40, qw=0.5, rw=1e-4)}, True),
    "two_segments": (mixed_docs, 2, {
        "query": {"match": {"body": "alpha gamma"}}, "size": 10,
        "rescore": rescore(QV, 100, qw=0.5, rw=1e-4)}, False),
}


class ParentTopDocs(TopDocs):
    """What `_collect_match_group` handed a window job at the parent:
    a `Hit` a candidate, made at the collect."""

    @classmethod
    def of_columns(cls, total, reader, scores, segments, docs,
                   relation="eq"):
        hits = [
            Hit(score=float(s), segment=int(si), local_doc=int(d),
                doc_id=reader.segments[int(si)].doc_ids[int(d)])
            for s, si, d in zip(scores, segments, docs)
        ]
        return TopDocs(total=total, hits=hits,
                       max_score=hits[0].score if hits else None,
                       relation=relation)


def parent_apply_perm(td, scores, perm):
    """`rescorer.apply_perm_to_topdocs` as the parent had it: the loop."""
    hits = []
    for s, p in zip(scores, perm):
        if not np.isfinite(s):
            break
        h = td.hits[int(p)]
        hits.append(Hit(score=float(s), segment=h.segment,
                        local_doc=h.local_doc, doc_id=h.doc_id))
    return TopDocs(total=td.total, hits=hits,
                   max_score=hits[0].score if hits else None,
                   relation=td.relation)


def answer(svc, body, shard):
    if shard:
        got = svc.shard_search_local(0, dict(body))
        return {k: got[k] for k in ("total", "relation", "max_score", "hits")}
    return svc.search(dict(body))["hits"]


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_columnar_windows_page_is_the_hit_paths_page(case, monkeypatch):
    docs, segments, body, shard = CASES[case]
    svc = make_service(f"cols-{case.replace('_', '-')}", docs(), segments)
    try:
        assert len(svc._executor(svc.local_shard(0)).reader.segments) == \
            segments
        before = rerank_model.stats_snapshot()
        got = answer(svc, body, shard)
        after = rerank_model.stats_snapshot()
        page = got["hits"]
        assert page and after["device_rescores"] == \
            before["device_rescores"] + 1
        # `Hit`s were made for the page and for nothing else
        assert after["hits_built"] - before["hits_built"] == len(page)
        if case == "tie_group_split_by_the_cut":
            assert after["window_ties_refilled"] == \
                before["window_ties_refilled"] + 1
            assert [h["_id"] for h in page] == [
                "039", "038", "037", "036", "035"]
        if case == "a_rescored_score_of_minus_infinity":
            assert len(page) == 22 and page[0]["_score"] == 0.0
        if case == "fewer_matches_than_the_window":
            assert got["total"] in (50, {"value": 50, "relation": "eq"})
        if case == "window_below_the_candidates":
            # below the window: first-stage scores, first-stage order
            tail = [h["_score"] for h in page[40:]]
            assert len(page) == 60 and tail == sorted(tail, reverse=True)
            assert min(h["_score"] for h in page[:40]) < tail[0]
        with monkeypatch.context() as m:
            m.setattr(batcher, "TopDocs", ParentTopDocs)
            m.setattr(rescorer, "apply_perm_to_topdocs", parent_apply_perm)
            want = answer(svc, body, shard)
        assert got == want
    finally:
        svc.close()


@pytest.mark.parametrize("way_out", ["mode_off", "score_fault",
                                     "missing_column"])
def test_a_way_out_returns_the_first_stages_first_page(way_out, monkeypatch):
    svc = make_service(f"cols-out-{way_out.replace('_', '-')}",
                       mixed_docs(), 2)
    try:
        query = {"match": {"body": "alpha beta"}}
        body = {"query": query, "size": 10,
                "rescore": rescore(QV, 100, qw=0.0, rw=1.0)}
        plain = svc.search({"query": query, "size": 10})["hits"]
        assert svc.search(dict(body))["hits"]["hits"] != plain["hits"]
        counter = "fallbacks" if way_out == "score_fault" else "skipped"
        if way_out == "mode_off":
            monkeypatch.setenv("ES_TPU_RERANK", "off")
        elif way_out == "score_fault":
            faults.configure(
                {"rules": [{"site": "rerank.score", "kind": "error"}]})
        else:
            monkeypatch.setattr(executor_jax.JaxExecutor, "rerank_column",
                                lambda self, model: None)
        before = rerank_model.stats_snapshot()
        got = svc.search(dict(body))
        after = rerank_model.stats_snapshot()
        assert got["hits"] == plain  # ids, order, scores, total, max_score
        assert got["_shards"]["failed"] == 0
        assert after[counter] == before[counter] + 1
        assert after["first_stage_kept"] == before["first_stage_kept"] + 1
        assert after["hits_built"] - before["hits_built"] == 10
    finally:
        faults.clear()
        svc.close()


def test_a_leg_cut_as_a_window_is_read_hit_by_hit_and_counts_them():
    """A retriever's `standard` leg that feeds a rescore asks the shard
    for its whole page cut as a window (`_exact_window`); where a
    batcher job serves it the page comes down as columns, the fetch
    reads `.hits`, and what that built is counted."""
    svc = make_service("cols-leg", tied_docs())
    try:
        leg = {"query": {"match": {"body": "alpha"}}, "size": 40,
               "_source": False}
        plain = svc.shard_search_local(0, dict(leg))
        before = rerank_model.stats_snapshot()
        got = svc.shard_search_local(0, {**leg, "_exact_window": True})
        after = rerank_model.stats_snapshot()
        assert got == plain
        assert [h["_id"] for h in got["hits"]] == [
            f"{i:03d}" for i in range(40)]
        assert after["hits_built"] - before["hits_built"] == 40
        assert after["requests"] == before["requests"]
    finally:
        svc.close()


def test_topdocs_of_columns_reads_as_a_list_would():
    """`len`, `head`, `.hits` (built once) and `as_columns` of both
    forms; an empty window has no best score."""
    svc = make_service("cols-unit", mixed_docs(20), 2)
    try:
        reader = svc._executor(svc.local_shard(0)).reader
        scores = np.array([3.5, 2.25, 2.25, 1.0], np.float32)
        segs = np.array([1, 0, 1, 0], np.int32)
        docs = np.array([4, 2, 0, 9], np.int32)
        td = TopDocs.of_columns(7, reader, scores, segs, docs, "gte")
        assert (len(td), td.total, td.relation, td.max_score) == (
            4, 7, "gte", 3.5)
        before = rerank_model.stats_snapshot()["hits_built"]
        head = td.head(2)
        assert [(h.score, h.segment, h.local_doc, h.doc_id)
                for h in head.hits] == [
            (3.5, 1, 4, reader.segments[1].doc_ids[4]),
            (2.25, 0, 2, reader.segments[0].doc_ids[2])]
        assert (head.total, head.relation, head.max_score) == (7, "gte", 3.5)
        assert all(type(h.score) is float and type(h.segment) is int
                   and type(h.local_doc) is int for h in head.hits)
        assert rerank_model.stats_snapshot()["hits_built"] == before + 2
        hits = td.hits
        assert td.hits is hits and len(hits) == 4 and len(td) == 4
        assert rerank_model.stats_snapshot()["hits_built"] == before + 6
        assert td.as_columns(reader) is td
        # a list turned into columns once, and back
        listed = TopDocs(total=7, hits=hits, max_score=3.5, relation="gte")
        assert len(listed) == 4 and listed.head(3).hits == hits[:3]
        cols = listed.as_columns(reader)
        assert all(np.array_equal(a, b) and a.dtype == b.dtype
                   for a, b in zip(cols.cols, (scores, segs, docs)))
        assert cols.hits == hits
        empty = TopDocs.of_columns(0, reader, scores[:0], segs[:0], docs[:0])
        assert len(empty) == 0 and empty.max_score is None
        assert empty.hits == [] and empty.head(10).hits == []
    finally:
        svc.close()
