"""The filtered vector-search deployment (big-ann-benchmarks' filter
track; `benchmarks/configs/yfcc10m-filtered-knn.json`) at a small size:
`knn` sections over a `byte` `dense_vector` field with a `filter` of tag
terms, over a few tens of thousands of seeded rows of the benchmark's
own corpus builder (`corpora/byte_vectors_tags.py`), served over HTTP
through the batcher's `knn` family and held to the benchmark's own
plain reference (`references/l2_filtered_knn.py`) by the benchmark's own
rule (`benchmarks/compare.py`, `exact`: ids tie group by tie group,
scores within 1e-6, `hits.total` equal).

30,000 rows: the commonest tag is carried by 30% of them, 9,000 rows in
71 tiles, more than one trip of the mask program takes
(`scoring.FILTER_CHUNK` = 64), and the vocabulary is whole (200,386
tags), so the rarest tags are in one row. The tags in 1,024 rows or more
(`dense_row_min_df`'s floor) hold a bit row of the segment, which the
mask reads instead of scattering their tiles; every other tag is
scattered, as every tag is where the HBM room refuses the rows.
"""

import http.client
import json
import os
import sys

import numpy as np
import pytest

from elasticsearch_tpu.common import tracing
from elasticsearch_tpu.common.faults import faults
from elasticsearch_tpu.ops import scoring
from elasticsearch_tpu.search import batcher as batcher_mod

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from compare import compare_all, compare_one, reference_body  # noqa: E402
from plugins import load_json, load_plugin  # noqa: E402
from run import place_segment  # noqa: E402

DOCS, SEED, N_BODIES = 30_000, 5, 24
JOB_SPANS = ["plan", "queue_wait", "dispatch", "inflight", "collect",
             "wake", "fetch"]


def call(port: int, method: str, path: str, body=None):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=300)
    try:
        conn.request(method, path,
                     body=None if body is None else json.dumps(body).encode(),
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        payload = resp.read()
        return resp.status, json.loads(payload) if payload else None
    finally:
        conn.close()


def ok(port: int, method: str, path: str, body=None) -> dict:
    status, payload = call(port, method, path, body)
    assert status in (200, 201), (status, payload)
    return payload


class Deployment:
    """One server holding the corpus, its plain reference, the bodies
    the cases pick from, and a second index over the same segment whose
    deletes a case may make."""

    def __init__(self):
        from elasticsearch_tpu.rest.server import ElasticsearchTpuServer

        self.config = load_json("configs", "yfcc10m-filtered-knn.json")
        corpus = load_plugin(
            "corpora", self.config["corpus"]["builder"]).build(
                self.config, SEED, DOCS)
        self.corpus = corpus
        self.server = ElasticsearchTpuServer(port=0)
        self.server.start_background()
        self.port = self.server.port
        self.index = self.config["index"]
        for index in (self.index, "yfcc-deletes"):
            ok(self.port, "PUT", f"/{index}", {
                "settings": self.config["settings"],
                "mappings": corpus["mappings"]})
            place_segment(self.server.cluster.indices[index],
                          corpus["segment"])
        self.ref = load_plugin(
            "references", self.config["reference"]).Reference(
                corpus["reference"], self.config)
        gen = load_plugin("bodies", self.config["body"]["generator"])
        self.bodies = [json.loads(b) for b in gen.make(
            corpus["body_context"], self.config["body"]["args"],
            np.random.default_rng([39, 9]), N_BODIES)]
        self.pf = corpus["segment"].postings["tags"]
        df = np.asarray(self.pf.term_df)
        self.by_df = np.argsort(-df, kind="stable")  # commonest first
        self.df = df
        # the tags that hold a bit row of the segment (30,000 rows:
        # dense_row_min_df is its floor)
        self.on_rows = {self.tag(t) for t in np.flatnonzero(df >= 1024)}

    def tag(self, t: int) -> str:
        return self.pf.terms[int(t)]

    def scattered_tiles(self, tags) -> int:
        """Postings tiles a mask launch scatters for `tags`: those of
        the tags without a bit row."""
        return sum(int(self.pf.term_tile_count[int(t[1:])])
                   for t in tags if t not in self.on_rows)

    def body(self, tags, vector=None, **knn) -> dict:
        section = dict(self.bodies[0]["knn"])
        if vector is not None:
            section["query_vector"] = vector
        section["filter"] = {"bool": {"filter": [
            {"term": {"tags": t}} for t in tags]}}
        section.update(knn)
        return {"knn": section, "size": 10, "_source": False}

    def search(self, body: dict, index=None) -> dict:
        return ok(self.port, "POST", f"/{index or self.index}/_search", body)

    def held(self, body: dict, served: dict) -> dict:
        g = self.config["guarantees"]
        (expected,) = self.ref.answer_many([reference_body(g["rule"], body)])
        got = compare_one(g["rule"], g["score_rtol"], body, served, expected)
        assert got["page_ok"], got["why"]
        assert got["total_ok"], (served["hits"]["total"],
                                 expected["hits"]["total"])
        assert got["score_rel"] <= g["score_rtol"], got["score_rel"]
        return expected

    def node(self) -> dict:
        return next(iter(
            ok(self.port, "GET", "/_nodes/stats")["nodes"].values()))

    def last_trace(self) -> dict:
        return ok(self.port, "GET", "/_internal/traces?n=1")["traces"][-1]


@pytest.fixture(scope="module")
def dep():
    d = Deployment()
    yield d
    d.server.close()


# ---- the deployment's own requests, and the shapes of a filter ----------

@pytest.mark.parametrize("i", range(8))
def test_generated_request_over_http_is_the_plain_references(dep, i):
    body = dep.bodies[i]
    clauses = body["knn"]["filter"]["bool"]["filter"]
    assert 1 <= len(clauses) <= 2 and len(body["knn"]["query_vector"]) == 192
    expected = dep.held(body, dep.search(body))
    assert expected["hits"]["total"]["value"] >= 1  # its own bag passes


def test_generated_requests_hold_one_tag_and_two_tag_filters(dep):
    counts = {len(b["knn"]["filter"]["bool"]["filter"]) for b in dep.bodies}
    assert counts == {1, 2}


def rows_passing(dep, tags) -> int:
    lists = dep.ref._rows_with({int(t[1:]) for t in tags})
    rows = None
    for t in tags:
        r = lists[int(t[1:])]
        rows = r if rows is None else np.intersect1d(rows, r)
    return len(rows)


def filter_cases(dep) -> dict:
    """name -> tags: filters that pass no row, one row, fewer than k,
    most of what any filter can, and a tag of more tiles than one trip
    of the mask program takes."""
    commonest, second = dep.tag(dep.by_df[0]), dep.tag(dep.by_df[1])
    single = next(dep.tag(t) for t in dep.by_df[::-1] if dep.df[t] == 1)
    few = next(dep.tag(t) for t in dep.by_df if 2 <= dep.df[t] <= 9)
    disjoint = next(
        [dep.tag(a), dep.tag(b)]
        for a in dep.by_df[2000:2100] for b in dep.by_df[2100:2200]
        if rows_passing(dep, [dep.tag(a), dep.tag(b)]) == 0)
    return {
        "no_such_tag": ["t999999"],
        "empty_intersection": disjoint,
        "one_row": [single],
        "one_row_of_two_tags": next(
            [dep.tag(t), commonest] for t in dep.by_df[::-1][:400]
            if dep.df[t] == 1
            and rows_passing(dep, [dep.tag(t), commonest]) == 1),
        "fewer_than_k": [few],
        "commonest_tag_many_trips": [commonest],
        "two_common_tags": [commonest, second],
    }


CASES = ["no_such_tag", "empty_intersection", "one_row",
         "one_row_of_two_tags", "fewer_than_k", "commonest_tag_many_trips",
         "two_common_tags"]


@pytest.mark.parametrize("case", CASES)
def test_filter_shape_over_http_is_the_plain_references(dep, case):
    tags = filter_cases(dep)[case]
    body = dep.body(tags)
    served = dep.search(body)
    dep.held(body, served)
    passing = rows_passing(dep, tags)
    hits = served["hits"]["hits"]
    assert len(hits) == min(10, passing)
    assert served["hits"]["total"] == {"value": min(10, passing),
                                       "relation": "eq"}
    if case in ("no_such_tag", "empty_intersection"):
        assert passing == 0 and served["hits"]["max_score"] is None
    if case.startswith("one_row"):
        assert passing == 1
    if case == "fewer_than_k":
        assert 2 <= passing <= 9
    if case == "commonest_tag_many_trips":
        tiles = int(dep.pf.term_tile_count[dep.by_df[0]])
        assert tiles > scoring.FILTER_CHUNK and passing > 0.25 * DOCS


def test_terms_clause_counts_once_however_many_of_its_tags_a_row_holds(dep):
    """`terms` is any-of: a row holding both tags of the clause passes
    once, and a second, one-tag clause still has to hold."""
    a, b, c = (dep.tag(t) for t in dep.by_df[:3])
    section = dict(dep.bodies[1]["knn"])
    section["filter"] = {"bool": {"filter": [
        {"terms": {"tags": [a, b]}}, {"term": {"tags": c}}]}}
    served = dep.search({"knn": section, "size": 10, "_source": False})
    lists = dep.ref._rows_with({int(t[1:]) for t in (a, b, c)})
    rows = np.intersect1d(np.union1d(lists[int(a[1:])], lists[int(b[1:])]),
                          lists[int(c[1:])])
    q = np.asarray(section["query_vector"], np.int64) + 128
    d2 = ((dep.corpus["reference"]["vectors"][rows].astype(np.int64)
           - q) ** 2).sum(axis=1)
    order = np.lexsort((rows, d2))[:10]
    assert [h["_id"] for h in served["hits"]["hits"]] == [
        str(int(rows[i])) for i in order]
    assert served["hits"]["hits"][0]["_score"] == pytest.approx(
        float(np.float32(1.0 / (1.0 + d2[order[0]]))), rel=1e-6)


def test_deleted_rows_do_not_pass(dep):
    """The tag holds a bit row, built once from the segment's postings:
    deletes are ANDed in from the live plane every launch."""
    assert dep.tag(dep.by_df[3]) in dep.on_rows
    body = dep.body([dep.tag(dep.by_df[3])])
    index = "yfcc-deletes"
    first = dep.search(body, index)
    dep.held(body, first)
    gone = [int(h["_id"]) for h in first["hits"]["hits"][:3]]
    eng = dep.server.cluster.indices[index].shards[0]
    live = np.ones(DOCS, bool)
    live[gone] = False
    eng.live_docs = [live]
    eng.change_generation += 1
    served = dep.search(body, index)
    wide = dep.body([dep.tag(dep.by_df[3])], k=20, num_candidates=100)
    wide["size"] = 20
    (expected,) = dep.ref.answer_many([wide])
    want = [h for h in expected["hits"]["hits"]
            if int(h["_id"]) not in gone][:10]
    assert [h["_id"] for h in served["hits"]["hits"]] == [
        h["_id"] for h in want]
    assert not {h["_id"] for h in served["hits"]["hits"]} & set(map(str, gone))


# ---- the commonest tags' bit rows ------------------------------------------

def test_common_tags_are_read_from_bit_rows_and_counted(dep):
    """The rows held are the tags over the rule; a filter of two such
    tags scatters nothing, one of a common and a rare tag scatters the
    rare tag's tiles alone; `_nodes/stats` counts both."""
    svc = dep.server.cluster.indices[dep.index]
    dp = svc._executor(svc.shards[0]).device_segments[0].postings["tags"]
    dep.search(dep.bodies[0])  # the field's first filtered search builds them
    rows = dp.filter_bits
    assert {dep.tag(t) for t in rows.row_of_term} == dep.on_rows
    assert 3 <= len(dep.on_rows) < 100 and dp._tfs is None
    assert rows.plane.shape == (len(dep.on_rows), scoring.filter_bit_words(DOCS))
    commonest, second = dep.tag(dep.by_df[0]), dep.tag(dep.by_df[1])
    rare = next(dep.tag(t) for t in dep.by_df if 100 <= dep.df[t] < 1024)
    for tags, on_rows in (([commonest, second], 2), ([commonest, rare], 1),
                          ([rare], 0)):
        kf0 = dep.node()["knn_filtered"]
        body = dep.body(tags)
        dep.held(body, dep.search(body))
        kf1 = dep.node()["knn_filtered"]
        assert kf1["filter_terms"] == kf0["filter_terms"] + len(tags)
        assert kf1["bitset_terms"] == kf0["bitset_terms"] + on_rows
        assert (kf1["filter_tiles"] - kf0["filter_tiles"]
                == dep.scattered_tiles(tags))
        assert kf1["fallbacks"] == kf0["fallbacks"]
    assert dep.node()["knn_filtered"]["bitset_terms"] > 0


def test_rows_the_hbm_room_refuses_leave_the_terms_on_their_tiles(dep):
    """No headroom in the ledger when a field's rows would be built:
    none is held, it is counted as a degrade, every tag is scattered
    (the commonest over more than one trip of the mask program) and
    the answers are the plain reference's."""
    from elasticsearch_tpu.common.memory import hbm_ledger

    index = "yfcc-no-room"
    ok(dep.port, "PUT", f"/{index}", {
        "settings": dep.config["settings"],
        "mappings": dep.corpus["mappings"]})
    place_segment(dep.server.cluster.indices[index], dep.corpus["segment"])
    bare = dict(dep.bodies[0]["knn"])
    del bare["filter"]
    dep.search({"knn": bare, "size": 1, "_source": False}, index)  # vectors up
    svc = dep.server.cluster.indices[index]
    dp = svc._executor(svc.shards[0]).device_segments[0].postings["tags"]
    budget, degraded = hbm_ledger.budget, hbm_ledger.stats()["degraded_allocations"]
    hbm_ledger.budget = hbm_ledger.used  # everything uploaded fits; no more
    try:
        before = dep.node()["knn_filtered"]
        bodies = [dep.body([dep.tag(dep.by_df[0])]),
                  dep.body([dep.tag(dep.by_df[1]), dep.tag(dep.by_df[2])]),
                  dep.bodies[6], dep.bodies[7]]
        for body in bodies:
            dep.held(body, dep.search(body, index))
    finally:
        hbm_ledger.budget = budget
    assert dp.filter_bits.plane is None
    assert hbm_ledger.stats()["degraded_allocations"] == degraded + 1
    after = dep.node()["knn_filtered"]
    assert after["bitset_terms"] == before["bitset_terms"]
    assert after["fallbacks"] == before["fallbacks"]
    assert after["searches"] == before["searches"] + len(bodies)
    assert (after["filter_tiles"] - before["filter_tiles"]
            >= int(dep.pf.term_tile_count[dep.by_df[0]]) > scoring.FILTER_CHUNK)
    mask = next(s for s in dep.last_trace()["spans"]
                if s["name"] == "filter_mask")
    assert mask["tags"]["bitset_rows_held"] == 0 == mask["tags"]["bitset_terms"]


# ---- one launch, several jobs, each under its own mask --------------------

def test_two_jobs_of_different_filters_share_one_launch(dep):
    """A group of two filtered jobs at a four-row bucket: one mask
    launch and one scan, each row under its own filter, pad rows empty."""
    svc = dep.server.cluster.indices[dep.index]
    ex = svc._executor(svc.shards[0])
    b = svc._batcher
    bodies = [dep.body([dep.tag(dep.by_df[0])]),
              dep.body([dep.tag(dep.by_df[5]), dep.tag(dep.by_df[1])],
                       vector=dep.bodies[2]["knn"]["query_vector"])]
    from elasticsearch_tpu.search import dsl

    jobs = []
    for body in bodies:
        plan = batcher_mod.extract_knn_plan(
            [dsl.parse_knn(body["knn"])], svc.mappings)
        assert plan is not None and plan.filter is not None
        jobs.append(batcher_mod._Job(ex, plan, 10, kind="knn"))
    share = batcher_mod.FAMILIES["knn"].share
    assert share(jobs[0].plan) == share(jobs[1].plan)
    before = dict(b.knn_filtered)
    b._collect_knn_group(jobs, b._dispatch_knn_group(jobs, rows=4))
    after = b.knn_filtered
    assert after["mask_launches"] == before["mask_launches"] + 1
    assert after["searches"] == before["searches"] + 2
    assert after["rows_scanned"] == before["rows_scanned"] + 2 * DOCS
    passed = [rows_passing(dep, [c["term"]["tags"] for c in
                                 body["knn"]["filter"]["bool"]["filter"]])
              for body in bodies]
    assert after["rows_passed"] == before["rows_passed"] + sum(passed)
    # a mask reads the tag field's doc ids alone: its tf plane stays home
    assert ex.device_segments[0].postings["tags"]._tfs is None
    for body, job in zip(bodies, jobs):
        td = job.result
        served = {"hits": {
            "total": {"value": td.total, "relation": td.relation},
            "hits": [{"_id": h.doc_id, "_score": h.score} for h in td.hits]}}
        dep.held(body, served)


def test_bare_and_filtered_jobs_never_share_a_group(dep):
    svc = dep.server.cluster.indices[dep.index]
    from elasticsearch_tpu.search import dsl

    bare = dict(dep.bodies[0]["knn"])
    del bare["filter"]
    plans = [batcher_mod.extract_knn_plan([dsl.parse_knn(k)], svc.mappings)
             for k in (bare, dep.bodies[0]["knn"])]
    share = batcher_mod.FAMILIES["knn"].share
    assert plans[0].filter is None and plans[1].filter is not None
    assert share(plans[0]) != share(plans[1])


def test_bare_knn_keeps_the_program_it_had(dep, monkeypatch):
    """A knn section with no filter launches `knn_topk_batch`, with the
    operands it always had, and neither program of the filtered path."""
    calls = []
    real = scoring.knn_topk_batch

    def spy(q, valid, vectors, cand, similarity, k):
        calls.append((q.shape, q.dtype, valid.shape, valid.dtype,
                      vectors.shape, vectors.dtype, cand.shape, cand.dtype,
                      similarity, k))
        return real(q, valid, vectors, cand, similarity, k)

    def never(*_a, **_k):
        raise AssertionError("a bare knn reached the filtered path")

    monkeypatch.setattr(scoring, "knn_topk_batch", spy)
    monkeypatch.setattr(scoring, "knn_filter_mask", never)
    monkeypatch.setattr(scoring, "knn_topk_filtered", never)
    section = dict(dep.bodies[0]["knn"])
    del section["filter"]
    served = dep.search({"knn": section, "size": 10, "_source": False})
    assert len(served["hits"]["hits"]) == 10
    assert real.__wrapped__.__name__ == "knn_topk_batch"  # jit_knn_topk_batch
    assert calls == [((1, 192), np.float32, (1,), np.bool_,
                      (DOCS, 192), np.int8, (DOCS,), np.bool_,
                      "l2_norm", 128)]
    q = np.asarray(section["query_vector"], np.int64) + 128
    d2 = ((dep.corpus["reference"]["vectors"].astype(np.int64) - q) ** 2
          ).sum(axis=1)
    order = np.lexsort((np.arange(DOCS), d2))[:10]
    assert [h["_id"] for h in served["hits"]["hits"]] == [
        str(int(i)) for i in order]


# ---- the normal path: spans, counters, transfers --------------------------

def test_request_is_a_knn_job_with_its_spans_and_counters(dep):
    body = dep.bodies[3]
    tags = [c["term"]["tags"] for c in body["knn"]["filter"]["bool"]["filter"]]
    tiles = dep.scattered_tiles(tags)
    on_rows = sum(t in dep.on_rows for t in tags)
    before = dep.node()
    served = dep.search(body)
    after = dep.node()
    dep.held(body, served)
    kf0, kf1 = before["knn_filtered"], after["knn_filtered"]
    assert kf1["searches"] == kf0["searches"] + 1
    assert kf1["mask_launches"] == kf0["mask_launches"] + 1
    assert kf1["rows_scanned"] == kf0["rows_scanned"] + DOCS
    assert kf1["rows_passed"] == kf0["rows_passed"] + rows_passing(dep, tags)
    assert kf1["filter_tiles"] == kf0["filter_tiles"] + tiles
    assert kf1["filter_terms"] == kf0["filter_terms"] + len(tags)
    assert kf1["bitset_terms"] == kf0["bitset_terms"] + on_rows
    assert kf1["fallbacks"] == kf0["fallbacks"]
    b0, b1 = (n["pipeline"]["batching"] for n in (before, after))
    assert b1["unplanned_queries"] == b0["unplanned_queries"]
    fo0, fo1 = (n["thread_pool"]["search"]["fan_out"] for n in (before, after))
    assert fo1["inline"] == fo0["inline"] + 1 and fo1["pooled"] == fo0["pooled"]
    spans = {s["name"]: s for s in dep.last_trace()["spans"]}
    shard = spans["shard_search"]
    for name in JOB_SPANS:
        assert spans[name]["parent_id"] == shard["id"], name
    disp = spans["dispatch"]
    assert disp["tags"]["family"] == "knn" and disp["tags"]["filtered"] is True
    assert disp["tags"]["clauses"] == len(tags)
    assert disp["tags"]["filter_tiles"] == tiles
    mask = spans["filter_mask"]
    assert mask["parent_id"] == disp["id"]
    assert mask["tags"] == {"segment": 0, "launches": 1, "tiles": tiles,
                            "bitset_terms": on_rows,
                            "bitset_rows_held": len(dep.on_rows)}
    assert spans["plan"]["tags"] == {"family": "knn", "planned": True}


@pytest.mark.parametrize("num_candidates, selects", [(16, 1), (100, 0)],
                         ids=["wide_enough", "too_narrow"])
def test_scan_selects_from_block_maxima_where_the_segment_is_wide_enough(
        dep, num_candidates, selects):
    """30,000 rows hold one whole group of 128 blocks: 8 x k of them at
    the candidate bucket 16, not at 128 (the deployment's, which takes
    the selection from 131,072 rows: its 10M). Either way the page is
    the plain reference's."""
    bucket = 16 if num_candidates == 16 else 128
    assert scoring.knn_block_select(DOCS, bucket) is bool(selects)
    for i in (1, 3, 5):
        body = json.loads(json.dumps(dep.bodies[i]))
        body["knn"]["num_candidates"] = num_candidates
        kf0 = dep.node()["knn_filtered"]
        dep.held(body, dep.search(body))
        kf1 = dep.node()["knn_filtered"]
        assert kf1["mask_launches"] == kf0["mask_launches"] + 1
        assert (kf1["block_select_launches"]
                == kf0["block_select_launches"] + selects)


def test_every_transfer_of_a_filtered_job_is_counted(dep):
    """Up: the mask plan (one row of 3 x 8 slots and the clause count),
    the query row, the merge's slot map and rank cut; down: the packed
    page (scores, segments, docs, the count and the rows passed)."""
    body = dep.bodies[4]
    dep.search(body)  # programs built
    before = tracing.transfer_stats()
    dep.search(body)
    after = tracing.transfer_stats()
    moved = {k: after[k] - before[k] for k in after}
    kc, k_out = 128, 10
    plan_bytes = 4 * (3 * scoring.FILTER_SLOT_BUCKETS[0] + 1)
    assert moved == {
        "h2d_count": 4,
        "h2d_bytes": plan_bytes + 4 * 192 + 4 * kc + kc,
        "d2h_count": 1,
        "d2h_bytes": 4 * (3 * k_out + 2),
    }


@pytest.mark.parametrize("section", [
    {"filter": {"range": {"tags": {"gte": "t1"}}}},
    {"filter": {"bool": {"must_not": [{"term": {"tags": "t000001"}}]}}},
    {"similarity": 0.0},
], ids=["range_filter", "must_not_filter", "similarity_cut_off"])
def test_knn_no_planner_took_is_counted_unplanned(dep, section):
    knn = dict(dep.bodies[0]["knn"])
    knn.pop("filter")
    knn.update(section)
    before = dep.node()["pipeline"]["batching"]["unplanned_queries"]
    dep.search({"knn": knn, "size": 10, "_source": False})
    assert dep.node()["pipeline"]["batching"]["unplanned_queries"] == before + 1
    plan = dep.last_trace()["spans"]
    assert {"family": None, "planned": False} in [
        s["tags"] for s in plan if s["name"] == "plan"]


def test_mask_launch_that_fails_falls_back_and_is_counted(dep):
    body = dep.bodies[5]
    want = dep.search(body)
    before = dep.node()["knn_filtered"]
    faults.configure({"rules": [{"site": "knn.filter", "kind": "error"}]})
    try:
        served = dep.search(body)
    finally:
        faults.clear()
    after = dep.node()["knn_filtered"]
    assert after["fallbacks"] == before["fallbacks"] + 1
    assert after["mask_launches"] == before["mask_launches"]
    assert served["hits"] == want["hits"]
    dep.held(body, served)


# ---- the check itself ------------------------------------------------------

def test_bf16_control_fails(dep):
    """The reference one precision down, put in the program's place, is
    caught by pages or scores; the reference itself passes."""
    g = dep.config["guarantees"]
    ref, bodies = dep.ref, dep.bodies
    refs = ref.answer_many([reference_body(g["rule"], b) for b in bodies])
    same = compare_all(g, bodies, ref.answer_many(bodies), refs)
    assert same["correct"], same
    low = compare_all(g, bodies, ref.answer_many(bodies, precision="lower"),
                      refs)
    assert not low["correct"]
    assert (low["numbers"]["page_mismatches"][0] > 0
            or low["numbers"]["score_rel_max"][0] > g["score_rtol"])
    assert low["numbers"]["score_rel_max"][0] > 10 * g["score_rtol"]


def test_configuration_keeps_the_sources_shapes(dep):
    c, args = dep.config, dep.config["corpus"]["args"]
    assert c["docs"] == 10_000_000 and set(c["reduced"]) == {"ingest", "fields"}
    assert (args["dims"], args["similarity"]) == (192, "l2_norm")
    assert args["tags"]["vocab"] == 200_386 == len(dep.pf.terms)
    assert c["body"]["args"] == {"k": 10, "num_candidates": 100, "size": 10,
                                 "one_tag_share": 0.5}
    vf = dep.corpus["segment"].vectors["vec"]
    assert vf.vectors.dtype == np.int8 and vf.vectors.shape == (DOCS, 192)
    u8 = dep.corpus["reference"]["vectors"]
    assert u8.dtype == np.uint8
    assert (vf.vectors.astype(np.int16) + 128 == u8).all()
    props = dep.corpus["mappings"]["properties"]
    assert props["vec"]["element_type"] == "byte"
    svc = dep.server.cluster.indices[dep.index]
    dev = svc._executor(svc.shards[0]).device_segments[0].vectors["vec"].rows
    assert dev.dtype == np.int8  # one byte an element on the device too


# ---- `element_type: byte` through the mapper, and beside `float` ----------

SMALL = 300


@pytest.fixture(scope="module")
def typed(dep):
    """The same 300 rows indexed over HTTP into a `byte` and a `float`
    field (several refreshes: several segments)."""
    rng = np.random.default_rng(17)
    vecs = rng.integers(-128, 128, (SMALL, 8))
    tags = [[f"g{t}" for t in rng.choice(6, rng.integers(1, 4), replace=False)]
            for _ in range(SMALL)]
    for index, etype in (("typed-byte", "byte"), ("typed-float", "float")):
        vec = {"type": "dense_vector", "dims": 8, "similarity": "l2_norm"}
        if etype == "byte":
            vec["element_type"] = "byte"
        ok(dep.port, "PUT", f"/{index}", {
            "settings": dep.config["settings"],
            "mappings": {"properties": {"vec": vec,
                                        "tags": {"type": "keyword"}}}})
        for i in range(SMALL):
            ok(dep.port, "PUT", f"/{index}/_doc/{i}",
               {"vec": vecs[i].tolist(), "tags": tags[i]})
            if i % 100 == 99:
                ok(dep.port, "POST", f"/{index}/_refresh")
    return vecs, tags


def test_byte_mapping_round_trips(dep, typed):
    m = ok(dep.port, "GET", "/typed-byte/_mapping")
    assert m["typed-byte"]["mappings"]["properties"]["vec"] == {
        "type": "dense_vector", "dims": 8, "similarity": "l2_norm",
        "element_type": "byte"}
    m = ok(dep.port, "GET", "/typed-float/_mapping")
    assert "element_type" not in m["typed-float"]["mappings"]["properties"]["vec"]
    svc = dep.server.cluster.indices["typed-byte"]
    held = [s.vectors["vec"].vectors.dtype
            for s in svc.shards[0].reader().segments]
    assert len(held) >= 3 and set(held) == {np.dtype(np.int8)}


def test_byte_rows_bring_their_norms_and_float_rows_none(dep, typed):
    """The norm plane is built at upload for integer rows under l2_norm,
    equal to `sum(v * v)` bit for bit; a float field uploads what it
    always did and its scan computes its own."""
    vecs, _tags = typed
    for index, held in (("typed-byte", True), ("typed-float", False)):
        svc = dep.server.cluster.indices[index]
        ex = svc._executor(svc.shards[0])
        first = 0
        for seg, dseg in zip(ex.reader.segments, ex.device_segments):
            dv = dseg.vectors["vec"]
            assert dv.rows.shape == (seg.num_docs, 8)
            if not held:
                assert dv.norms is None and dv.rows.dtype == np.float32
                continue
            assert dv.norms.dtype == np.float32
            want = (vecs[first:first + seg.num_docs] ** 2).sum(axis=1)
            np.testing.assert_array_equal(np.asarray(dv.norms), want)
            first += seg.num_docs
    svc = dep.server.cluster.indices[dep.index]
    dv = svc._executor(svc.shards[0]).device_segments[0].vectors["vec"]
    rows = dep.corpus["segment"].vectors["vec"].vectors.astype(np.int64)
    np.testing.assert_array_equal(np.asarray(dv.norms), (rows ** 2).sum(axis=1))


@pytest.mark.parametrize("vector, why", [
    ([1, 2, 3, 4, 5, 6, 7, 200], "between [-128, 127] but found [200]"),
    ([1, 2, 3, 4, 5, 6, 7, 1.5], "non-decimal values but found decimal value [1.5]"),
    ([1, 2, 3], "has dims [8] but the indexed vector has [3] dimensions"),
], ids=["out_of_range", "decimal", "wrong_dims"])
def test_byte_field_refuses_what_it_cannot_store(dep, typed, vector, why):
    status, payload = call(dep.port, "PUT", "/typed-byte/_doc/bad",
                           {"vec": vector, "tags": ["g1"]})
    assert status == 400 and why in payload["error"]["reason"], payload


@pytest.mark.parametrize("vector, why", [
    ([1, 2, 3, 4, 5, 6, 7, 200], "between [-128, 127] but found [200]"),
    ([1, 2, 3, 4, 5, 6, 7, 1.5], "non-decimal values but found decimal value [1.5]"),
], ids=["out_of_range", "decimal"])
def test_byte_field_refuses_a_query_vector_it_cannot_hold(dep, typed, vector,
                                                          why):
    status, payload = call(dep.port, "POST", "/typed-byte/_search", {
        "knn": {"field": "vec", "query_vector": vector, "k": 3,
                "num_candidates": 10}})
    assert status == 400 and why in payload["error"]["reason"], payload


def test_unknown_element_type_is_refused(dep):
    status, payload = call(dep.port, "PUT", "/typed-bad", {"mappings": {
        "properties": {"vec": {"type": "dense_vector", "dims": 4,
                               "element_type": "nibble"}}}})
    assert status == 400 and "element_type" in json.dumps(payload)


@pytest.mark.parametrize("index", ["typed-byte", "typed-float"])
@pytest.mark.parametrize("tags", [["g1"], ["g1", "g3"]], ids=["one", "two"])
def test_float_and_byte_fields_answer_alike(dep, typed, index, tags):
    vecs, bags = typed
    q = [3, -7, 100, -20, 0, 55, -128, 127]
    before = dep.node()["knn_filtered"]
    served = dep.search({"knn": {
        "field": "vec", "query_vector": q, "k": 5, "num_candidates": 20,
        "filter": {"bool": {"filter": [{"term": {"tags": t}} for t in tags]}},
    }, "size": 5, "_source": False}, index)
    after = dep.node()["knn_filtered"]
    assert after["fallbacks"] == before["fallbacks"]
    assert after["searches"] > before["searches"]  # the planned path
    rows = np.array([i for i in range(SMALL) if set(tags) <= set(bags[i])])
    d2 = ((vecs[rows] - np.array(q)) ** 2).sum(axis=1)
    order = np.lexsort((rows, d2))[:5]
    assert [h["_id"] for h in served["hits"]["hits"]] == [
        str(int(rows[i])) for i in order]
    for h, i in zip(served["hits"]["hits"], order):
        assert h["_score"] == pytest.approx(1.0 / (1.0 + d2[i]), rel=1e-6)
    assert served["hits"]["total"] == {"value": min(5, len(rows)),
                                       "relation": "eq"}
