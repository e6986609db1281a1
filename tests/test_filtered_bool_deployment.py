"""The filtered and negated Boolean deployment (`benchmarks/configs/
msmarco-filtered-bool.json`) at a small size: luceneutil's OrHighNot* and
Filtered* task classes beside two plain ones over a few thousand seeded
passages of the benchmark's own corpus builder (text and a bag of
keyword tags a passage), served over HTTP as `serve` jobs of the batcher
- the fused text program under a mask of the row's own and a veto - and
held to the benchmark's own plain reference (`benchmarks/references/
bm25_bool_filtered.py`) by the benchmark's own rule (`benchmarks/
compare.py`, `exact`).

The segment is small, so `dense_row_min_df` is lowered for the module: the
commonest tags then hold a bit row and the others stay on their tiles,
as at the deployment's size, and some excluded terms ride a dense hot
row while others are scattered.
"""

import hashlib
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
from elasticsearch_tpu.search import dsl

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from compare import compare_one, reference_body  # noqa: E402
from plugins import load_json, load_plugin  # noqa: E402
from run import place_segment  # noqa: E402

DOCS, SEED, N_BODIES = 6000, 7, 320
MIN_DF = 64  # the module's `dense_row_min_df`
NEGATED = ("OrHighNotHigh", "OrHighNotMed", "OrHighNotLow", "OrNotHighLow")
FILTERED = ("FilteredAndHighHigh", "FilteredAndHighMed",
            "FilteredOrHighHigh", "FilteredOrHighMed")
PLAIN = ("OrHighMed", "AndHighMed")
CLASSES = NEGATED + FILTERED + PLAIN


def call(port: int, path: str, body: dict, method: str = "POST") -> dict:
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=300)
    try:
        conn.request(method, path, body=json.dumps(body).encode(),
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        payload = resp.read()
        assert resp.status == 200, (resp.status, payload[:400])
        return json.loads(payload)
    finally:
        conn.close()


class Deployment:
    def __init__(self):
        from elasticsearch_tpu.rest.server import ElasticsearchTpuServer

        self.config = load_json("configs", "msmarco-filtered-bool.json")
        self.corpus = load_plugin(
            "corpora", self.config["corpus"]["builder"]).build(
            self.config, SEED, DOCS)
        self.ref = load_plugin(
            "references", self.config["reference"]).Reference(
            self.corpus["reference"], self.config)
        self.gen = load_plugin("bodies", self.config["body"]["generator"])
        ctx, args = self.corpus["body_context"], self.config["body"]["args"]
        self.ctx = ctx
        raw = self.gen.make(ctx, args, np.random.default_rng([48, 9]),
                            N_BODIES)
        terms = self.gen.class_terms(ctx, args)
        self.bodies = [json.loads(b) for b in raw]
        self.classes = [self.gen.class_of(b, ctx["field"], terms)
                        for b in self.bodies]
        self.server = ElasticsearchTpuServer(port=0)
        self.server.start_background()
        self.port = self.server.port
        self.index = self.config["index"]
        self.make_index(self.index, self.corpus["segment"])
        # tags by rank (1: the commonest), and each tag's passages
        self.tag_df = np.bincount(self.corpus["reference"]["bag_tags"],
                                  minlength=10_000)
        self.tag_by_rank = np.argsort(-self.tag_df, kind="stable")

    def make_index(self, index: str, *segments):
        call(self.port, f"/{index}", {"settings": self.config["settings"],
                                      "mappings": self.corpus["mappings"]},
             "PUT")
        svc = self.server.cluster.indices[index]
        place_segment(svc, segments[0])
        if len(segments) > 1:
            eng = svc.shards[0]
            for i, seg in enumerate(segments[1:], 1):
                n = seg.num_docs
                eng.segments.append(seg)
                eng.live_docs.append(None)
                eng.seg_versions.append(np.ones(n, np.int64))
                eng.seg_seqnos.append(np.arange(n, dtype=np.int64))
                eng.seg_names.append(f"seg_0_{i}")
            eng.change_generation += 1
        return svc

    @property
    def svc(self):
        return self.server.cluster.indices[self.index]

    def search(self, body: dict, index: str = None) -> dict:
        return call(self.port, f"/{index or self.index}/_search", body)

    def held(self, body: dict, served: dict, ref=None) -> dict:
        g = self.config["guarantees"]
        (expected,) = (ref or self.ref).answer_many(
            [reference_body(g["rule"], body)])
        got = compare_one(g["rule"], g["score_rtol"], body, served, expected)
        assert got["page_ok"], got["why"]
        assert got["total_ok"], (served["hits"]["total"],
                                 expected["hits"]["total"])
        assert got["score_rel"] <= g["score_rtol"]
        return expected

    def node(self) -> dict:
        from elasticsearch_tpu.rest.actions import RestActions

        _status, body = RestActions(self.server.cluster).nodes_stats(
            None, {}, {})
        return body["nodes"]["node-0"]

    def tag(self, rank: int) -> str:
        return self.tag_name(int(self.tag_by_rank[rank - 1]))

    def tag_name(self, tid: int) -> str:
        return f"t{tid:0{self.ctx['tag_width']}d}"

    def df_of(self, q: dict) -> int:
        """Passages that carry the tag of a one-term filter."""
        tid = int(q["filter"][0]["term"]["tag"][1:])
        return int(self.tag_df[tid]) if tid < len(self.tag_df) else 0

    def word(self, t: int) -> str:
        return f"w{t:0{self.ctx['term_width']}d}"

    def body(self, q: dict, size: int = 10) -> dict:
        return {"query": {"bool": q}, "size": size, "_source": False}


@pytest.fixture(scope="module")
def dep():
    from elasticsearch_tpu.search import executor_jax

    orig = executor_jax.FUSED_MIN_DOCS, executor_jax.dense_row_min_df
    executor_jax.FUSED_MIN_DOCS = 10  # the deployment's text kernel
    executor_jax.dense_row_min_df = lambda n_docs: MIN_DF
    d = Deployment()
    yield d
    d.server.close()
    executor_jax.FUSED_MIN_DOCS, executor_jax.dense_row_min_df = orig


def term(d, t):
    return {"term": {"body": d.word(t) if isinstance(t, int) else t}}


def flt(d, rank):
    return {"term": {"tag": rank if isinstance(rank, str) else d.tag(rank)}}


def words_of(body: dict) -> list:
    """The text words of a body, scoring ones first, then excluded."""
    q = body["query"]["bool"]
    out = []
    for occur in ("must", "should", "must_not"):
        for c in q.get(occur, []):
            (_kind, inner), = c.items()
            out += inner["body"].split()
    return out


# ---- the mix over HTTP ------------------------------------------------------

def first_of(dep, cls: str) -> dict:
    """The class's body with most hits (a filtered conjunction's are
    chance co-occurrences: at this size most answer none)."""
    return max((b for b, c in zip(dep.bodies, dep.classes) if c == cls),
               key=lambda b: dep.ref.answer(b)["hits"]["total"]["value"])


@pytest.mark.parametrize("cls", CLASSES)
def test_served_answer_over_http_is_the_plain_references(dep, cls):
    body = first_of(dep, cls)
    expected = dep.held(body, dep.search(body))
    # a filtered conjunction's hits are chance: often none at this size
    assert expected["hits"]["total"]["value"] > 0 or "And" in cls


@pytest.mark.parametrize("want", ["fewer_than_10_hits", "no_hits"])
def test_short_and_empty_pages_are_answered_alike(dep, want):
    ok = (lambda t: 0 < t < 10) if want == "fewer_than_10_hits" else (
        lambda t: t == 0)
    body = next(b for cls in ("FilteredAndHighMed", "FilteredAndHighHigh")
                for b, c in zip(dep.bodies, dep.classes)
                if c == cls and ok(
                    dep.ref.answer(b)["hits"]["total"]["value"]))
    served = dep.search(body)
    n = dep.held(body, served)["hits"]["total"]["value"]
    assert len(served["hits"]["hits"]) == n < 10


def test_classes_come_in_equal_shares_and_tell_their_class(dep):
    share = {c: dep.classes.count(c) / len(dep.classes) for c in CLASSES}
    assert all(0.05 < s < 0.16 for s in share.values()), share
    for body, cls in zip(dep.bodies, dep.classes):
        q = body["query"]["bool"]
        words = words_of(body)
        assert len(set(words)) == len(words) == 2
        if cls in NEGATED:
            assert set(q) == {"should", "must_not"}
        elif cls in PLAIN:
            assert set(q) == {"must"} or set(q) == {"should"}
        else:
            occur = "must" if "And" in cls else "should"
            assert set(q) - {"minimum_should_match"} == {occur, "filter"}
            assert (q.get("minimum_should_match") == 1) == (occur == "should")
            (f,) = q["filter"]
            assert dep.df_of(q) > 0  # a stored passage's own tag


def test_every_request_of_the_mix_is_one_serve_job(dep):
    """No request leaves the batcher: nothing unplanned, no per-job
    fallback, one fused launch a request whose packed row is downloaded
    as it is, and `serve_filtered` says what the mix held. Both mask
    forms and both kinds of excluded term are engaged."""
    n0, b0 = dep.node(), None
    b0 = n0["pipeline"]["batching"]
    for body in dep.bodies:
        dep.search(body)
    n1 = dep.node()
    b1 = n1["pipeline"]["batching"]
    n = len(dep.bodies)
    sf = {k: n1["serve_filtered"][k] - n0["serve_filtered"][k]
          for k in n1["serve_filtered"]}
    pool0, pool1 = n0["thread_pool"]["search"], n1["thread_pool"]["search"]
    assert b1["unplanned_queries"] == b0["unplanned_queries"]
    assert pool1["serve_fallback_jobs"] == pool0["serve_fallback_jobs"]
    assert pool1["completed"] - pool0["completed"] == n
    assert pool1["serve_launches"] - pool0["serve_launches"] == n
    assert b1["direct_collect_groups"] - b0["direct_collect_groups"] == n
    negated = sum(c in NEGATED for c in dep.classes)
    filtered = sum(c in FILTERED for c in dep.classes)
    assert sf["searches"] == negated + filtered
    assert sf["mask_launches"] == sf["filter_terms"] == filtered
    assert 0 < sf["bitset_terms"] < sf["filter_terms"]
    assert sf["filter_tiles"] > 0 and sf["fallbacks"] == 0
    assert sf["rows_scanned"] == filtered * DOCS
    # the documents the filters passed, counted on the device
    assert sf["rows_passed"] == sum(
        dep.df_of(b["query"]["bool"])
        for b, c in zip(dep.bodies, dep.classes) if c in FILTERED)
    assert sf["excluded_terms"] == negated
    assert 0 < sf["excluded_tiles"]


def test_the_oracle_gives_the_same_pages(dep):
    """The tie to the host oracle: `NumpyExecutor` over the same reader
    answers a body of every class with the served ids, scores, totals."""
    from elasticsearch_tpu.search.executor import NumpyExecutor

    oracle = NumpyExecutor(dep.svc.shards[0].reader())
    for cls in CLASSES:
        body = first_of(dep, cls)
        served = dep.svc.search(json.loads(json.dumps(body)))
        td = oracle.search(dsl.parse_query(body["query"]), size=10)
        assert served["hits"]["total"]["value"] == td.total, cls
        assert [(h["_id"], round(h["_score"], 4))
                for h in served["hits"]["hits"]] == [
            (h.doc_id, round(h.score, 4)) for h in td.hits], cls


# ---- shapes of the fused program under masks and a veto --------------------

def by_df(dep):
    df = np.asarray(dep.ctx["term_df"])
    return df, np.argsort(-df, kind="stable")


def shape_cases(dep) -> dict:
    df, order = by_df(dep)
    parts = dep.svc._executor(dep.svc.shards[0]).fused_parts(0, "body")
    pf = dep.corpus["segment"].postings["body"]
    hot = [t for t in order[50:400]
           if pf.term_id(dep.word(int(t))) in parts["hot_rank"]]
    rare = [int(t) for t in order if 3 <= df[t] < MIN_DF][:8]
    assert len(hot) >= 4 and len(rare) >= 4
    a, b, c, e = (int(t) for t in hot[:4])
    on_row = 1  # the commonest tag: a bit row
    on_tiles = next(r for r in range(2, 10_000)
                    if 2 <= dep.tag_df[dep.tag_by_rank[r - 1]] < MIN_DF)
    absent = dep.tag_name(10_007)  # past the vocabulary: no passage's
    return {
        "excluded_hot": {"should": [term(dep, a)],
                           "must_not": [term(dep, b)]},
        "excluded_rare": {"should": [term(dep, a)],
                            "must_not": [term(dep, rare[0])]},
        "excluded_match_of_several_words_is_none_of_them": {
            "must": [term(dep, a)],
            "must_not": [{"match": {"body": " ".join(
                dep.word(t) for t in (b, rare[1], rare[2]))}}]},
        "two_excluded_clauses": {
            "should": [term(dep, a), term(dep, c)],
            "must_not": [term(dep, b), term(dep, rare[0])]},
        "excluded_term_is_a_scored_one": {
            "should": [term(dep, a), term(dep, b)],
            "must_not": [term(dep, b)]},
        "msm_2_of_3_with_an_exclusion": {
            "should": [term(dep, a), term(dep, b),
                       {"match": {"body": f"{dep.word(c)} {dep.word(e)}"}}],
            "minimum_should_match": 2,
            "must_not": [term(dep, rare[0])]},
        "filter_on_a_bit_row": {"must": [term(dep, a)],
                                "filter": [flt(dep, on_row)]},
        "filter_on_tiles": {"must": [term(dep, a)],
                            "filter": [flt(dep, on_tiles)]},
        "filter_that_passes_nothing": {"must": [term(dep, a)],
                                       "filter": [flt(dep, absent)]},
        "filter_and_exclusion": {
            "must": [term(dep, a)], "should": [term(dep, c)],
            "filter": [flt(dep, on_row)], "must_not": [term(dep, b)]},
        "filter_of_two_clauses_needs_both_tags": {
            "must": [term(dep, a)],
            "filter": [flt(dep, on_row), flt(dep, 2)]},
        "terms_filter_is_any_of_its_values": {
            "must": [term(dep, a)],
            "filter": [{"terms": {"tag": [dep.tag(on_row),
                                          dep.tag(on_tiles),
                                          dep.tag(3)]}}]},
        "filter_that_is_a_bool_of_terms": {
            "must": [term(dep, a)],
            "filter": [{"bool": {"filter": [flt(dep, on_row)],
                                 "must": [flt(dep, 2)]}}]},
        "should_with_explicit_msm_beside_a_filter": {
            "should": [term(dep, a), term(dep, b)],
            "minimum_should_match": 1, "filter": [flt(dep, on_row)]},
    }


SHAPES = [
    "excluded_hot", "excluded_rare",
    "excluded_match_of_several_words_is_none_of_them",
    "two_excluded_clauses", "excluded_term_is_a_scored_one",
    "msm_2_of_3_with_an_exclusion", "filter_on_a_bit_row",
    "filter_on_tiles", "filter_that_passes_nothing",
    "filter_and_exclusion", "filter_of_two_clauses_needs_both_tags",
    "terms_filter_is_any_of_its_values",
    "filter_that_is_a_bool_of_terms",
    "should_with_explicit_msm_beside_a_filter",
]


def oracle_answer(dep, body: dict, index: str = None, size: int = 10):
    from elasticsearch_tpu.search.executor import NumpyExecutor

    svc = dep.server.cluster.indices[index or dep.index]
    td = NumpyExecutor(svc.shards[0].reader()).search(
        dsl.parse_query(body["query"]), size=size)
    return td.total, [(h.doc_id, round(h.score, 4)) for h in td.hits]


@pytest.mark.parametrize("shape", SHAPES)
def test_shape_is_planned_and_answers_as_the_oracle(dep, shape):
    q = shape_cases(dep)[shape]
    body = dep.body(q)
    plan = batcher_mod.extract_serve_plan(
        dsl.parse_query(body["query"]), dep.svc.mappings, dep.svc.analysis)
    assert plan is not None
    assert (plan.filter is not None) == ("filter" in q)
    assert (plan.excluded > 0) == ("must_not" in q)
    before = dep.node()
    served = dep.search(body)
    after = dep.node()
    assert (after["pipeline"]["batching"]["unplanned_queries"]
            == before["pipeline"]["batching"]["unplanned_queries"])
    assert (after["thread_pool"]["search"]["serve_fallback_jobs"]
            == before["thread_pool"]["search"]["serve_fallback_jobs"])
    total, page = oracle_answer(dep, body)
    assert served["hits"]["total"]["value"] == total
    assert [(h["_id"], round(h["_score"], 4))
            for h in served["hits"]["hits"]] == page
    if "passes_nothing" in shape:
        assert total == 0
    elif shape not in ("filter_on_tiles", "filter_and_exclusion"):
        assert total > 0
    if "bool_of_terms" not in shape:
        dep.held(body, served)  # the plain reference parses these too


def test_veto_takes_hot_and_rare_terms_alike(dep):
    cases = shape_cases(dep)
    for shape, terms, on_tiles in (("excluded_hot", 1, False),
                                   ("excluded_rare", 1, True),
                                   ("two_excluded_clauses", 2, True)):
        sf0 = dep.node()["serve_filtered"]
        body = dep.body(cases[shape])
        served = dep.search(body)
        sf1 = dep.node()["serve_filtered"]
        assert sf1["excluded_terms"] - sf0["excluded_terms"] == terms
        # a term on a dense row takes a hot slot and no tile
        assert (sf1["excluded_tiles"] > sf0["excluded_tiles"]) == on_tiles
        # no page holds a passage that holds an excluded word
        banned = words_of(body)[len(cases[shape].get("should", [])):]
        ref = dep.corpus["reference"]
        holders = set()
        for w in banned:
            t = int(w[1:])
            holders |= set(ref["post_doc"][
                ref["post_start"][t]:ref["post_start"][t + 1]].tolist())
        assert not {int(h["_id"]) for h in served["hits"]["hits"]} & holders


def test_hits_total_at_and_past_the_cap(dep):
    """`hits.total` counts what the filter and the veto leave: an
    exact count under the request's cap, a `gte` bound at it."""
    df, order = by_df(dep)
    a = int(order[50])
    q = {"should": [term(dep, a)], "must_not": [term(dep, int(order[300]))]}
    total, _page = oracle_answer(dep, dep.body(q))
    assert total > 40
    for tth, want in ((True, (total, "eq")), (total, (total, "eq")),
                      (total - 1, (total - 1, "gte")), (10, (10, "gte"))):
        served = dep.search({**dep.body(q), "track_total_hits": tth})
        assert (served["hits"]["total"]["value"],
                served["hits"]["total"]["relation"]) == want, tth


# ---- one launch, several jobs, each under its own mask ---------------------

def jobs_of(dep, queries, ex=None, svc=None):
    svc = svc or dep.svc
    ex = ex or svc._executor(svc.shards[0])
    jobs = []
    for q in queries:
        query = dsl.parse_query({"bool": q})
        plan = batcher_mod.extract_serve_plan(
            query, svc.mappings, svc.analysis)
        assert plan is not None
        jobs.append(batcher_mod._Job(ex, plan, 10, kind="serve", query=query))
    return jobs


def served_of(job) -> dict:
    td = job.result
    return {"hits": {"total": {"value": td.total, "relation": td.relation},
                     "hits": [{"_id": h.doc_id, "_score": h.score}
                              for h in td.hits]}}


def test_rows_of_one_launch_hold_different_filters(dep):
    """Three filtered jobs at a four-row bucket: ONE fused launch, each
    row under its own filter (a bit row, tiles, a tag no passage
    holds), the pad row empty; an unfiltered job of the same batch is a
    group of its own and keeps the program it had."""
    cases = shape_cases(dep)
    qs = [cases["filter_on_a_bit_row"], cases["filter_on_tiles"],
          cases["filter_that_passes_nothing"]]
    jobs = jobs_of(dep, qs)
    bare = jobs_of(dep, [{"must": qs[0]["must"]}])[0]
    share = batcher_mod.FAMILIES["serve"].share
    assert len({share(j.plan) for j in jobs}) == 1
    assert share(bare.plan) != share(jobs[0].plan)
    b = dep.svc._batcher
    sf0, launches0 = dict(b.serve_filtered), b.stats["serve_launches"]
    b._collect_serve_group(jobs, 16, b._dispatch_serve_group(jobs, 16, rows=4))
    assert b.stats["serve_launches"] == launches0 + 1
    sf1 = b.serve_filtered
    assert sf1["mask_launches"] == sf0["mask_launches"] + 1
    assert sf1["searches"] == sf0["searches"] + 3
    assert sf1["bitset_terms"] == sf0["bitset_terms"] + 1
    assert sf1["rows_scanned"] == sf0["rows_scanned"] + 3 * DOCS
    passed = [dep.df_of(q) for q in qs]
    assert passed[0] > passed[1] > passed[2] == 0
    assert sf1["rows_passed"] == sf0["rows_passed"] + sum(passed)
    for q, job in zip(qs, jobs):
        dep.held(dep.body(q), served_of(job))
    # a mask reads the tag field's doc ids alone: its tf plane stays home
    ex = jobs[0].executor
    assert ex.device_segments[0].postings["tag"]._tfs is None


def test_a_batch_of_filtered_negated_and_bare_jobs_answers_each(dep):
    """Submitted together: the batcher makes a group a key, every job
    comes back with its own answer."""
    cases = shape_cases(dep)
    qs = [cases["filter_on_a_bit_row"], cases["excluded_hot"],
          {"must": cases["filter_on_a_bit_row"]["must"]},
          cases["filter_on_tiles"], cases["filter_and_exclusion"]]
    jobs = jobs_of(dep, qs)
    b = dep.svc._batcher
    b._collect_batch(b._dispatch_batch(jobs))
    for q, job in zip(qs, jobs):
        assert job.error is None
        dep.held(dep.body(q), served_of(job))


def test_two_segments_go_through_the_merge_program(dep):
    """A filtered, negated job over two segments: a fused launch a
    segment, `_group_topk`'s merged branch, the passed counts summed."""
    other = load_plugin("corpora", dep.config["corpus"]["builder"]).build(
        dep.config, SEED + 1, DOCS)["segment"]
    # ids of the second segment must not collide with the first's
    other.doc_ids = [f"b{d}" for d in other.doc_ids]
    svc = dep.make_index("two-segments", dep.corpus["segment"], other)
    cases = shape_cases(dep)
    tracing.clear()
    for shape in ("filter_on_a_bit_row", "filter_and_exclusion",
                  "excluded_rare", "filter_on_tiles"):
        body = dep.body(cases[shape])
        sf0 = dict(svc._batcher.serve_filtered)
        handle = tracing.begin("search", index=svc.name)
        served = svc.search(json.loads(json.dumps(body)))
        tracing.end(handle)
        total, page = oracle_answer(dep, body, "two-segments")
        assert served["hits"]["total"]["value"] == total, shape
        assert [(h["_id"], round(h["_score"], 4))
                for h in served["hits"]["hits"]] == page, shape
        spans = tracing.recent(1)[0]["spans"]
        (collect,) = [s for s in spans if s["name"] == "collect"]
        assert collect["tags"]["merged"] is True
        sf1 = svc._batcher.serve_filtered
        if "filter" in cases[shape]:
            assert len([s for s in spans
                        if s["name"] == "filter_mask"]) == 2
            assert sf1["mask_launches"] == sf0["mask_launches"] + 2
            assert (sf1["rows_passed"] - sf0["rows_passed"]
                    == 2 * dep.df_of(cases[shape]))  # the bags are the
            # configuration's: a tag's df is the same in both seeds' segments


def test_deleted_passages_do_not_pass(dep):
    svc = dep.make_index("with-deletes", dep.corpus["segment"])
    body = dep.body(shape_cases(dep)["filter_on_a_bit_row"])
    first = svc.search(json.loads(json.dumps(body)))
    gone = [int(h["_id"]) for h in first["hits"]["hits"][:3]]
    eng = svc.shards[0]
    live = np.ones(DOCS, bool)
    live[gone] = False
    eng.live_docs = [live]
    eng.change_generation += 1
    served = svc.search(json.loads(json.dumps(body)))
    assert (served["hits"]["total"]["value"]
            == first["hits"]["total"]["value"] - 3)
    assert not {int(h["_id"]) for h in served["hits"]["hits"]} & set(gone)
    total, page = oracle_answer(dep, body, "with-deletes")
    assert served["hits"]["total"]["value"] == total
    assert [(h["_id"], round(h["_score"], 4))
            for h in served["hits"]["hits"]] == page


# ---- the launch against an oracle written out by hand ----------------------

FILTERS = ("none", "bit_row_tag", "scattered_tag", "terms_of_both",
           "absent_tag")
MUST_NOTS = ("none", "one_term", "two_word_match", "dense_row_term")
SCORED = ("must", "should_msm_1", "should_msm_2")


@pytest.fixture(scope="module")
def thinned(dep):
    """The deployment's segment in an index of its own, a tenth of its
    passages deleted (every tenth id, so every tag and word loses some)."""
    svc = dep.make_index("thinned", dep.corpus["segment"])
    live = np.ones(DOCS, bool)
    live[3::10] = False
    svc.shards[0].live_docs = [live]
    svc.shards[0].change_generation += 1
    return svc, live


def parity_query(dep, flt_kind: str, not_kind: str, scored: str,
                 shift: int = 0) -> dict:
    """One bool of the parity grid; `shift` picks other words and tags
    of the same kinds (the rows beside the one under test)."""
    df, order = by_df(dep)
    parts = dep.svc._executor(dep.svc.shards[0]).fused_parts(0, "body")
    pf = dep.corpus["segment"].postings["body"]
    hot = [int(t) for t in order[50:400]
           if pf.term_id(dep.word(int(t))) in parts["hot_rank"]]
    rare = [int(t) for t in order if 3 <= df[t] < MIN_DF]
    a, c, e, b = hot[4 * shift: 4 * shift + 4]
    r0, r1 = rare[2 * shift: 2 * shift + 2]
    scattered = [r for r in range(2, 10_000)
                 if 2 <= dep.tag_df[dep.tag_by_rank[r - 1]] < MIN_DF]
    q = {}
    if scored == "must":
        q["must"] = [term(dep, a), term(dep, c)]
    else:
        q["should"] = [term(dep, a), term(dep, c),
                       {"match": {"body": f"{dep.word(e)} {dep.word(r1)}"}}]
        q["minimum_should_match"] = int(scored[-1])
    if flt_kind != "none":
        q["filter"] = [{
            "bit_row_tag": flt(dep, 1 + shift),
            "scattered_tag": flt(dep, scattered[shift]),
            "terms_of_both": {"terms": {"tag": [
                dep.tag(2 + shift), dep.tag(scattered[3 + shift])]}},
            "absent_tag": flt(dep, dep.tag_name(10_007 + shift)),
        }[flt_kind]]
    if not_kind != "none":
        q["must_not"] = [{
            "one_term": term(dep, r0),
            "two_word_match": {"match": {
                "body": f"{dep.word(b)} {dep.word(r0)}"}},
            "dense_row_term": term(dep, b),
        }[not_kind]]
    return q


def by_hand(dep, q: dict, live: np.ndarray):
    """(the passages that match, each one's score): sets of passage ids
    from the raw posting stream and the raw bags, nothing of the
    program's; the scores are the plain reference's float64 plane."""
    ref = dep.corpus["reference"]

    def holders(clause) -> set:
        (_kind, inner), = clause.items()
        out = set()
        for w in inner["body"].split():
            t = int(w[1:])
            out |= set(ref["post_doc"][
                ref["post_start"][t]:ref["post_start"][t + 1]].tolist())
        return out

    def tagged(clause) -> set:
        (kind, inner), = clause.items()
        names = inner["tag"] if kind == "terms" else [inner["tag"]]
        want = {int(n[1:]) for n in names}
        out = set()
        for i in range(DOCS):
            bag = ref["bag_tags"][ref["bag_start"][i]:ref["bag_start"][i + 1]]
            if want & set(bag.tolist()):
                out.add(int(ref["bag_row"][i]))
        return out

    match = set(np.flatnonzero(live).tolist())
    for c in q.get("must", []):
        match &= holders(c)
    if "should" in q:
        hits = {}
        for c in q["should"]:
            for d in holders(c):
                hits[d] = hits.get(d, 0) + 1
        match &= {d for d, n in hits.items()
                  if n >= q["minimum_should_match"]}
    for c in q.get("filter", []):
        match &= tagged(c)
    for c in q.get("must_not", []):
        match -= holders(c)
    _hit, plane = dep.ref._bool(q, False)
    return match, plane


@pytest.mark.parametrize("rows", [1, 2, 4])
@pytest.mark.parametrize("scored", SCORED)
@pytest.mark.parametrize("not_kind", MUST_NOTS)
@pytest.mark.parametrize("flt_kind", FILTERS)
def test_launch_matches_the_oracle_by_hand(dep, thinned, flt_kind, not_kind,
                                           scored, rows):
    """One fused launch of `rows` jobs of one group key over a segment
    with deleted passages, every row under its own filter and excluded
    terms: each row's ids, scores and total are the oracle's (totals
    counted from sets; pages of no hit among them)."""
    svc, live = thinned
    qs = [parity_query(dep, flt_kind, not_kind, scored, shift=i)
          for i in range(rows)]
    jobs = jobs_of(dep, qs, svc=svc)
    b = svc._batcher
    falls0 = b.stats["serve_fallback_jobs"]
    b._collect_serve_group(
        jobs, 16, b._dispatch_serve_group(jobs, 16, rows=rows))
    assert b.stats["serve_fallback_jobs"] == falls0
    for q, job in zip(qs, jobs):
        assert job.error is None
        match, plane = by_hand(dep, q, live)
        assert job.result.total == len(match)
        ranked = sorted(match, key=lambda d: (-plane[d], d))[:11]
        expected = {"hits": {"total": {"value": len(match), "relation": "eq"},
                             "hits": [{"_id": str(d), "_score": plane[d]}
                                      for d in ranked]}}
        got = compare_one("exact", 1e-5, dep.body(q), served_of(job), expected)
        assert got["page_ok"], got["why"]
        assert got["score_rel"] <= 1e-5
        if flt_kind == "absent_tag":
            assert not match and not job.result.hits


# ---- spans, counters, transfers ---------------------------------------------

def traced(dep, body):
    tracing.clear()
    handle = tracing.begin("search", index=dep.svc.name)
    dep.svc.search(json.loads(json.dumps(body)))
    tracing.end(handle)
    return tracing.recent(1)[0]["spans"]


@pytest.mark.parametrize("shape,filtered,negated", [
    ("filter_on_a_bit_row", True, False), ("excluded_rare", False, True),
    ("filter_and_exclusion", True, True)])
def test_spans_say_filtered_and_negated(dep, shape, filtered, negated):
    spans = traced(dep, dep.body(shape_cases(dep)[shape]))
    (plan,) = [s for s in spans if s["name"] == "plan"]
    assert plan["tags"]["family"] == "serve" and plan["tags"]["planned"]
    assert (plan["tags"]["filtered"], plan["tags"]["negated"]) == (
        filtered, negated)
    (dispatch,) = [s for s in spans if s["name"] == "dispatch"]
    assert dispatch["tags"]["filtered"] == filtered
    assert dispatch["tags"]["filter_clauses"] == int(filtered)
    assert dispatch["tags"]["excluded_terms"] == int(negated)
    masks = [s for s in spans if s["name"] == "filter_mask"]
    assert len(masks) == int(filtered)
    if filtered:
        (mask,) = masks
        assert mask["parent_id"] == dispatch["id"]
        assert mask["tags"]["launches"] == 0  # built inside the fused launch
        assert mask["tags"]["segment"] == 0 and mask["tags"]["tiles"] == 0
        assert mask["tags"]["bitset_terms"] == 1
        assert mask["tags"]["bitset_rows_held"] >= 3
        assert dispatch["tags"]["launches"] == 1


def test_spans_of_a_rest_request_reach_the_trace_ring(dep):
    """A filtered, negated `bool` POSTed over HTTP: its trace in
    `GET /_internal/traces` holds the job's spans under `shard_search`,
    `dispatch` tagged as the group was and `filter_mask` under it."""
    call(dep.port, "/_internal/traces", {}, "DELETE")
    dep.search(dep.body(shape_cases(dep)["filter_and_exclusion"]))
    for _ in range(100):  # the trace reaches the ring after the response
        traces = call(dep.port, "/_internal/traces?n=8", {}, "GET")["traces"]
        spans = [t["spans"] for t in traces
                 if any(s["name"] == "dispatch" for s in t["spans"])]
        if spans:
            break
    (spans,) = spans
    by_name = {s["name"]: s for s in spans}
    assert {"http", "coordinator", "shard_search", "plan", "queue_wait",
            "dispatch", "filter_mask", "inflight", "collect",
            "wake"} <= set(by_name)
    tags = by_name["dispatch"]["tags"]
    assert (tags["family"], tags["filtered"], tags["filter_clauses"],
            tags["excluded_terms"], tags["launches"]) == (
        "serve", True, 1, 1, 1)
    assert by_name["filter_mask"]["parent_id"] == by_name["dispatch"]["id"]
    assert by_name["filter_mask"]["tags"]["launches"] == 0
    assert by_name["plan"]["tags"]["filtered"] is True
    assert by_name["plan"]["tags"]["negated"] is True


def test_unfiltered_bool_says_neither(dep):
    spans = traced(dep, dep.body(
        {"must": shape_cases(dep)["filter_on_a_bit_row"]["must"]}))
    (plan,) = [s for s in spans if s["name"] == "plan"]
    assert (plan["tags"]["filtered"], plan["tags"]["negated"]) == (
        False, False)
    (dispatch,) = [s for s in spans if s["name"] == "dispatch"]
    assert not {"filtered", "filter_clauses",
                "excluded_terms"} & set(dispatch["tags"])
    assert not [s for s in spans if s["name"] == "filter_mask"]


def test_every_transfer_of_a_filtered_job_is_counted(dep):
    """Up: the fused plan, a bool's tie scalar and the mask plan (3 x 8
    + 1 int32 a row); down: the packed page with the passed count in
    it. One sync."""
    body = dep.body(shape_cases(dep)["filter_on_tiles"])
    dep.search(body)  # warm
    t0 = dep.node()["transfer"]["scoring"]
    dep.search(body)
    t1 = dep.node()["transfer"]["scoring"]
    fs = dep.svc._executor(dep.svc.shards[0]).fused_scorer_mf(0, ("body",))
    up = 4 * int(np.prod(fs.plan_shape_rows(1))) + 4 + 4 * (3 * 8 + 1)
    assert t1["h2d_bytes"] - t0["h2d_bytes"] == up
    assert t1["d2h_count"] - t0["d2h_count"] == 1
    assert t1["d2h_bytes"] - t0["d2h_bytes"] == 4 * (2 * 16 + 2)


# ---- what the planner turns away, counted ----------------------------------

def turned_away(dep) -> dict:
    df, order = by_df(dep)
    a, b = int(order[60]), int(order[61])
    must = [term(dep, a)]
    many = [dep.tag(r) for r in range(1, 70)]
    banned = " ".join(dep.word(int(t)) for t in order[100:117])
    return {
        "range_filter": {"must": must, "filter": [
            {"range": {"tag": {"gte": "t0001", "lt": "t0004"}}}]},
        "exists_filter": {"must": must,
                          "filter": [{"exists": {"field": "tag"}}]},
        "prefix_filter": {"must": must,
                          "filter": [{"prefix": {"tag": "t00"}}]},
        "filter_on_a_text_field": {"must": must, "filter": [term(dep, b)]},
        "filter_that_is_a_bool_with_a_should": {"must": must, "filter": [
            {"bool": {"should": [flt(dep, 1), flt(dep, 2)]}}]},
        "should_only_beside_a_filter": {"should": must,
                                        "filter": [flt(dep, 1)]},
        "more_filter_terms_than_a_plan_holds": {"must": must, "filter": [
            {"terms": {"tag": many}}]},
        "must_not_of_a_phrase": {"must": must, "must_not": [
            {"match_phrase": {"body": f"{dep.word(a)} {dep.word(b)}"}}]},
        "must_not_of_a_keyword_term": {"must": must,
                                       "must_not": [flt(dep, 1)]},
        "must_not_alone": {"must_not": [term(dep, b)]},
        "more_excluded_terms_than_the_digit_holds": {
            "must": must, "must_not": [{"match": {"body": banned}}]},
        "four_counted_clauses_of_several_words_and_an_exclusion": {
            "must": [{"match": {"body": f"{dep.word(a)} {dep.word(b + i)}"}}
                     for i in range(4)],
            "must_not": [term(dep, b)]},
    }


AWAY = [
    "range_filter", "exists_filter", "prefix_filter",
    "filter_on_a_text_field", "filter_that_is_a_bool_with_a_should",
    "should_only_beside_a_filter", "more_filter_terms_than_a_plan_holds",
    "must_not_of_a_phrase", "must_not_of_a_keyword_term", "must_not_alone",
    "more_excluded_terms_than_the_digit_holds",
    "four_counted_clauses_of_several_words_and_an_exclusion",
]


@pytest.mark.parametrize("case", AWAY)
def test_what_the_planner_turns_away_is_counted_and_answered(dep, case):
    body = dep.body(turned_away(dep)[case])
    assert batcher_mod.extract_serve_plan(
        dsl.parse_query(body["query"]), dep.svc.mappings,
        dep.svc.analysis) is None
    before = dep.node()["pipeline"]["batching"]["unplanned_queries"]
    served = dep.search(body)
    assert (dep.node()["pipeline"]["batching"]["unplanned_queries"]
            == before + 1)
    total, page = oracle_answer(dep, body)
    assert served["hits"]["total"]["value"] == min(total, 10_000)
    assert [(h["_id"], round(h["_score"], 4))
            for h in served["hits"]["hits"]] == page


def test_filters_on_two_fields_are_turned_away(dep):
    mappings = {"properties": {**dep.corpus["mappings"]["properties"],
                               "lang": {"type": "keyword"},
                               "year": {"type": "integer"}}}
    call(dep.port, "/two-fields", {"settings": dep.config["settings"],
                                   "mappings": mappings}, "PUT")
    svc = dep.server.cluster.indices["two-fields"]
    must = [{"term": {"body": "w1"}}]
    for flts in ([flt(dep, 1), {"term": {"lang": "en"}}],
                 [{"term": {"year": 2020}}]):
        q = dsl.parse_query({"bool": {"must": must, "filter": flts}})
        assert batcher_mod.extract_serve_plan(
            q, svc.mappings, svc.analysis) is None
    q = dsl.parse_query({"bool": {"must": must,
                                  "filter": [{"term": {"lang": "en"}}]}})
    plan = batcher_mod.extract_serve_plan(q, svc.mappings, svc.analysis)
    assert plan.filter.field == "lang" and plan.filter.clauses == (("en",),)


def test_mesh_twin_and_retriever_leg_leave_it_to_the_shard(dep):
    """Neither plans a filtered or negated bool: `_plan_leg` gives no
    handle (the sync path runs the shard search), as for any query the
    mesh step cannot take."""
    cases = shape_cases(dep)
    for shape in ("filter_on_a_bit_row", "excluded_hot"):
        assert dep.svc._plan_leg(
            "standard", {"query": {"bool": cases[shape]}}, 10, None) is None
    handle = dep.svc._plan_leg("standard", {"query": {"bool": {
        "must": cases["filter_on_a_bit_row"]["must"]}}}, 10, None)
    assert handle is not None and handle[2] == "serve"


def test_mask_plan_that_fails_falls_back_and_is_counted(dep):
    body = dep.body(shape_cases(dep)["filter_on_a_bit_row"])
    sf0 = dep.node()["serve_filtered"]
    pool0 = dep.node()["thread_pool"]["search"]["serve_fallback_jobs"]
    faults.configure({"rules": [{"site": "serve.filter", "kind": "error"}]})
    try:
        served = dep.search(body)
    finally:
        faults.clear()
    dep.held(body, served)
    sf1 = dep.node()["serve_filtered"]
    assert sf1["fallbacks"] == sf0["fallbacks"] + 1
    assert sf1["mask_launches"] == sf0["mask_launches"]
    assert (dep.node()["thread_pool"]["search"]["serve_fallback_jobs"]
            == pool0 + 1)


# ---- the check fails where it must ------------------------------------------

def test_bf16_control_fails_by_scores_not_totals(dep):
    from compare import compare_all

    g = dep.config["guarantees"]
    bodies = dep.bodies[:96]
    refs = dep.ref.answer_many([reference_body(g["rule"], b) for b in bodies])
    sound = compare_all(g, bodies, dep.ref.answer_many(bodies), refs)
    assert sound["correct"], sound
    low = compare_all(g, bodies,
                      dep.ref.answer_many(bodies, precision="lower"), refs)
    assert not low["correct"]
    assert low["numbers"]["score_rel_max"][0] > 10 * g["score_rtol"]
    assert low["numbers"]["total_mismatches"][0] == 0


@pytest.mark.parametrize("dropped", ["filter", "must_not"])
def test_a_dropped_filter_or_exclusion_fails_the_check(dep, dropped):
    """The answers to the same bodies without their `filter` (or their
    `must_not`) put in the program's place: pages and totals differ."""
    from compare import compare_all

    g = dep.config["guarantees"]
    bodies = [b for b in dep.bodies if dropped in b["query"]["bool"]][:48]
    broken = []
    for b in bodies:
        q = {k: v for k, v in b["query"]["bool"].items() if k != dropped}
        broken.append(dep.search({**b, "query": {"bool": q}}))
    refs = dep.ref.answer_many([reference_body(g["rule"], b) for b in bodies])
    out = compare_all(g, bodies, broken, refs)
    assert not out["correct"]
    assert out["numbers"]["total_mismatches"][0] > 0
    assert out["numbers"]["page_mismatches"][0] > 0


# ---- an unfiltered launch is the parent's program ----------------------------

def lowered_text(dep, **kw) -> str:
    fs = dep.svc._executor(dep.svc.shards[0]).fused_scorer_mf(0, ("body",))
    plan = np.zeros(fs.plan_shape_rows(1), np.int32)
    return scoring._fused_query_mf.lower(
        tuple(p["doc_ids"] for p in fs.parts),
        tuple(p["tfs"] for p in fs.parts),
        tuple(p["inv_norm"] for p in fs.parts),
        tuple(p["dense"] for p in fs.parts),
        fs.live, plan, None, tuple(p["wide"] for p in fs.parts),
        t_rare=fs.t_rare, n_hot=fs.n_hot_slots, k=10, combine="sum", **kw,
    ).as_text()


def test_unfiltered_launch_lowers_to_the_program_it_was(dep):
    """The lowering of a launch with no filter and no excluded term holds
    nothing of either (no operand, no shift by the veto's digit),
    whichever way it is spelled, and is a different text from both."""
    bare = lowered_text(dep)
    assert bare == lowered_text(dep, fmask=None, negated=False)
    digit = scoring.COUNT_TERM_BITS + (
        scoring.CLAUSE_DIGIT_BITS * scoring.VETO_DIGIT)
    assert f"dense<{digit}>" not in bare and "4369" in bare  # 0x1111
    negated = lowered_text(dep, negated=True)
    assert f"dense<{digit}>" in negated and "273" in negated  # 0x0111
    ex = dep.svc._executor(dep.svc.shards[0])
    dp = ex.device_segments[0].postings["tag"]
    fplan = np.zeros((1, 3 * 8 + 1), np.int32)
    masked = lowered_text(dep, fmask=(dp.doc_ids, fplan, dp.filter_bits.plane))
    assert len({bare, negated, masked}) == 3
    assert bare.count("func.func") <= masked.count("func.func")


def _made_up_segment():
    rng = np.random.default_rng(0)
    n, tiles = 1000, 12
    doc_ids = np.sort(rng.integers(0, n, (tiles, 128)), axis=1).astype(np.int32)
    tfs = rng.integers(1, 5, (tiles, 128)).astype(np.int32)
    return n, doc_ids, tfs


def _fused_text(**kw) -> str:
    n, doc_ids, tfs = _made_up_segment()
    return scoring._fused_query_mf.lower(
        (doc_ids,), (tfs,), (np.ones(n, np.float32),),
        (np.zeros((4, n), np.uint8),), np.ones(n, bool),
        np.zeros((2, 2 * scoring.FUSED_T_RARE + 2 * scoring.FUSED_H + 1),
                 np.int32),
        None, (None,),
        t_rare=scoring.FUSED_T_RARE, n_hot=scoring.FUSED_H, k=10,
        combine="sum", **kw,
    ).as_text()


def _mask_text(with_bits: bool) -> str:
    n, doc_ids, _tfs = _made_up_segment()
    bits = (np.zeros((3, scoring.filter_bit_words(n)), np.uint32),
            ) if with_bits else ()
    return scoring.knn_filter_mask.lower(
        doc_ids, np.ones(n, bool), np.zeros((2, 25), np.int32), *bits,
    ).as_text()


# program -> (its lowering on a fixed made-up segment, the digest that
# text has at this PR's parent, c64ddfe, computed there with these very
# functions)
PARENT_PROGRAMS = {
    "serve_launch": (
        lambda: _fused_text(),
        "3d8933d52d847db085edac4b19219705b0de1a6fe38bcd6ac719723cce7c35a5"),
    "match_launch": (
        lambda: _fused_text(counted=False),
        "19a348dfe662e0d5fbc7d5cf779b93509a70c2c219dba8001422ab20571031d7"),
    "knn_mask_scattered": (
        lambda: _mask_text(False),
        "3a66f288969243b5892f1e9a78db7101c63d7b751f1fe2047c3be88c05810d89"),
    "knn_mask_bit_rows": (
        lambda: _mask_text(True),
        "9ca36df01ba547ec1e065870eb8c0764e04c966b9aa86893d1afb12f3dd90dd8"),
}


@pytest.mark.parametrize("program", sorted(PARENT_PROGRAMS))
def test_untouched_launch_text_is_the_parents(program):
    """An unfiltered, un-negated serve launch, a `match` launch and the
    knn family's mask program (whose body the fused program now traces
    too) lower to the parent's program text. A change that alters one
    of them on purpose updates its digest."""
    lower, digest = PARENT_PROGRAMS[program]
    assert hashlib.sha256(lower().encode()).hexdigest() == digest
