"""The phrase deployment (luceneutil's HighPhrase / MedPhrase /
LowPhrase task classes as `match_phrase`; `benchmarks/configs/
msmarco-phrase.json`) at a small size, served over HTTP through the
batcher's `phrase` family and held to plain references by the
benchmark's own rule (`benchmarks/compare.py`, `exact`: ids tie group by
tie group, scores within 1e-5, `hits.total` equal).

Two corpora. `dep`: a few thousand seeded passages of the benchmark's
own corpus builder (`corpora/zipf_text_ordered.py`: word order under its
collocation law, the prebuilt segment with its columnar positions),
against the benchmark's plain reference (`references/bm25_phrase.py`,
from the raw token stream). `hand`: texts written here, indexed through
the REST API (the real analyzer, two refreshes: two segments whose
positions `SegmentBuilder._attach_positions` left), against the plain
reference kept in this file (`Hand.answer`: Python lists, nothing of the
program).

Guarantee under test: a passage matches iff the words stand at
consecutive positions in the query's order at least once; `_score` =
boost x (sum of the words' idfs) x f / (f + k1 x (1 - b + b x dl /
avgdl)), f the number of starts (Lucene's PhraseWeight), on the planned
path, the unbatched executor and the oracle alike.
"""

import http.client
import json
import math
import os
import sys

import numpy as np
import pytest

from elasticsearch_tpu.common import tracing
from elasticsearch_tpu.common.faults import faults
from elasticsearch_tpu.common.memory import hbm_ledger
from elasticsearch_tpu.ops import phrase as phrase_ops
from elasticsearch_tpu.search import batcher as batcher_mod
from elasticsearch_tpu.search import dsl

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from compare import compare_all, compare_one, reference_body  # noqa: E402
from plugins import load_json, load_plugin  # noqa: E402
from run import place_segment  # noqa: E402

DOCS, SEED, N_BODIES = 6_000, 5, 60
RTOL = 1e-5
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


def phrase_body(field: str, text: str, **extra) -> dict:
    spec = {"query": text, **extra} if extra else text
    return {"query": {"match_phrase": {field: spec}}, "size": 10,
            "_source": False}


class Server:
    def __init__(self):
        from elasticsearch_tpu.rest.server import ElasticsearchTpuServer

        self.server = ElasticsearchTpuServer(port=0)
        self.server.start_background()
        self.port = self.server.port

    def search(self, index: str, body: dict) -> dict:
        return ok(self.port, "POST", f"/{index}/_search", body)

    def node(self) -> dict:
        return next(iter(
            ok(self.port, "GET", "/_nodes/stats")["nodes"].values()))

    def last_trace(self) -> dict:
        return ok(self.port, "GET", "/_internal/traces?n=1")["traces"][-1]

    def service(self, index: str):
        return self.server.cluster.indices[index]

    def executor(self, index: str):
        svc = self.service(index)
        return svc._executor(svc.shards[0])


@pytest.fixture(scope="module")
def srv():
    s = Server()
    yield s
    s.server.close()


# ---- the benchmark's corpus at a small size --------------------------------

class Deployment:
    def __init__(self, srv: Server):
        self.srv = srv
        self.config = load_json("configs", "msmarco-phrase.json")
        corpus = load_plugin(
            "corpora", self.config["corpus"]["builder"]).build(
                self.config, SEED, DOCS)
        self.corpus = corpus
        self.index = self.config["index"]
        for index in (self.index, "phrase-deletes"):
            ok(srv.port, "PUT", f"/{index}", {
                "settings": self.config["settings"],
                "mappings": corpus["mappings"]})
            place_segment(srv.service(index), corpus["segment"])
        self.ref = load_plugin(
            "references", self.config["reference"]).Reference(
                corpus["reference"], self.config)
        self.gen = load_plugin("bodies", self.config["body"]["generator"])
        # the classes' cuts at this size: the deployment's (shares of a
        # million passages) would leave Low no whole number of passages
        self.args = {**self.config["body"]["args"], "df_share": {
            "High": [0.005, None], "Med": [0.001, 0.005],
            "Low": [0.0003, 0.001]}}
        self.bodies = [json.loads(b) for b in self.gen.make(
            corpus["body_context"], self.args,
            np.random.default_rng([44, 9]), N_BODIES)]
        self.pf = corpus["segment"].postings["body"]
        self.width = corpus["body_context"]["term_width"]

    def word(self, t: int) -> str:
        return f"w{int(t):0{self.width}d}"

    def body(self, terms, **extra) -> dict:
        return phrase_body("body", " ".join(self.word(t) for t in terms),
                           **extra)

    def search(self, body: dict, index=None) -> dict:
        return self.srv.search(index or self.index, body)

    def held(self, body: dict, served: dict) -> dict:
        g = self.config["guarantees"]
        (expected,) = self.ref.answer_many([reference_body(g["rule"], body)])
        got = compare_one(g["rule"], g["score_rtol"], body, served, expected)
        assert got["page_ok"], got["why"]
        assert got["total_ok"], (served["hits"]["total"],
                                 expected["hits"]["total"])
        assert got["score_rel"] <= g["score_rtol"], got["score_rel"]
        return expected


@pytest.fixture(scope="module")
def dep(srv):
    return Deployment(srv)


def served_of(td) -> dict:
    """A TopDocs as the REST layer would report it (small totals)."""
    return {"hits": {
        "total": {"value": td.total, "relation": td.relation},
        "hits": [{"_id": h.doc_id, "_score": h.score} for h in td.hits]}}


@pytest.mark.parametrize("i", range(15))
def test_generated_request_over_http_is_the_plain_references(dep, i):
    body = dep.bodies[i]
    words = body["query"]["match_phrase"]["body"].split()
    assert 2 <= len(words) <= 3 and len(set(words)) == len(words)
    expected = dep.held(body, dep.search(body))
    # a phrase is a run of stored tokens: some passage holds it
    assert expected["hits"]["total"]["value"] >= 1


def test_generated_requests_cover_the_five_classes(dep):
    phrases = dep.gen.class_phrases(dep.corpus["body_context"], dep.args)
    assert set(phrases) == set(dep.gen.CLASSES)
    seen = {dep.gen.class_of(b, "body", phrases) for b in dep.bodies}
    assert seen == set(dep.gen.CLASSES)
    docs = DOCS
    for name, (cls, words) in dep.gen.CLASSES.items():
        lo, hi = dep.args["df_share"][cls]
        assert phrases[name].shape[1] == words and len(phrases[name])
        for row in phrases[name][:: max(1, len(phrases[name]) // 5)][:5]:
            held, _f = dep.ref.phrase_freq(row.tolist())
            assert len(held) >= lo * docs
            assert hi is None or len(held) < hi * docs


def test_phrase_of_the_two_most_frequent_words(dep):
    """Both words stand in most passages; the phrase in far fewer."""
    df = np.asarray(dep.pf.term_df)
    a, b = np.argsort(-df, kind="stable")[:2]
    pairs = [(a, b), (b, a)]
    for x, y in pairs:
        body = dep.body([x, y])
        served = dep.search(body)
        expected = dep.held(body, served)
        held, _f = dep.ref.phrase_freq([int(x), int(y)])
        assert min(df[x], df[y]) > 0.5 * DOCS
        assert 10 < len(held) < min(df[x], df[y])
        assert served["hits"]["total"]["value"] == len(held)
        assert len(expected["hits"]["hits"]) == 11


def test_a_collocation_is_most_of_its_rarer_words_passages(dep):
    """The corpus builder's law plants content collocations: a term
    whose partner follows it with q > 0.6 stands before it in over half
    of its passages, and the phrase answers them."""
    ctx = dep.corpus["body_context"]
    df = np.asarray(dep.pf.term_df)
    strong = [t for t in np.flatnonzero(ctx["q"] > 0.6)
              if t >= 50 and df[t] >= 20][:3]
    assert strong
    for t in strong:
        body = dep.body([t, ctx["partner"][t]])
        served = dep.search(body)
        dep.held(body, served)
        assert served["hits"]["total"]["value"] > 0.5 * df[t]


def test_no_such_word_and_no_such_phrase_answer_nothing(dep):
    df = np.asarray(dep.pf.term_df)
    rare = np.flatnonzero(df == 1)
    a, b = rare[0], rare[-1]  # one passage each, not the same, not adjacent
    for body in (phrase_body("body", "w99999999 " + dep.word(0)),
                 dep.body([a, b])):
        served = dep.search(body)
        dep.held(body, served)
        assert served["hits"]["total"] == {"value": 0, "relation": "eq"}
        assert served["hits"]["hits"] == []
        assert served["hits"]["max_score"] is None


def test_fewer_than_k_hits(dep):
    phrases = dep.gen.class_phrases(dep.corpus["body_context"], dep.args)
    for row in phrases["LowPhrase3"]:
        held, _f = dep.ref.phrase_freq(row.tolist())
        if 2 <= len(held) <= 9:
            break
    body = dep.body(row)
    served = dep.search(body)
    dep.held(body, served)
    assert len(served["hits"]["hits"]) == len(held)
    assert served["hits"]["total"] == {"value": len(held), "relation": "eq"}


def test_deleted_passages_do_not_match(dep):
    df = np.asarray(dep.pf.term_df)
    a, b = np.argsort(-df, kind="stable")[:2]
    body = dep.body([a, b])
    index = "phrase-deletes"
    first = dep.search(body, index)
    dep.held(body, first)
    gone = [int(h["_id"]) for h in first["hits"]["hits"][:3]]
    eng = dep.srv.service(index).shards[0]
    live = np.ones(DOCS, bool)
    live[gone] = False
    eng.live_docs = [live]
    eng.change_generation += 1
    served = dep.search(body, index)
    wide = {**body, "size": 20}
    (expected,) = dep.ref.answer_many([wide])
    want = [h for h in expected["hits"]["hits"]
            if int(h["_id"]) not in gone][:10]
    assert [h["_id"] for h in served["hits"]["hits"]] == [
        h["_id"] for h in want]
    assert served["hits"]["total"]["value"] == (
        first["hits"]["total"]["value"] - 3)


# ---- one launch, several jobs ------------------------------------------------

def phrase_jobs(dep, bodies):
    svc = dep.srv.service(dep.index)
    ex = dep.srv.executor(dep.index)
    jobs = []
    for body in bodies:
        q = dsl.parse_query(body["query"])
        plan = batcher_mod.extract_phrase_plan(q, svc.mappings, svc.analysis)
        assert plan is not None
        jobs.append(batcher_mod._Job(ex, plan, 10, kind="phrase", query=q))
    return svc._batcher, jobs


def test_two_jobs_of_different_phrases_share_one_launch(dep):
    """A group of two phrases of one span at a four-row bucket: one
    launch, each row its own words, pad rows matching nothing."""
    two = [b for b in dep.bodies
           if len(b["query"]["match_phrase"]["body"].split()) == 2]
    bodies = [two[0], two[1]]
    b, jobs = phrase_jobs(dep, bodies)
    share = batcher_mod.FAMILIES["phrase"].share
    assert share(jobs[0].plan) == share(jobs[1].plan)
    before = dict(b.phrase)
    b._collect_phrase_group(
        jobs, 16, b._dispatch_phrase_group(jobs, 16, rows=4))
    after = b.phrase
    assert after["launches"] == before["launches"] + 1
    assert after["searches"] == before["searches"] + 2
    assert after["words"] == before["words"] + 4
    for body, job in zip(bodies, jobs):
        dep.held(body, served_of(job.result))


def test_phrases_of_two_and_three_words_never_share_a_group(dep):
    by_len = {len(b["query"]["match_phrase"]["body"].split()): b
              for b in dep.bodies}
    _b, jobs = phrase_jobs(dep, [by_len[2], by_len[3]])
    share = batcher_mod.FAMILIES["phrase"].share
    assert jobs[0].plan.width == 2 and jobs[1].plan.width == 3
    assert share(jobs[0].plan) != share(jobs[1].plan)


# ---- the normal path: spans, counters, transfers -----------------------------

def candidate_counts(dep, words):
    """(passages holding every word, occurrences of the words inside
    them): from the plain reference's raw token stream."""
    ref = dep.ref
    per_word = []
    for w in words:
        here = ref.tok == w
        per_word.append(np.bincount(ref.slot_passage[here], minlength=DOCS))
    hold = np.all([c > 0 for c in per_word], axis=0)
    return int(hold.sum()), int(sum(c[hold].sum() for c in per_word))


@pytest.mark.parametrize("n_words", [2, 3])
def test_request_is_a_phrase_job_with_its_spans_and_counters(dep, n_words):
    body = next(b for b in dep.bodies
                if len(b["query"]["match_phrase"]["body"].split()) == n_words)
    words = [int(w[1:]) for w in body["query"]["match_phrase"]["body"].split()]
    before = dep.srv.node()
    served = dep.search(body)
    after = dep.srv.node()
    expected = dep.held(body, served)
    p0, p1 = before["phrase"], after["phrase"]
    held, occ = candidate_counts(dep, words)
    tokens = len(dep.ref.tok)
    df_min = int(min(dep.pf.term_df[w] for w in words))
    least = phrase_ops.least_bytes(df_min, DOCS, occ)
    assert least == min(4 * df_min, math.ceil(DOCS / 8)) + occ
    assert {k: p1[k] - p0[k] for k in p1} == {
        "searches": 1, "launches": 1, "words": n_words,
        "occurrences_read": tokens, "candidates": held,
        "candidate_occurrences": occ,
        "matches": expected["hits"]["total"]["value"],
        "least_bytes": least, "fallbacks": 0,
    }
    b0, b1 = (n["pipeline"]["batching"] for n in (before, after))
    assert b1["unplanned_queries"] == b0["unplanned_queries"]
    assert b1["direct_collect_groups"] == b0["direct_collect_groups"] + 1
    spans = {s["name"]: s for s in dep.srv.last_trace()["spans"]}
    shard = spans["shard_search"]
    for name in JOB_SPANS:
        assert spans[name]["parent_id"] == shard["id"], name
    disp = spans["dispatch"]
    assert disp["tags"]["family"] == "phrase"
    assert disp["tags"]["words"] == n_words
    assert disp["tags"]["occurrences"] == tokens
    assert disp["tags"]["launches"] == 1
    plan = spans["phrase_plan"]
    assert plan["parent_id"] == disp["id"]
    assert plan["tags"] == {"segment": 0, "launches": 1, "words": n_words}
    assert spans["collect"]["tags"]["merged"] is False
    assert spans["plan"]["tags"] == {"family": "phrase", "planned": True}


@pytest.mark.parametrize("n_words", [2, 3])
def test_every_transfer_of_a_phrase_job_is_counted(dep, n_words):
    """Up: the plan (one row: a term id and a flag a slot, the weight);
    down: the packed page (scores, docs, the total, two counters)."""
    body = next(b for b in dep.bodies
                if len(b["query"]["match_phrase"]["body"].split()) == n_words)
    dep.search(body)  # the program built, the plane uploaded
    before = tracing.transfer_stats()
    dep.search(body)
    after = tracing.transfer_stats()
    moved = {k: after[k] - before[k] for k in after}
    kb = 16
    assert moved == {
        "h2d_count": 1, "h2d_bytes": 4 * (2 * n_words + 1),
        "d2h_count": 1,
        "d2h_bytes": 4 * (2 * kb + 1 + phrase_ops.PHRASE_EXTRA),
    }


def test_positions_plane_is_charged_and_released_with_the_segment(dep):
    ex = dep.srv.executor(dep.index)
    plane = dep.pf.positions_plane(DOCS)
    dev, inv, live = ex.phrase_plane(0, "body")
    assert dev.occurrences == len(dep.ref.tok) == plane.occurrences
    assert sum(int(m.nbytes) for m in dev.mats) + int(
        dev.order.nbytes) == plane.nbytes
    assert ("positions", plane.nbytes) in ex._charges
    assert hbm_ledger.stats()["by_category"]["positions"] >= plane.nbytes
    # the plane the corpus builder laid out from its forward stream is
    # the one the program builds from the columnar positions a refresh
    # leaves (what a segment indexed over HTTP gets at its first phrase)
    from elasticsearch_tpu.index.segment import build_positions_plane

    turned = build_positions_plane(dep.pf, DOCS)
    assert turned.widths == plane.widths
    assert (turned.order == plane.order).all()
    assert all((a == b).all() for a, b in zip(turned.mats, plane.mats))
    # every token of every passage, at its position, in the plane
    tok, start = dep.ref.tok, dep.ref.doc_start
    for w, m in zip(plane.widths, plane.mats):
        assert m.shape[0] == w and m.dtype == np.int32
    col = {int(d): j for j, d in enumerate(plane.order)}
    bounds = np.cumsum([0] + [m.shape[1] for m in plane.mats])
    for p in (0, 17, DOCS - 1):
        j = col[int(dep.ref.passage_id[p])]
        c = int(np.searchsorted(bounds, j, side="right") - 1)
        column = plane.mats[c][:, j - bounds[c]]
        n = int(start[p + 1] - start[p])
        assert column[:n].tolist() == tok[start[p]: start[p + 1]].tolist()
        assert (column[n:] == -1).all()


# ---- what the planner turns away: counted, and still answered ----------------

def test_match_keeps_its_program_and_uploads_no_positions(srv, dep,
                                                         monkeypatch):
    """A `match` and a `bool` of an index never asked a phrase launch
    the text programs they launched, and its positions stay home."""
    index = "phrase-untouched"
    ok(srv.port, "PUT", f"/{index}", {
        "settings": dep.config["settings"],
        "mappings": dep.corpus["mappings"]})
    place_segment(srv.service(index), dep.corpus["segment"])

    def never(*_a, **_k):
        raise AssertionError("a bag of words reached the phrase kernel")

    monkeypatch.setattr(phrase_ops, "phrase_topk", never)
    text = " ".join(dep.word(t) for t in (0, 3, 700))
    for query in ({"match": {"body": text}},
                  {"bool": {"must": [{"term": {"body": dep.word(3)}}],
                            "should": [{"match": {"body": text}}]}}):
        before = srv.node()["phrase"]
        served = srv.search(index, {"query": query, "size": 10,
                                    "_source": False})
        assert served["hits"]["total"]["value"] > 0
        assert srv.node()["phrase"] == before
    ex = srv.executor(index)
    assert "body" not in ex.device_segments[0].positions._cache
    assert not ex._phrase_planes
    assert not [c for c in ex._charges if c[0] == "positions"]


@pytest.mark.parametrize("case", ["slop", "one_word", "inside_bool",
                                  "too_long"])
def test_what_the_planner_turns_away_is_counted_and_still_answers(dep, case):
    df = np.asarray(dep.pf.term_df)
    a, b = (int(t) for t in np.argsort(-df, kind="stable")[:2])
    svc = dep.srv.service(dep.index)
    if case == "slop":
        query = dep.body([a, b], slop=1)["query"]
    elif case == "one_word":
        query = dep.body([a])["query"]
    elif case == "inside_bool":
        query = {"bool": {"must": [dep.body([a, b])["query"]]}}
    else:
        query = dep.body(list(range(phrase_ops.PHRASE_TERMS_MAX + 1)))["query"]
    parsed = dsl.parse_query(query)
    assert batcher_mod.extract_phrase_plan(
        parsed, svc.mappings, svc.analysis) is None
    before = dep.srv.node()
    served = dep.search({"query": query, "size": 10, "_source": False})
    after = dep.srv.node()
    b0, b1 = (n["pipeline"]["batching"] for n in (before, after))
    assert b1["unplanned_queries"] == b0["unplanned_queries"] + 1
    assert after["phrase"] == before["phrase"]
    plan = [s for s in dep.srv.last_trace()["spans"] if s["name"] == "plan"]
    assert [s["tags"] for s in plan] == [{"family": None, "planned": False}]
    if case == "inside_bool":  # the exact phrase's own answer
        dep.held(dep.body([a, b]), served)
    elif case == "slop":  # at least the exact phrase's passages
        exact = dep.search(dep.body([a, b]))
        assert (served["hits"]["total"]["value"]
                >= exact["hits"]["total"]["value"] > 0)
    elif case == "one_word":
        assert served["hits"]["total"]["value"] == df[a]
    else:
        assert served["hits"]["total"]["value"] == 0


def test_launch_that_fails_falls_back_and_is_counted(dep):
    body = dep.bodies[2]
    want = dep.search(body)
    before = dep.srv.node()["phrase"]
    faults.configure({"rules": [{"site": "phrase.score", "kind": "error"}]})
    try:
        served = dep.search(body)
    finally:
        faults.clear()
    after = dep.srv.node()["phrase"]
    assert after["fallbacks"] == before["fallbacks"] + 1
    assert after["launches"] == before["launches"]
    assert [h["_id"] for h in served["hits"]["hits"]] == [
        h["_id"] for h in want["hits"]["hits"]]
    dep.held(body, served)


def test_plane_the_breaker_refuses_falls_back_and_is_counted(srv, dep):
    """A positions plane the HBM ledger has no room for is not uploaded:
    the segment's phrases are served by `_exec_phrase`, right, counted."""
    index = "phrase-no-room"
    ok(srv.port, "PUT", f"/{index}", {
        "settings": dep.config["settings"],
        "mappings": dep.corpus["mappings"]})
    place_segment(srv.service(index), dep.corpus["segment"])
    body = dep.bodies[1]
    budget = hbm_ledger.budget
    hbm_ledger.budget = hbm_ledger.used + 1024
    try:
        before = srv.node()["phrase"]
        served = srv.search(index, body)
        after = srv.node()["phrase"]
    finally:
        hbm_ledger.budget = budget
    assert after["fallbacks"] == before["fallbacks"] + 1
    assert after["launches"] == before["launches"]
    dep.held(body, served)
    assert "body" not in srv.executor(index).device_segments[0].positions._cache


# ---- the check itself ---------------------------------------------------------

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
    assert c["docs"] == 1_000_000
    assert set(c["reduced"]) == {"docs", "ingest", "fields"}
    passage = load_json("configs", "msmarco-passage-bm25.json")
    for key in ("source_docs", "vocab_at_source", "heaps_beta", "zipf_s",
                "length", "id_block", "stats_seed"):
        assert args[key] == passage["corpus"]["args"][key], key
    assert c["guarantees"]["rule"] == "exact"
    assert c["guarantees"]["score_rtol"] == RTOL
    lengths = np.diff(dep.ref.doc_start)
    assert lengths.min() >= 8 and lengths.max() <= 256
    assert abs(lengths.mean() - 56) < 1.5
    # positions: one for every token, in the layout a refresh leaves
    pf = dep.pf
    assert len(pf.pos_data) == len(dep.ref.tok) == int(pf.term_total_tf.sum())
    assert len(pf.pos_offsets) == int(pf.term_df.sum()) + 1
    t = int(np.argmax(pf.term_df))
    p = int(dep.ref.passage_id[0])
    mine = np.flatnonzero(
        dep.ref.tok[dep.ref.doc_start[0]: dep.ref.doc_start[1]] == t)
    if len(mine):
        assert pf.doc_positions(t, p).tolist() == mine.tolist()


# ---- texts written here, through the REST API: two segments ------------------

HAND = "phrase-hand"
FIRST = [
    "new york city is large",
    "new york new york so good they named it twice",
    "york new jersey",
    "new jersey and old york",
    "the city of new york",
    "to be or not to be that is the question",
    "be to or",
    "a quick brown fox",
    "quick a brown fox",
    "new york is near new york ok",  # 9: the phrase twice in 7 tokens
    "new york is near old jersey ok",  # 10: once in 7 tokens
]
SECOND = [
    "i love new york",
    "new york new york new york",
    "nothing to see here",
    "the quick brown fox jumps over the lazy dog",
    "to be is to do",
]


def filler(rng, n):
    vocab = ("new york city the of to be or not a quick brown fox jersey "
             "old is it so good named large love see here lazy dog").split()
    return [" ".join(rng.choice(vocab, size=int(rng.integers(3, 40))))
            for _ in range(n)]


class Hand:
    """The hand-written corpus and its plain reference: Python lists of
    tokens, BM25 from the published formulas, nothing of the program."""

    def __init__(self, srv: Server):
        self.srv = srv
        rng = np.random.default_rng(44)
        self.texts = (FIRST + filler(rng, 150)) + (SECOND + filler(rng, 120))
        cut = len(FIRST) + 150
        ok(srv.port, "PUT", f"/{HAND}", {
            "settings": {"number_of_shards": 1, "search.backend": "jax"},
            "mappings": {"properties": {"body": {"type": "text"}}}})
        for lo, hi in ((0, cut), (cut, len(self.texts))):
            for i in range(lo, hi):
                ok(srv.port, "PUT", f"/{HAND}/_doc/{i}",
                   {"body": self.texts[i]})
            ok(srv.port, "POST", f"/{HAND}/_refresh")
        self.docs = [t.split() for t in self.texts]
        quantized = load_plugin("references", "bm25_match").quantized_lengths
        self.dl = quantized(np.asarray([len(d) for d in self.docs]))
        self.avgdl = sum(len(d) for d in self.docs) / len(self.docs)

    def freq(self, words, doc) -> int:
        w = len(words)
        return sum(doc[i: i + w] == words for i in range(len(doc) - w + 1))

    def answer(self, text: str, boost: float = 1.0, size: int = 11) -> dict:
        words = text.split()
        n = len(self.docs)
        idf = 0.0
        for w in words:
            df = sum(w in d for d in self.docs)
            idf += math.log(1.0 + (n - df + 0.5) / (df + 0.5))
        hits = []
        for i, doc in enumerate(self.docs):
            f = self.freq(words, doc)
            if f:
                denom = 1.2 * (1 - 0.75 + 0.75 * float(self.dl[i]) / self.avgdl)
                hits.append((-(boost * idf * f / (f + denom)), i))
        hits.sort()
        return {"hits": {
            "total": {"value": len(hits), "relation": "eq"},
            "hits": [{"_id": str(i), "_score": -s} for s, i in hits[:size]]}}

    def held(self, text: str, served: dict, boost: float = 1.0) -> dict:
        body = phrase_body("body", text)
        expected = self.answer(text, boost)
        got = compare_one("exact", RTOL, body, served, expected)
        assert got["page_ok"], got["why"]
        assert got["total_ok"], (served["hits"]["total"],
                                 expected["hits"]["total"])
        assert got["score_rel"] <= RTOL, got["score_rel"]
        return expected


@pytest.fixture(scope="module")
def hand(srv):
    return Hand(srv)


PHRASES = ["new york", "york new", "to be", "be to", "quick brown fox",
           "a quick brown", "new york city", "to be or not to be",
           "the city of new york", "old new", "lazy dog", "of the",
           "new york new york"]


@pytest.mark.parametrize("text", PHRASES)
def test_hand_phrase_over_two_segments_is_the_plain_references(hand, text):
    reader = hand.srv.executor(HAND).reader
    assert len(reader.segments) == 2
    served = hand.srv.search(HAND, phrase_body("body", text))
    hand.held(text, served)
    spans = {s["name"]: s for s in hand.srv.last_trace()["spans"]}
    assert spans["dispatch"]["tags"]["family"] == "phrase"
    assert spans["dispatch"]["tags"]["launches"] == 2  # one a segment
    assert spans["collect"]["tags"]["merged"] is True  # `_group_topk`'s


@pytest.mark.parametrize("text", PHRASES)
def test_unbatched_executor_and_oracle_score_as_the_planned_path(hand, text):
    """`_exec_phrase` of both executors (what a phrase inside a `bool`
    runs) gives the plain reference's page too: phrase frequency as tf,
    summed idf."""
    ex = hand.srv.executor(HAND)
    q = dsl.parse_query({"match_phrase": {"body": text}})
    for executor in (ex, ex._oracle):
        served = served_of(executor.search(q, size=10))
        hand.held(text, served)


def test_a_phrase_standing_twice_scores_above_once(hand):
    """Phrase frequency 2 and 1 score apart, by the formula's own
    ratio: leaving tf out because the page stays right is not support."""
    served = hand.srv.search(HAND, {**phrase_body("body", "new york"),
                                    "size": 400})
    score = {int(h["_id"]): h["_score"] for h in served["hits"]["hits"]}
    once, twice = 0, 1  # FIRST[0], FIRST[1]
    assert hand.freq(["new", "york"], hand.docs[twice]) == 2
    assert hand.freq(["new", "york"], hand.docs[once]) == 1

    def tf(f, i):
        return f / (f + 1.2 * (0.25 + 0.75 * float(hand.dl[i]) / hand.avgdl))

    assert score[twice] / score[once] == pytest.approx(
        tf(2, twice) / tf(1, once), rel=1e-5)
    # at EQUAL length the passage holding it twice ranks above
    assert len(hand.docs[9]) == len(hand.docs[10]) == 7
    assert score[9] > score[10]
    assert score[9] / score[10] == pytest.approx(tf(2, 9) / tf(1, 10),
                                                 rel=1e-5)


def test_words_present_but_not_adjacent_or_reversed_do_not_match(hand):
    served = hand.srv.search(HAND, {**phrase_body("body", "new york"),
                                    "size": 400})
    ids = {int(h["_id"]) for h in served["hits"]["hits"]}
    assert {0, 1, 4} <= ids
    assert not ids & {2, 3}  # "york new jersey", "new jersey and old york"
    served = hand.srv.search(HAND, {**phrase_body("body", "york new"),
                                    "size": 400})
    ids = {int(h["_id"]) for h in served["hits"]["hits"]}
    assert 2 in ids and 1 in ids and not ids & {0, 3, 4}


def test_a_word_repeated_inside_the_phrase_is_held(hand):
    text = "to be or not to be"
    served = hand.srv.search(HAND, phrase_body("body", text))
    expected = hand.held(text, served)
    assert expected["hits"]["total"]["value"] >= 1
    assert served["hits"]["hits"][0]["_id"] == "5"


def test_boost_multiplies_the_score(hand):
    body = phrase_body("body", "quick brown fox", boost=2.5)
    served = hand.srv.search(HAND, body)
    hand.held("quick brown fox", served, boost=2.5)


def test_a_stop_filter_leaves_a_hole_the_phrase_keeps(srv):
    """An analyzer that removes stop words leaves position increments:
    "fox over dog" must stand two apart, whatever word fills the hole."""
    index = "phrase-stops"
    ok(srv.port, "PUT", f"/{index}", {
        "settings": {"number_of_shards": 1, "search.backend": "jax",
                     "analysis": {"analyzer": {"stops": {
                         "type": "standard", "stopwords": ["the", "a"]}}}},
        "mappings": {"properties": {"body": {
            "type": "text", "analyzer": "stops"}}}})
    texts = ["fox jumps the dog", "fox jumps dog", "fox jumps a dog barks",
             "fox the jumps dog"]
    for i, t in enumerate(texts):
        ok(srv.port, "PUT", f"/{index}/_doc/{i}", {"body": t})
    ok(srv.port, "POST", f"/{index}/_refresh")
    svc = srv.service(index)
    plan = batcher_mod.extract_phrase_plan(
        dsl.parse_query({"match_phrase": {"body": "jumps the dog"}}),
        svc.mappings, svc.analysis)
    if plan is None or plan.rel != (0, 2):
        pytest.skip("this analyzer keeps no position increments")
    before = srv.node()["phrase"]["launches"]
    served = srv.search(index, phrase_body("body", "jumps the dog"))
    assert srv.node()["phrase"]["launches"] == before + 1
    assert sorted(h["_id"] for h in served["hits"]["hits"]) == ["0", "2"]
    oracle = srv.executor(index)._oracle.search(
        dsl.parse_query({"match_phrase": {"body": "jumps the dog"}}), size=10)
    assert sorted(h.doc_id for h in oracle.hits) == ["0", "2"]


def test_tokens_stacked_at_one_position_get_no_plane_and_still_match(srv):
    """An index-time synonym filter puts two tokens at one position; a
    slot of the plane holds one term, so such a field gets no plane and
    its phrases are served by `_exec_phrase`, counted."""
    index = "phrase-synonyms"
    ok(srv.port, "PUT", f"/{index}", {
        "settings": {"number_of_shards": 1, "search.backend": "jax",
                     "analysis": {
                         "filter": {"syn": {"type": "synonym",
                                            "synonyms": ["quick, fast"]}},
                         "analyzer": {"stacked": {
                             "tokenizer": "standard",
                             "filter": ["lowercase", "syn"]}}}},
        "mappings": {"properties": {"body": {
            "type": "text", "analyzer": "stacked",
            "search_analyzer": "standard"}}}})
    texts = ["the quick fox", "the fast fox", "a slow fox", "fast the fox"]
    for i, t in enumerate(texts):
        ok(srv.port, "PUT", f"/{index}/_doc/{i}", {"body": t})
    ok(srv.port, "POST", f"/{index}/_refresh")
    pf = srv.executor(index).reader.segments[0].postings["body"]
    assert pf.positions_plane(len(texts)) is None
    before = srv.node()
    for text in ("quick fox", "fast fox", "the fast"):
        served = srv.search(index, phrase_body("body", text))
        assert sorted(h["_id"] for h in served["hits"]["hits"]) == ["0", "1"]
    after = srv.node()
    assert after["phrase"]["fallbacks"] == before["phrase"]["fallbacks"] + 3
    assert after["phrase"]["launches"] == before["phrase"]["launches"]
