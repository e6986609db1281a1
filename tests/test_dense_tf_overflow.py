"""Dense hot-term rows at document length (PR 27): a hot term whose tf
passes `scoring.DENSE_TF_MAX` (255) in some document keeps a dense row,
a uint16 one; nothing is clipped.

Contract under test: on a two-field segment holding hot terms whose
largest tf is 255, 256 and 4,000, the fused program (`_fused_query_mf`:
`match` at one field, `multi_match` as `sum` and `max_tie`) gives
the ids, order, totals and scores (rtol 1e-6) of the NumPy executor, and
the very floats of the all-sparse scoring of the same terms (no dense row
at all) for one- and two-term queries, with the dense budget ample and
exhausted; the serve family's plans show on its `dispatch` span and in
`_nodes/stats`; its per-job fallback is counted, with its transfers.
"""

import json
import urllib.request

import numpy as np
import pytest

from elasticsearch_tpu.cluster.indices import IndexService
from elasticsearch_tpu.common import tracing
from elasticsearch_tpu.ops import scoring
from elasticsearch_tpu.search import executor_jax

N_DOCS = 1300  # a term wants a dense row from max(1024, n // 128) postings
PLAIN = [f"h{i}" for i in range(4)]  # hot, tf 1..3 everywhere
RARE = [f"rare{i:02d}" for i in range(30)]
PEAKS = {"t255": 255, "t256": 256, "t4000": 4000}  # term -> its largest tf
MAPPINGS = {"properties": {"title": {"type": "text"},
                           "body": {"type": "text"}}}


def documents() -> list:
    """Every PLAIN word and `t255` in ~88% of the bodies, `t256` and
    `t4000` in ~97% (the two most frequent terms: an exhausted budget
    keeps exactly their rows); each PEAKS word once at its largest tf,
    and two of them together in one long document. Titles: `t256` in
    ~88% (hot there too), `h0` in half, `onlytitle` in a tenth and in no
    body; `t4000` and `t255` are in no title."""
    rng = np.random.default_rng(27)
    docs = []
    for i in range(N_DOCS):
        body = []
        for w in PLAIN + ["t255"]:
            if rng.random() < 0.88:
                body += [w] * int(rng.integers(1, 4))
        for w in ("t256", "t4000"):
            if rng.random() < 0.97:
                body += [w] * int(rng.integers(1, 4))
        body += list(rng.choice(RARE, int(rng.integers(1, 5))))
        title = ["t256"] if rng.random() < 0.88 else ["untitled"]
        if rng.random() < 0.5:
            title.append("h0")
        if rng.random() < 0.1:
            title.append("onlytitle")
        docs.append({"title": " ".join(title), "body": body})
    for i, (w, peak) in enumerate(PEAKS.items()):
        docs[3 + i]["body"] = [x for x in docs[3 + i]["body"] if x != w] + [w] * peak
    docs[9]["body"] = ([x for x in docs[9]["body"] if x != "t256"]
                       + ["t4000"] * 300 + ["t256"] * 256)
    for d in docs:
        rng.shuffle(d["body"])
        d["body"] = " ".join(d["body"])
    return docs


DOCS = documents()


def make_service(name: str, backend: str) -> IndexService:
    svc = IndexService(
        name, settings={"number_of_shards": 1, "search.backend": backend},
        mappings_json=MAPPINGS,
    )
    for i, d in enumerate(DOCS):
        svc.index_doc(str(i), d)
    svc.refresh()
    return svc


@pytest.fixture(scope="module")
def service():
    """One segment on the jax backend, the fused kernels forced on."""
    orig = executor_jax.FUSED_MIN_DOCS
    executor_jax.FUSED_MIN_DOCS = 10
    svc = make_service("dense-tf", "jax")
    yield svc
    svc.close()
    executor_jax.FUSED_MIN_DOCS = orig


@pytest.fixture(scope="module")
def oracle():
    svc = make_service("dense-tf-oracle", "numpy")
    yield svc
    svc.close()


BUDGETS = {"ample": None, "exhausted": 4 * N_DOCS, "all_sparse": 0}


def with_budget(svc, monkeypatch, budget: str):
    """The segment's fused parts rebuilt under a dense-row budget."""
    if BUDGETS[budget] is not None:
        monkeypatch.setattr(executor_jax, "DENSE_ROWS_HBM_BUDGET",
                            BUDGETS[budget])
    ex = svc._executor(svc.shards[0])
    for cache in (ex._fused_parts, ex._fused_mf):
        cache.clear()
    return ex


def search(svc, body: dict) -> dict:
    return svc.search(json.loads(json.dumps(body)))


def page(resp: dict):
    hits = resp["hits"]["hits"]
    return ([h["_id"] for h in hits], [h["_score"] for h in hits],
            resp["hits"]["total"])


def body_for(path: str, words: str) -> dict:
    if path == "match":
        query = {"match": {"body": words}}
    else:
        query = {"multi_match": {
            "query": words, "fields": ["title", "body"],
            **({"type": "most_fields"} if path == "sum"
               else {"tie_breaker": 0.3})}}
    return {"query": query, "size": 10, "track_total_hits": True}


# one and two terms: the sums are the same floats in any order
SHORT = ["t4000", "t256", "t255", "t4000 t256", "t255 rare03", "h1 t4000",
         "onlytitle", "onlytitle t4000"]
LONG = ["rare01 t4000 t256 h0 h1 t255", "h0 h1 h2 h3 t255 t256 t4000 rare07"]


def test_the_index_holds_what_the_contract_names(service, monkeypatch):
    ex = with_budget(service, monkeypatch, "ample")
    pf = service.shards[0].segments[0].postings["body"]
    for w, peak in PEAKS.items():
        tid = pf.term_id(w)
        s0, c = int(pf.term_tile_start[tid]), int(pf.term_tile_count[tid])
        assert int(pf.tile_max_tf[s0:s0 + c].max()) == peak
    parts = ex.fused_parts(0, "body")
    assert parts["dense"].dtype == np.uint8
    assert parts["wide"].dtype == np.uint16
    # t255 fits a uint8 row; t256 and t4000 hold a uint16 row each
    assert parts["dense"].shape == (len(PLAIN) + 1, N_DOCS)
    assert parts["wide"].shape == (2, N_DOCS)
    assert parts["rows_wanted"] == parts["rows_held"] == len(PLAIN) + 3
    # docs 4 and 9 (t256 x 256), 5 (t4000 x 4000) and 9 (t4000 x 300+)
    assert parts["tf_overflow_postings"] == 4
    n8 = parts["dense"].shape[0]
    assert sorted(parts["hot_rank"][pf.term_id(w)] - n8
                  for w in ("t256", "t4000")) == [0, 1]
    wide = np.asarray(parts["wide"])
    assert wide.max() == 4000 and int(wide[:, 9].min()) == 256  # doc 9

    ex = with_budget(service, monkeypatch, "exhausted")
    parts = ex.fused_parts(0, "body")
    # four rows' worth: the two most frequent terms, two rows each
    assert parts["dense"] is None and parts["wide"].shape == (2, N_DOCS)
    assert (parts["rows_wanted"], parts["rows_held"]) == (len(PLAIN) + 3, 2)


@pytest.mark.parametrize("budget", ["ample", "exhausted"])
@pytest.mark.parametrize("path", ["match", "sum", "max_tie"])
def test_fused_paths_keep_tf_over_255_exact(service, oracle, monkeypatch,
                                            path, budget):
    """ids, order and totals of the NumPy executor, scores within 1e-6
    of it, and bit-equal to the all-sparse scoring for short queries."""
    with_budget(service, monkeypatch, budget)
    stats = service._batcher.stats
    before = dict(stats)
    served = {w: page(search(service, body_for(path, w)))
              for w in SHORT + LONG}
    assert stats["fused_jobs"] - before["fused_jobs"] == len(served)
    assert stats["fused_overflow_jobs"] == before["fused_overflow_jobs"]
    assert stats["serve_fallback_jobs"] == before["serve_fallback_jobs"]
    with_budget(service, monkeypatch, "all_sparse")
    for w, (ids, scores, total) in served.items():
        want_ids, want_scores, want_total = page(
            search(oracle, body_for(path, w)))
        assert ids == want_ids and total == want_total, w
        np.testing.assert_allclose(scores, want_scores, rtol=1e-6, err_msg=w)
        sparse_ids, sparse_scores, sparse_total = page(
            search(service, body_for(path, w)))
        assert (sparse_ids, sparse_total) == (ids, total), w
        if w in SHORT:
            assert scores == sparse_scores, w
        else:
            np.testing.assert_allclose(scores, sparse_scores, rtol=1e-6)
    # the documents past 255 lead their term's page
    assert set(served["t4000"][0][:2]) == {"5", "9"}


@pytest.mark.parametrize("query", [
    # a uint16 and a uint8 row in ONE counted clause of two words: their
    # slot ids carry the clause's counter through the split of the planes
    {"bool": {"must": [{"term": {"body": "rare03"}},
                       {"match": {"body": "t4000 h1"}}]}},
    {"bool": {"must": [{"match": {"body": "t256 rare05"}},
                       {"match": {"body": "t4000 t255 rare07"}}]}},
    {"bool": {"should": [{"match": {"body": "rare01 rare02"}},
                         {"match": {"body": "t256 rare03"}},
                         {"term": {"title": "onlytitle"}}],
              "minimum_should_match": 2}},
], ids=["term_and_both_planes", "two_clauses_both_planes",
        "should_msm2_two_fields"])
def test_clause_counts_ride_wide_and_uint8_rows(service, oracle, monkeypatch,
                                                query):
    with_budget(service, monkeypatch, "ample")
    stats = service._batcher.stats
    before = dict(stats)
    body = {"query": query, "size": 10, "track_total_hits": True}
    ids, scores, total = page(search(service, body))
    assert stats["fused_jobs"] == before["fused_jobs"] + 1
    assert stats["serve_fallback_jobs"] == before["serve_fallback_jobs"]
    assert stats["serve_multi_term_clauses"] > before[
        "serve_multi_term_clauses"]
    want_ids, want_scores, want_total = page(search(oracle, body))
    assert ids == want_ids and total == want_total and total["value"] > 0
    np.testing.assert_allclose(scores, want_scores, rtol=1e-6)


def traced_dispatch_tags(svc, body: dict) -> dict:
    tracing.clear()
    handle = tracing.begin("search", index=svc.name)
    search(svc, body)
    tracing.end(handle)
    spans = tracing.recent(1)[0]["spans"]
    tracing.clear()
    return next(s["tags"] for s in spans if s["name"] == "dispatch")


def test_serve_plans_show_on_the_span_and_in_the_counters(service,
                                                          monkeypatch):
    ex = with_budget(service, monkeypatch, "ample")
    b = service._batcher
    body = body_for("max_tie", "t4000 t256 h0 rare03 onlytitle")
    search(service, body)  # nothing left to build
    before, hist0 = dict(b.stats), b.batching_stats()["serve_hot_slots"]
    tags = traced_dispatch_tags(service, body)
    pf = {f: service.shards[0].segments[0].postings[f]
          for f in ("title", "body")}
    # body: t4000, t256, h0 hold rows, rare03 is sparse; title: t256
    # holds a row, h0 and onlytitle are sparse, the others absent
    rare = {"body": int(pf["body"].term_tile_count[pf["body"].term_id("rare03")]),
            "title": sum(int(pf["title"].term_tile_count[pf["title"].term_id(w)])
                         for w in ("h0", "onlytitle"))}
    assert tags["family"] == "serve" and tags["overflow"] is False
    assert (tags["fields"], tags["hot_slots"]) == (2, 3)
    assert tags["rare_tiles"] == max(rare.values())
    delta = {k: b.stats[k] - before[k] for k in (
        "serve_launches", "serve_rare_tiles", "serve_hot_rows",
        "serve_fallback_jobs", "fused_jobs")}
    assert delta == {"serve_launches": 1, "serve_hot_rows": 3 + 1,
                     "serve_rare_tiles": sum(rare.values()),
                     "serve_fallback_jobs": 0, "fused_jobs": 1}
    hist = b.batching_stats()["serve_hot_slots"]
    assert {h: hist[h] - hist0[h] for h in hist if hist[h] != hist0[h]} == {
        "1": 1, "3": 1}  # one count a field's section
    # a match job's span carries no serve tags
    assert "fields" not in traced_dispatch_tags(
        service, body_for("match", "t4000 h0"))
    assert ex.dense_rows_stats() == {
        "dense_rows_wanted": len(PLAIN) + 3 + 1,
        "dense_rows_held": len(PLAIN) + 3 + 1,
        "dense_tf_overflow_postings": 4,
    }


def test_the_fallback_is_counted_with_its_transfers(service, monkeypatch):
    """A serve job whose plan does not fit runs `segment_topk` per job:
    counted, and its uploads and downloads noted."""
    ex = with_budget(service, monkeypatch, "ample")
    monkeypatch.setattr(ex, "fused_plan_field", lambda *a, **kw: None)
    body = body_for("max_tie", "t4000 onlytitle")
    want = page(search(service, body))  # compiles what the fallback runs
    b = service._batcher
    before, t0 = dict(b.stats), tracing.transfer_stats()
    tags = traced_dispatch_tags(service, body)
    t1 = tracing.transfer_stats()
    assert tags["overflow"] is True and "fields" not in tags
    assert b.stats["serve_fallback_jobs"] - before["serve_fallback_jobs"] == 1
    assert b.stats["fused_overflow_jobs"] - before["fused_overflow_jobs"] == 1
    assert b.stats["serve_launches"] == before["serve_launches"]
    # down: the page's scores, its doc ids and the total; up: a field's
    # tile ids, weights and validity (title has one of the words, body one)
    assert t1["d2h_count"] - t0["d2h_count"] == 3
    assert t1["h2d_count"] - t0["h2d_count"] == 2 * 3
    monkeypatch.undo()
    with_budget(service, monkeypatch, "ample")
    got = page(search(service, body))
    assert got[0] == want[0] and got[2] == want[2]
    np.testing.assert_allclose(got[1], want[1], rtol=1e-6)


def test_nodes_stats_reports_rows_and_serve_counters(service, monkeypatch):
    from elasticsearch_tpu.cluster.service import ClusterService
    from elasticsearch_tpu.rest.actions import RestActions

    with_budget(service, monkeypatch, "exhausted")
    c = ClusterService()
    try:
        c.indices[service.name] = service
        search(service, body_for("sum", "t4000 h0"))
        _, resp = RestActions(c).nodes_stats(None, {}, {})
        node = next(iter(resp["nodes"].values()))
        batching = node["pipeline"]["batching"]
        # body wants 7 rows and holds 2 terms' (4 rows' worth: both
        # uint16); title wants and holds t256's
        assert batching["dense_rows_wanted"] == len(PLAIN) + 3 + 1
        assert batching["dense_rows_held"] == 2 + 1
        assert batching["dense_tf_overflow_postings"] == 4
        assert (batching["serve_hot_slots"]
                == service._batcher.batching_stats()["serve_hot_slots"])
        pool = node["thread_pool"]["search"]
        for k in ("serve_fallback_jobs", "serve_launches",
                  "serve_rare_tiles", "serve_hot_rows"):
            assert pool[k] == service._batcher.stats[k]
        assert pool["serve_launches"] >= 1
    finally:
        c.indices.pop(service.name, None)  # the fixture closes it
        c.close()


# ---------------------------------------------------------------------
# multi_match over HTTP, against the NumPy executor
# ---------------------------------------------------------------------


@pytest.fixture(scope="module")
def rest():
    from elasticsearch_tpu.rest.server import ElasticsearchTpuServer

    orig = executor_jax.FUSED_MIN_DOCS
    executor_jax.FUSED_MIN_DOCS = 10
    srv = ElasticsearchTpuServer(port=0)
    srv.start_background()

    def call(method, path, body=None, ndjson=None):
        data, ctype = None, "application/json"
        if ndjson is not None:
            data = ("\n".join(json.dumps(x) for x in ndjson) + "\n").encode()
            ctype = "application/x-ndjson"
        elif body is not None:
            data = json.dumps(body).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{srv.port}{path}", data=data, method=method,
            headers={"Content-Type": ctype})
        with urllib.request.urlopen(req) as resp:
            return json.loads(resp.read() or b"null")

    for backend in ("jax", "numpy"):
        call("PUT", f"/mm-{backend}", {
            "settings": {"number_of_shards": 1, "search.backend": backend},
            "mappings": MAPPINGS})
        lines = []
        for i, d in enumerate(DOCS):
            lines += [{"index": {"_id": str(i)}}, d]
        out = call("POST", f"/mm-{backend}/_bulk?refresh=true", ndjson=lines)
        assert out["errors"] is False
    yield call
    srv.close()
    executor_jax.FUSED_MIN_DOCS = orig


@pytest.mark.parametrize("tie", [0, 0.3])
@pytest.mark.parametrize("words", [
    "onlytitle",  # in titles only
    "t4000",  # in bodies only, tf over 255
    "t256 onlytitle rare05",  # both fields, one word a field only
    "h0 t256 t4000 rare01 rare02 onlytitle",
])
def test_multi_match_over_http_equals_the_numpy_executor(rest, words, tie):
    body = {"query": {"multi_match": {"query": words,
                                      "fields": ["title", "body"],
                                      "tie_breaker": tie}},
            "size": 10, "track_total_hits": True, "_source": False}
    got, want = (page(rest("POST", f"/mm-{b}/_search", body))
                 for b in ("jax", "numpy"))
    assert got[0] == want[0] and got[2] == want[2]
    np.testing.assert_allclose(got[1], want[1], rtol=1e-6)
    assert len(got[0]) == 10
