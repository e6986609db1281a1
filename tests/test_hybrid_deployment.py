"""The hybrid deployment (BASELINE config 5; `benchmarks/configs/
msmarco-hybrid-rrf.json`) at a small size: upstream's `rrf` retriever
over a `match` leg and an exact kNN leg on ONE segment that holds both
fields, served through `IndexService.search` and held to the benchmark's
own plain reference (`benchmarks/references/rrf_match_knn.py`) by the
benchmark's own rule (`benchmarks/compare.py`, `exact`: ids tie group by
tie group, scores within 1e-5, `hits.total` equal).

The corpus is crafted so that each case is what its name says (the cases
check that too): a word every passage but 300 holds (`hits.total` past
10,000), one 299 hold, one five hold, forty passages of one shape (exact
BM25 ties), two passages with one row (an exact kNN tie).
"""

import json
import os
import sys

import numpy as np
import pytest

from elasticsearch_tpu.cluster import ClusterService
from elasticsearch_tpu.common import tracing
from elasticsearch_tpu.rest.actions import RestActions

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from compare import compare_one, reference_body  # noqa: E402
from plugins import load_plugin  # noqa: E402

N, DIMS, WINDOW = 10_500, 8, 20
COMMON = 10_200  # passages 0..COMMON-1 hold "w0"
FIVE = (11, 222, 3333, 4444, 5555)  # hold "w2"
SAME_SHAPE = range(100, 140)  # exactly "w0 w3": one BM25 score
TWIN = (200, 201)  # one row
BOTH = 350  # exactly "w0 w1", the shortest passage holding "w1"
GUARANTEES = {"rule": "exact", "score_rtol": 1e-5,
              "bm25_k1": 1.2, "bm25_b": 0.75}


def passage(i: int) -> list:
    if i in SAME_SHAPE:
        return ["w0", "w3"]
    if i == BOTH:
        return ["w0", "w1"]
    words = ["w0"] if i < COMMON else []
    if i % 35 == 0:
        words.append("w1")
    if i in FIVE:
        words.append("w2")
    return words + [f"w{10 + i % 7}"] * (i % 5 + 1)


@pytest.fixture(scope="module")
def deployment():
    """(index service, REST actions, plain reference, stored rows)."""
    from elasticsearch_tpu.search import executor_jax

    orig = executor_jax.FUSED_MIN_DOCS
    executor_jax.FUSED_MIN_DOCS = 10  # the deployment's text kernel
    rng = np.random.default_rng(31)
    rows = rng.standard_normal((N, DIMS)).astype(np.float32)
    rows[TWIN[1]] = rows[TWIN[0]]
    cluster = ClusterService()
    cluster.create_index("hybrid", {
        "settings": {"number_of_shards": 1, "search.backend": "jax"},
        "mappings": {"properties": {
            "text": {"type": "text"},
            "vec": {"type": "dense_vector", "dims": DIMS,
                    "similarity": "cosine"},
        }},
    })
    svc = cluster.indices["hybrid"]
    tokens = [passage(i) for i in range(N)]
    for i, words in enumerate(tokens):
        svc.index_doc(str(i), {"text": " ".join(words),
                               "vec": rows[i].tolist()})
    svc.refresh()
    (segment,) = svc.shards[0].segments  # one segment holds both fields
    assert segment.doc_ids[:3] == ["0", "1", "2"]
    # the reference's data: the raw posting stream of the token lists
    # above, and the rows as the field stores them
    term = [np.array([int(w[1:]) for w in ws], np.int64) for ws in tokens]
    doc = np.repeat(np.arange(N), [len(t) for t in term])
    key = np.unique(np.concatenate(term) * N + doc, return_counts=True)
    vocab = int(key[0].max() // N) + 1
    post_start = np.zeros(vocab + 1, np.int64)
    np.cumsum(np.bincount(key[0] // N, minlength=vocab), out=post_start[1:])
    data = {
        "docs": N,
        "text": {"field": "text", "docs": N,
                 "lengths": np.array([len(t) for t in term], np.int64),
                 "post_start": post_start, "post_doc": key[0] % N,
                 "post_tf": key[1]},
        "vector": {"field": "vec", "docs": N,
                   "vectors": segment.vectors["vec"].unit_vectors},
    }
    ref = load_plugin("references", "rrf_match_knn").Reference(
        data, {"guarantees": GUARANTEES})
    yield svc, RestActions(cluster), ref, rows
    cluster.close()
    executor_jax.FUSED_MIN_DOCS = orig


def hybrid(words: str, vector, k=WINDOW, size=10, window=WINDOW) -> dict:
    return {
        "retriever": {"rrf": {
            "retrievers": [
                {"standard": {"query": {"match": {"text": words}}}},
                {"knn": {"field": "vec", "k": k, "num_candidates": 50,
                         "query_vector": [float(x) for x in vector]}},
            ],
            "rank_window_size": window, "rank_constant": 60,
        }},
        "size": size, "_source": False,
    }


def ids(resp: dict) -> list:
    return [int(h["_id"]) for h in resp["hits"]["hits"]]


def random_row(seed: int) -> np.ndarray:
    return np.random.default_rng(seed).standard_normal(DIMS)


# name -> (body from the stored rows, what the served answer must show
# for the case to be the one its name says)
CASES = {
    "disjoint_legs": (
        lambda rows: hybrid("w2", random_row(1), size=40),
        # the union counts every leg's passage once: none is in both; the
        # fused list is cut to the window
        lambda r: r["hits"]["total"] == {"value": 5 + WINDOW, "relation": "eq"}
        and len(set(ids(r))) == WINDOW,
    ),
    "a_document_in_both_legs": (
        lambda rows: hybrid("w1", rows[BOTH]),
        lambda r: ids(r)[0] == BOTH
        and abs(r["hits"]["hits"][0]["_score"] - 2 / 61) < 1e-7,
    ),
    "a_leg_shorter_than_the_window": (
        lambda rows: hybrid("w2", random_row(2)),
        lambda r: set(FIVE) <= set(ids(r)),
    ),
    "score_ties_inside_each_leg": (
        lambda rows: hybrid("w3", rows[TWIN[0]], size=2 * WINDOW),
        # forty passages tie in BM25: the text leg ranks them by id, and
        # the window keeps each leg's first ten; the twins tie at the top
        # of the kNN leg, lower id first
        lambda r: len(ids(r)) == WINDOW
        and set(range(100, 110)) <= set(ids(r))
        and not set(range(110, 140)) & set(ids(r))
        and set(ids(r)[:2]) == {TWIN[0], 100}
        and set(ids(r)[2:4]) == {TWIN[1], 101},
    ),
    "size_past_the_fused_list": (
        lambda rows: hybrid("w2", random_row(3), k=8, size=30),
        lambda r: len(ids(r)) == 5 + 8,
    ),
    "total_below_10000": (
        lambda rows: hybrid("w1", random_row(4)),
        lambda r: r["hits"]["total"]["relation"] == "eq"
        and 299 <= r["hits"]["total"]["value"] <= 299 + WINDOW,
    ),
    "total_above_10000": (
        lambda rows: hybrid("w0 w1", random_row(5)),
        lambda r: r["hits"]["total"] == {"value": 10_000, "relation": "gte"},
    ),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_served_answer_is_the_plain_references(deployment, case):
    svc, _actions, ref, rows = deployment
    make, shows = CASES[case]
    body = make(rows)
    served = svc.search(json.loads(json.dumps(body)))
    (expected,) = ref.answer_many([reference_body("exact", body)])
    got = compare_one("exact", GUARANTEES["score_rtol"], body, served,
                      expected)
    assert got["page_ok"], got["why"]
    assert got["total_ok"], (served["hits"]["total"],
                             expected["hits"]["total"])
    assert got["score_rel"] <= GUARANTEES["score_rtol"]
    assert shows(served), served["hits"]


def test_rank_form_equals_the_retriever_form(deployment):
    """The 8.8 hybrid API (`query` + `knn` + `rank: {rrf}`) rides the
    same path and gives the same answer."""
    svc, _actions, ref, rows = deployment
    body = hybrid("w1 w2", rows[BOTH])
    rrf = body["retriever"]["rrf"]
    ranked = {
        "query": rrf["retrievers"][0]["standard"]["query"],
        "knn": rrf["retrievers"][1]["knn"],
        "rank": {"rrf": {"rank_window_size": WINDOW, "rank_constant": 60}},
        "size": 10, "_source": False,
    }
    a = svc.search(json.loads(json.dumps(body)))
    b = svc.search(json.loads(json.dumps(ranked)))
    assert a["hits"]["total"] == b["hits"]["total"]
    assert [(h["_id"], h["_score"]) for h in a["hits"]["hits"]] == [
        (h["_id"], h["_score"]) for h in b["hits"]["hits"]]
    (expected,) = ref.answer_many([reference_body("exact", body)])
    got = compare_one("exact", 1e-5, body, b, expected)
    assert got["page_ok"] and got["total_ok"], got


def test_no_source_page_reads_no_id_table(deployment):
    """A page that asks for no source builds nothing over the shard's
    ids; one that asks for it reads the hits' own (segment, doc)."""
    svc, _actions, _ref, rows = deployment
    svc.search(hybrid("w2", rows[BOTH]))
    ex = svc.pin_executors()[0]
    assert getattr(ex, "_reader_locations_cache", None) is None
    with_source = {**hybrid("w2", rows[BOTH]), "_source": True}
    resp = svc.search(with_source)
    assert resp["hits"]["hits"][0]["_source"]["text"]
    assert getattr(ex, "_reader_locations_cache", None) is None


def node_numbers(actions) -> dict:
    _status, body = actions.nodes_stats(None, {}, {})
    node = body["nodes"]["node-0"]
    return {**{f"transfer.{k}": v
               for k, v in node["transfer"]["scoring"].items()},
            **{f"rrf.{k}": v for k, v in node["pipeline"]["rrf"].items()}}


def delta(actions, svc, body) -> dict:
    svc.search(json.loads(json.dumps(body)))  # nothing left to build
    before = node_numbers(actions)
    svc.search(json.loads(json.dumps(body)))
    after = node_numbers(actions)
    return {k: after[k] - before[k] for k in after}


def test_spans_and_counters_of_a_hybrid_request(deployment):
    svc, actions, _ref, rows = deployment
    body = hybrid("w0 w1", rows[7])
    rrf = body["retriever"]["rrf"]["retrievers"]
    text_only = {"query": rrf[0]["standard"]["query"], "size": WINDOW,
                 "_source": False}
    knn_only = {"knn": rrf[1]["knn"], "size": WINDOW, "_source": False}
    both = delta(actions, svc, body)
    text, knn = delta(actions, svc, text_only), delta(actions, svc, knn_only)
    # the legs' transfers are those of the two requests alone, and
    # nothing more: the fuse is the host's, over hits it already holds
    for key in ("transfer.h2d_count", "transfer.h2d_bytes",
                "transfer.d2h_count", "transfer.d2h_bytes"):
        assert both[key] == text[key] + knn[key], key
    assert both["rrf.searches"] == 1 and both["rrf.host_fused"] == 1
    assert both["rrf.device_fused"] == 0 and both["rrf.fuse_ms"] > 0
    assert both["rrf.bm25_leg_ms"] > 0 and both["rrf.knn_leg_ms"] > 0
    assert text["rrf.searches"] == knn["rrf.searches"] == 0

    tracing.clear()
    handle = tracing.begin("search", index=svc.name)
    svc.search(json.loads(json.dumps(body)))
    tracing.end(handle)
    spans = tracing.recent(1)[0]["spans"]
    by_id = {s["id"]: s for s in spans}
    by = {s["name"]: s for s in spans if s["name"] not in (
        "queue_wait", "dispatch", "inflight", "collect")}

    def end(span):
        return span["start_ns"] + span["duration_ns"]

    def parent(span):
        return by_id[span["parent_id"]]["name"]

    root = by["coordinator"]
    assert root["parent_id"] is None
    assert {"index", "shards", "took_ms"} <= set(root["tags"])
    # the root is tiled by this path's phases
    tiles = [by["retriever"], by["rescore"], by["fetch"]]
    assert all(parent(t) == "coordinator" for t in tiles)
    assert tiles[0]["start_ns"] == root["start_ns"]
    assert end(tiles[0]) == tiles[1]["start_ns"]
    assert end(tiles[1]) == tiles[2]["start_ns"]
    assert end(tiles[2]) == end(root)
    assert parent(by["rrf"]) == "retriever"
    legs = {name: by[name] for name in ("leg:bm25", "leg:knn")}
    assert all(parent(s) == "rrf" for s in (*legs.values(), by["fuse"]))
    # the job spans hang under their own leg, and a leg ends where its
    # own job's `collect` ends, not where the request thread stopped
    # waiting for it
    for name, family in (("leg:bm25", "match"), ("leg:knn", "knn")):
        jobs = [s for s in spans if s["parent_id"] == legs[name]["id"]]
        assert [s["name"] for s in jobs] == [
            "queue_wait", "dispatch", "inflight", "collect"]
        assert jobs[1]["tags"]["family"] == family
        assert legs[name]["start_ns"] == by["rrf"]["start_ns"]
        assert end(legs[name]) == end(jobs[-1])
    assert by["fuse"]["start_ns"] >= max(end(s) for s in legs.values())
    assert end(by["fuse"]) <= end(by["rrf"])
    assert by["fuse"]["tags"] == {"window": WINDOW}
    assert by["rrf"]["tags"] == {"index": by["rrf"]["tags"]["index"],
                                 "legs": 2}
    # the request thread's own part of the node: planning and submitting
    # the legs, and its wake-up between the last leg and the fuse
    assert parent(by["plan_legs"]) == parent(by["wake"]) == "rrf"
    assert by["plan_legs"]["start_ns"] == by["rrf"]["start_ns"]
    assert by["plan_legs"]["tags"].keys() == {"legs", "bm25_ms", "knn_ms"}
    assert by["wake"]["start_ns"] == max(end(s) for s in legs.values())
    assert end(by["wake"]) == by["fuse"]["start_ns"]


@pytest.mark.parametrize("case", ["ties_reordered", "no_tie_untouched",
                                  "padding_stays_last"])
def test_rank_order_settles_exact_ties_on_the_host(case):
    """The device's top-k promises no order among exact ties (the TPU's
    returns them in any); the host puts a downloaded row in score desc,
    (segment, doc) asc."""
    from elasticsearch_tpu.ops.scoring import rank_order

    inf = np.float32(-np.inf)
    if case == "ties_reordered":
        s = np.array([[3.0, 2.0, 2.0, 2.0, 1.0]], np.float32)
        seg = np.array([[0, 1, 0, 0, 0]], np.int32)
        doc = np.array([[7, 1, 9, 4, 2]], np.int32)
        rs, rseg, rdoc = rank_order(s, seg, doc)
        assert rs.tolist() == s.tolist()
        assert list(zip(rseg[0].tolist(), rdoc[0].tolist())) == [
            (0, 7), (0, 4), (0, 9), (1, 1), (0, 2)]
    elif case == "no_tie_untouched":
        s = np.array([[3.0, 2.0, 1.0, inf, inf]], np.float32)
        seg = np.zeros((1, 5), np.int32)
        doc = np.array([[5, 3, 9, 0, 0]], np.int32)
        out = rank_order(s, seg, doc)
        assert out[0] is s and out[1] is seg and out[2] is doc
    else:
        s = np.array([[2.0, 2.0, inf, inf], [1.0, inf, inf, inf]], np.float32)
        seg = np.zeros((2, 4), np.int32)
        doc = np.array([[8, 3, 0, 0], [6, 0, 0, 0]], np.int32)
        rs, _rseg, rdoc = rank_order(s, seg, doc)
        assert rdoc[0].tolist()[:2] == [3, 8] and rdoc[1, 0] == 6
        assert np.isinf(rs[0, 2:]).all() and np.isinf(rs[1, 1:]).all()
