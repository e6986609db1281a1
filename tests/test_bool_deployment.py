"""The Boolean deployment (BASELINE config 2; `benchmarks/configs/
msmarco-bool-wand.json`) at a small size: luceneutil's eight Boolean task
classes over a few thousand seeded passages of the benchmark's own corpus
builder, served over HTTP through the serve family's fused kernel and held
to the benchmark's own plain reference (`benchmarks/references/
bm25_bool.py`) by the benchmark's own rule (`benchmarks/compare.py`,
`exact`: ids tie group by tie group, scores within 1e-5, `hits.total`
equal).

The bodies are the configuration's generator's (`bodies/bool_classes.py`,
its thresholds and stop list), a fixed seeded set; the cases pick from it
one body a class, one that answers fewer than ten hits and one that
answers none (the reference says which they are).
"""

import http.client
import json
import os
import sys

import numpy as np
import pytest

from elasticsearch_tpu.common import tracing

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from compare import compare_one, reference_body  # noqa: E402
from plugins import load_json, load_plugin  # noqa: E402
from run import place_segment  # noqa: E402

DOCS, SEED, N_BODIES = 6000, 5, 240
SHORT_PAGES = ("fewer_than_10_hits", "no_hits")


def post(port: int, path: str, body: dict, method: str = "POST") -> dict:
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


@pytest.fixture(scope="module")
def deployment():
    """(server, index service, configuration, reference, {case: body},
    every body with its class)."""
    from elasticsearch_tpu.rest.server import ElasticsearchTpuServer
    from elasticsearch_tpu.search import executor_jax

    orig = executor_jax.FUSED_MIN_DOCS
    executor_jax.FUSED_MIN_DOCS = 10  # the deployment's text kernel
    config = load_json("configs", "msmarco-bool-wand.json")
    corpus = load_plugin("corpora", config["corpus"]["builder"]).build(
        config, SEED, DOCS)
    ref = load_plugin("references", config["reference"]).Reference(
        corpus["reference"], config)
    gen = load_plugin("bodies", config["body"]["generator"])
    ctx, args = corpus["body_context"], config["body"]["args"]
    raw = gen.make(ctx, args, np.random.default_rng([33, 9]), N_BODIES)
    terms = gen.class_terms(ctx, args)
    bodies = [json.loads(b) for b in raw]
    classes = [gen.class_of(b, ctx["field"], terms) for b in bodies]
    cases = {}
    for body, cls in zip(bodies, classes):
        cases.setdefault(cls, body)
        total = ref.answer(body)["hits"]["total"]["value"]
        if 0 < total < 10:
            cases.setdefault("fewer_than_10_hits", body)
        elif total == 0:
            cases.setdefault("no_hits", body)

    server = ElasticsearchTpuServer(port=0)
    server.start_background()
    index = config["index"]
    post(server.port, f"/{index}", {"settings": config["settings"],
                                    "mappings": corpus["mappings"]}, "PUT")
    svc = server.cluster.indices[index]
    # the seeded, prebuilt segment becomes the shard's one segment, as
    # the benchmark places it (its configuration's `reduced.ingest`)
    place_segment(svc, corpus["segment"])
    yield server, svc, config, ref, cases, list(zip(bodies, classes))
    server.close()
    executor_jax.FUSED_MIN_DOCS = orig


def held_to_reference(config, ref, body, served):
    g = config["guarantees"]
    (expected,) = ref.answer_many([reference_body(g["rule"], body)])
    got = compare_one(g["rule"], g["score_rtol"], body, served, expected)
    assert got["page_ok"], got["why"]
    assert got["total_ok"], (served["hits"]["total"],
                             expected["hits"]["total"])
    assert got["score_rel"] <= g["score_rtol"]
    return expected


@pytest.mark.parametrize("case", [
    "AndHighHigh", "AndHighMed", "AndHighLow", "OrHighHigh", "OrHighMed",
    "OrHighLow", "AndHighOrMedMed", "AndMedOrHighHigh", *SHORT_PAGES])
def test_served_answer_over_http_is_the_plain_references(deployment, case):
    server, svc, config, ref, cases, _all = deployment
    body = cases[case]
    served = post(server.port, f"/{config['index']}/_search", body)
    expected = held_to_reference(config, ref, body, served)
    n = expected["hits"]["total"]["value"]
    if case == "fewer_than_10_hits":
        assert 0 < len(served["hits"]["hits"]) == n < 10
    elif case == "no_hits":
        assert served["hits"]["hits"] == [] and n == 0
    elif case.startswith("Or"):
        # either word is enough: more passages than the rarer word holds
        assert n > 10


def node_numbers(server) -> dict:
    from elasticsearch_tpu.rest.actions import RestActions

    _status, body = RestActions(server.cluster).nodes_stats(None, {}, {})
    node = body["nodes"]["node-0"]
    pool = node["thread_pool"]["search"]
    return {**{k: v for k, v in pool.items() if isinstance(v, int)},
            **{f"fan_out.{k}": v for k, v in pool["fan_out"].items()},
            **{k: v for k, v in node["pipeline"]["batching"].items()
               if isinstance(v, int)}}


def test_every_request_of_the_mix_is_one_serve_job(deployment):
    """No request leaves the batcher (no unplanned query, no per-job
    fallback), each is one fused launch whose packed row is downloaded as
    it is, and the clause counters say what the mix holds."""
    server, svc, config, ref, _cases, every = deployment
    path = f"/{config['index']}/_search"
    before = node_numbers(server)
    for body, _cls in every:
        post(server.port, path, body)
    after = node_numbers(server)
    moved = {k: after[k] - before[k] for k in after}
    n = len(every)
    mixed = sum(cls in ("AndHighOrMedMed", "AndMedOrHighHigh")
                for _b, cls in every)
    assert 0 < mixed < n
    assert moved["unplanned_queries"] == 0
    assert moved["serve_fallback_jobs"] == 0
    assert moved["fused_overflow_jobs"] == 0
    assert moved["completed"] == moved["serve_launches"] == n
    assert moved["direct_collect_groups"] == n
    assert moved["serve_clauses"] == 2 * n
    assert moved["serve_multi_term_clauses"] == mixed
    # one shard, every request a job: none left the request thread
    assert (moved["fan_out.inline"], moved["fan_out.pooled"]) == (n, 0)
    # what the planner still turns away is counted, once a query (the
    # request thread only asks; the pool's run of it is the one counted)
    post(server.port, path, {"query": {"bool": {
        "must": [{"term": {"body": "w00051"}}],
        "must_not": [{"match_phrase": {"body": "w00052 w00053"}}]}},
        "size": 10})
    last = node_numbers(server)
    assert last["unplanned_queries"] == after["unplanned_queries"] + 1
    assert (last["fan_out.inline"], last["fan_out.pooled"]) == (
        after["fan_out.inline"], after["fan_out.pooled"] + 1)


@pytest.mark.parametrize("case,clauses,msm", [
    ("AndHighHigh", 2, 2), ("OrHighMed", 2, 1), ("AndHighOrMedMed", 2, 2)])
def test_dispatch_span_says_clauses_and_msm(deployment, case, clauses, msm):
    _server, svc, _config, _ref, cases, _all = deployment
    tracing.clear()
    handle = tracing.begin("search", index=svc.name)
    svc.search(json.loads(json.dumps(cases[case])))
    tracing.end(handle)
    (dispatch,) = [s for s in tracing.recent(1)[0]["spans"]
                   if s["name"] == "dispatch"]
    tags = dispatch["tags"]
    assert tags["family"] == "serve" and tags["fields"] == 1
    assert (tags["clauses"], tags["msm"]) == (clauses, msm)


def test_the_oracle_gives_the_same_pages(deployment):
    """The tie to the host oracle: `NumpyExecutor` over the same reader
    answers every class with the served ids, scores and totals."""
    from elasticsearch_tpu.search import dsl
    from elasticsearch_tpu.search.executor import NumpyExecutor

    _server, svc, _config, _ref, cases, _all = deployment
    oracle = NumpyExecutor(svc.shards[0].reader())
    for case, body in sorted(cases.items()):
        served = svc.search(json.loads(json.dumps(body)))
        td = oracle.search(dsl.parse_query(body["query"]), size=10)
        assert served["hits"]["total"]["value"] == td.total, case
        assert [(h["_id"], round(h["_score"], 4))
                for h in served["hits"]["hits"]] == [
            (h.doc_id, round(h.score, 4)) for h in td.hits], case
