"""The late-interaction rescoring deployment (`benchmarks/configs/
msmarco-colbert-rescore.json`) at a small size: ColBERT's re-ranking of a
BM25 window as a byte `rank_vectors` field read by a `rescore`, over a
few thousand seeded passages of the benchmark's own corpus builder,
served over HTTP through the batcher's `match` and `rerank` families and
held to the benchmark's own plain reference (`benchmarks/references/
maxsim_rescore.py`) by the benchmark's own rule (`benchmarks/compare.py`,
`exact`).

What the deployment forced, each held here: the window is collected a
shard whatever the page (`size`), its cut is Lucene's under exact ties,
the column holds the mapped bytes (no float32, no scales) and is
assembled on the device in blocks, the kernel states its precision (a
bfloat16 query row is told apart), a column that does not fit is
counted, and the second stage has spans and counters.
"""

import http.client
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticsearch_tpu.cluster.indices import IndexService
from elasticsearch_tpu.common import tracing
from elasticsearch_tpu.common.memory import hbm_ledger
from elasticsearch_tpu.index.mapping import MappingParseError, Mappings
from elasticsearch_tpu.index.segment import byte_multi_vector_field
from elasticsearch_tpu.models import rerank as rerank_model
from elasticsearch_tpu.ops import rerank as rerank_ops
from elasticsearch_tpu.ops import scoring
from elasticsearch_tpu.search import executor_jax

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from compare import compare_one, reference_body  # noqa: E402
from lowprec import to_bf16  # noqa: E402
from plugins import load_json, load_plugin  # noqa: E402
from run import place_segment  # noqa: E402

DOCS, SEED, N_BODIES = 6000, 11, 12
DIMS = 8


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

        self.config = load_json("configs", "msmarco-colbert-rescore.json")
        self.corpus = load_plugin(
            "corpora", self.config["corpus"]["builder"]).build(
            self.config, SEED, DOCS)
        self.ref = load_plugin(
            "references", self.config["reference"]).Reference(
            self.corpus["reference"], self.config)
        gen = load_plugin("bodies", self.config["body"]["generator"])
        raw = gen.make(self.corpus["body_context"],
                       self.config["body"]["args"],
                       np.random.default_rng([53, 9]), N_BODIES)
        self.bodies = [json.loads(b) for b in raw]
        self.server = ElasticsearchTpuServer(port=0)
        self.server.start_background()
        self.port = self.server.port
        self.index = self.config["index"]
        call(self.port, f"/{self.index}",
             {"settings": self.config["settings"],
              "mappings": self.corpus["mappings"]}, "PUT")
        place_segment(self.svc, self.corpus["segment"])

    @property
    def svc(self):
        return self.server.cluster.indices[self.index]

    def search(self, body: dict) -> dict:
        return call(self.port, f"/{self.index}/_search", body)

    def held(self, body: dict, served: dict) -> dict:
        g = self.config["guarantees"]
        (expected,) = self.ref.answer_many(
            [reference_body(g["rule"], body)])
        got = compare_one(g["rule"], g["score_rtol"], body, served, expected)
        assert got["page_ok"], got["why"]
        assert got["total_ok"], (served["hits"]["total"],
                                 expected["hits"]["total"])
        assert got["score_rel"] <= g["score_rtol"], got["score_rel"]
        return expected

    def node(self) -> dict:
        from elasticsearch_tpu.rest.actions import RestActions

        _status, body = RestActions(self.server.cluster).nodes_stats(
            None, {}, {})
        return body["nodes"]["node-0"]

    def rare_question(self, lo: int, hi: int) -> str:
        """One word whose document frequency lies in [lo, hi]."""
        ref = self.corpus["reference"]["text"]
        df = np.diff(ref["post_start"])
        t = int(np.flatnonzero((df >= lo) & (df <= hi))[0])
        width = self.corpus["body_context"]["text"]["term_width"]
        return f"w{t:0{width}d}", int(df[t])

    def close(self):
        self.server.close()


@pytest.fixture(scope="module")
def dep():
    d = Deployment()
    yield d
    d.close()


def with_window(body: dict, window: int, size: int = 10) -> dict:
    return {**body, "size": size,
            "rescore": {**body["rescore"], "window_size": window}}


# ---------------------------------------------------------------------------
# the served page against the plain reference
# ---------------------------------------------------------------------------


def test_the_configurations_bodies_are_served_as_the_reference_answers(dep):
    before = dep.node()["rescore"]
    for body in dep.bodies:
        assert body["rescore"]["window_size"] == 1000 and body["size"] == 10
        qv = body["rescore"]["query"]["rescore_query"]["rank_vectors"]
        assert np.asarray(qv["query_vectors"]).shape == (32, 128)
        dep.held(body, dep.search(body))
    after = dep.node()["rescore"]
    n = len(dep.bodies)
    assert after["device_rescores"] - before["device_rescores"] == n
    assert after["requests"] - before["requests"] == n
    assert after["first_stage_kept"] == before["first_stage_kept"]
    # the window, not the page, is rescored: ~69 token rows a candidate
    docs = after["windows_docs"] - before["windows_docs"]
    tokens = after["tokens_scored"] - before["tokens_scored"]
    assert docs > 100 * n and 50 < tokens / docs < 90, (docs, tokens)
    assert (after["slots_gathered"] - before["slots_gathered"]
            == n * 1024 * 180)  # the window's bucket x tmax a launch
    assert (after["least_bytes"] - before["least_bytes"]
            == rerank_model.least_bytes(tokens, docs, 128, 1))
    # the benchmark's reader reckons the same bytes
    reader = load_plugin("readers", "maxsim_gather_roofline")
    assert reader.least_bytes(tokens, docs, 128) == rerank_model.least_bytes(
        tokens, docs, 128, 1)


def test_hits_are_built_for_the_page_and_not_for_the_window(dep):
    """A window of 1,000 under a page of 10 (the cell's request): the
    window travels as columns (PR 58) and ten `Hit`s are made."""
    body = dep.bodies[0]
    assert body["rescore"]["window_size"] == 1000 and body["size"] == 10
    before = dep.node()["rescore"]
    served = dep.search(body)
    after = dep.node()["rescore"]
    dep.held(body, served)
    assert len(served["hits"]["hits"]) == 10
    assert served["hits"]["total"]["value"] > 100  # a window worth the name
    assert after["requests"] - before["requests"] == 1
    assert after["hits_built"] - before["hits_built"] == 10
    assert after["device_rescores"] - before["device_rescores"] == 1


@pytest.mark.parametrize("where", ["below", "at", "above"])
def test_window_below_at_and_above_the_number_of_matches(dep, where):
    word, df = dep.rare_question(40, 400)
    window = {"below": df - 7, "at": df, "above": df + 50}[where]
    body = with_window(
        {**dep.bodies[0], "query": {"match": {"text": word}}}, window)
    served = dep.search(body)
    expected = dep.held(body, served)
    assert served["hits"]["total"]["value"] == df
    assert len(served["hits"]["hits"]) == 10
    assert expected["hits"]["hits"][0]["_score"] == pytest.approx(
        served["hits"]["max_score"], rel=1e-5)


def test_the_fused_program_cuts_the_window_as_the_chunked_path_does(
        dep, monkeypatch):
    """At the deployment's size the first stage is the fused program;
    here the segment is far below `FUSED_MIN_DOCS`, so the module's
    index ran the chunked path. A second index over the same segment
    with the threshold lowered runs `_fused_query_mf` with `tie_window`
    and answers the same pages."""
    monkeypatch.setattr(executor_jax, "FUSED_MIN_DOCS", 1000)
    index = "colbert-fused"
    call(dep.port, f"/{index}", {"settings": dep.config["settings"],
                                 "mappings": dep.corpus["mappings"]}, "PUT")
    svc = dep.server.cluster.indices[index]
    place_segment(svc, dep.corpus["segment"])
    fused0 = svc._batcher.stats["fused_jobs"]
    for body in dep.bodies[:4]:
        served = call(dep.port, f"/{index}/_search", body)
        dep.held(body, served)
        assert pairs(served) == pairs(dep.search(body))
    assert svc._batcher.stats["fused_jobs"] - fused0 == 4


def test_the_bfloat16_reference_differs_by_more_than_the_limit(dep):
    g = dep.config["guarantees"]
    worst = 0.0
    for body in dep.bodies[:6]:
        served = dep.search(body)
        full = dep.held(body, served)
        (low,) = dep.ref.answer_many([reference_body(g["rule"], body)],
                                     precision="lower")
        got = compare_one(g["rule"], g["score_rtol"], body, low, full)
        worst = max(worst, got["score_rel"])
    assert worst > 5 * g["score_rtol"], worst


# ---------------------------------------------------------------------------
# small hand-made indices: the page and `size`, ties at the window's edge
# ---------------------------------------------------------------------------

MAPPINGS = {"properties": {
    "body": {"type": "text"},
    "tok": {"type": "rank_vectors", "element_type": "byte", "dims": DIMS,
            "similarity": "dot_product"},
}}
WORDS = ["alpha beta", "alpha gamma", "beta gamma delta", "alpha beta gamma"]


def make_service(name, backend="jax", shards=1):
    return IndexService(
        name, settings={"number_of_shards": shards,
                        "search.backend": backend},
        mappings_json=MAPPINGS)


def byte_rows(rng, n):
    return rng.integers(-127, 128, size=(n, DIMS)).tolist()


def fill(svc, n=200, seed=5, body=None):
    rng = np.random.default_rng(seed)
    for i in range(n):
        svc.index_doc(str(i), {
            "body": body or WORDS[i % 4],
            "tok": byte_rows(rng, 1 + i % 5),
        })
    svc.refresh()
    return rng


def rescore(qv, window, qw=0.0, rw=1.0):
    return {"window_size": window, "query": {
        "rescore_query": {"rank_vectors": {"field": "tok",
                                           "query_vectors": qv}},
        "query_weight": qw, "rescore_query_weight": rw}}


def pairs(resp):
    return [(h["_id"], h["_score"]) for h in resp["hits"]["hits"]]


@pytest.mark.parametrize("backend,shards", [
    ("jax", 1), ("jax", 2), ("numpy", 1), ("numpy", 2)])
def test_the_page_of_a_rescored_search_does_not_depend_on_size(
        backend, shards):
    svc = make_service(f"page-{backend}-{shards}", backend, shards)
    try:
        rng = fill(svc)
        qv = rng.normal(size=(3, DIMS)).round(3).tolist()
        body = {"query": {"match": {"body": "alpha"}},
                "rescore": rescore(qv, 100)}
        rerank_model.reset_stats()
        small = svc.search({**body, "size": 10})
        windows = rerank_model.stats_snapshot()["windows"]
        large = svc.search({**body, "size": 100})
        assert pairs(small) == pairs(large)[:10]
        assert small["hits"]["total"] == large["hits"]["total"]
        assert small["hits"]["max_score"] == large["hits"]["max_score"]
        # a window of 100 a shard (fewer where a shard matches fewer;
        # one for all shards where the mesh twin served), never a
        # window of the page
        assert windows and all(int(w) > 10 for w in windows), windows
        if shards == 1:
            assert windows == {"100": 1}
        # the first stage alone pages the same way
        plain = svc.search({"query": body["query"], "size": 10})
        assert plain["hits"]["total"] == small["hits"]["total"]
    finally:
        svc.close()


def test_a_retrievers_rescore_window_is_wider_than_its_page():
    svc = make_service("page-retriever")
    try:
        rng = fill(svc)
        qv = rng.normal(size=(3, DIMS)).round(3).tolist()
        query = {"match": {"body": "alpha"}}
        direct = svc.search({"query": query, "size": 10,
                             "rescore": rescore(qv, 100)})
        before = rerank_model.stats_snapshot()
        ranked = svc.search({
            "retriever": {"standard": {"query": query}}, "size": 10,
            "rescore": rescore(qv, 100)})
        after = rerank_model.stats_snapshot()
        assert pairs(ranked) == pairs(direct)
        # its leg ran on the request's pinned executor, whose candidates
        # are a list from the start: nothing was built from columns
        assert after["requests"] - before["requests"] == 1
        assert after["hits_built"] == before["hits_built"]
    finally:
        svc.close()


def tie_service(name, backend):
    """120 passages with one body (their BM25 scores tie exactly); the
    best token rows belong to the passages of the HIGHEST ids."""
    svc = make_service(name, backend)
    for i in range(120):
        svc.index_doc(f"{i:03d}", {"body": "alpha beta",
                                   "tok": [[i] * DIMS]})
    svc.refresh()
    return svc


@pytest.mark.parametrize("path", ["batched", "numpy", "rescore_ranked"])
def test_a_tie_at_the_windows_edge_is_cut_by_lowest_doc_id(path):
    svc = tie_service(f"tie-{path}", "numpy" if path == "numpy" else "jax")
    try:
        part = {"size": 5, "rescore": rescore([[1.0] * DIMS], 40)}
        query = {"match": {"body": "alpha"}}
        if path == "rescore_ranked":
            body = {"retriever": {"standard": {"query": query}}, **part}
        else:
            body = {"query": query, **part}
        got = svc.search(body)
        # the window is passages 000..039 (Lucene: score desc, doc asc),
        # of which the rescore prefers the highest ids; 040..119 score
        # higher and are never rescored
        assert [h["_id"] for h in got["hits"]["hits"]] == [
            "039", "038", "037", "036", "035"]
        assert got["hits"]["hits"][0]["_score"] == 39.0 * DIMS
    finally:
        svc.close()


def test_window_cut_takes_the_groups_members_from_the_refill():
    """The chip's `top_k` returns exact ties in no particular order and,
    at the cut, not the lowest-index members: whatever members it
    fetched, the window holds the lowest doc ids."""
    rng = np.random.default_rng(3)
    n, k, window = 400, 16, 10
    plane = np.full(n, -np.inf, np.float32)
    above = np.array([7, 90, 233, 310, 399])
    plane[above] = [9.0, 8.0, 7.5, 7.0, 6.5]
    rest = np.setdiff1d(np.arange(n), above)
    tied = np.sort(rng.choice(rest, 60, replace=False))
    plane[tied] = 5.0
    masked = jnp.asarray(plane[None, :])
    top_s, _ = jax.lax.top_k(masked, k)
    refill = np.asarray(scoring.window_tie_refill(masked, top_s, window))[0]
    assert refill.tolist() == tied[:k].tolist()
    # an adversarial fetch: the five above and eleven members of the
    # group that are NOT its lowest
    fetched = np.r_[above, tied[-11:]]
    s, sg, d = scoring.rank_order(
        plane[fetched][None, :], np.zeros((1, k), np.int32),
        fetched[None, :].astype(np.int32))
    scores, docs, refilled = scoring.window_cut(s[0], d[0], refill, window)
    assert refilled
    assert docs.tolist() == above.tolist() + tied[:5].tolist()
    assert scores.tolist() == [9.0, 8.0, 7.5, 7.0, 6.5] + [5.0] * 5
    # no refill where the group ends inside the fetch, or fewer matched
    plane2 = plane.copy()
    plane2[tied[3:]] = -np.inf
    masked2 = jnp.asarray(plane2[None, :])
    top2, d2 = jax.lax.top_k(masked2, k)
    refill2 = np.asarray(scoring.window_tie_refill(masked2, top2, window))
    assert (refill2 == -1).all()
    _s, docs2, refilled2 = scoring.window_cut(
        np.asarray(top2)[0], np.asarray(d2)[0], refill2[0], window)
    assert not refilled2 and len(docs2) == 8


def test_a_refilled_window_is_counted_and_tagged():
    svc = tie_service("tie-counted", "jax")
    try:
        rerank_model.reset_stats()
        tr = tracing.Trace("test")
        tok = tracing.TRACE_CTX.set(tr)
        try:
            svc.search({"query": {"match": {"body": "alpha"}}, "size": 5,
                        "rescore": rescore([[1.0] * DIMS], 40)})
        finally:
            tracing.TRACE_CTX.reset(tok)
        # 120 tied passages, a bucket of 48 or 64 fetched: the group at
        # rank 40 runs past it
        assert rerank_model.stats_snapshot()["window_ties_refilled"] == 1
        collects = [s for s in tr.to_dict()["spans"]
                    if s["name"] == "collect" and "window" in s["tags"]]
        assert [s["tags"]["window"] for s in collects] == [40]
        assert collects[0]["tags"]["ties_refilled"] == 1
    finally:
        svc.close()


# ---------------------------------------------------------------------------
# the byte form: mapping, `_bulk` against the prebuilt plane, the column
# ---------------------------------------------------------------------------


def test_byte_rank_vectors_mapping_holds_rows_to_bytes():
    from elasticsearch_tpu.analysis import AnalysisRegistry
    from elasticsearch_tpu.index.mapping import DocumentParser

    m = Mappings(MAPPINGS)
    assert m.to_json()["properties"]["tok"]["element_type"] == "byte"
    parser = DocumentParser(m, AnalysisRegistry())
    ok = parser.parse("1", {"body": "a",
                            "tok": [[1, -128, 127, 0, 0, 0, 0, 0]]})
    assert ok.multi_vectors["tok"] == [[1.0, -128.0, 127.0, 0, 0, 0, 0, 0]]
    for bad in ([[0.5] + [0] * 7], [[128] + [0] * 7], [["x"] + [0] * 7]):
        with pytest.raises(MappingParseError):
            parser.parse("2", {"tok": bad})
    with pytest.raises(MappingParseError):
        Mappings({"properties": {"t": {
            "type": "rank_vectors", "element_type": "byte", "dims": 4,
            "similarity": "cosine"}}})
    with pytest.raises(MappingParseError):
        Mappings({"properties": {"t": {
            "type": "rank_vectors", "element_type": "bit", "dims": 4}}})


def test_bulk_builds_the_plane_the_prebuilt_constructor_holds():
    svc = make_service("bulk-vs-plane")
    twin = make_service("bulk-vs-plane-twin")
    try:
        rng = fill(svc, n=60)
        (seg,) = svc.shards[0].segments
        mvf = seg.multi_vectors["tok"]
        assert mvf.tok_vectors.dtype == np.int8 and mvf.element_type == "byte"
        # the same rows drawn again, as one plane
        again = np.random.default_rng(5)
        rows = np.concatenate([
            np.asarray(byte_rows(again, 1 + i % 5), np.int8)
            for i in range(60)])
        offsets = np.r_[0, np.cumsum([1 + i % 5 for i in range(60)])]
        plane = byte_multi_vector_field(rows, offsets)
        assert (mvf.tok_vectors == plane.tok_vectors).all()
        assert (mvf.tok_offsets == plane.tok_offsets).all()
        assert (mvf.exists == plane.exists).all()
        assert plane.tok_vectors is rows  # held as it is: no copy
        with pytest.raises(ValueError):
            byte_multi_vector_field(rows.astype(np.float32), offsets)
        # the prebuilt plane placed beside the same text serves the same
        fill(twin, n=60)
        twin.shards[0].segments[0].multi_vectors["tok"] = plane
        twin.shards[0].change_generation += 1
        qv = rng.normal(size=(4, DIMS)).round(3).tolist()
        body = {"query": {"match": {"body": "alpha"}}, "size": 10,
                "rescore": rescore(qv, 30, qw=0.5)}
        assert pairs(svc.search(body)) == pairs(twin.search(body))
    finally:
        svc.close()
        twin.close()


def test_the_column_holds_the_bytes_and_is_assembled_in_blocks(monkeypatch):
    """Two segments, upload blocks of 64 rows: blocks inside a plane,
    across the planes' seam, and the zero-filled last one."""
    monkeypatch.setattr(executor_jax, "RERANK_BLOCK_ROWS", 64)
    svc = make_service("column-blocks")
    try:
        rng = np.random.default_rng(9)
        for i in range(150):
            svc.index_doc(str(i), {"body": WORDS[i % 4],
                                   "tok": byte_rows(rng, 1 + i % 5)})
            if i == 79:
                svc.refresh()
        svc.refresh()
        segs = svc.shards[0].segments
        assert len(segs) == 2
        ex = svc._executor(svc.shards[0])
        model = rerank_model.resolve_model(svc.mappings, svc.settings, "tok")
        assert model.element_type == "byte" and not model.quantized
        used0 = hbm_ledger.stats()["by_category"].get("rerank", 0)
        col = ex.rerank_column(model)
        host = np.concatenate([s.multi_vectors["tok"].tok_vectors
                               for s in segs])
        assert col["toks"].dtype == jnp.int8 and col["scales"] is None
        dev = np.asarray(col["toks"])
        assert dev.shape[0] % 64 == 0
        assert dev.shape[0] >= len(host) + col["tmax"]
        assert (dev[: len(host)] == host).all() and not dev[len(host):].any()
        assert col["rows"] == len(host) and col["tmax"] == 5
        # the ledger is charged what is resident
        assert col["nbytes"] == dev.nbytes + 2 * 4 * 150
        assert (hbm_ledger.stats()["by_category"]["rerank"] - used0
                == col["nbytes"])
        # and the served scores are the float oracle's over the bytes
        qv = rng.normal(size=(3, DIMS)).round(3).tolist()
        body = {"query": {"match": {"body": "alpha"}}, "size": 20,
                "rescore": rescore(qv, 50)}
        got = pairs(svc.search(body))
        q = np.asarray(qv, np.float64)
        by_id = {}
        for seg in segs:
            mvf = seg.multi_vectors["tok"]
            for d, name in enumerate(seg.doc_ids):
                rows = mvf.tok_vectors[
                    mvf.tok_offsets[d]:mvf.tok_offsets[d + 1]]
                by_id[name] = float(
                    (q @ rows.astype(np.float64).T).max(axis=1).sum())
        for name, score in got:
            assert score == pytest.approx(by_id[name], rel=1e-5)
    finally:
        svc.close()


def test_row_blocks_are_views_inside_a_plane_and_copies_at_a_seam():
    a = np.arange(10 * 2, dtype=np.int8).reshape(10, 2)
    b = np.arange(100, 100 + 7 * 2, dtype=np.int8).reshape(7, 2)
    blocks = list(executor_jax._row_blocks([a, b], 4))
    assert [at for at, _ in blocks] == [0, 4, 8, 12, 16]
    assert np.shares_memory(blocks[0][1], a)  # a view, not a copy
    assert np.shares_memory(blocks[1][1], a)
    assert not np.shares_memory(blocks[2][1], a)  # the seam: a copy
    joined = np.concatenate([blk for _, blk in blocks])
    assert (joined[:17] == np.concatenate([a, b])).all()
    assert not joined[17:].any() and len(joined) == 20


def test_the_kernel_tells_a_bfloat16_query_row_apart():
    """The byte path's stated precision: exact products of the float32
    query against the bytes. The same contraction with the query rounded
    to ONE bfloat16 part (the einsum's default on the chip) is off by
    more than the configuration's limit."""
    rng = np.random.default_rng(2)
    n, tmax = 40, 12
    counts = rng.integers(1, tmax + 1, n).astype(np.int32)
    starts = np.r_[0, np.cumsum(counts)[:-1]].astype(np.int32)
    toks = np.zeros((int(counts.sum()) + tmax, 128), np.int8)
    toks[: counts.sum()] = np.clip(np.rint(
        rng.standard_normal((int(counts.sum()), 128)) * 11.225), -127, 127)
    q = rng.standard_normal((2, 32, 128)).astype(np.float32)
    q /= np.linalg.norm(q, axis=2, keepdims=True)
    docs = rng.integers(0, n, (2, 16)).astype(np.int32)

    def served(qq):
        return np.asarray(rerank_ops.maxsim_candidates(
            jnp.asarray(qq), jnp.ones((2, 32), bool), jnp.asarray(starts),
            jnp.asarray(counts), jnp.asarray(toks), None, jnp.asarray(docs),
            tmax))

    exact = np.zeros((2, 16))
    for b in range(2):
        for w, d in enumerate(docs[b]):
            rows = toks[starts[d]:starts[d] + counts[d]].astype(np.float64)
            exact[b, w] = (q[b].astype(np.float64) @ rows.T).max(axis=1).sum()
    assert np.abs(served(q) / exact - 1).max() < 1e-6
    assert np.abs(served(to_bf16(q)) / exact - 1).max() > 1e-4
    # the three parts ARE the float32 row
    parts = np.asarray(rerank_ops.split_bf16(jnp.asarray(q)).astype(
        jnp.float32))
    assert (parts.sum(axis=0) == q).all()


def test_a_column_that_does_not_fit_is_counted_and_readable(dep, monkeypatch):
    """Degrade to SKIP answers the first-stage ranking with HTTP 200: at
    the deployment's size a wrong answer, so `_nodes/stats` names it."""
    svc = make_service("column-refused")
    try:
        rng = fill(svc, n=80)
        monkeypatch.setattr(hbm_ledger, "budget", hbm_ledger.used + 64)
        before = rerank_model.stats_snapshot()
        qv = rng.normal(size=(3, DIMS)).round(3).tolist()
        query = {"match": {"body": "alpha"}}
        got = svc.search({"query": query, "size": 10,
                          "rescore": rescore(qv, 30)})
        first = svc.search({"query": query, "size": 10})
        assert pairs(got) == pairs(first)
        after = rerank_model.stats_snapshot()
        assert after["columns_refused"] - before["columns_refused"] == 1
        assert after["skipped"] - before["skipped"] == 1
        assert after["first_stage_kept"] - before["first_stage_kept"] == 1
        assert after["requests"] - before["requests"] == 1
        # what the benchmark's `rerank_skipped_share` reads
        block = dep.node()["rescore"]
        assert block["first_stage_kept"] >= 1 and block["requests"] >= 1
    finally:
        svc.close()


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------


def test_the_new_spans_tile_rescore(dep):
    call(dep.port, "/_internal/traces", {}, "DELETE")
    dep.search(dep.bodies[1])
    conn = http.client.HTTPConnection("127.0.0.1", dep.port, timeout=60)
    spans = None
    for _ in range(50):  # the trace reaches the ring after the answer
        conn.request("GET", "/_internal/traces?n=4")
        traces = json.loads(conn.getresponse().read())["traces"]
        found = [t for t in traces
                 if any(s["name"] == "rescore" for s in t["spans"])]
        if found:
            spans = found[-1]["spans"]
            break
    conn.close()
    assert spans is not None
    by_id = {s["id"]: s for s in spans}
    (resc,) = [s for s in spans if s["name"] == "rescore"]
    assert by_id[resc["parent_id"]]["name"] == "shard_search"
    assert resc["tags"]["window"] == 1000
    kids = sorted((s for s in spans if s["parent_id"] == resc["id"]),
                  key=lambda s: s["start_ns"])
    assert [k["name"] for k in kids] == [
        "rerank_plan", "queue_wait", "dispatch", "inflight", "collect",
        "wake"]
    assert kids[0]["start_ns"] >= resc["start_ns"]
    for a, b in zip(kids, kids[1:]):  # end to start, no overlap
        assert a["start_ns"] + a["duration_ns"] <= b["start_ns"] + 1000
    end = kids[-1]["start_ns"] + kids[-1]["duration_ns"]
    assert end <= resc["start_ns"] + resc["duration_ns"]
    covered = sum(k["duration_ns"] for k in kids)
    assert covered > 0.6 * resc["duration_ns"], (covered, resc["duration_ns"])
    # the rerank job's launch, download and unpack under its phases
    (disp,) = [k for k in kids if k["name"] == "dispatch"]
    (coll,) = [k for k in kids if k["name"] == "collect"]
    assert disp["tags"]["family"] == "rerank"
    launches = [s for s in spans if s["parent_id"] == disp["id"]
                and s["name"] == "launch"]
    assert [s["tags"]["program"] for s in launches] == [
        "maxsim_rescore_batch"]
    assert launches[0]["tags"]["host_operands"] == 6
    under = {s["name"] for s in spans if s["parent_id"] == coll["id"]}
    assert {"download", "unpack"} <= under
    # the first stage's collect says where the window was cut
    cut = [s for s in spans if s["name"] == "collect"
           and s["tags"].get("window") == 1000]
    assert len(cut) == 1 and by_id[cut[0]["parent_id"]]["name"] == (
        "shard_search")
