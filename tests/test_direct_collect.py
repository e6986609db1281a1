"""One fused text request, one device program (search/batcher.py
`_group_topk`, ops/scoring.py `packed_segment_topk` / `_merge_segments`).

Contract under test:
  * a group whose only device item is one fused launch downloads the
    kernel's packed row as it is and decodes it on the host: the same
    floats, ids, order, totals and `max_score` as the merge program gave
    for the same plans (ties doc asc, `-inf` pads dropped, the live-docs
    mask honoured), at one and at four rows, match and serve family;
  * with more items the packed rows are unpacked inside the merge's own
    trace, beside the chunked path's triples: equal to the old merge of
    eagerly unpacked triples;
  * a warm one-segment fused request launches no merge program and
    counts in `direct_collect_groups`; a two-segment one launches the
    merge once and does not; the `collect` span's `merged` tag agrees.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticsearch_tpu.cluster.indices import IndexService
from elasticsearch_tpu.common import tracing
from elasticsearch_tpu.ops import scoring
from elasticsearch_tpu.search import dsl, executor_jax
from elasticsearch_tpu.search.batcher import (
    QueryBatcher,
    extract_match_plan,
    extract_serve_plan,
)

WORDS = ["alpha", "beta", "gamma", "delta", "epsilon", "zeta", "eta",
         "theta", "iota", "kappa"]
KB = 16  # the groups' top-k bucket: more than `few` matches, fewer than `tie`
FAMILIES = ["match", "serve"]


def make_service(name, waves, seed=3):
    """One shard, one segment a wave (`waves`: docs in each). Every wave
    starts with 24 identical documents (equal scores for "tie": more of
    them than KB) and ends with the three that hold "few"; the rest is
    random."""
    rng = np.random.default_rng(seed)
    svc = IndexService(
        name,
        settings={"number_of_shards": 1, "search.backend": "jax"},
        mappings_json={"properties": {
            "title": {"type": "text"}, "body": {"type": "text"},
        }},
    )
    n = 0
    for docs in waves:
        for i in range(docs):
            if i < 24:
                doc = {"title": "tie", "body": "tie alpha beta"}
            else:
                words = list(rng.choice(WORDS, int(rng.integers(3, 9))))
                if i >= docs - 3:
                    words.append("few")
                doc = {"title": " ".join(words[:2]), "body": " ".join(words)}
            svc.index_doc(str(n), doc)
            n += 1
        svc.refresh()
    return svc


def load_fused(svc, fused_min_docs):
    """Builds the shard's fused scorers with the kernel's gate (normally
    large segments only) at `fused_min_docs`; the executor keeps them.
    -> which segments got one."""
    orig = executor_jax.FUSED_MIN_DOCS
    executor_jax.FUSED_MIN_DOCS = fused_min_docs
    try:
        ex = svc._executor(svc.shards[0])
        return [
            ex.fused_scorer_mf(si, ("body",)) is not None
            and ex.fused_scorer_mf(si, ("title", "body")) is not None
            for si in range(len(ex.reader.segments))
        ]
    finally:
        executor_jax.FUSED_MIN_DOCS = orig


@pytest.fixture(scope="module")
def one_seg():
    """One fused segment, the best "gamma" hit deleted afterwards (the
    fused kernel's live-docs mask)."""
    svc = make_service("dc-one", [300])
    top = svc.search({"query": {"match": {"body": "gamma"}}, "size": 1})
    svc.victim = top["hits"]["hits"][0]["_id"]
    svc.delete_doc(svc.victim)
    svc.refresh()
    assert load_fused(svc, 10) == [True]
    yield svc
    svc.close()


@pytest.fixture(scope="module")
def two_fused():
    svc = make_service("dc-two-fused", [150, 120])
    assert load_fused(svc, 10) == [True, True]
    yield svc
    svc.close()


@pytest.fixture(scope="module")
def mixed(request):
    """Segments on both sides of the fused gate: 150 and 120 documents
    score in the fused kernel, 40 and 30 on the chunked path (match) or
    per job on the host (serve)."""
    waves = {2: [150, 40], 4: [150, 40, 120, 30]}[request.param]
    svc = make_service(f"dc-mixed{request.param}", waves)
    assert load_fused(svc, 100) == [d >= 100 for d in waves]
    yield svc
    svc.close()


def plans_of(svc, family, texts):
    out = []
    for text in texts:
        if family == "match":
            q = dsl.parse_query({"match": {"body": text}})
            p = extract_match_plan(q, svc.mappings, svc.analysis, 10_000)
        else:
            q = dsl.parse_query({"multi_match": {
                "query": text, "fields": ["title", "body"],
                "tie_breaker": 0.3}})
            p = extract_serve_plan(q, svc.mappings, svc.analysis)
        assert p is not None
        out.append((p, q))
    return out


def run_group(b, ex, plans, family, rows):
    """ONE group through the real dispatch / collect pair."""
    jobs = [b.submit_nowait(ex, p, 10, kind=family, query=q)
            for p, q in plans]
    if family == "match":
        b._collect_match_group(jobs, KB, b._dispatch_match_group(
            jobs, plans[0][0].field, KB, rows=rows))
    else:
        b._collect_serve_group(
            jobs, KB, b._dispatch_serve_group(jobs, KB, rows=rows))
    return [QueryBatcher.wait(j, timeout=60) for j in jobs]


def fingerprint(td):
    """Exact (unrounded) identity of a TopDocs."""
    return ([(h.doc_id, h.segment, h.local_doc, h.score) for h in td.hits],
            td.total, td.relation, td.max_score)


@pytest.fixture
def batcher(monkeypatch):
    b = QueryBatcher(workers=1)
    monkeypatch.setattr(b, "_ensure_thread", lambda: None)
    yield b
    b.close()


def old_triple_merge(monkeypatch):
    """The flow this replaced, in the program's place: every fused
    launch's packed row unpacked by eager device programs, and the
    merge program over triples only, one item or several."""
    merge = scoring.merge_segment_topk

    def eager(part):
        if isinstance(part, tuple):
            return part
        k = (part.shape[1] - 1) // 2
        return (jax.lax.bitcast_convert_type(part[:, :k], jnp.float32),
                part[:, k: 2 * k], part[:, 2 * k])

    monkeypatch.setattr(scoring, "is_packed", lambda part: False)
    monkeypatch.setattr(
        scoring, "merge_segment_topk",
        lambda items, k: merge([(si, eager(p)) for si, p in items], k))


CASES = {
    "ties": "tie",  # 24 equal scores: the lowest KB doc ids, ascending
    "few": "few",  # three matches: the row's -inf pads are dropped
    "live": "gamma",  # its best hit is deleted: masked in the kernel
}
FILLERS = ["alpha beta", "delta", "kappa eta zeta"]


class TestOneSegmentDirect:
    @pytest.mark.parametrize("rows", [1, 4])
    @pytest.mark.parametrize("case", list(CASES))
    @pytest.mark.parametrize("family", FAMILIES)
    def test_direct_equals_merge(
        self, one_seg, batcher, monkeypatch, family, case, rows
    ):
        ex = one_seg._executor(one_seg.shards[0])
        plans = plans_of(one_seg, family, [CASES[case]] + FILLERS[: rows - 1])
        got = run_group(batcher, ex, plans, family, rows)
        assert batcher.stats["direct_collect_groups"] == 1
        with monkeypatch.context() as m:
            old_triple_merge(m)
            ref = run_group(batcher, ex, plans, family, rows)
        assert batcher.stats["direct_collect_groups"] == 1  # not the merge
        for g, r in zip(got, ref):
            assert fingerprint(g) == fingerprint(r)
        td = got[0]
        assert td.relation == "eq"
        assert td.max_score == td.hits[0].score
        docs = [h.local_doc for h in td.hits]
        if case == "ties":
            assert len({h.score for h in td.hits}) == 1
            assert docs == list(range(10))  # doc asc among equal scores
            assert td.total == 24
        elif case == "few":
            assert len(td.hits) == td.total == 3
        else:
            assert one_seg.victim not in [h.doc_id for h in td.hits]
            scores = [h.score for h in td.hits]
            assert scores == sorted(scores, reverse=True)


class TestSeveralSegments:
    @pytest.mark.parametrize("mixed", [2, 4], indirect=True,
                             ids=["two_segments", "four_segments"])
    @pytest.mark.parametrize("family", FAMILIES)
    def test_traced_unpack_equals_triple_merge(
        self, mixed, batcher, monkeypatch, family
    ):
        ex = mixed._executor(mixed.shards[0])
        n_seg = len(ex.reader.segments)
        for rows, texts in ((1, ["tie"]), (1, ["few"]),
                            (4, ["tie", "few"] + FILLERS[:2])):
            plans = plans_of(mixed, family, texts)
            got = run_group(batcher, ex, plans, family, rows)
            with monkeypatch.context() as m:
                old_triple_merge(m)
                ref = run_group(batcher, ex, plans, family, rows)
            for g, r in zip(got, ref):
                assert fingerprint(g) == fingerprint(r), texts
            if texts[0] == "tie":  # (segment, doc) asc among equal scores
                assert [(h.segment, h.local_doc) for h in got[0].hits] == [
                    (0, d) for d in range(10)]
                assert got[0].total == 24 * n_seg
        # a serve group's small segments score per job on the host and
        # join after the download: with two segments its one fused
        # launch is still collected directly
        direct = family == "serve" and n_seg == 2
        assert batcher.stats["direct_collect_groups"] == (3 if direct else 0)

    def test_merge_takes_packed_rows_and_triples_alike(self):
        rng = np.random.default_rng(11)
        rows, k = 4, 8

        def triple(width):
            s = -np.sort(-rng.random((rows, width), np.float32), axis=1)
            s[:, width - 2:] = -np.inf  # pads
            d = rng.integers(0, 1000, (rows, width)).astype(np.int32)
            return s, d, rng.integers(0, 50, rows).astype(np.int32)

        def packed(s, d, t):
            return jnp.asarray(np.concatenate(
                [s.view(np.int32), d, t[:, None]], axis=1))

        a, b, c = triple(8), triple(8), triple(5)
        as_triples = [(0, tuple(map(jnp.asarray, a))),
                      (2, tuple(map(jnp.asarray, b))),
                      (5, tuple(map(jnp.asarray, c)))]
        as_mixed = [(0, packed(*a)), (2, as_triples[1][1]), (5, packed(*c))]
        want = scoring.merge_segment_topk(as_triples, k)
        got = scoring.merge_segment_topk(as_mixed, k)
        for w, g in zip(want, got):
            np.testing.assert_array_equal(w, g)
        assert set(np.unique(got[1])) <= {0, 2, 5}
        assert got[3].shape == (rows, 3)
        # one packed item alone: the host decode is the merge's answer
        lone = scoring.merge_segment_topk([(7, packed(*a))], k)
        direct = scoring.packed_segment_topk(7, packed(*a))
        for w, g in zip(lone, direct):
            np.testing.assert_array_equal(w, g)
            assert w.dtype == g.dtype and w.shape == g.shape


def traced_search(svc, body):
    tracing.clear()
    handle = tracing.begin("search", index=svc.name)
    out = svc.search(json.loads(json.dumps(body)))
    tracing.end(handle)
    spans = tracing.recent(1)[0]["spans"]
    tracing.clear()
    return out, {s["name"]: s for s in spans}


BODIES = {
    "match": {"query": {"match": {"body": "alpha beta"}}, "size": 10},
    "serve": {"query": {"multi_match": {
        "query": "alpha beta", "fields": ["title", "body"]}}, "size": 10},
}


class TestWhatARequestLaunches:
    @pytest.fixture
    def merges(self, monkeypatch):
        """Calls of the merge program, counted."""
        calls = []
        real = scoring._merge_segments

        def spy(*a, **kw):
            calls.append(kw.get("segs"))
            return real(*a, **kw)

        monkeypatch.setattr(scoring, "_merge_segments", spy)
        return calls

    def test_no_eager_unpacking_is_left(self):
        assert not hasattr(scoring.MultiFusedScorer, "device_result")

    @pytest.mark.parametrize("family", FAMILIES)
    def test_one_segment_request_is_one_program(
        self, one_seg, merges, family
    ):
        one_seg.search(json.loads(json.dumps(BODIES[family])))  # warm
        merges.clear()
        stats = one_seg._batcher.stats
        before = dict(stats)
        xfer = tracing.transfer_stats()
        out, by = traced_search(one_seg, BODIES[family])
        assert out["hits"]["hits"]
        assert merges == []
        assert stats["direct_collect_groups"] == (
            before["direct_collect_groups"] + 1)
        assert stats["launches"] == before["launches"] + 1
        assert by["collect"]["tags"] == {"d2h_bytes": 132, "merged": False}
        moved = {k: v - xfer[k] for k, v in tracing.transfer_stats().items()}
        # up: the plan (and the serve family's tie_breaker); down: the
        # kernel's packed i32[1, 2 * 16 + 1]
        assert moved["h2d_count"] == (1 if family == "match" else 2)
        assert (moved["d2h_count"], moved["d2h_bytes"]) == (1, 132)

    @pytest.mark.parametrize("match", [
        "alpha beta", {"query": "alpha beta", "operator": "and"},
    ], ids=["uncounted", "counted"])
    def test_a_fused_match_launch_moves_its_plan_and_its_page(
        self, one_seg, match
    ):
        """Up: the packed one-field plan, rows x (2T + 2H + 1) int32,
        and nothing beside it (no tie_breaker scalar: nothing reads one
        at one field); down: the packed page, rows x (2k + 1) int32, in
        one blocking download; counted or not."""
        body = {"query": {"match": {"body": match}}, "size": 10}
        one_seg.search(json.loads(json.dumps(body)))  # warm
        rows, k = 1, 16
        T, H = scoring.FUSED_T_RARE, scoring.FUSED_H
        jobs = one_seg._batcher.stats["fused_jobs"]
        xfer = tracing.transfer_stats()
        assert one_seg.search(json.loads(json.dumps(body)))["hits"]["hits"]
        assert one_seg._batcher.stats["fused_jobs"] == jobs + 1
        moved = {k_: v - xfer[k_]
                 for k_, v in tracing.transfer_stats().items()}
        assert moved == {
            "h2d_count": 1, "h2d_bytes": 4 * rows * (2 * T + 2 * H + 1),
            "d2h_count": 1, "d2h_bytes": 4 * rows * (2 * k + 1),
        }

    @pytest.mark.parametrize("family", FAMILIES)
    def test_two_segment_request_merges_once(
        self, two_fused, merges, family
    ):
        two_fused.search(json.loads(json.dumps(BODIES[family])))  # warm
        merges.clear()
        stats = two_fused._batcher.stats
        before = dict(stats)
        xfer = tracing.transfer_stats()
        out, by = traced_search(two_fused, BODIES[family])
        assert {h["_id"] for h in out["hits"]["hits"]}
        assert merges == [(0, 1)]
        assert stats["direct_collect_groups"] == (
            before["direct_collect_groups"])
        assert stats["launches"] == before["launches"] + 2
        # down: the merge's packed i32[1, 3 * 16 + 2]; nothing goes up
        # for it (the slots' segments are constants of its trace)
        assert by["collect"]["tags"] == {"d2h_bytes": 200, "merged": True}
        moved = {k: v - xfer[k] for k, v in tracing.transfer_stats().items()}
        assert moved["h2d_count"] == (2 if family == "match" else 4)
        assert (moved["d2h_count"], moved["d2h_bytes"]) == (1, 200)

    def test_counter_reaches_nodes_stats_and_starts_at_zero(
        self, monkeypatch
    ):
        from elasticsearch_tpu.cluster.service import ClusterService
        from elasticsearch_tpu.rest.actions import RestActions

        monkeypatch.setattr(executor_jax, "FUSED_MIN_DOCS", 10)
        c = ClusterService()
        try:
            c.create_index("dc-stats", {
                "settings": {"search.backend": "jax"},
                "mappings": {"properties": {"body": {"type": "text"}}},
            })
            idx = c.indices["dc-stats"]
            for i in range(40):
                idx.index_doc(str(i), {"body": f"alpha beta {i}"})
            idx.refresh()

            def direct():
                _, resp = RestActions(c).nodes_stats(None, {}, {})
                return resp["nodes"]["node-0"]["pipeline"]["batching"][
                    "direct_collect_groups"]

            assert direct() == 0
            idx.search({"query": {"match": {"body": "alpha"}}})
            assert direct() == 1
        finally:
            c.close()
