"""Round-5 serving-path extension: bool / multi_match / knn plans ride
the batched device kernels (BASELINE configs 2-4).

Parity contract: every batched result must be hit-for-hit identical to
the unbatched executor path (forced via min_score=0, which the fast
path rejects).
"""

import numpy as np
import pytest

from elasticsearch_tpu.cluster.indices import IndexService
from elasticsearch_tpu.search import dsl
from elasticsearch_tpu.search.batcher import (
    extract_knn_plan,
    extract_serve_plan,
)

WORDS = [
    "alpha", "beta", "gamma", "delta", "epsilon", "zeta", "eta", "theta",
    "iota", "kappa", "lam", "mu", "nu", "xi", "omicron", "pi",
]


def _zipf(n):
    w = 1.0 / np.arange(1, n + 1)
    return w / w.sum()


def make_service(n_docs=300, n_shards=1, seed=0, dims=8):
    rng = np.random.default_rng(seed)
    svc = IndexService(
        "sp",
        settings={"number_of_shards": n_shards, "search.backend": "jax"},
        mappings_json={
            "properties": {
                "title": {"type": "text"},
                "body": {"type": "text"},
                "vec": {"type": "dense_vector", "dims": dims,
                        "similarity": "cosine"},
            }
        },
    )
    for i in range(n_docs):
        kt = int(rng.integers(1, 4))
        kb = int(rng.integers(3, 12))
        svc.index_doc(
            str(i),
            {
                "title": " ".join(rng.choice(WORDS, kt, p=_zipf(len(WORDS)))),
                "body": " ".join(rng.choice(WORDS, kb, p=_zipf(len(WORDS)))),
                "vec": [float(x) for x in rng.normal(size=dims)],
            },
        )
    svc.refresh()
    return svc


@pytest.fixture(scope="module")
def service():
    svc = make_service()
    yield svc
    svc.close()


def _ids_scores(resp):
    return [
        (h["_id"], round(h["_score"], 4)) for h in resp["hits"]["hits"]
    ]


def check_parity(svc, body, require_total=True):
    batched = svc.search(body)
    unbatched = svc.search({**body, "min_score": 0})
    assert _ids_scores(batched) == _ids_scores(unbatched), body
    if require_total:
        assert (
            batched["hits"]["total"]["value"]
            == unbatched["hits"]["total"]["value"]
        )
    return batched


class TestExtraction:
    @pytest.mark.parametrize("body,msm,clauses,multi,counts", [
        # a must clause of two words is ONE counted clause: a digit
        ({"bool": {"must": [{"term": {"body": "alpha"}},
                            {"match": {"body": "beta gamma"}}]}},
         2, 2, 1, {"alpha": 1, "beta": 2, "gamma": 2}),
        # a nested disjunction is one too, over both its fields
        ({"bool": {"must": [{"term": {"body": "alpha"}}, {"bool": {"should": [
            {"term": {"title": "beta"}}, {"match": {"body": "gamma delta"}},
        ]}}]}}, 2, 2, 1, {"alpha": 1, "beta": 2, "gamma": 2, "delta": 2}),
        # two of them: two digits
        ({"bool": {"must": [{"match": {"body": "alpha beta"}},
                            {"match": {"body": "gamma delta"}}]}},
         2, 2, 2, {"alpha": 2, "beta": 2, "gamma": 3, "delta": 3}),
        # one must clause of several words: any hit passes, term by term
        ({"bool": {"must": [{"match": {"body": "alpha beta"}}]}},
         1, 1, 1, {"alpha": 1, "beta": 1}),
        # should-only at msm 2: clauses are counted, not words
        ({"bool": {"should": [{"match": {"body": "alpha beta"}},
                              {"match": {"body": "gamma delta"}},
                              {"term": {"body": "epsilon"}}],
                   "minimum_should_match": 2}},
         2, 3, 2, {"alpha": 2, "beta": 2, "gamma": 3, "delta": 3,
                   "epsilon": 1}),
        # at the default msm 1 the same clauses stay a flat plan
        ({"bool": {"should": [{"match": {"body": "alpha beta"}},
                              {"term": {"body": "epsilon"}}]}},
         1, 2, 1, {"alpha": 1, "beta": 1, "epsilon": 1}),
    ], ids=["must_term_and_match", "must_nested_should", "two_digits",
            "lone_multi_word_must", "should_msm2", "should_msm1_flat"])
    def test_clause_level_plans(self, service, body, msm, clauses, multi,
                                counts):
        plan = extract_serve_plan(
            dsl.parse_query(body), service.mappings, service.analysis)
        assert plan is not None
        assert (plan.msm, plan.clauses, plan.multi_term_clauses) == (
            msm, clauses, multi)
        got = {t: c for g in plan.groups for t, _b, c in g.terms}
        assert got == counts
        assert plan.counts_clauses == any(c > 1 for c in counts.values())

    def test_bool_must_should(self, service):
        q = dsl.parse_query({"bool": {
            "must": [{"match": {"body": "alpha"}},
                     {"term": {"body": "beta"}}],
            "should": [{"match": {"title": "gamma delta"}}],
        }})
        plan = extract_serve_plan(q, service.mappings, service.analysis)
        assert plan is not None
        assert plan.msm == 2 and plan.combine == "sum"
        by_field = {g.field: g.terms for g in plan.groups}
        assert by_field["body"] == (("alpha", 1.0, True), ("beta", 1.0, True))
        assert by_field["title"] == (("gamma", 1.0, False),
                                     ("delta", 1.0, False))

    def test_bool_pure_should_msm(self, service):
        q = dsl.parse_query({"bool": {
            "should": [{"match": {"body": "alpha"}},
                       {"match": {"body": "beta"}},
                       {"match": {"body": "gamma"}}],
            "minimum_should_match": 2,
        }})
        plan = extract_serve_plan(q, service.mappings, service.analysis)
        assert plan is not None and plan.msm == 2
        assert all(t[2] for g in plan.groups for t in g.terms)

    def test_rejections(self, service):
        cases = [
            # a prohibition with nothing positive beside it
            {"bool": {"must_not": [{"match": {"body": "x"}}]}},
            # a prohibited clause that is no text clause
            {"bool": {"must_not": [{"match_phrase": {"body": "x z"}}],
                      "should": [{"match": {"body": "y"}}]}},
            # a filter on a text field (PR 47 plans keyword filters and
            # text prohibitions: tests/test_filtered_bool_deployment.py)
            {"bool": {"filter": [{"term": {"body": "x"}}],
                      "must": [{"match": {"body": "y"}}]}},
            # every word required INSIDE a clause: not a count of clauses
            {"bool": {"must": [{"term": {"body": "gamma"}},
                               {"match": {"body": {"query": "alpha beta",
                                                   "operator": "and"}}}]}},
            # two thresholds: the musts and a count over the shoulds
            {"bool": {"must": [{"term": {"body": "alpha"}}],
                      "should": [{"term": {"body": "beta"}},
                                 {"term": {"body": "gamma"}}],
                      "minimum_should_match": 1}},
            {"bool": {"must": [{"match_phrase": {"body": "alpha beta"}}]}},
            # a nested bool that is more than a disjunction
            {"bool": {"must": [{"term": {"body": "alpha"}}, {"bool": {
                "must": [{"term": {"body": "beta"}}]}}]}},
            # more clauses of several words than the count plane has digits
            {"bool": {"must": [{"match": {"body": f"alpha w{i}"}}
                               for i in range(5)]}},
            # more words in one counted clause than a digit holds
            {"bool": {"must": [{"term": {"body": "alpha"}}, {"match": {
                "body": " ".join(f"w{i}" for i in range(16))}}]}},
            {"multi_match": {"query": "a", "fields": ["title", "body"],
                             "operator": "and"}},
            {"multi_match": {"query": "a", "fields": ["title", "body"],
                             "type": "cross_fields"}},
        ]
        for c in cases:
            q = dsl.parse_query(c)
            assert extract_serve_plan(
                q, service.mappings, service.analysis
            ) is None, c

    def test_bare_term_on_text_plan(self, service):
        q = dsl.parse_query({"term": {"body": "alpha"}})
        plan = extract_serve_plan(q, service.mappings, service.analysis)
        assert plan is not None and plan.msm == 1
        assert plan.groups[0].terms == (("alpha", 1.0, True),)

    def test_bare_term_parity(self, service):
        check_parity(service, {"query": {"term": {"body": "alpha"}},
                               "size": 10})

    def test_multi_match_plan(self, service):
        q = dsl.parse_query({"multi_match": {
            "query": "alpha beta", "fields": ["title^2", "body"],
            "type": "best_fields", "tie_breaker": 0.3,
        }})
        plan = extract_serve_plan(q, service.mappings, service.analysis)
        assert plan is not None
        assert plan.combine == "max_tie" and plan.tie == 0.3
        boosts = {g.field: g.terms[0][1] for g in plan.groups}
        assert boosts == {"title": 2.0, "body": 1.0}

    def test_knn_plan(self, service):
        secs = [dsl.parse_knn({"field": "vec", "query_vector": [1.0] * 8,
                               "k": 5, "num_candidates": 20})]
        plan = extract_knn_plan(secs, service.mappings)
        assert plan is not None and plan.k == 5
        secs[0].filter = dsl.parse_query({"term": {"body": "alpha"}})
        assert extract_knn_plan(secs, service.mappings) is None


BOOL_BODIES = [
    {"query": {"bool": {
        "must": [{"match": {"body": "alpha"}}],
        "should": [{"match": {"body": "gamma delta"}}],
    }}, "size": 10},
    {"query": {"bool": {
        "must": [{"term": {"body": "alpha"}}, {"term": {"body": "beta"}}],
    }}, "size": 10},
    {"query": {"bool": {
        "should": [{"match": {"body": "alpha"}},
                   {"match": {"body": "epsilon"}},
                   {"match": {"title": "gamma"}}],
        "minimum_should_match": 2,
    }}, "size": 10},
]

MM_BODIES = [
    {"query": {"multi_match": {
        "query": "alpha gamma", "fields": ["title", "body"],
    }}, "size": 10},
    {"query": {"multi_match": {
        "query": "alpha gamma", "fields": ["title^2", "body"],
        "tie_breaker": 0.3,
    }}, "size": 10},
    {"query": {"multi_match": {
        "query": "beta epsilon", "fields": ["title", "body"],
        "type": "most_fields",
    }}, "size": 10},
]


class TestServeParityFallback:
    """Small segments: the serve path falls back to per-segment device
    execution; results must still be exact."""

    @pytest.mark.parametrize("body", BOOL_BODIES + MM_BODIES)
    def test_parity(self, service, body):
        check_parity(service, body)


class TestServeParityFused:
    """Forced fused multi-field kernel (FUSED_MIN_DOCS lowered)."""

    @pytest.fixture(scope="class")
    def fused_service(self):
        from elasticsearch_tpu.search import executor_jax

        orig = executor_jax.FUSED_MIN_DOCS
        executor_jax.FUSED_MIN_DOCS = 10
        svc = make_service(n_docs=400, seed=7)
        yield svc
        executor_jax.FUSED_MIN_DOCS = orig
        svc.close()

    @pytest.mark.parametrize("body", BOOL_BODIES + MM_BODIES)
    def test_parity(self, fused_service, body):
        check_parity(fused_service, body)

    def test_fused_jobs_counted(self, fused_service):
        base = fused_service._batcher.stats["fused_jobs"]
        fused_service.search(BOOL_BODIES[0])
        assert fused_service._batcher.stats["fused_jobs"] > base

    def test_deletes_respected(self, fused_service):
        body = {"query": {"bool": {
            "must": [{"match": {"body": "alpha"}}]}}, "size": 1}
        victim = fused_service.search(body)["hits"]["hits"][0]["_id"]
        fused_service.delete_doc(victim)
        fused_service.refresh()
        after = fused_service.search({**body, "size": 400})
        assert victim not in [h["_id"] for h in after["hits"]["hits"]]


class TestKnnBatched:
    def test_knn_parity(self, service):
        body = {
            "knn": {"field": "vec", "query_vector": [0.5] * 8, "k": 10,
                    "num_candidates": 50},
            "size": 10,
        }
        check_parity(service, body, require_total=False)

    def test_knn_multi_shard(self):
        svc = make_service(n_docs=200, n_shards=3, seed=3)
        try:
            body = {
                "knn": {"field": "vec", "query_vector": [1.0] * 8, "k": 8,
                        "num_candidates": 30},
                "size": 8,
            }
            check_parity(svc, body, require_total=False)
        finally:
            svc.close()

    def test_knn_batched_launch_counted(self, service):
        base = service._batcher.stats["fused_jobs"]
        service.search({
            "knn": {"field": "vec", "query_vector": [0.1] * 8, "k": 3,
                    "num_candidates": 10},
        })
        assert service._batcher.stats["fused_jobs"] > base


class TestHybridRrf:
    def test_rrf_retriever_over_batched_children(self, service):
        resp = service.search({
            "retriever": {"rrf": {
                "retrievers": [
                    {"standard": {"query": {"multi_match": {
                        "query": "alpha gamma",
                        "fields": ["title", "body"]}}}},
                    {"knn": {"field": "vec", "query_vector": [0.5] * 8,
                             "k": 10, "num_candidates": 40}},
                ],
                "rank_constant": 60,
            }},
            "size": 10,
        })
        assert len(resp["hits"]["hits"]) == 10
        scores = [h["_score"] for h in resp["hits"]["hits"]]
        assert scores == sorted(scores, reverse=True)


# ---- clause-level counts (BooleanQuery counts clauses, not words) -----

CLAUSE_DOCS = [
    "xx yy",        # 0: both words of a two-word clause, and not zz
    "xx zz",        # 1
    "zz",           # 2
    "yy zz qq",     # 3
    "xx yy zz",     # 4
] + ["ff gg"] * 15  # 5..19

# name -> (query, the passages that match it)
CLAUSE_CASES = {
    # by terms, passage 0 would count two (xx, yy) and pass
    "must_term_and_two_word_match": (
        {"bool": {"must": [{"term": {"body": "zz"}},
                           {"match": {"body": "xx yy"}}]}}, {1, 3, 4}),
    "must_two_two_word_matches": (
        {"bool": {"must": [{"match": {"body": "xx yy"}},
                           {"match": {"body": "zz qq"}}]}}, {1, 3, 4}),
    "must_term_and_nested_should": (
        {"bool": {"must": [{"term": {"body": "zz"}}, {"bool": {"should": [
            {"term": {"body": "xx"}}, {"match": {"body": "yy qq"}}]}}]}},
        {1, 3, 4}),
    # by terms, passage 0 (xx, yy) and the fillers' (ff, gg) would pass
    "should_two_word_clauses_msm2": (
        {"bool": {"should": [{"match": {"body": "xx yy"}},
                             {"match": {"body": "zz qq"}},
                             {"match": {"body": "ff gg"}}],
                  "minimum_should_match": 2}}, {1, 3, 4}),
    "must_with_scoring_should": (
        {"bool": {"must": [{"term": {"body": "zz"}},
                           {"match": {"body": "xx yy"}}],
                  "should": [{"match": {"body": "qq ff"}}]}}, {1, 3, 4}),
    "a_clause_whose_words_the_index_lacks": (
        {"bool": {"must": [{"term": {"body": "zz"}},
                           {"match": {"body": "nope nada"}}]}}, set()),
}


@pytest.fixture(scope="module")
def clause_service():
    from elasticsearch_tpu.search import executor_jax

    orig = executor_jax.FUSED_MIN_DOCS
    executor_jax.FUSED_MIN_DOCS = 10
    svc = IndexService(
        "clauses",
        settings={"number_of_shards": 1, "search.backend": "jax"},
        mappings_json={"properties": {"body": {"type": "text"}}},
    )
    for i, text in enumerate(CLAUSE_DOCS):
        svc.index_doc(str(i), {"body": text})
    svc.refresh()
    yield svc
    executor_jax.FUSED_MIN_DOCS = orig
    svc.close()


@pytest.mark.parametrize("case", sorted(CLAUSE_CASES))
def test_clauses_are_counted_not_words(clause_service, case):
    query, want = CLAUSE_CASES[case]
    stats = clause_service._batcher.stats
    before = dict(stats)
    body = {"query": query, "size": 20}
    served = check_parity(clause_service, body)
    assert {int(h["_id"]) for h in served["hits"]["hits"]} == want
    assert served["hits"]["total"]["value"] == len(want)
    # one fused launch, no per-job fallback, nothing unplanned
    assert stats["fused_jobs"] == before["fused_jobs"] + 1
    assert stats["serve_fallback_jobs"] == before["serve_fallback_jobs"]
    assert stats["unplanned_queries"] == before["unplanned_queries"]


def test_mesh_twin_refuses_clause_plans():
    """The mesh kernels count terms: a plan that counts a clause of
    several words once is sent to the shards, never to the mesh."""
    svc = make_service(n_docs=60, n_shards=2, seed=3)
    try:
        body = {"query": {"bool": {"must": [
            {"term": {"body": "alpha"}},
            {"match": {"body": "beta gamma"}}]}}, "size": 10}
        mesh = svc.mesh_executor()
        flat = {"query": {"bool": {"must": [
            {"term": {"body": "alpha"}}, {"term": {"body": "beta"}}]}},
            "size": 10}
        routed = mesh.stats["routed"]
        check_parity(svc, flat)
        assert mesh.stats["routed"] == routed + 1  # a flat plan rides it
        check_parity(svc, body)
        assert mesh.stats["routed"] == routed + 1
    finally:
        svc.close()


def term_counting_mf(doc_ids_f, tfs_f, inv_norm_f, dense_f, plan, tie, *,
                     t_rare, n_hot, k, combine):
    """`_fused_query_mf` as it was before clauses were counted: every
    positive-weight slot adds one to the count plane and the mask holds
    the plane itself to msm. The form a flat plan is held to."""
    import jax
    import jax.numpy as jnp

    from elasticsearch_tpu.ops import scoring

    F, n = len(doc_ids_f), inv_norm_f[0].shape[0]
    T, H = t_rare, n_hot
    sec = 2 * T + 2 * H
    B = plan.shape[0]
    msm = plan[:, F * sec]

    def f32(x):
        return jax.lax.bitcast_convert_type(x, jnp.float32)

    cnt = jnp.zeros(B * (n + 1), jnp.int32)
    accs = []
    for f in range(F):
        base = f * sec
        acc, cnt = scoring._add_rare_tiles(
            jnp.zeros(B * (n + 1), jnp.float32), cnt, doc_ids_f[f], tfs_f[f],
            inv_norm_f[f], plan[:, base: base + T],
            f32(plan[:, base + T: base + 2 * T]), signed=True)
        accs.append(scoring._doc_planes(acc, B, n))
    cnt = scoring._doc_planes(cnt, B, n)
    for f in range(F):
        base = f * sec
        accs[f], cnt = scoring._add_hot_terms(
            accs[f], cnt, dense_f[f], None, inv_norm_f[f],
            plan[:, base + 2 * T: base + 2 * T + H],
            f32(plan[:, base + 2 * T + H: base + sec]), signed=True)
    if combine == "sum":
        combined = sum(accs[1:], accs[0])
    else:
        stack = jnp.stack(accs)
        best = stack.max(axis=0)
        combined = best + tie * (stack.sum(axis=0) - best)
    mask = cnt >= jnp.maximum(msm, 1)[:, None]
    top_s, top_d = jax.lax.top_k(jnp.where(mask, combined, -jnp.inf), k)
    return jnp.concatenate(
        [jax.lax.bitcast_convert_type(top_s, jnp.int32), top_d,
         mask.sum(axis=1, dtype=jnp.int32)[:, None]], axis=1)


@pytest.mark.parametrize("combine", ["sum", "max_tie"])
@pytest.mark.parametrize("tiles", [[0, 3], [17, 40, 1, 200]],
                         ids=["two_rows", "four_rows"])
def test_flat_plan_row_is_bit_equal_to_term_counting(combine, tiles):
    """A flat plan (every counted clause one term: multi_match,
    most_fields, term-only bools) feeds only the count plane's term
    counter, and its packed row - scores, ids, total - is bit for bit
    what the term-counting program packed."""
    import functools

    import jax
    import jax.numpy as jnp

    import test_rare_tiles_loop as loop_mod
    from elasticsearch_tpu.ops import scoring

    fields = [loop_mod.make_field(5), loop_mod.make_field(6)]
    plans = loop_mod.make_plans("mf_" + combine, tiles, seed=11)
    rows = len(tiles)
    got = loop_mod.launch("mf_" + combine, fields, plans, rows)
    fs = scoring.MultiFusedScorer(("title", "body"), fields, None)
    old = jax.jit(functools.partial(
        term_counting_mf, t_rare=loop_mod.T, n_hot=loop_mod.H, k=loop_mod.K,
        combine=combine))
    want = np.asarray(old(
        tuple(f["doc_ids"] for f in fields), tuple(f["tfs"] for f in fields),
        tuple(f["inv_norm"] for f in fields),
        tuple(f["dense"] for f in fields),
        jnp.asarray(fs.pack_plans(plans, rows=rows)), loop_mod.TIE))
    assert np.array_equal(got, want)
    assert (got[:, 2 * loop_mod.K] > 0).any()  # rows that match something


def test_clause_counters_of_the_count_plane():
    """`clause_units` / `clauses_hit`: the term counter and the digits
    of one int32 plane, digit 3's top bit being the sign bit."""
    import jax.numpy as jnp

    from elasticsearch_tpu.ops import scoring

    ids = jnp.asarray(np.array(
        [scoring.clause_slot_ids([5], count)[0] for count in (0, 1)]
        + [scoring.clause_slot_ids([5], 2 + d)[0] for d in range(4)] + [-1],
        np.int32))[None, :]
    plain, units = scoring.clause_units(ids)
    assert plain.tolist() == [[5, 5, 5, 5, 5, 5, -1]]
    assert units.tolist() == [
        [1, 1, 1 << 16, 1 << 20, 1 << 24, 1 << 28, 0]]
    u = np.asarray(units)[0, 1:].astype(np.int64)
    full = scoring.CLAUSE_TERMS_MAX
    planes = {
        0: 0,
        7 * u[0]: 7,                        # seven one-term clauses
        full * u[1]: 1,                     # fifteen words of one clause
        3 * u[0] + 2 * u[2] + u[4]: 5,
        full * (u[1] + u[2] + u[3] + u[4]): 4,  # wraps into the sign bit
    }
    cnt = np.array(list(planes), np.int64).astype(np.uint32).view(np.int32)
    assert scoring.clauses_hit(jnp.asarray(cnt)).tolist() == list(
        planes.values())
