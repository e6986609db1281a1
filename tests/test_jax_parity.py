"""Property tests: the JAX executor must match the NumPy oracle hit-for-hit
on randomized corpora (the recall-parity gate from SURVEY.md §4, in-process
form). Runs on CPU JAX (conftest forces JAX_PLATFORMS=cpu)."""

import numpy as np
import pytest

from elasticsearch_tpu.analysis import AnalysisRegistry
from elasticsearch_tpu.index.mapping import DocumentParser, Mappings
from elasticsearch_tpu.index.segment import SegmentBuilder
from elasticsearch_tpu.search import dsl
from elasticsearch_tpu.search.executor import NumpyExecutor, ShardReader
from elasticsearch_tpu.search.executor_jax import JaxExecutor

VOCAB = [
    "alpha", "bravo", "charlie", "delta", "echo", "foxtrot", "golf",
    "hotel", "india", "juliet", "kilo", "lima", "mike", "november",
    "oscar", "papa", "quebec", "romeo", "sierra", "tango",
]

MAPPING = {
    "properties": {
        "title": {"type": "text"},
        "body": {"type": "text"},
        "tag": {"type": "keyword"},
        "views": {"type": "integer"},
        "vec": {"type": "dense_vector", "dims": 8, "similarity": "cosine"},
    }
}


def zipf_text(rng, n_words):
    # zipfian-ish draw over the vocab
    p = 1.0 / np.arange(1, len(VOCAB) + 1)
    p /= p.sum()
    return " ".join(rng.choice(VOCAB, size=n_words, p=p))


def build_readers(n_docs=300, n_segments=1, seed=7):
    rng = np.random.default_rng(seed)
    mappings = Mappings(MAPPING)
    analysis = AnalysisRegistry()
    parser = DocumentParser(mappings, analysis)
    segs = []
    doc_num = 0
    for _ in range(n_segments):
        builder = SegmentBuilder(mappings)
        for _ in range(n_docs // n_segments):
            src = {
                "title": zipf_text(rng, int(rng.integers(2, 8))),
                "body": zipf_text(rng, int(rng.integers(5, 60))),
                "tag": str(rng.choice(["a", "b", "c", "d"])),
                "views": int(rng.integers(0, 1000)),
                "vec": rng.standard_normal(8).astype(np.float32).tolist(),
            }
            builder.add(parser.parse(f"doc-{doc_num}", src))
            doc_num += 1
        segs.append(builder.build())
    reader = ShardReader(segs, mappings, analysis)
    return NumpyExecutor(reader), JaxExecutor(reader)


ORACLE, JAXEX = build_readers()
ORACLE_MULTI, JAXEX_MULTI = build_readers(n_docs=200, n_segments=3, seed=11)

QUERIES = [
    {"match": {"body": "alpha"}},
    {"match": {"body": "alpha bravo charlie"}},
    {"match": {"body": {"query": "alpha bravo", "operator": "and"}}},
    {"match": {"body": {"query": "alpha bravo charlie delta", "minimum_should_match": 3}}},
    {"match": {"body": {"query": "alpha", "boost": 2.5}}},
    {"term": {"tag": "a"}},
    {"terms": {"tag": ["a", "c"]}},
    {"term": {"views": 500}},
    {"range": {"views": {"gte": 100, "lt": 700}}},
    {"range": {"tag": {"gte": "a", "lte": "b"}}},
    {"exists": {"field": "views"}},
    {"match_all": {}},
    {"constant_score": {"filter": {"match": {"body": "echo"}}, "boost": 3.0}},
    {"multi_match": {"query": "alpha echo", "fields": ["title^2", "body"]}},
    {"multi_match": {"query": "alpha echo", "fields": ["title", "body"], "type": "most_fields"}},
    {"multi_match": {"query": "alpha echo", "fields": ["title", "body"], "tie_breaker": 0.3}},
    {
        "bool": {
            "must": [{"match": {"body": "alpha"}}],
            "filter": [{"range": {"views": {"gte": 50}}}],
            "should": [{"term": {"tag": "b"}}],
            "must_not": [{"term": {"tag": "d"}}],
        }
    },
    {
        "bool": {
            "should": [
                {"match": {"title": "bravo"}},
                {"match": {"body": "quebec tango"}},
            ],
            "minimum_should_match": 1,
        }
    },
    {"bool": {"must_not": [{"term": {"tag": "a"}}]}},
    {
        "bool": {
            "must": [
                {
                    "bool": {
                        "should": [
                            {"match": {"body": "alpha"}},
                            {"match": {"body": "bravo"}},
                        ]
                    }
                }
            ],
            "boost": 2.0,
        }
    },
]


def assert_same(res_np, res_jax, scores_rtol=1e-5):
    assert res_np.total == res_jax.total
    assert len(res_np.hits) == len(res_jax.hits)
    np_scores = np.array([h.score for h in res_np.hits])
    jax_scores = np.array([h.score for h in res_jax.hits])
    np.testing.assert_allclose(jax_scores, np_scores, rtol=scores_rtol, atol=1e-6)
    # doc order must match except where adjacent scores are ulp-equal
    for i, (hn, hj) in enumerate(zip(res_np.hits, res_jax.hits)):
        if hn.doc_id != hj.doc_id:
            # permissible only if scores tie within tolerance
            assert np.isclose(hn.score, hj.score, rtol=scores_rtol), (
                i,
                hn,
                hj,
            )


@pytest.mark.parametrize("qi", range(len(QUERIES)))
def test_query_parity_single_segment(qi):
    q = dsl.parse_query(QUERIES[qi])
    assert_same(ORACLE.search(q, size=20), JAXEX.search(q, size=20))


@pytest.mark.parametrize("qi", range(0, len(QUERIES), 3))
def test_query_parity_multi_segment(qi):
    q = dsl.parse_query(QUERIES[qi])
    assert_same(ORACLE_MULTI.search(q, size=20), JAXEX_MULTI.search(q, size=20))


def test_knn_parity():
    rng = np.random.default_rng(3)
    vec = rng.standard_normal(8).tolist()
    knn = [dsl.parse_knn({"field": "vec", "query_vector": vec, "k": 15, "num_candidates": 50})]
    assert_same(ORACLE.search(None, knn=knn, size=15), JAXEX.search(None, knn=knn, size=15))


def test_knn_filtered_parity():
    rng = np.random.default_rng(4)
    vec = rng.standard_normal(8).tolist()
    knn = [
        dsl.parse_knn(
            {
                "field": "vec",
                "query_vector": vec,
                "k": 10,
                "filter": {"term": {"tag": "b"}},
            }
        )
    ]
    assert_same(ORACLE.search(None, knn=knn, size=10), JAXEX.search(None, knn=knn, size=10))


def test_hybrid_parity():
    rng = np.random.default_rng(5)
    vec = rng.standard_normal(8).tolist()
    knn = [dsl.parse_knn({"field": "vec", "query_vector": vec, "k": 10})]
    q = dsl.parse_query({"match": {"body": "alpha bravo"}})
    assert_same(ORACLE.search(q, knn=knn, size=20), JAXEX.search(q, knn=knn, size=20))


def test_knn_multi_segment_parity():
    rng = np.random.default_rng(6)
    vec = rng.standard_normal(8).tolist()
    knn = [dsl.parse_knn({"field": "vec", "query_vector": vec, "k": 12, "num_candidates": 30})]
    assert_same(
        ORACLE_MULTI.search(None, knn=knn, size=12),
        JAXEX_MULTI.search(None, knn=knn, size=12),
    )


def test_pagination_parity():
    q = dsl.parse_query({"match": {"body": "alpha bravo charlie"}})
    r_np = ORACLE.search(q, size=5, from_=5)
    r_jx = JAXEX.search(q, size=5, from_=5)
    assert_same(r_np, r_jx)


def test_min_score_parity():
    q = dsl.parse_query({"match": {"body": "alpha"}})
    r_np = ORACLE.search(q, size=50, min_score=0.5)
    r_jx = JAXEX.search(q, size=50, min_score=0.5)
    assert_same(r_np, r_jx)


# ---- the filtered scan's selection from block maxima (ops/scoring.py
# `_block_topk`) against the plain `lax.top_k` it stands for ----------------

BLOCK_K = 16
BLOCK_N = 3 * 128 * 128  # three whole groups of 128 rows of 128 lanes


def _block_case(case: str, rows: int):
    """(scores float32[rows, n], mask bool[rows, n]) of one case; every
    query row draws its own."""
    rng = np.random.default_rng([42, rows, BLOCK_CASES.index(case)])
    n = BLOCK_N + (77 if case in ("ragged_tail", "best_in_the_tail") else 0)
    scores = rng.random((rows, n), dtype=np.float32)
    mask = rng.random((rows, n)) < 0.3
    if case == "best_in_the_tail":
        scores[:, BLOCK_N:] += 1.0
    elif case == "fewer_than_k_pass":
        mask[:] = False
        for r in range(rows):
            mask[r, rng.choice(n, 5, replace=False)] = True
    elif case == "one_passes":
        mask[:] = False
        mask[np.arange(rows), rng.integers(0, n, rows)] = True
    elif case == "none_passes":
        mask[:] = False
    elif case == "all_scores_equal":
        scores[:] = 0.25
    elif case == "tie_group_across_the_kth_block":
        # three levels: 15 columns hold a 3, 120 a 2, the rest a 1: the
        # k-th block maximum is a 2 that blocks not chosen hold too
        scores[:] = 1.0
        mask[:] = True
        for r in range(rows):
            cols = rng.choice(n, 135, replace=False)
            scores[r, cols[:15]] = 3.0
            scores[r, cols[15:]] = 2.0
    return scores, mask


BLOCK_CASES = (
    "whole_groups",  # n a multiple of the group
    "ragged_tail",  # 77 columns past the last whole group
    "best_in_the_tail",  # and the best scores lie there
    "fewer_than_k_pass",  # 5 columns
    "one_passes",
    "none_passes",
    "all_scores_equal",
    "tie_group_across_the_kth_block",
)


@pytest.mark.parametrize("rows", [1, 2, 4])
@pytest.mark.parametrize("case", BLOCK_CASES)
def test_block_selection_is_the_plain_topk(case, rows):
    import jax
    import jax.numpy as jnp

    from elasticsearch_tpu.ops import scoring

    scores, mask = _block_case(case, rows)
    masked = jnp.where(jnp.asarray(mask), jnp.asarray(scores), -jnp.inf)
    assert scoring.knn_block_select(masked.shape[1], BLOCK_K)
    got_s, got_d = (np.asarray(x) for x in jax.jit(
        scoring._block_topk, static_argnums=1)(masked, BLOCK_K))
    want_s = np.asarray(jax.lax.top_k(masked, BLOCK_K)[0])
    assert got_s.shape == got_d.shape == (rows, BLOCK_K)
    for r in range(rows):
        # the same multiset of scores, best first; -inf past the passing
        np.testing.assert_array_equal(got_s[r], want_s[r])
        assert (got_s[r][:-1] >= got_s[r][1:]).all()
        held = np.isfinite(got_s[r])
        cols = got_d[r][held]
        assert held.sum() == min(BLOCK_K, int(mask[r].sum()))
        assert len(set(cols.tolist())) == len(cols)  # no row twice
        assert mask[r][cols].all()  # every returned row passes its mask
        np.testing.assert_array_equal(scores[r][cols], got_s[r][held])


@pytest.mark.parametrize("n, k, selects", [
    (8 * 128 * 128, 128, True), (8 * 128 * 128 - 1, 128, False),
    (10_000_000, 128, True), (20_000, 128, False), (20_000, 16, True),
    (16_383, 16, False),
])
def test_block_selection_engages_from_eight_k_whole_blocks(n, k, selects):
    from elasticsearch_tpu.ops import scoring

    assert scoring.knn_block_select(n, k) is selects


@pytest.mark.parametrize("rows", [1, 2, 4])
def test_filtered_scan_with_the_norm_plane_is_the_scan_without(rows):
    """int8 rows under l2_norm: the norm plane is the in-program
    `sum(v * v)` bit for bit, so the scores are; the wide plane's block
    selection returns what the plain top-k returns."""
    import jax
    import jax.numpy as jnp

    from elasticsearch_tpu.ops import scoring

    rng = np.random.default_rng([42, 7, rows])
    n, d, k = BLOCK_N + 50, 192, BLOCK_K
    vectors = rng.integers(-128, 128, (n, d)).astype(np.int8)
    queries = rng.integers(-128, 128, (rows, d)).astype(np.float32)
    mask = rng.random((rows, n)) < 0.05
    norms = scoring.knn_row_norms(vectors)
    np.testing.assert_array_equal(
        np.asarray(norms), (vectors.astype(np.int64) ** 2).sum(axis=1))
    plain = scoring.knn_scores(queries, vectors, "l2_norm")
    np.testing.assert_array_equal(
        np.asarray(plain),
        np.asarray(scoring.knn_scores(queries, vectors, "l2_norm", norms)))
    got_s, got_d = scoring.knn_topk_filtered(
        queries, vectors, mask, "l2_norm", k, norms)
    want_s, _ = jax.lax.top_k(jnp.where(mask, plain, -jnp.inf), k)
    np.testing.assert_array_equal(np.asarray(got_s), np.asarray(want_s))
    # whole-number distances tie: rows of equal score may swap
    picked = np.take_along_axis(np.asarray(plain), np.asarray(got_d), axis=1)
    np.testing.assert_array_equal(picked, np.asarray(want_s))
    assert np.take_along_axis(mask, np.asarray(got_d), axis=1).all()
