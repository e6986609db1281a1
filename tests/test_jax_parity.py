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


# ---- the filtered kNN mask: terms answered from bit rows (ops/scoring.py
# `knn_filter_mask` with `FilterBitRows`) against the scatter-only program --

BITS_N = 8_011  # not a multiple of 32; dense_row_min_df(8,011) = 1,024
# term -> df: one tag in 30% of the rows, two more over the rule, one just
# under it, rare ones, one row
BITS_DF = {"common": 2_403, "second": 1_500, "third": 1_024,
           "under": 1_023, "rare": 300, "few": 7, "one": 1}


@pytest.fixture(scope="module")
def bits_field():
    """(PostingsField of BITS_DF's tags over BITS_N documents, its
    DevicePostings, the live / exists plane)."""
    from elasticsearch_tpu.index.segment import TILE, FieldStats, PostingsField
    from elasticsearch_tpu.search.executor_jax import DevicePostings

    rng = np.random.default_rng(45)
    terms = sorted(BITS_DF)
    df = np.asarray([BITS_DF[t] for t in terms], np.int32)
    count = ((df + TILE - 1) // TILE).astype(np.int32)
    start = (np.cumsum(count) - count).astype(np.int32)
    doc_ids = np.full((int(count.sum()), TILE), -1, np.int32)
    for t, (s, c, d) in enumerate(zip(start, count, df)):
        docs = np.sort(rng.choice(BITS_N, int(d), replace=False))
        doc_ids[s : s + c].reshape(-1)[:d] = docs
    tfs = (doc_ids >= 0).astype(np.int32)
    pf = PostingsField(
        terms=terms, term_df=df, term_total_tf=df.astype(np.int64),
        term_tile_start=start, term_tile_count=count, doc_ids=doc_ids,
        tfs=tfs, tile_max_tf=tfs.max(axis=1),
        tile_min_norm=np.zeros(len(doc_ids), np.uint8),
        norms=np.zeros(BITS_N, np.uint8), stats=FieldStats(),
    )
    cand = rng.random(BITS_N) < 0.9
    return pf, DevicePostings(pf, n_docs=BITS_N), cand


# name -> the launch's filters (each a job's clauses); one job unless named
# a mix
BITS_FILTERS = {
    "dense_and_dense": [(("common",), ("second",))],
    "dense_and_rare": [(("common",), ("rare",))],
    "rare_and_rare": [(("under",), ("rare",))],
    "one_dense_term": [(("third",),)],
    "one_term_just_under_the_rule": [(("under",),)],
    "clause_of_a_dense_and_a_rare_term": [(("common", "rare"), ("second",))],
    "clause_of_dense_terms_alone": [(("common", "third"), ("second",))],
    "two_clauses_of_dense_terms": [(("common", "third"), ("second", "third"))],
    "term_the_segment_lacks": [(("common",), ("nowhere",))],
    "dense_clause_with_a_term_the_segment_lacks": [(("common", "nowhere"),)],
    "only_a_term_the_segment_lacks": [(("nowhere",),)],
    "four_rows_mixed": [
        (("common",), ("second",)), (("common",), ("rare",)),
        (("under",), ("few",)), (("common", "rare"), ("third",))],
    "three_rows_all_from_bit_rows": [
        (("common",),), (("second",), ("third",)), (("common", "second"),)],
}


@pytest.mark.parametrize("case", BITS_FILTERS)
def test_mask_from_bit_rows_is_the_scattered_mask(bits_field, case):
    """Masks and rows passed equal the scatter-only program's bit for
    bit; pad rows stay empty; only the terms without a row are
    scattered."""
    from elasticsearch_tpu.ops import scoring

    pf, dp, cand = bits_field
    rows = dp.filter_bits
    filters = BITS_FILTERS[case]
    width = 4 if len(filters) > 1 else 2  # pad rows either way
    got = scoring.pack_filter_plans(pf, filters, width, rows)
    want = scoring.pack_filter_plans(pf, filters, width)
    assert got.plan.shape == want.plan.shape
    held = {pf.terms[t] for t in rows.row_of_term}
    assert held == {"common", "second", "third"}
    present = [[t for t in c if t in BITS_DF] for f in filters for c in f]
    on_rows = [c for c in present if c and set(c) <= held]
    assert got.terms == want.terms == sum(len(c) for f in filters for c in f)
    assert got.bit_terms == sum(map(len, on_rows)) and want.bit_terms == 0
    assert got.tiles == sum(
        int(pf.term_tile_count[pf.term_id(t)])
        for c in present if c not in on_rows for t in c)
    assert want.tiles == sum(
        int(pf.term_tile_count[pf.term_id(t)]) for c in present for t in c)
    mask, passed = scoring.knn_filter_mask(
        dp.doc_ids, cand, got.plan, rows.plane)
    ref_mask, ref_passed = scoring.knn_filter_mask(dp.doc_ids, cand, want.plan)
    np.testing.assert_array_equal(np.asarray(mask), np.asarray(ref_mask))
    np.testing.assert_array_equal(np.asarray(passed), np.asarray(ref_passed))
    assert not np.asarray(mask)[len(filters):].any()
    # and both are the filter's own meaning
    for ji, clauses in enumerate(filters):
        rows_ok = cand.copy()
        for clause in clauses:
            any_of = np.zeros(BITS_N, bool)
            for t in clause:
                if t in BITS_DF:
                    any_of[pf.term_docs(pf.term_id(t))] = True
            rows_ok &= any_of
        np.testing.assert_array_equal(np.asarray(mask)[ji], rows_ok)


def test_each_bit_row_is_the_packed_scatter_mask_of_its_tag(bits_field):
    from elasticsearch_tpu.ops import scoring

    pf, dp, _cand = bits_field
    rows = dp.filter_bits
    w = scoring.filter_bit_words(BITS_N)
    assert w % scoring.FILTER_BIT_WORDS_ALIGN == 0 and 32 * w >= BITS_N
    assert rows.plane.shape == (3, w) and rows.plane.dtype == np.uint32
    assert rows.nbytes == 3 * w * 4
    plane = np.asarray(rows.plane)
    everyone = np.ones(BITS_N, bool)
    for tid, r in rows.row_of_term.items():
        docs = pf.term_docs(tid)
        want = np.zeros(w, np.uint32)  # document d: bit d // w of word d % w
        np.bitwise_or.at(want, docs % w, np.uint32(1) << (docs // w).astype(np.uint32))
        np.testing.assert_array_equal(plane[r], want)
        (mask, _) = scoring.knn_filter_mask(
            dp.doc_ids, everyone,
            scoring.pack_filter_plans(pf, [((pf.terms[tid],),)], 1).plan)
        np.testing.assert_array_equal(
            np.asarray(scoring._pack_bit_rows(mask))[0], plane[r])
        np.testing.assert_array_equal(
            np.asarray(scoring._unpack_bit_rows(rows.plane[r:r + 1], BITS_N)),
            np.asarray(mask))
    # rows number by df rank, the commonest first
    by_rank = sorted(rows.row_of_term, key=rows.row_of_term.get)
    assert [pf.terms[t] for t in by_rank] == ["common", "second", "third"]


@pytest.mark.parametrize("n_docs, held", [(1_023, 0), (1_024, 1), (131_200, 0)],
                         ids=["under_the_floor", "at_the_floor", "rule_above_every_df"])
def test_segment_builds_bit_rows_only_for_terms_over_the_rule(n_docs, held):
    """A tag in every row of a segment under `dense_row_min_df`'s floor
    of 1,024 builds no row (the program runs as it always did); at the
    floor it holds one; a wide segment whose rule (n / 128) no tag
    passes holds none."""
    from elasticsearch_tpu.index.segment import TILE, FieldStats, PostingsField
    from elasticsearch_tpu.search.executor_jax import (
        DevicePostings, dense_row_min_df)

    df = min(n_docs, 1_024)
    assert (df >= dense_row_min_df(n_docs)) is bool(held)
    count = -(-df // TILE)
    doc_ids = np.full((count, TILE), -1, np.int32)
    doc_ids.reshape(-1)[:df] = np.arange(df)
    tfs = (doc_ids >= 0).astype(np.int32)
    pf = PostingsField(
        terms=["every"], term_df=np.asarray([df], np.int32),
        term_total_tf=np.asarray([df], np.int64),
        term_tile_start=np.zeros(1, np.int32),
        term_tile_count=np.asarray([count], np.int32), doc_ids=doc_ids,
        tfs=tfs, tile_max_tf=tfs.max(axis=1),
        tile_min_norm=np.zeros(count, np.uint8),
        norms=np.zeros(n_docs, np.uint8), stats=FieldStats(),
    )
    charged = []
    dp = DevicePostings(pf, charge=lambda *a: charged.append(a), n_docs=n_docs)
    rows = dp.filter_bits
    assert dp._tfs is None  # a filter's rows never upload the tf plane
    if not held:
        assert rows.plane is None and not rows.row_of_term and charged == []
        return
    assert len(rows.row_of_term) == 1 and dp.filter_bits is rows  # built once
    assert charged == [("postings", rows.nbytes, False)]
