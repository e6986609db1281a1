"""Learned sparse retrieval: impact-ordered quantized postings, the
`sparse_vector` field + query, and the third hybrid leg.

Contract under test (the sparse-retrieval tentpole):
  * segment builds are BIT-IDENTICAL host vs device for every
    SparseField plane (impact-ordered doc/weight tiles, int8 qweights
    twin, scales, tile_max/tile_qmax sidecars), and the impact-ordering
    invariants hold (weight desc within a term, non-increasing tile
    bounds, term maxima in first tiles);
  * the fp32 serving path is FLOAT-IDENTICAL to the NumpyExecutor's
    dense term-at-a-time oracle — with or without block-max pruning —
    and the int8 column holds recall@10 ≥ 0.95 against it;
  * block-max pruning is exact: dropped tiles never change the
    returned hits, nor `hits.total` as Elasticsearch reports it (a job
    drops tiles only once its total is proved past `track_total_hits`,
    and then answers that cap and "gte");
  * every device-path failure (injected `sparse.score` fault, HBM
    budget breach) deterministically falls back to the dense host
    oracle — same answer, counters bumped;
  * hot terms of the int8 column (df >= max(1024, n / 128), held by df
    rank inside the text family's row budget) are scored from dense
    int8 rows: rows + tiles answer what tiles alone answer, whatever a
    launch holds, and the float32 column builds none;
  * the mesh SPMD path is bit-identical to the per-shard path in both
    storage modes;
  * `sparse_vector` fuses as a third `rrf` retriever leg beside BM25
    and kNN, with its own leg timing in rrf_stats;
  * malformed `sparse_vector` queries are request-scoped 400s, and
    `_nodes/stats` carries the `sparse` block with the ≥2x int8
    compression headline.
"""

import functools
import os
import time

import numpy as np
import pytest

from elasticsearch_tpu.analysis import AnalysisRegistry
from elasticsearch_tpu.cluster.indices import IndexService
from elasticsearch_tpu.common.faults import faults
from elasticsearch_tpu.index import segment_build
from elasticsearch_tpu.index.mapping import DocumentParser, Mappings
from elasticsearch_tpu.index.segment import SegmentBuilder
from elasticsearch_tpu.ops import impact as impact_ops
from elasticsearch_tpu.search import sparse as sparse_mod
from elasticsearch_tpu.search.dsl import QueryParseError

VOCAB = [f"tok{i:02d}" for i in range(40)]
DIMS = 4

SPARSE_MAPPINGS = {
    "properties": {
        "ml": {"type": "sparse_vector"},
        "body": {"type": "text"},
        "vec": {"type": "dense_vector", "dims": DIMS,
                "similarity": "cosine"},
    }
}


def sparse_docs(n=300, vocab=VOCAB, seed=3, lo=2, hi=9):
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        nt = int(rng.integers(lo, min(hi, len(vocab))))
        toks = [str(t) for t in rng.choice(vocab, size=nt, replace=False)]
        vec = {t: float(np.round(rng.random() * 3 + 0.05, 4)) for t in toks}
        out.append(
            (
                str(i),
                {
                    "ml": vec,
                    "body": " ".join(toks),
                    "vec": [
                        float(x) for x in rng.normal(size=DIMS)
                    ],
                },
            )
        )
    return out


def make_service(name, backend="jax", quant="int8", shards=1, docs=None,
                 **extra):
    svc = IndexService(
        name,
        settings={
            "number_of_shards": shards,
            "search.backend": backend,
            "sparse.quantization": quant,
            **extra,
        },
        mappings_json=SPARSE_MAPPINGS,
    )
    for i, s in (docs if docs is not None else sparse_docs()):
        svc.index_doc(i, s)
    svc.refresh()
    return svc


def qbody(seed, size=10, exact=False):
    rng = np.random.default_rng(seed)
    nt = int(rng.integers(2, 6))
    toks = [str(t) for t in rng.choice(VOCAB, size=nt, replace=False)]
    qv = {t: float(np.round(rng.random() * 2 + 0.1, 4)) for t in toks}
    b = {
        "query": {"sparse_vector": {"field": "ml", "query_vector": qv}},
        "size": size,
    }
    if exact:
        b["exact"] = True
    return b


def hits_of(resp):
    return [(h["_id"], h["_score"]) for h in resp["hits"]["hits"]]


def _arrays_equal(name, a, b):
    a = np.asarray(a)
    b = np.asarray(b)
    assert a.dtype == b.dtype, (name, a.dtype, b.dtype)
    assert a.shape == b.shape, (name, a.shape, b.shape)
    assert np.array_equal(a, b), name


# ---------------------------------------------------------------------------
# build: host == device, bit for bit; impact-ordering invariants
# ---------------------------------------------------------------------------


class TestSparseBuildParity:
    def _parsed(self, n=137, seed=5):
        maps = Mappings(SPARSE_MAPPINGS)
        parser = DocumentParser(maps, AnalysisRegistry())
        return maps, [
            parser.parse(i, s) for i, s in sparse_docs(n, seed=seed)
        ]

    def test_device_build_bit_identical(self, monkeypatch):
        monkeypatch.setenv("ES_TPU_DEVICE_BUILD", "force")
        maps, docs = self._parsed()
        b = SegmentBuilder(maps)
        for d in docs:
            b.add(d)
        host = b.build()
        dev = segment_build.build_segment(maps, docs)
        assert sorted(host.sparse) == sorted(dev.sparse) == ["ml"]
        hs, ds = host.sparse["ml"], dev.sparse["ml"]
        assert hs.terms == ds.terms
        assert hs.pruned == ds.pruned
        for attr in (
            "term_df", "term_tile_start", "term_tile_count", "doc_ids",
            "weights", "qweights", "scales", "tile_max", "tile_qmax",
            "exists",
        ):
            _arrays_equal(attr, getattr(hs, attr), getattr(ds, attr))

    def test_impact_ordering_invariants(self):
        maps, docs = self._parsed(200, seed=9)
        b = SegmentBuilder(maps)
        for d in docs:
            b.add(d)
        sf = b.build().sparse["ml"]
        for tid in range(len(sf.terms)):
            pdocs, pw = sf.term_postings(tid)
            # impact ordering: weight DESC, doc asc tie-break
            assert all(
                (pw[i], -pdocs[i]) >= (pw[i + 1], -pdocs[i + 1])
                for i in range(len(pw) - 1)
            ), sf.terms[tid]
            start = int(sf.term_tile_start[tid])
            count = int(sf.term_tile_count[tid])
            tmax = sf.tile_max[start : start + count]
            # tile bounds non-increasing within a term; the term's
            # global max lives in its FIRST tile
            assert np.all(tmax[:-1] >= tmax[1:]), sf.terms[tid]
            if len(pw):
                assert np.float32(tmax[0]) == np.float32(pw.max())
            # int8 soundness: tile_qmax bounds the DEQUANTIZED values
            scale = np.float32(sf.scales[tid])
            for t in range(count):
                row_q = sf.qweights[start + t].astype(np.float32) * scale
                valid = sf.doc_ids[start + t] >= 0
                if valid.any():
                    assert np.float32(sf.tile_qmax[start + t]) >= np.float32(
                        row_q[valid].max()
                    )


# ---------------------------------------------------------------------------
# kernel: ImpactScorer vs the dense numpy oracle, across k/row buckets
# ---------------------------------------------------------------------------


class TestImpactKernel:
    def _field(self, n=300, seed=3):
        maps = Mappings({"properties": {"ml": {"type": "sparse_vector"}}})
        parser = DocumentParser(maps, AnalysisRegistry())
        b = SegmentBuilder(maps)
        docs = sparse_docs(n, seed=seed)
        for i, s in docs:
            b.add(parser.parse(i, {"ml": s["ml"]}))
        return b.build(), docs

    def _oracle(self, sf, n_docs, tids, tws):
        """Term-at-a-time fp32 accumulation in term order — the exact
        float-op order the serving kernel must reproduce."""
        acc = np.zeros(n_docs, np.float32)
        for tid, tw in zip(tids, tws):
            start = int(sf.term_tile_start[tid])
            count = int(sf.term_tile_count[tid])
            d = sf.doc_ids[start : start + count].ravel()
            v = sf.values_plane[start : start + count].ravel()
            m = d >= 0
            np.add.at(acc, d[m], np.float32(tw) * v[m].astype(np.float32))
        return acc

    @pytest.mark.parametrize("quantized", [False, True])
    @pytest.mark.parametrize("k", [5, 16, 40])
    def test_scorer_matches_oracle(self, quantized, k):
        seg, _docs = self._field()
        sf = seg.sparse["ml"]
        sf.values_plane = sf.qweights if quantized else sf.weights
        sc = impact_ops.ImpactScorer(
            sf.doc_ids, sf.values_plane, seg.num_docs
        )
        rng = np.random.default_rng(17)
        queries = []
        for _ in range(6):
            toks = [
                str(t) for t in rng.choice(VOCAB, size=4, replace=False)
            ]
            ws = [float(np.round(rng.random() * 2 + 0.1, 4)) for _ in toks]
            queries.append((toks, ws))
        tile_lists, weight_lists, oracles = [], [], []
        for toks, ws in queries:
            tids, tws, _bws, starts, counts = impact_ops.impact_tile_lists(
                sf, toks, ws, quantized
            )
            tiles = np.concatenate(
                [
                    np.arange(s, s + c, dtype=np.int64)
                    for s, c in zip(starts, counts)
                ]
            ) if len(tids) else np.zeros(0, np.int64)
            tws_full = np.concatenate(
                [
                    np.full(int(c), tw, np.float32)
                    for tw, c in zip(tws, counts)
                ]
            ) if len(tids) else np.zeros(0, np.float32)
            tile_lists.append(tiles)
            weight_lists.append(tws_full)
            oracles.append(self._oracle(sf, seg.num_docs, tids, tws))
        acc, cnt = sc.new_acc()
        acc, cnt = sc.score_into(acc, cnt, tile_lists, weight_lists)
        scores, docs, totals = sc.finalize(acc, cnt, k)
        for ji, oracle in enumerate(oracles):
            matched = np.flatnonzero(oracle != 0.0)
            order = sorted(matched, key=lambda d: (-oracle[d], d))
            want = order[: min(k, seg.num_docs)]
            finite = np.isfinite(scores[ji])
            got_docs = docs[ji][finite]
            got_scores = scores[ji][finite]
            assert list(got_docs) == [int(d) for d in want], ji
            # float-identical accumulation, both storage modes
            assert np.array_equal(
                got_scores, oracle[got_docs].astype(np.float32)
            ), ji
            assert int(totals[ji]) == len(matched)


# ---------------------------------------------------------------------------
# the tile pass: ONE looped program a scoring (PR 52)
# ---------------------------------------------------------------------------

LOOP_DOCS, LOOP_TILES = 3000, 700
IN_USE = [0, 1, 127, 128, 129, 511, 512, 513, 1264,
          impact_ops.TILE_CAP, impact_ops.TILE_CAP + 1]


@functools.lru_cache(maxsize=None)
def loop_scorer(storage: str):
    """A made-up column whose tiles hold documents in no order of id,
    some twice (a plan may name a tile twice too): every cell's float32
    sum depends on the order its addends arrive in."""
    rng = np.random.default_rng(52)
    doc_ids = rng.integers(-1, LOOP_DOCS, (LOOP_TILES, 128)).astype(np.int32)
    if storage == "int8":
        values = rng.integers(-127, 128, (LOOP_TILES, 128)).astype(np.int8)
    else:
        values = rng.random((LOOP_TILES, 128)).astype(np.float32)
    return impact_ops.ImpactScorer(doc_ids, values, LOOP_DOCS)


def loop_lists(in_use: int, rows: int):
    """Row 0 holds `in_use` tiles, the rows under it fewer."""
    rng = np.random.default_rng([52, in_use, rows])
    tiles = [rng.integers(0, LOOP_TILES, max(in_use - 5 * j, 0))
             for j in range(rows)]
    weights = [(rng.random(len(t)) + 0.01).astype(np.float32) for t in tiles]
    return tiles, weights


def impact_sum(sc, tiles, weights):
    """The parent's formula, one posting at a time in plan order:
    float32(tw) x float32(value) added into the document's float32
    cell, 1 into its count; pad postings (-1) nowhere."""
    doc_ids, values = np.asarray(sc.doc_ids), np.asarray(sc.values)
    acc = np.zeros(sc.n_docs, np.float32)
    cnt = np.zeros(sc.n_docs, np.int32)
    d = doc_ids[tiles].ravel()
    p = (np.repeat(weights, 128).astype(np.float32)
         * values[tiles].ravel().astype(np.float32))
    np.add.at(acc, d[d >= 0], p[d >= 0])
    np.add.at(cnt, d[d >= 0], 1)
    return acc, cnt


@pytest.mark.parametrize("storage", ["int8", "float32"])
@pytest.mark.parametrize("rows", [1, 4])
@pytest.mark.parametrize("in_use", IN_USE)
def test_looped_tile_pass_is_the_impact_sum(in_use, rows, storage):
    """Whatever a plan holds, the looped program's planes are the
    NumPy impact sum over the tiles in use, bit for bit, its counts
    equal, and a scoring is one launch up to TILE_CAP tiles."""
    sc = loop_scorer(storage)
    tiles, weights = loop_lists(in_use, rows)
    assert impact_ops.chunk_launches(tiles) == -(-in_use // impact_ops.TILE_CAP)
    full, rest = divmod(in_use, impact_ops.TILE_CAP)
    assert impact_ops.tile_trips(tiles) == (
        full * (impact_ops.TILE_CAP // impact_ops.TILE_STEP)
        + -(-rest // impact_ops.TILE_STEP))
    acc, cnt = sc.score_into(*sc.new_acc(rows), tiles, weights)
    acc, cnt = np.asarray(acc), np.asarray(cnt)
    for j in range(rows):
        want_acc, want_cnt = impact_sum(sc, tiles[j], weights[j])
        assert np.array_equal(acc[j, :-1].view(np.int32),
                              want_acc.view(np.int32)), j
        assert np.array_equal(cnt[j, :-1], want_cnt), j


def test_tile_counts_of_one_row_bucket_share_one_program():
    """The trips are read from the plan: two scorings of different tile
    counts at one row bucket build no program between them."""
    sc = loop_scorer("int8")
    sc.score_into(*sc.new_acc(4), *loop_lists(3, 4))
    built = impact_ops._impact_chunk_add._cache_size()
    for in_use in (700, 1, impact_ops.TILE_CAP + 9):
        sc.score_into(*sc.new_acc(4), *loop_lists(in_use, 4))
    assert impact_ops._impact_chunk_add._cache_size() == built


def _finalize_text() -> str:
    from elasticsearch_tpu.ops import scoring

    return scoring._finalize.lower(
        np.zeros((2, 1001), np.float32), np.zeros((2, 1001), np.int32),
        np.ones(1000, bool), np.ones(2, np.int32), k=16).as_text()


def _dense_add_text() -> str:
    stride = impact_ops.impact_row_stride(1000)
    return impact_ops._impact_dense_add.lower(
        np.zeros(3 * stride, np.int8),
        np.zeros((2, impact_ops.DENSE_SLOTS), np.int32),
        np.zeros((2, impact_ops.DENSE_SLOTS), np.float32),
        width=1001).as_text()


def _programs_left_alone() -> dict:
    """program -> (its lowering, that text's digest at this PR's parent,
    7ea22af, computed there with these very functions): the programs a
    sparse scoring launches beside the looped one, and one `match`, one
    serve and one kNN launch of the families that never build an
    `ImpactScorer` (PR 48's and PR 50's pins, held again)."""
    import test_filtered_bool_deployment as text_programs
    import test_knn_lead as knn_programs

    return {
        "finalize": (
            _finalize_text,
            "5c1d754b373a42950862a123ce2a567184f0418c5dfa9cd223091c3484ae587f"),
        "impact_dense_add": (
            _dense_add_text,
            "4a83a7b5b20c7ddddd4142e8bdda19ae54ed048b77644b2bfc415f4d0a463639"),
        "impact_zeros": (
            lambda: impact_ops._impact_zeros.lower(
                rows=2, width=1001).as_text(),
            "ca46f5eb5b2969cd742b2fb121e96a3e6d8f058b66959dd35d274f4a32383bf3"),
        "match_launch": text_programs.PARENT_PROGRAMS["match_launch"],
        "serve_launch": text_programs.PARENT_PROGRAMS["serve_launch"],
        "knn_bare_float_rows": knn_programs.SCAN_PROGRAMS[
            "knn_bare_float_rows"],
    }


@pytest.mark.parametrize("program", [
    "finalize", "impact_dense_add", "impact_zeros", "match_launch",
    "serve_launch", "knn_bare_float_rows"])
def test_programs_beside_the_looped_one_lower_to_the_parents_text(program):
    import hashlib

    lower, digest = _programs_left_alone()[program]
    assert hashlib.sha256(lower().encode()).hexdigest() == digest


# ---------------------------------------------------------------------------
# serving: fp32 float parity, int8 recall gate, exact escape hatch
# ---------------------------------------------------------------------------


def kept_by_loop(bm, theta):
    """`SparseBlockMax.kept` as it stood until PR 46, a Python loop a
    term that goes through tiles: the reference its array form is held
    to, bit for bit."""
    tiles, weights = [], []
    dropped = 0
    for i in np.flatnonzero(~bm.dense):
        c = int(bm.counts[i])
        rng = np.arange(bm.starts[i], bm.starts[i] + c, dtype=np.int64)
        if c > 1 and np.isfinite(theta):
            others = bm.sum_bound - float(bm.bws[i] * bm.term_max[i])
            bound = (
                bm.bws[i] * bm.tile_bound[rng].astype(np.float32)
                + np.float32(others)
            )
            keep = bound >= theta
            keep[0] = True  # first tile anchors theta; never drop
            dropped += int((~keep).sum())
            rng = rng[keep]
        if len(rng):
            tiles.append(rng)
            weights.append(np.full(len(rng), bm.tws[i], np.float32))
    return (
        np.concatenate(tiles) if tiles else np.zeros(0, np.int64),
        np.concatenate(weights) if weights else np.zeros(0, np.float32),
        dropped,
    )


def random_block_max(kind, seed):
    """A seeded plan over a made-up field whose tile bounds fall
    within a term, as impact ordering leaves them."""
    rng = np.random.default_rng([seed, sum(map(ord, kind))])
    n_terms = 60
    counts = rng.integers(1, 12, n_terms).astype(np.int32)
    if kind == "one_tile_terms":
        counts[:] = 1
    starts = (np.cumsum(counts) - counts).astype(np.int32)
    bound = np.concatenate([
        np.sort(rng.random(c).astype(np.float32) * 3 + 0.01)[::-1]
        for c in counts])
    n_q = 0 if kind == "empty" else int(rng.integers(1, 40))
    tids = np.sort(rng.choice(n_terms, n_q, replace=False)).tolist()
    bws = (rng.random(n_q) * 2 + 0.05).astype(np.float32)
    tws = (bws * rng.random(n_q).astype(np.float32)).astype(np.float32)
    dense = {"all_dense": np.ones(n_q, bool),
             "no_dense": np.zeros(n_q, bool)}.get(kind, rng.random(n_q) < 0.4)
    return impact_ops.SparseBlockMax(
        starts, counts, bound, tids, tws, bws, dense=dense)


def thetas_of(bm, kind):
    """The thresholds a plan is tried at; `tie` is the bound of one of
    its tail tiles exactly (kept: the test is `>=`), `above_tie` the
    next float32 (dropped)."""
    if kind == "-inf":
        return [-np.inf]
    if kind == "first_tiles_only":
        return [float(np.float32(1e30))]
    if kind == "quantiles":
        return [float(np.float32(bm.sum_bound * f))
                for f in (0.25, 0.6, 0.9, 1.0)]
    out = []
    for i in np.flatnonzero(~bm.dense & (bm.counts > 1)):
        others = bm.sum_bound - float(bm.bws[i] * bm.term_max[i])
        tail = bm.tile_bound[bm.starts[i] + bm.counts[i] - 1]
        tie = bm.bws[i] * np.float32(tail) + np.float32(others)
        assert tie.dtype == np.float32
        out.append(float(tie) if kind == "tie" else float(
            np.nextafter(tie, np.float32(np.inf))))
    return out[:6]


class TestKeptAsArrays:
    @pytest.mark.parametrize("seed", range(3))
    @pytest.mark.parametrize("theta_kind", [
        "-inf", "tie", "above_tie", "first_tiles_only", "quantiles"])
    @pytest.mark.parametrize("plan_kind", [
        "mixed", "all_dense", "no_dense", "one_tile_terms", "empty"])
    def test_kept_is_the_loops_bit_for_bit(self, plan_kind, theta_kind,
                                           seed):
        bm = random_block_max(plan_kind, seed)
        cold = ~bm.dense
        thetas = thetas_of(bm, theta_kind)
        if theta_kind in ("tie", "above_tie"):
            assert bool(thetas) == bool((cold & (bm.counts > 1)).any())
        for theta in thetas:
            tiles, weights, dropped = bm.kept(theta)
            want_t, want_w, want_dropped = kept_by_loop(bm, theta)
            _arrays_equal("tiles", tiles, want_t)
            _arrays_equal("weights", weights, want_w)
            assert dropped == want_dropped and isinstance(dropped, int)
            assert len(tiles) + dropped == int(bm.counts[cold].sum())
            if theta_kind == "first_tiles_only":
                assert np.array_equal(tiles, bm.starts[cold])
            if theta_kind == "-inf":
                assert dropped == 0
        if theta_kind == "tie" and thetas:
            # the tied tile stays at its own bound and goes one ulp up
            assert (bm.kept(thetas[0])[2]
                    < bm.kept(float(np.nextafter(
                        np.float32(thetas[0]), np.float32(np.inf))))[2])


class TestServingParity:
    def test_fp32_serving_float_identical_to_oracle(self):
        jx = make_service("sp-fp32", quant="none")
        nps = make_service("sp-fp32-np", backend="numpy", quant="none")
        try:
            for s in range(12):
                for size in (5, 16, 40):
                    b = qbody(s, size=size)
                    assert hits_of(jx.search(dict(b))) == hits_of(
                        nps.search(dict(b))
                    ), (s, size)
        finally:
            jx.close()
            nps.close()

    def test_exact_escape_hatch_on_quantized_index(self):
        jx = make_service("sp-exact", quant="int8")
        nps = make_service("sp-exact-np", backend="numpy")
        try:
            before = sparse_mod.SPARSE_STATS["exact_searches"]
            for s in range(8):
                b = qbody(s, exact=True)
                assert hits_of(jx.search(dict(b))) == hits_of(
                    nps.search(dict(b))
                ), s
            assert (
                sparse_mod.SPARSE_STATS["exact_searches"] >= before + 8
            )
        finally:
            jx.close()
            nps.close()

    def test_quantized_recall_at_10(self):
        jx = make_service("sp-rec", quant="int8")
        nps = make_service("sp-rec-np", backend="numpy")
        try:
            rec = []
            for s in range(40):
                b = qbody(s, size=10)
                got = {h["_id"] for h in jx.search(dict(b))["hits"]["hits"]}
                want = [
                    h["_id"] for h in nps.search(dict(b))["hits"]["hits"]
                ]
                if want:
                    rec.append(len(got & set(want)) / len(want))
            assert np.mean(rec) >= 0.95, np.mean(rec)
        finally:
            jx.close()
            nps.close()

    def test_boost_and_negative_weights(self):
        jx = make_service("sp-boost", quant="none")
        nps = make_service("sp-boost-np", backend="numpy", quant="none")
        try:
            qv = {"tok00": 1.5, "tok03": -0.7, "tok09": 1.1}
            b = {
                "query": {
                    "sparse_vector": {
                        "field": "ml", "query_vector": qv, "boost": 2.5,
                    }
                },
                "size": 10,
            }
            assert hits_of(jx.search(dict(b))) == hits_of(
                nps.search(dict(b))
            )
        finally:
            jx.close()
            nps.close()


# ---------------------------------------------------------------------------
# block-max pruning: exact hits, "gte" totals, monotone vs deep k
# ---------------------------------------------------------------------------


class TestPruning:
    """A term-heavy corpus (few tokens, many docs) so every term spans
    several 128-posting tiles and phase-A thetas actually drop tails."""

    def _docs(self, n=600):
        return sparse_docs(n, vocab=VOCAB[:6], seed=21, lo=2, hi=5)

    def test_pruning_is_exact_and_flags_gte(self):
        docs = self._docs()
        jx = make_service("sp-prune", quant="none", docs=docs)
        nps = make_service(
            "sp-prune-np", backend="numpy", quant="none", docs=docs
        )
        try:
            before = dict(sparse_mod.SPARSE_STATS)
            b = {
                "query": {
                    "sparse_vector": {
                        "field": "ml",
                        "query_vector": {"tok00": 2.0, "tok01": 1.0},
                    }
                },
                "size": 5,
                # tok00 alone is in more than 100 docs: the total is
                # proved past the cap, so tiles may drop
                "track_total_hits": 100,
            }
            rj = jx.search(dict(b))
            rn = nps.search(dict(b))
            assert hits_of(rj) == hits_of(rn)
            after = dict(sparse_mod.SPARSE_STATS)
            assert after["tiles_pruned"] > before["tiles_pruned"]
            assert after["pruned_searches"] > before["pruned_searches"]
            # dropped docs provably score below the kth best and are no
            # longer counted, but the reported total does not move
            assert rj["hits"]["total"] == {"value": 100, "relation": "gte"}
            assert rj["hits"]["total"] == rn["hits"]["total"]
            # at the default cap (10,000) nothing proves the total of a
            # 600-doc index: every tile is scored and the count is exact
            mid = dict(sparse_mod.SPARSE_STATS)
            del b["track_total_hits"]
            rj = jx.search(dict(b))
            assert hits_of(rj) == hits_of(rn)
            assert rj["hits"]["total"] == nps.search(dict(b))["hits"]["total"]
            assert rj["hits"]["total"]["relation"] == "eq"
            assert sparse_mod.SPARSE_STATS["tiles_pruned"] == mid["tiles_pruned"]
        finally:
            jx.close()
            nps.close()

    def test_int8_pruning_exact_wrt_quantized_scores(self):
        """Regression: the tile_qmax sidecar is already DEQUANTIZED, so
        the block-max bound must use the RAW query weight — bounding
        with the scale-folded kernel weight scales twice, prunes tiles
        that still hold competitive mass, and silently craters recall.
        int8 pruned serving must return exactly the pure-quantized
        (unpruned) ranking."""
        docs = self._docs()
        jx = make_service("sp-prune-q", quant="int8", docs=docs)
        try:
            eng = jx.local_shard(0)
            sf = eng.segments[0].sparse["ml"]
            qv = {"tok00": 2.0, "tok01": 1.0}
            # host oracle over the DEQUANTIZED column, term order
            acc = np.zeros(eng.segments[0].num_docs, np.float32)
            for t, w in sorted(qv.items()):
                tid = sf.term_id(t)
                d, _wv = sf.term_postings(tid)
                start = int(sf.term_tile_start[tid])
                count = int(sf.term_tile_count[tid])
                df = int(sf.term_df[tid])
                q = sf.qweights[start : start + count].ravel()[:df]
                tw = np.float32(np.float32(w) * sf.scales[tid])
                np.add.at(acc, d, tw * q.astype(np.float32))
            matched = np.flatnonzero(acc != 0.0)
            want = sorted(matched, key=lambda i: (-acc[i], i))[:5]
            before = sparse_mod.SPARSE_STATS["tiles_pruned"]
            r = jx.search(
                {
                    "query": {"sparse_vector": {
                        "field": "ml", "query_vector": qv}},
                    "size": 5,
                    "track_total_hits": False,  # tiles may drop
                }
            )
            assert (
                sparse_mod.SPARSE_STATS["tiles_pruned"] > before
            )  # the pruning path actually engaged
            got = [
                (h["_id"], h["_score"]) for h in r["hits"]["hits"]
            ]
            assert got == [
                (eng.segments[0].doc_ids[i], float(acc[i])) for i in want
            ]
        finally:
            jx.close()

    def test_pruned_topk_equals_deep_unpruned_prefix(self):
        jx = make_service("sp-mono", quant="none", docs=self._docs())
        try:
            b5 = {
                "query": {
                    "sparse_vector": {
                        "field": "ml",
                        "query_vector": {"tok02": 1.4, "tok04": 0.9},
                    }
                },
                "size": 5,
                "track_total_hits": False,  # tiles may drop
            }
            deep = dict(b5)
            deep["size"] = 400  # k ≥ df: theta can't drop anything
            shallow_hits = hits_of(jx.search(b5))
            deep_hits = hits_of(jx.search(deep))
            assert shallow_hits == deep_hits[:5]
        finally:
            jx.close()


# ---------------------------------------------------------------------------
# degraded paths: HBM budget breach, injected fault (see test_faults too)
# ---------------------------------------------------------------------------


class TestDegradedPaths:
    def test_hbm_budget_breach_degrades_to_host_oracle(self):
        from elasticsearch_tpu.common.memory import hbm_ledger

        jx = make_service("sp-hbm", quant="none")
        nps = make_service("sp-hbm-np", backend="numpy", quant="none")
        try:
            b = qbody(1)
            expected = hits_of(nps.search(dict(b)))
            old_budget = hbm_ledger.budget
            hbm_ledger.budget = hbm_ledger.used  # zero headroom
            f_before = sparse_mod.SPARSE_STATS["fallbacks"]
            d_before = hbm_ledger.stats()["degraded_allocations"]
            try:
                got = hits_of(jx.search(dict(b)))
            finally:
                hbm_ledger.budget = old_budget
            assert got == expected
            assert sparse_mod.SPARSE_STATS["fallbacks"] > f_before
            assert (
                hbm_ledger.stats()["degraded_allocations"] > d_before
            )
        finally:
            jx.close()
            nps.close()

    def test_sparse_score_fault_is_exact(self):
        jx = make_service("sp-flt", quant="none")
        nps = make_service("sp-flt-np", backend="numpy", quant="none")
        try:
            b = qbody(2)
            expected = hits_of(nps.search(dict(b)))
            faults.configure(
                {"rules": [{"site": "sparse.score", "kind": "error"}]}
            )
            before = sparse_mod.SPARSE_STATS["fallbacks"]
            assert hits_of(jx.search(dict(b))) == expected
            assert sparse_mod.SPARSE_STATS["fallbacks"] > before
        finally:
            faults.clear()
            jx.close()
            nps.close()


# ---------------------------------------------------------------------------
# dense rows: the int8 column's hot terms leave the scatter
# ---------------------------------------------------------------------------

HOT = [f"hot{i}" for i in range(5)]  # df 1,230-2,400 of 3,000: want a row
COLD = [f"cold{i}" for i in range(8)]  # df 90-700: several tiles, no row
ROW_DOCS = 3000


@functools.lru_cache(maxsize=None)
def row_docs(seed=41):
    """3,000 docs over five tokens frequent enough to want a row and
    eight that are not; `hot0` has a posting whose stored int8 impact
    is 0 (doc 7: 0.005 beside doc 3's 3.5, the term's scale x 127)."""
    rng = np.random.default_rng(seed)
    p_hot = [0.8, 0.65, 0.55, 0.47, 0.41]
    p_cold = [0.23, 0.17, 0.12, 0.1, 0.08, 0.06, 0.04, 0.03]
    out = []
    for i in range(ROW_DOCS):
        vec = {}
        for t, p in zip(HOT + COLD, p_hot + p_cold):
            if rng.random() < p:
                vec[t] = float(np.round(rng.random() * 3 + 0.05, 4))
        if i == 3:
            vec["hot0"] = 3.5
        if i == 7:
            vec["hot0"] = 0.005
        if not vec:
            vec["cold0"] = 0.5
        out.append((str(i), {"ml": vec}))
    return out


def row_body(tokens, seed, size=10, **extra):
    rng = np.random.default_rng(seed)
    qv = {t: float(np.round(rng.random() * 2 + 0.1, 4)) for t in tokens}
    return {"query": {"sparse_vector": {"field": "ml", "query_vector": qv}},
            "size": size, **extra}


ROW_QUERIES = {
    "all_hot": [HOT[0], HOT[2], HOT[4]],
    "none_hot": [COLD[0], COLD[3], COLD[7]],
    "mixed": [HOT[1], HOT[3], COLD[1], COLD[2], COLD[6], "absent"],
    "zero_q": [HOT[0]],
}


def run_group(svc, bodies, rows):
    """One launch group of `bodies` at a `rows`-wide bucket, driven by
    hand through the sparse family's dispatch / collect pair."""
    from elasticsearch_tpu.search import batcher as batcher_mod
    from elasticsearch_tpu.search import dsl

    ex = svc._executor(svc.shards[0])
    jobs = []
    for body in bodies:
        q = dsl.parse_query(body["query"])
        q.sparse = sparse_mod.resolve(svc.settings, False)
        plan = batcher_mod.extract_sparse_plan(
            q, svc.mappings, body.get("track_total_hits", 10_000))
        jobs.append(batcher_mod._Job(ex, plan, body["size"], kind="sparse",
                                     query=q))
    b = svc._batcher
    b._collect_sparse_group(jobs, 16,
                            b._dispatch_sparse_group(jobs, 16, rows=rows))
    return [(j.result.total, j.result.relation,
             [(h.doc_id, h.score) for h in j.result.hits]) for j in jobs]


def same_answers(got, want, rtol=1e-6):
    assert len(got) == len(want)
    for (gt, gr, gh), (wt, wr, wh) in zip(got, want):
        assert (gt, gr) == (wt, wr)
        assert [d for d, _ in gh] == [d for d, _ in wh]
        for (_, a), (_, b) in zip(gh, wh):
            assert abs(a - b) <= rtol * abs(b), (a, b)


class TestDenseRows:
    @pytest.fixture(scope="class")
    def pair(self):
        """The same docs twice: `rows` as the node builds the scorer,
        `tiles` with the scorer built under a zero row budget (the
        test's own scorer: no setting switches rows off)."""
        from elasticsearch_tpu.search import executor_jax

        docs = row_docs()
        rows = make_service("sp-rows", docs=docs)
        tiles = make_service("sp-rows-off", docs=docs)
        for svc in (rows, tiles):
            for i in (11, 500, 2999):  # deleted docs, one in every tile range
                svc.delete_doc(str(i))
            svc.refresh()
        budget = executor_jax.DENSE_ROWS_HBM_BUDGET
        executor_jax.DENSE_ROWS_HBM_BUDGET = 0
        try:
            tiles.search(row_body(ROW_QUERIES["mixed"], 1))
        finally:
            executor_jax.DENSE_ROWS_HBM_BUDGET = budget
        rows.search(row_body(ROW_QUERIES["mixed"], 1))
        yield rows, tiles
        rows.close()
        tiles.close()

    @staticmethod
    def scorer(svc):
        return svc._executor(svc.shards[0]).impact_scorer(0, "ml", True)

    def test_rows_are_the_postings_in_another_layout(self, pair):
        rows, tiles = pair
        assert self.scorer(tiles).rows is None
        held = self.scorer(rows).rows
        seg = rows.shards[0].reader().segments[0]
        sf = seg.sparse["ml"]
        assert held.n_rows == len(HOT)
        plane = np.asarray(held.plane).reshape(held.n_rows, -1)
        by_df = sorted(HOT, key=lambda t: -int(sf.term_df[sf.term_id(t)]))
        zero_q = 0
        for r, t in enumerate(by_df):
            tid = sf.term_id(t)
            assert held.row_of_term[tid] == r
            lo = int(sf.term_tile_start[tid])
            d = sf.doc_ids[lo : lo + int(sf.term_tile_count[tid])].ravel()
            q = sf.qweights[lo : lo + int(sf.term_tile_count[tid])].ravel()
            want = np.full(plane.shape[1], impact_ops.ROW_ABSENT, np.int8)
            want[d[d >= 0]] = q[d >= 0]
            assert np.array_equal(plane[r], want), t
            zero_q += int((q[d >= 0] == 0).sum())
        assert zero_q >= 1  # a present posting whose stored impact is 0
        assert (held.row_of_term >= 0).sum() == len(HOT)

    @pytest.mark.parametrize("launch_rows", [1, 2, 4])
    @pytest.mark.parametrize("which", sorted(ROW_QUERIES))
    def test_rows_and_tiles_answer_what_tiles_alone_answer(
        self, pair, which, launch_rows
    ):
        rows, tiles = pair
        tokens = ROW_QUERIES[which]
        bodies = [row_body(tokens, 7 * launch_rows + j,
                           track_total_hits=True)
                  for j in range(launch_rows)]
        if launch_rows > 1:  # a launch whose rows differ in what is hot
            bodies[-1] = row_body(ROW_QUERIES["mixed"], 99,
                                  track_total_hits=True)
        before = dict(sparse_mod.SPARSE_STATS)
        got = run_group(rows, bodies, launch_rows)
        moved = {k: v - before[k] for k, v in sparse_mod.SPARSE_STATS.items()}
        same_answers(got, run_group(tiles, bodies, launch_rows))
        hot = sum(t in HOT for b in bodies
                  for t in b["query"]["sparse_vector"]["query_vector"])
        assert moved["dense_rows_scored"] == hot
        assert moved["dense_launches"] == (hot > 0)
        assert (moved["tiles_dense"] > 0) == (hot > 0)
        if which == "all_hot" and launch_rows == 1:
            assert moved["chunk_launches"] == moved["tiles_scored"] == 0
        if which == "none_hot" and launch_rows == 1:
            assert moved["tiles_dense"] == 0 and moved["tiles_scored"] > 0
        # exact totals: every holder of any token, live, counted once,
        # the holder of a zero stored impact among them
        deleted = {"11", "500", "2999"}
        for body, (total, relation, _hits) in zip(bodies, got):
            qv = body["query"]["sparse_vector"]["query_vector"]
            holders = sum(1 for i, s in row_docs()
                          if i not in deleted and set(s["ml"]) & set(qv))
            assert (total, relation) == (holders, "eq")

    def test_zero_stored_impact_matches_and_counts(self, pair):
        rows, tiles = pair
        body = row_body([HOT[0]], 5, size=ROW_DOCS, track_total_hits=True)
        served = rows.search(dict(body))
        ids = {h["_id"]: h["_score"] for h in served["hits"]["hits"]}
        assert ids["7"] == 0.0  # present, stored impact 0: a hit of score 0
        assert served["hits"]["total"]["value"] == len(ids)
        assert hits_of(served) == hits_of(tiles.search(dict(body)))

    def test_over_http_shaped_search_uses_rows(self, pair):
        rows, tiles = pair
        body = row_body(ROW_QUERIES["mixed"], 3)
        before = dict(sparse_mod.SPARSE_STATS)
        a = rows.search(dict(body))
        moved = {k: v - before[k] for k, v in sparse_mod.SPARSE_STATS.items()}
        b = tiles.search(dict(body))
        assert a["hits"]["total"] == b["hits"]["total"]
        assert [h["_id"] for h in a["hits"]["hits"]] == [
            h["_id"] for h in b["hits"]["hits"]]
        assert moved["dense_rows_scored"] == 2 and moved["dense_launches"] == 1

    def test_hot_terms_past_the_slots_go_through_tiles(self, pair, monkeypatch):
        """DENSE_SLOTS bounds a query row's rows: the least frequent of
        its hot terms overflow to their tiles, with equal answers."""
        rows, tiles = pair
        monkeypatch.setattr(impact_ops, "DENSE_SLOTS", 2)
        sc = self.scorer(rows)
        sf = rows.shards[0].reader().segments[0].sparse["ml"]
        slots = sc.row_slots([sf.term_id(t) for t in HOT])
        assert sorted(slots[slots >= 0]) == [0, 1]  # the two of most df
        bodies = [row_body(HOT + COLD[:2], 13, track_total_hits=True)]
        before = dict(sparse_mod.SPARSE_STATS)
        got = run_group(rows, bodies, 1)
        assert (sparse_mod.SPARSE_STATS["dense_rows_scored"]
                - before["dense_rows_scored"]) == 2
        same_answers(got, run_group(tiles, bodies, 1))


    def test_back_to_back_requests_answer_as_fresh_ones(self, pair):
        """The row launch zeroes the planes it adds into and every
        later launch is donated them: nothing of one request is left
        for the next. A, B, A on one scorer answer as each does on the
        scorer that never held a row, and the two A's bit for bit."""
        rows, tiles = pair
        a = [row_body(ROW_QUERIES["mixed"], 21, track_total_hits=True)]
        b = [row_body(ROW_QUERIES["all_hot"], 22, track_total_hits=True)]
        c = [row_body(ROW_QUERIES["none_hot"], 23, track_total_hits=True)]
        first = run_group(rows, a, 1)
        for other in (b, c):  # a row launch alone, then the fill alone
            same_answers(run_group(rows, other, 1),
                         run_group(tiles, other, 1))
            assert run_group(rows, a, 1) == first
        same_answers(first, run_group(tiles, a, 1))

    @pytest.mark.parametrize("bucket", [1, 4, 8, 16, 32])
    def test_the_first_kernel_starts_from_zeroed_planes(self, pair, bucket):
        """At every bucket of the ladder: the fill program hands the
        tile pass zeros, and the row launch's answer is its rows'
        products and nothing else (the query rows that name no row read
        zero everywhere)."""
        rows, _tiles = pair
        sc = self.scorer(rows)
        width = sc.n_docs + 1
        for plane, dtype in zip(sc.new_acc(bucket),
                                (np.float32, np.int32)):
            got = np.asarray(plane)
            assert got.shape == (bucket, width) and got.dtype == dtype
            assert not got.any()
        stride = impact_ops.impact_row_stride(sc.n_docs)
        held = np.asarray(sc.rows.plane).reshape(sc.rows.n_rows, stride)
        last = bucket - 1  # the one query row that names a row
        lists = [np.zeros(0, np.int32)] * last + [np.asarray([2], np.int32)]
        ws = [np.zeros(0, np.float32)] * last + [np.asarray([1.5],
                                                            np.float32)]
        acc, cnt = (np.asarray(x) for x in sc.add_rows(bucket, lists, ws))
        assert acc.shape == cnt.shape == (bucket, width)
        assert not acc[:last].any() and not cnt[:last].any()
        present = held[2, :width] != impact_ops.ROW_ABSENT
        assert np.array_equal(cnt[last], present.astype(np.int32))
        assert np.array_equal(acc[last], np.where(
            present, np.float32(1.5) * held[2, :width].astype(np.float32),
            np.float32(0)))
        empty = sc.add_rows(bucket, [], [])
        assert not np.asarray(empty[0]).any()
        assert not np.asarray(empty[1]).any()

    @pytest.mark.parametrize("which", sorted(ROW_QUERIES))
    def test_a_warmed_request_makes_no_eager_fill(self, pair, which,
                                                  monkeypatch):
        """Once a bucket has served a request of each kind, a request
        builds nothing and runs no eager `jnp.zeros` / `jnp.ones` /
        `jnp.full` in ops/impact: its planes come zeroed from the row
        launch (or the one fill program), `_finalize`'s `msm` from the
        scorer's constant."""
        import jax.numpy as jnp

        rows, tiles = pair
        for warm in sorted(ROW_QUERIES):
            rows.search(row_body(ROW_QUERIES[warm], 31))
        programs = (impact_ops._impact_zeros, impact_ops._impact_dense_add,
                    impact_ops._impact_chunk_add)
        built = [p._cache_size() for p in programs]
        eager = []

        class NoFill:
            def __getattr__(self, name):
                if name in ("zeros", "ones", "full"):
                    eager.append(name)
                    raise AssertionError(f"eager jnp.{name} in ops/impact")
                return getattr(jnp, name)

        monkeypatch.setattr(impact_ops, "jnp", NoFill())
        body = row_body(ROW_QUERIES[which], 32)
        before = dict(sparse_mod.SPARSE_STATS)
        served = rows.search(dict(body))
        assert eager == []
        assert sparse_mod.SPARSE_STATS["fallbacks"] == before["fallbacks"]
        assert sparse_mod.SPARSE_STATS["searches"] == before["searches"] + 1
        assert [p._cache_size() for p in programs] == built
        monkeypatch.undo()
        want = tiles.search(dict(body))
        assert served["hits"]["total"] == want["hits"]["total"]
        assert [h["_id"] for h in served["hits"]["hits"]] == [
            h["_id"] for h in want["hits"]["hits"]]

    def test_sparse_plan_span_holds_the_hosts_share_of_dispatch(self, pair):
        """`sparse_plan`: a child of `dispatch` beside `sparse_theta`,
        from the segment's entry to just before the first chunk launch
        is enqueued: it holds `sparse_theta`, ends before `dispatch`
        does, and says what it planned."""
        from elasticsearch_tpu.common import tracing

        rows, _tiles = pair
        tokens = ROW_QUERIES["mixed"]
        # no total to hold: tiles may drop, so theta is computed
        body = row_body(tokens, 41, track_total_hits=False)
        rows.search(dict(body))
        handle = tracing.begin("search", index="sp-rows")
        rows.search(dict(body))
        tracing.end(handle)
        spans = tracing.recent(1)[0]["spans"]
        by_name = {s["name"]: s for s in spans}
        by_id = {s["id"]: s for s in spans}
        plan, theta, disp = (by_name[n] for n in (
            "sparse_plan", "sparse_theta", "dispatch"))
        assert by_id[plan["parent_id"]]["name"] == "dispatch"
        assert by_id[theta["parent_id"]]["name"] == "dispatch"

        def ends(sp):
            return sp["start_ns"] + sp["duration_ns"]

        assert disp["start_ns"] <= plan["start_ns"] <= theta["start_ns"]
        assert ends(theta) <= ends(plan) < ends(disp)
        present = [t for t in tokens if t != "absent"]
        hot = [t for t in present if t in HOT]
        assert plan["tags"] == {
            "segment": 0, "terms": len(present),
            "cold_terms": len(present) - len(hot),
            "tiles_kept": disp["tags"]["tiles_scored"]}
        assert disp["tags"]["tiles_scored"] > 0


class TestDenseRowBudget:
    def _service(self, name, quant="int8"):
        return make_service(name, quant=quant, docs=row_docs())

    @pytest.mark.parametrize("limit", ["budget", "headroom"])
    def test_rows_are_held_by_df_rank_inside_the_budget(self, limit,
                                                        monkeypatch):
        from elasticsearch_tpu.common.memory import hbm_ledger
        from elasticsearch_tpu.search import executor_jax

        full = self._service(f"sp-budget-full-{limit}")
        part = self._service(f"sp-budget-part-{limit}")
        try:
            row = impact_ops.impact_row_stride(ROW_DOCS)
            k = 2
            want = full.search(row_body(ROW_QUERIES["mixed"], 1))
            used0 = hbm_ledger.stats()["by_category"].get("dense_rows", 0)
            d0 = hbm_ledger.stats()["degraded_allocations"]
            ex = part._executor(part.shards[0])
            sf = part.shards[0].reader().segments[0].sparse["ml"]
            tiles_bytes = int(sf.doc_ids.nbytes + sf.qweights.nbytes)
            if limit == "budget":
                monkeypatch.setattr(executor_jax, "DENSE_ROWS_HBM_BUDGET",
                                    k * row + row // 2)
            else:
                # headroom left once the tile planes are charged: k rows
                monkeypatch.setattr(
                    hbm_ledger, "budget",
                    hbm_ledger.used + tiles_bytes + k * (row + 1) + row // 2)
            sc = ex.impact_scorer(0, "ml", True)
            monkeypatch.undo()
            assert sc.rows.n_rows == k and sc.rows_wanted == len(HOT)
            by_df = sorted(HOT, key=lambda t: -int(sf.term_df[sf.term_id(t)]))
            held = {t for t in HOT if sc.rows.row_of_term[sf.term_id(t)] >= 0}
            assert held == set(by_df[:k])  # exactly the k most frequent
            assert hbm_ledger.stats()["degraded_allocations"] == d0 + 1
            assert (hbm_ledger.stats()["by_category"]["dense_rows"]
                    == used0 + k * row == used0 + sc.rows.nbytes)
            assert ex.impact_rows_stats() == {
                "dense_rows_wanted": len(HOT), "dense_rows_held": k,
                "dense_rows_bytes": k * row}
            # the rest are served through tiles, with equal answers
            got = part.search(row_body(ROW_QUERIES["mixed"], 1))
            same_answers([(0, "", hits_of(got))], [(0, "", hits_of(want))])
            assert got["hits"]["total"] == want["hits"]["total"]
            # released with the scorer's generation
            part.close()
            assert (hbm_ledger.stats()["by_category"].get("dense_rows", 0)
                    == used0)
        finally:
            full.close()
            part.close()

    def test_float32_column_builds_no_row(self):
        from elasticsearch_tpu.common.memory import hbm_ledger

        svc = self._service("sp-f32-norows", quant="none")
        nps = make_service("sp-f32-norows-np", backend="numpy", quant="none",
                           docs=row_docs())
        try:
            used0 = hbm_ledger.stats()["by_category"].get("dense_rows", 0)
            body = row_body(ROW_QUERIES["mixed"], 2)
            before = dict(sparse_mod.SPARSE_STATS)
            got = svc.search(dict(body))
            ex = svc._executor(svc.shards[0])
            assert ex.impact_scorer(0, "ml", False).rows is None
            assert ex.impact_rows_stats()["dense_rows_held"] == 0
            assert (hbm_ledger.stats()["by_category"].get("dense_rows", 0)
                    == used0)
            assert (sparse_mod.SPARSE_STATS["dense_launches"]
                    == before["dense_launches"])
            # and stays bit-equal to the oracle
            assert hits_of(got) == hits_of(nps.search(dict(body)))
        finally:
            svc.close()
            nps.close()

    def test_exact_escape_on_an_int8_index_stays_on_tiles(self):
        svc = self._service("sp-exact-norows")
        nps = make_service("sp-exact-norows-np", backend="numpy",
                           quant="none", docs=row_docs())
        try:
            body = row_body(ROW_QUERIES["mixed"], 4)
            body["exact"] = True
            before = dict(sparse_mod.SPARSE_STATS)
            got = svc.search(dict(body))
            assert (sparse_mod.SPARSE_STATS["dense_launches"]
                    == before["dense_launches"])
            assert hits_of(got) == hits_of(nps.search(dict(body)))
        finally:
            svc.close()
            nps.close()


# ---------------------------------------------------------------------------
# mesh SPMD serving: bit-identical to the per-shard path, both modes
# ---------------------------------------------------------------------------


@pytest.mark.mesh
class TestMeshSparse:
    @pytest.mark.parametrize("quant", ["int8", "none"])
    def test_mesh_vs_shard_parity(self, monkeypatch, quant):
        import jax

        if len(jax.devices()) < 2:
            pytest.skip("needs >= 2 devices")
        svc = make_service(
            f"spm-{quant}", quant=quant, shards=4,
            docs=sparse_docs(240, vocab=VOCAB[:30], seed=11, lo=2, hi=8),
        )
        try:
            mex = svc.mesh_executor()
            rng = np.random.default_rng(5)
            for s in range(4):
                toks = [
                    str(t)
                    for t in rng.choice(
                        VOCAB[:30], size=int(rng.integers(2, 6)),
                        replace=False,
                    )
                ]
                body = {
                    "query": {
                        "sparse_vector": {
                            "field": "ml",
                            "query_vector": {
                                t: float(
                                    np.round(rng.random() * 2 + 0.1, 4)
                                )
                                for t in toks
                            },
                        }
                    },
                    "size": 10,
                }
                monkeypatch.setenv("ES_TPU_MESH", "force")
                routed0 = mex.stats["routed"]
                rm = svc.search(dict(body))
                assert mex.stats["routed"] == routed0 + 1, (quant, s)
                monkeypatch.setenv("ES_TPU_MESH", "off")
                rs = svc.search(dict(body))
                assert hits_of(rm) == hits_of(rs), (quant, s)
                assert rm["hits"]["total"] == rs["hits"]["total"]
        finally:
            svc.close()


# ---------------------------------------------------------------------------
# the third hybrid leg: rrf over bm25 + knn + sparse
# ---------------------------------------------------------------------------


class TestHybridThirdLeg:
    def _body(self, qv):
        return {
            "retriever": {
                "rrf": {
                    "retrievers": [
                        {"standard": {
                            "query": {"match": {"body": "tok00 tok01"}}}},
                        {"knn": {
                            "field": "vec",
                            "query_vector": [0.4, -0.1, 0.7, 0.2],
                            "k": 20, "num_candidates": 40,
                        }},
                        {"standard": {"query": {"sparse_vector": {
                            "field": "ml", "query_vector": qv}}}},
                    ],
                    "rank_constant": 60,
                    "rank_window_size": 50,
                }
            },
            "size": 10,
        }

    def test_three_leg_rrf_parity_and_leg_stats(self):
        jx = make_service("rrf3", quant="none")
        nps = make_service("rrf3-np", backend="numpy", quant="none")
        try:
            qv = {t: 1.0 for t in ("tok00", "tok02", "tok05", "tok07")}
            body = self._body(qv)
            rj = jx.search(dict(body))
            rn = nps.search(dict(body))
            assert rj["hits"]["hits"]
            # every leg is float-exact on both backends, so the fused
            # rank ORDER is identical end to end (the fused rrf score
            # itself is f32 on device vs f64 on host — compare ranks)
            assert [h["_id"] for h in rj["hits"]["hits"]] == [
                h["_id"] for h in rn["hits"]["hits"]
            ]
            for hj, hn in zip(rj["hits"]["hits"], rn["hits"]["hits"]):
                assert hj["_score"] == pytest.approx(
                    hn["_score"], rel=1e-5
                )
            # the sparse leg gets its own timing bucket
            assert jx.rrf_leg_samples["sparse"]
            assert jx.rrf_stats["sparse_leg_ms"] >= 0.0
        finally:
            jx.close()
            nps.close()

    def test_sparse_leg_contributes_to_fusion(self):
        jx = make_service("rrf3-c", quant="none")
        try:
            qv = {"tok09": 3.0, "tok11": 2.5}
            with_sparse = self._body(qv)
            without = self._body(qv)
            without["retriever"]["rrf"]["retrievers"] = without[
                "retriever"
            ]["rrf"]["retrievers"][:2]
            ids_with = [
                h["_id"]
                for h in jx.search(with_sparse)["hits"]["hits"]
            ]
            ids_without = [
                h["_id"] for h in jx.search(without)["hits"]["hits"]
            ]
            assert ids_with != ids_without
        finally:
            jx.close()


# ---------------------------------------------------------------------------
# DSL validation: request-scoped 400s
# ---------------------------------------------------------------------------


class TestSparseDsl400s:
    BAD_BODIES = [
        {"query": {"sparse_vector": {"query_vector": {"a": 1.0}}}},
        {"query": {"sparse_vector": {"field": "ml"}}},
        {"query": {"sparse_vector": {
            "field": "ml", "query_vector": {}}}},
        {"query": {"sparse_vector": {
            "field": "ml", "query_vector": {"a": "x"}}}},
        {"query": {"sparse_vector": {
            "field": "ml", "query_vector": {"a": float("nan")}}}},
        {"query": {"sparse_vector": {
            "field": "body", "query_vector": {"a": 1.0}}}},
        {"query": {"sparse_vector": {
            "field": "missing", "query_vector": {"a": 1.0}}}},
    ]

    def test_malformed_queries_raise_parse_errors(self):
        svc = make_service("sp-400", docs=sparse_docs(20))
        try:
            for bad in self.BAD_BODIES:
                with pytest.raises(QueryParseError):
                    svc.search(dict(bad))
            # the same validation guards retriever-nested legs
            with pytest.raises(QueryParseError):
                svc.search(
                    {
                        "retriever": {
                            "rrf": {
                                "retrievers": [
                                    {"standard": {"query": {
                                        "sparse_vector": {
                                            "field": "body",
                                            "query_vector": {"a": 1.0},
                                        }}}},
                                    {"standard": {"query": {
                                        "match": {"body": "tok00"}}}},
                                ]
                            }
                        }
                    }
                )
        finally:
            svc.close()


# ---------------------------------------------------------------------------
# observability: the `sparse` block of _nodes/stats over REST
# ---------------------------------------------------------------------------


class TestNodesStatsSparse:
    @pytest.fixture
    def es(self):
        import json as _json
        import urllib.error
        import urllib.request

        from elasticsearch_tpu.rest.server import ElasticsearchTpuServer

        srv = ElasticsearchTpuServer(port=0)
        srv.start_background()
        base = f"http://127.0.0.1:{srv.port}"

        def call(method, path, body=None):
            data = None
            headers = {}
            if body is not None:
                data = _json.dumps(body).encode()
                headers["Content-Type"] = "application/json"
            req = urllib.request.Request(
                base + path, data=data, method=method, headers=headers
            )
            try:
                with urllib.request.urlopen(req) as resp:
                    return resp.status, _json.loads(resp.read() or b"null")
            except urllib.error.HTTPError as e:
                return e.code, _json.loads(e.read() or b"null")

        try:
            yield call
        finally:
            srv.close()

    def test_sparse_block_and_compression_gate(self, es):
        sparse_mod.reset_stats()
        status, _ = es(
            "PUT", "/ml-idx",
            {
                "settings": {"index": {"search.backend": "jax"}},
                "mappings": {"properties": {
                    "ml": {"type": "sparse_vector"}}},
            },
        )
        assert status == 200
        rng = np.random.default_rng(13)
        for i in range(80):
            toks = [
                str(t) for t in rng.choice(VOCAB, size=4, replace=False)
            ]
            es(
                "PUT", f"/ml-idx/_doc/{i}",
                {"ml": {
                    t: float(np.round(rng.random() * 2 + 0.1, 4))
                    for t in toks
                }},
            )
        es("POST", "/ml-idx/_refresh")
        status, r = es(
            "POST", "/ml-idx/_search",
            {
                "query": {"sparse_vector": {
                    "field": "ml",
                    "query_vector": {"tok00": 1.0, "tok01": 0.5},
                }},
                "size": 10,
            },
        )
        assert status == 200 and r["hits"]["hits"]
        status, stats = es("GET", "/_nodes/stats")
        assert status == 200
        blk = stats["nodes"]["node-0"]["sparse"]
        for key in (
            "searches", "quantized_searches", "exact_searches",
            "fallbacks", "tiles_scored", "tiles_pruned",
            "pruned_searches", "impact_bytes",
            "impact_fp32_equivalent_bytes", "ledger_bytes",
            "batched_jobs",
        ):
            assert key in blk, key
        assert blk["searches"] >= 1
        assert blk["quantized_searches"] >= 1  # int8 is the default
        assert blk["ledger_bytes"] > 0
        # the headline: int8 impact postings at least 2x smaller than
        # the fp32-equivalent column
        assert blk["impact_bytes"] > 0
        assert (
            blk["impact_fp32_equivalent_bytes"]
            >= 2 * blk["impact_bytes"]
        )

    def test_invalid_sparse_query_is_http_400(self, es):
        es(
            "PUT", "/ml-400",
            {"mappings": {"properties": {
                "ml": {"type": "sparse_vector"},
                "body": {"type": "text"},
            }}},
        )
        status, body = es(
            "POST", "/ml-400/_search",
            {"query": {"sparse_vector": {
                "field": "body", "query_vector": {"a": 1.0}}}},
        )
        assert status == 400
        assert "sparse_vector" in str(body["error"])
