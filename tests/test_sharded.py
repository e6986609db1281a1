"""Sharded SPMD search on an 8-virtual-device CPU mesh vs the oracle.

InternalTestCluster analog (SURVEY.md §4): the "cluster" is a (data=2,
shards=4) mesh in one process; results must merge to exactly what a
single-shard oracle over the union corpus would rank (modulo per-shard
IDF, which we verify separately by comparing to a per-shard oracle merge).
"""

import numpy as np
import pytest

from elasticsearch_tpu.analysis import AnalysisRegistry
from elasticsearch_tpu.index.mapping import DocumentParser, Mappings
from elasticsearch_tpu.index.segment import SegmentBuilder
from elasticsearch_tpu.models import bm25
from elasticsearch_tpu.parallel import (
    ShardedIndex,
    build_sharded_bm25_step,
    build_sharded_knn_step,
    make_mesh,
)
from elasticsearch_tpu.search import dsl
from elasticsearch_tpu.search.executor import NumpyExecutor, ShardReader

MAPPING = {
    "properties": {
        "body": {"type": "text"},
        "embedding": {"type": "dense_vector", "dims": 8, "similarity": "cosine"},
    }
}

VOCAB = [
    "quick", "brown", "fox", "lazy", "dog", "jumps", "river", "stone",
    "cloud", "rain", "forest", "mountain", "search", "engine", "index",
]


def make_shards(n_shards=4, docs_per_shard=40, seed=7):
    rng = np.random.default_rng(seed)
    mappings = Mappings(MAPPING)
    analysis = AnalysisRegistry()
    parser = DocumentParser(mappings, analysis)
    segments = []
    corpus = []  # (global_doc, shard, local, text)
    g = 0
    for s in range(n_shards):
        builder = SegmentBuilder(mappings)
        for i in range(docs_per_shard):
            n_words = int(rng.integers(3, 12))
            words = rng.choice(VOCAB, size=n_words).tolist()
            text = " ".join(words)
            vec = rng.standard_normal(8).astype(np.float32)
            builder.add(parser.parse(f"{s}-{i}", {"body": text, "embedding": vec.tolist()}))
            corpus.append((g, s, i, text))
            g += 1
        segments.append(builder.build())
    return mappings, analysis, segments, corpus


@pytest.fixture(scope="module")
def sharded():
    mesh = make_mesh(n_shards=4, n_data=2)
    mappings, analysis, segments, corpus = make_shards()
    index = ShardedIndex(mesh, segments, "body", vector_field="embedding")
    return mesh, mappings, analysis, segments, corpus, index


def oracle_merge(segments, mappings, analysis, terms, operator, k):
    """Per-shard oracle search merged coordinator-style (score desc,
    shard asc, doc asc) — what SearchPhaseController.reducedQueryPhase
    would produce."""
    entries = []
    total = 0
    for si, seg in enumerate(segments):
        reader = ShardReader([seg], mappings, analysis)
        ex = NumpyExecutor(reader)
        q = dsl.parse_query(
            {"match": {"body": {"query": " ".join(terms), "operator": operator}}}
        )
        td = ex.search(q, size=seg.num_docs)
        total += td.total
        for h in td.hits:
            entries.append((-h.score, si, h.local_doc))
    entries.sort()
    return entries[:k], total


class TestShardedBM25:
    def test_matches_per_shard_oracle_merge(self, sharded):
        mesh, mappings, analysis, segments, corpus, index = sharded
        step = build_sharded_bm25_step(index, k=10)
        queries = [
            (["quick", "fox"], "or"),
            (["lazy", "dog", "river"], "or"),
            (["forest", "mountain"], "and"),
            (["search", "engine"], "or"),
            (["quick"], "or"),
            (["stone", "cloud"], "and"),
            (["rain"], "or"),
            (["index", "fox"], "or"),
        ]
        term_lists = [t for t, _ in queries]
        ops = [o for _, o in queries]
        ti, tw, tv, msm = index.compile_queries(term_lists, ops)
        out = step(ti, tw, tv, msm)
        scores = np.asarray(out.scores)
        docs = np.asarray(out.global_docs)
        totals = np.asarray(out.totals)

        doc_base = np.cumsum([0] + [s.num_docs for s in segments[:-1]])
        for bi, (terms, op) in enumerate(queries):
            expect, exp_total = oracle_merge(segments, mappings, analysis, terms, op, 10)
            assert totals[bi] == exp_total, f"query {bi} total"
            got = [
                (float(scores[bi, j]), int(docs[bi, j]))
                for j in range(10)
                if np.isfinite(scores[bi, j])
            ]
            assert len(got) == len(expect), f"query {bi} hit count"
            for j, ((negs, si, local), (gs, gd)) in enumerate(zip(expect, got)):
                assert gd == doc_base[si] + local, f"query {bi} rank {j} doc"
                np.testing.assert_allclose(gs, -negs, rtol=1e-5)

    def test_empty_and_unknown_terms(self, sharded):
        _, _, _, segments, _, index = sharded
        step = build_sharded_bm25_step(index, k=5)
        ti, tw, tv, msm = index.compile_queries(
            [["zzzznotaterm"], ["fox"]] * 4, ["or"] * 8
        )
        out = step(ti, tw, tv, msm)
        assert np.asarray(out.totals)[0] == 0
        assert not np.isfinite(np.asarray(out.scores)[0]).any()
        assert np.asarray(out.totals)[1] > 0


class TestShardedKnn:
    def test_matches_host_brute_force(self, sharded):
        _, _, _, segments, _, index = sharded
        step = build_sharded_knn_step(index, k=10, similarity="cosine")
        rng = np.random.default_rng(3)
        q = rng.standard_normal((8, 8)).astype(np.float32)
        out = step(q)
        docs = np.asarray(out.global_docs)
        scores = np.asarray(out.scores)

        # host reference over the concatenated corpus
        mats = []
        for seg in segments:
            vf = seg.vectors["embedding"]
            mats.append(vf.unit_vectors)
        allv = np.concatenate(mats, axis=0)
        qn = q / np.linalg.norm(q, axis=1, keepdims=True)
        ref = (1.0 + qn @ allv.T) / 2.0
        for bi in range(q.shape[0]):
            order = np.argsort(-ref[bi], kind="stable")[:10]
            np.testing.assert_array_equal(docs[bi], order)
            np.testing.assert_allclose(scores[bi], ref[bi][order], rtol=1e-5)
