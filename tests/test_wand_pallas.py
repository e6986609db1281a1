"""Block-max pruning exactness tests."""

import numpy as np
import pytest

from elasticsearch_tpu.analysis import AnalysisRegistry
from elasticsearch_tpu.index.mapping import DocumentParser, Mappings
from elasticsearch_tpu.index.segment import SegmentBuilder
from elasticsearch_tpu.ops.scoring import BPAD, ChunkedScorer
from elasticsearch_tpu.ops.wand import BlockMaxIndex, get_tiling


def build_segment(n_docs=3000, vocab=300, seed=11):
    """Zipf corpus big enough that frequent terms go doc-block aligned."""
    rng = np.random.default_rng(seed)
    probs = 1.0 / np.arange(1, vocab + 1)
    probs /= probs.sum()
    words = np.array([f"w{i}" for i in range(vocab)])
    mappings = Mappings({"properties": {"body": {"type": "text"}}})
    analysis = AnalysisRegistry()
    parser = DocumentParser(mappings, analysis)
    builder = SegmentBuilder(mappings)
    for i in range(n_docs):
        n = int(rng.integers(5, 25))
        text = " ".join(words[rng.choice(vocab, size=n, p=probs)])
        builder.add(parser.parse(str(i), {"body": text}))
    return builder.build()


@pytest.fixture(scope="module")
def seg():
    return build_segment()


def make_index(seg, block_size=512, hot_min=8, live=None):
    from elasticsearch_tpu.models import bm25

    pf = seg.postings["body"]
    st = pf.stats
    avgdl = bm25.avg_field_length(st.sum_total_term_freq, st.doc_count or 1)
    cache = bm25.norm_inverse_cache(avgdl)
    df = pf.term_df.astype(np.float64)
    weights = np.float32(np.log(1.0 + (st.doc_count - df + 0.5) / (df + 0.5)))
    tiling = get_tiling(pf, seg.num_docs, block_size, hot_min)
    bmx = BlockMaxIndex(tiling, weights, cache)
    inv_norm = cache[pf.norms.astype(np.int64)]
    cs = ChunkedScorer(
        tiling.doc_ids, tiling.tfs, inv_norm, live, block_size=block_size
    )
    return bmx, cs


def all_tiles(bmx, terms):
    tl, wl = [], []
    for p in bmx.plan(terms):
        tl.append(np.arange(p.tile_start, p.tile_start + p.tile_count))
        wl.append(np.full(p.tile_count, p.weight, np.float32))
    return (
        np.concatenate(tl) if tl else np.empty(0, np.int64),
        np.concatenate(wl) if wl else np.empty(0, np.float32),
    )


def exact_search(bmx, cs, term_lists, k):
    """Reference: score every tile of every term (no pruning)."""
    tiles = []
    ws = []
    for terms in term_lists:
        tl, wl = all_tiles(bmx, terms)
        tiles.append(tl)
        ws.append(wl)
    acc, cnt = cs.new_acc(False)
    acc, _ = cs.score_into(acc, cnt, tiles, ws)
    return cs.finalize(acc, None, np.ones(BPAD, np.int32), k)


def pruned_search(bmx, cs, term_lists, k):
    """The batcher's two-phase pruned flow (search/batcher.py mirror)."""
    a_tiles, a_w, deferred = [], [], []
    for terms in term_lists:
        tl, wl, hots = [], [], []
        for p in bmx.plan(terms):
            if p.hot:
                hots.append(p)
            else:
                tl.append(np.arange(p.tile_start, p.tile_start + p.tile_count))
                wl.append(np.full(p.tile_count, p.weight, np.float32))
        if not tl and hots:
            hots.sort(key=lambda p: p.tile_count)
            p = hots.pop(0)
            tl.append(np.arange(p.tile_start, p.tile_start + p.tile_count))
            wl.append(np.full(p.tile_count, p.weight, np.float32))
        a_tiles.append(np.concatenate(tl) if tl else np.empty(0, np.int64))
        a_w.append(np.concatenate(wl) if wl else np.empty(0, np.float32))
        deferred.append(hots)
    acc, cnt = cs.new_acc(False)
    acc, _ = cs.score_into(acc, cnt, a_tiles, a_w)
    stats = {"hot_tiles_total": 0, "phase_b_tiles": 0}
    if any(deferred):
        theta, accmax = cs.threshold(acc, k)
        b_tiles, b_w = [], []
        for ji, hots in enumerate(deferred):
            tl, wl = [], []
            if hots:
                sum_bounds = np.zeros(bmx.tiling.n_blocks, np.float32)
                for p in hots:
                    sum_bounds += bmx.block_bounds(p)
                potential = accmax[ji] + sum_bounds
                for p in hots:
                    stats["hot_tiles_total"] += p.tile_count
                    kept = bmx.surviving_tiles(p, potential, theta[ji])
                    stats["phase_b_tiles"] += len(kept)
                    if len(kept):
                        tl.append(kept)
                        wl.append(np.full(len(kept), p.weight, np.float32))
            b_tiles.append(np.concatenate(tl) if tl else np.empty(0, np.int64))
            b_w.append(np.concatenate(wl) if wl else np.empty(0, np.float32))
        acc, _ = cs.score_into(acc, None, b_tiles, b_w)
    s, d, tot = cs.finalize(acc, None, np.ones(BPAD, np.int32), k)
    return s, d, tot, stats


class TestBlockMaxWand:
    def test_exact_topk_vs_dense(self, seg):
        k = 10
        bmx, cs = make_index(seg)
        assert bool(bmx.tiling.term_hot.any()), "corpus should have hot terms"
        pf = seg.postings["body"]
        rng = np.random.default_rng(5)
        queries = []
        for _ in range(16):
            n = int(rng.integers(1, 4))
            terms = [f"w{int(rng.integers(0, 10))}"] + [
                f"w{int(rng.integers(10, 300))}" for _ in range(n)
            ]
            queries.append([t for t in terms if pf.term_id(t) >= 0])
        s, d, tot, stats = pruned_search(bmx, cs, queries, k)
        rs, rd, rtot = exact_search(bmx, cs, queries, k)
        for bi in range(len(queries)):
            n_hits = int((rs[bi] > -np.inf).sum())
            nn = min(n_hits, k)
            np.testing.assert_allclose(
                s[bi][:nn], rs[bi][:nn], rtol=1e-5,
                err_msg=f"query {bi} scores",
            )
            np.testing.assert_array_equal(d[bi][:nn], rd[bi][:nn])
            # pruned totals are a lower bound (track_total_hits: gte)
            assert tot[bi] <= rtot[bi]

    def test_pruning_happens(self, seg):
        bmx, cs = make_index(seg)
        # rare term + very common term: common term's tiles should prune
        queries = [["w200", "w0"]] * 4
        s, d, tot, stats = pruned_search(bmx, cs, queries, 5)
        assert stats["hot_tiles_total"] > 0
        assert stats["phase_b_tiles"] < stats["hot_tiles_total"]

    def test_pure_rare_query_no_phase_b(self, seg):
        bmx, cs = make_index(seg)
        s, d, tot, stats = pruned_search(bmx, cs, [["w250"], ["w299"]], 5)
        assert stats["hot_tiles_total"] == 0

    def test_pruning_exact_with_deleted_docs(self, seg):
        """Deletions must not break pruned exactness: stale (pre-delete)
        bounds only overestimate, and θ/collection mask deleted docs."""
        k = 10
        rng = np.random.default_rng(9)
        live = np.ones(seg.num_docs, bool)
        live[rng.choice(seg.num_docs, size=seg.num_docs // 5, replace=False)] = False
        bmx, cs = make_index(seg, live=live)
        queries = [["w0", "w150"], ["w1", "w2", "w250"], ["w3"], ["w0", "w1"]]
        s, d, tot, stats = pruned_search(bmx, cs, queries, k)
        rs, rd, rtot = exact_search(bmx, cs, queries, k)
        for bi in range(len(queries)):
            nn = min(int((rs[bi] > -np.inf).sum()), k)
            np.testing.assert_allclose(s[bi][:nn], rs[bi][:nn], rtol=1e-5)
            np.testing.assert_array_equal(d[bi][:nn], rd[bi][:nn])
            assert not np.isin(d[bi][:nn], np.nonzero(~live)[0]).any()
