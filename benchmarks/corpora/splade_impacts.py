"""Seeded synthetic SPLADE-encoded passages at the shapes of
big-ann-benchmarks' sparse track (`sparse-full`: MS MARCO passage under
SPLADE CoCondenser-EnsembleDistil): one `sparse_vector` field over BERT's
whole word-piece vocabulary, ~127 positive float32 weights a passage, as
the configuration's `corpus.args` state them.

The collection's structure is the configuration's, drawn from
`stats_seed` and the same in every run, because the program's device
layout is sized by it (tiles a term: the shapes of its programs): which
ids of the vocabulary are in use, every term's document frequency (a
shifted power law, flatter than Zipf(1), its most frequent term capped at
a stated share of the passages), which terms share a passage
(independent draws, a passage chosen in proportion to its own number of
non-zeros). `--seed` decides which passage id holds which passage and
every posting's weight (a gamma law whose mean falls with the term's
document frequency, capped at SPLADE's log(1 + ReLU) range).

The builder lays out the plan `segment.sparse_plan` would make from a
dictionary of dictionaries - terms sorted, a term's postings in impact
order (weight descending, passage ascending), flat scatter destinations
- as arrays, and hands it to the program's own
`segment.sparse_from_plan`: quantization, scales and the block-max
sidecars are the program's. The raw posting stream in (term, passage)
order (`post_start`, `post_doc`, `post_w`) is kept apart for the plain
reference, which takes nothing the program has made.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

SLICES = 16  # part of the data's definition: do not change


def draw_counts(rng, law: dict, n: int) -> np.ndarray:
    """Non-zeros a vector: log-normal with the stated mean, clipped."""
    sigma = float(law["sigma"])
    mu = np.log(float(law["mean"])) - 0.5 * sigma * sigma
    x = np.rint(rng.lognormal(mu, sigma, size=n)).astype(np.int64)
    return np.clip(x, int(law["min"]), int(law["max"]))


def df_law(terms: int, total: int, docs: int, p: dict) -> np.ndarray:
    """Target document frequency by rank: C / (rank + q) ** s, summing
    to `total` postings, q the shift that puts the first term in
    `max_share` of the passages (found by bisection; with q = 0 the law
    would put it in more passages than there are)."""
    s, cap = float(p["exponent"]), float(p["max_share"]) * docs
    rank = np.arange(1, terms + 1, dtype=np.float64)

    def first(q: float) -> float:
        law = (rank + q) ** -s
        return total * law[0] / law.sum()

    lo, hi = 0.0, float(terms)
    if first(lo) <= cap:
        hi = 0.0
    for _ in range(60):
        mid = 0.5 * (lo + hi)
        lo, hi = (mid, hi) if first(mid) > cap else (lo, mid)
    law = (rank + hi) ** -s
    return np.maximum(1, np.rint(total * law / law.sum())).astype(np.int64)


def weight_means(term_df: np.ndarray, docs: int, law: dict) -> np.ndarray:
    """Mean weight of a term's postings (and of the term in a query): it
    falls with the share of the passages that hold the term."""
    share = np.maximum(term_df, 1) / float(docs)
    m = float(law["mean_at_1pct"]) * (share / 0.01) ** -float(law["df_slope"])
    return np.clip(m, float(law["mean_min"]), float(law["mean_max"]))


def draw_weights(rng, means: np.ndarray, law: dict) -> np.ndarray:
    """Positive float32 weights: gamma of the stated shape around each
    posting's mean, inside (0, `max`] (SPLADE's log(1 + ReLU) range)."""
    k = float(law["gamma_shape"])
    w = rng.gamma(k, means / k).astype(np.float32)
    return np.clip(w, np.float32(law["min"]), np.float32(law["max"]))


def structure(p: dict, docs: int):
    """(in-use term ids, the sorted distinct keys `term index x docs +
    passage index` of each slice): the collection's structure, from
    `stats_seed` alone."""
    stats = [int(p["stats_seed"]), docs]
    rng = np.random.default_rng(stats)
    nnz = draw_counts(rng, p["nnz"], docs)
    total = int(nnz.sum())
    vocab, in_use = int(p["vocab"]), int(p["vocab_in_use"])
    used = np.sort(rng.permutation(vocab)[:in_use])
    # rank -> term: the frequent terms lie anywhere in the vocabulary
    target = np.empty(in_use, np.int64)
    target[rng.permutation(in_use)] = df_law(in_use, total, docs, p["df_law"])
    target = np.minimum(target, int(0.95 * docs))
    # draws with replacement that leave about `target` distinct passages
    draws = np.maximum(target, np.rint(
        -docs * np.log1p(-target / float(docs))).astype(np.int64))
    slot_doc = np.repeat(np.arange(docs, dtype=np.int32), nnz)
    end = np.cumsum(draws)
    cut = np.r_[0, np.searchsorted(
        end, np.linspace(0, end[-1], SLICES + 1)[1:-1]) + 1, in_use]
    cut = np.minimum(cut, in_use)

    def one_slice(i: int) -> np.ndarray:
        t0, t1 = int(cut[i]), int(cut[i + 1])
        m = int(draws[t0:t1].sum())
        if not m:
            return np.empty(0, np.int64)
        child = np.random.default_rng(stats + [i])
        key = np.repeat(np.arange(t0, t1, dtype=np.int64) * docs,
                        draws[t0:t1])
        key += slot_doc[child.integers(0, total, size=m)]
        key.sort()
        return key[np.r_[True, key[1:] != key[:-1]]]

    with ThreadPoolExecutor(max_workers=8) as pool:
        keys = list(pool.map(one_slice, range(SLICES)))
    return used, keys


def build(config: dict, seed: int, docs: int) -> dict:
    from elasticsearch_tpu.index.segment import (
        TILE,
        Segment,
        sparse_from_plan,
    )

    p = config["corpus"]["args"]
    field = p["field"]
    used, keys = structure(p, docs)
    n_terms = len(used)
    perm = np.random.default_rng([int(seed), 1]).permutation(docs).astype(
        np.int32)
    term_df = np.zeros(n_terms, np.int64)
    for key in keys:
        term_df += np.bincount(key // docs, minlength=n_terms)
    means = weight_means(term_df, docs, p["weights"])

    def one_slice(i: int):
        """The slice's postings twice: in (term, passage) order for the
        reference, in impact order (weight desc, passage asc) for the
        plan."""
        key = keys[i]
        term = key // docs
        doc = perm[key - term * docs]
        # (term, passage id) order under this seed's ids
        order = np.argsort(term * docs + doc)  # distinct keys
        term, doc = term[order], doc[order]
        w = draw_weights(np.random.default_rng([int(seed), 2, i]),
                         means[term], p["weights"])
        # stable on a stream already in passage order: ties keep it
        bits = w.view(np.uint32).astype(np.int64)  # positive floats
        impact = np.argsort((term << 32) | (0xFFFFFFFF - bits), kind="stable")
        return doc, w, doc[impact], w[impact]

    with ThreadPoolExecutor(max_workers=8) as pool:
        parts = list(pool.map(one_slice, range(SLICES)))
    del keys
    post_doc = np.concatenate([a for a, _b, _c, _d in parts])
    post_w = np.concatenate([b for _a, b, _c, _d in parts])
    plan_docs = np.concatenate([c for _a, _b, c, _d in parts])
    plan_w = np.concatenate([d for _a, _b, _c, d in parts])
    del parts

    width = len(str(int(p["vocab"]) - 1))
    tile_count = ((term_df + TILE - 1) // TILE).astype(np.int32)
    tile_start = np.zeros(n_terms, np.int32)
    np.cumsum(tile_count[:-1], out=tile_start[1:])
    post_start = np.zeros(n_terms + 1, np.int64)
    np.cumsum(term_df, out=post_start[1:])
    dest = np.arange(len(plan_docs), dtype=np.int64)
    dest += np.repeat(tile_start.astype(np.int64) * TILE - post_start[:-1],
                      term_df)
    plan = {
        # fixed width: sorted lexicographically, as a term dictionary is
        "terms": [f"t{int(t):0{width}d}" for t in used],
        "term_df": term_df.astype(np.int32),
        "term_tile_start": tile_start,
        "term_tile_count": tile_count,
        "n_tiles": int(tile_count.sum()),
        "pruned": 0,  # no static pruning: every posting is held
        "docs": plan_docs,
        "weights": plan_w,
        "dest": dest,
        "tile_term": np.repeat(np.arange(n_terms, dtype=np.int32), tile_count),
    }
    exists = np.bincount(post_doc, minlength=docs) > 0
    sf = sparse_from_plan(plan, docs, exists)
    del plan, plan_docs, plan_w, dest
    segment = Segment(
        num_docs=docs,
        doc_ids=[str(i) for i in range(docs)],
        sources=[None] * docs,
        postings={},
        numerics={},
        ordinals={},
        vectors={},
        sparse={field: sf},
    )
    return {
        "segment": segment,
        "mappings": {"properties": {field: {"type": "sparse_vector"}}},
        "reference": {
            "field": field,
            "docs": docs,
            "terms": used,
            "post_start": post_start,
            "post_doc": post_doc,
            "post_w": post_w,
        },
        "body_context": {
            "field": field,
            "term_width": width,
            "terms": used,
            # query tokens are drawn by posting mass
            "term_df": term_df,
            "term_weight_mean": means,
        },
    }
