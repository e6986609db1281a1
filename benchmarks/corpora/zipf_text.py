"""Seeded synthetic passages at MS MARCO passage's published shapes: one
`text` field, passage lengths and vocabulary as the configuration's
`corpus.args` state them (mean length, Heaps-scaled vocabulary, Zipf
term law), every token kept (the standard analyzer removes no stop word).

From `bench.py` `build_postings` / `build_corpus` (PR 23 verdict: sound
generator), cut to the one field the text cell serves and made from
`--seed`. The tiled `PostingsField` is the layout the program's engine
holds after a refresh; the raw posting stream (`post_start`, `post_doc`,
`post_tf`, `lengths`) is kept apart for the plain reference, which takes
nothing the program has made.

The collection's statistics (every term's total frequency, the multiset
of passage lengths, which tokens share a passage) are the
configuration's: drawn from `stats_seed`, the same in every run, because
the program sizes its device layout by them (tile count, number of dense
hot-term rows) and a layout that moved with the seed would compile anew
in every run. `--seed` decides which passage id holds which passage: a
permutation that keeps every `id_block` consecutive ids together (blocks
trade places, ids move inside their block), because the program's
block-max layout cuts the hot terms' postings at those blocks and its
size, hence its programs' shapes, would otherwise move with the seed (2-5
programs compiled anew in every run, 10-37 s; PERF.md section 6).
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

SLICES = 16  # part of the data's definition: do not change


def draw_lengths(rng, law: dict, docs: int) -> np.ndarray:
    """Passage lengths in tokens: log-normal with the stated mean."""
    sigma = float(law["sigma"])
    mu = np.log(float(law["mean"])) - 0.5 * sigma * sigma
    x = np.rint(rng.lognormal(mu, sigma, size=docs)).astype(np.int64)
    return np.clip(x, int(law["min"]), int(law["max"]))


def passage_ids(seed: int, docs: int, block: int) -> np.ndarray:
    """perm[i] = the id of passage i under this seed."""
    rng = np.random.default_rng([int(seed), 1])
    full = docs // block if block else 0
    if full < 2:
        return rng.permutation(docs)
    perm = np.empty(docs, np.int64)
    to_block = rng.permutation(full)
    inside = rng.permuted(np.tile(np.arange(block), (full, 1)), axis=1)
    perm[:full * block] = (to_block[:, None] * block + inside).ravel()
    perm[full * block:] = full * block + rng.permutation(docs - full * block)
    return perm


def build(config: dict, seed: int, docs: int) -> dict:
    from elasticsearch_tpu.index.segment import (
        INVALID_DOC,
        TILE,
        FieldStats,
        PostingsField,
        Segment,
    )
    from elasticsearch_tpu.utils.smallfloat import encode_norms

    p = config["corpus"]["args"]
    field = p["field"]
    # Heaps' law: the vocabulary grows with the square root of the
    # collection, from the source's own point (vocab_at_source terms in
    # source_docs passages)
    vocab = max(1000, int(round(
        p["vocab_at_source"] * (docs / p["source_docs"]) ** p["heaps_beta"])))
    stats = [int(p["stats_seed"]), docs]
    rng = np.random.default_rng(stats)
    target = draw_lengths(rng, p["length"], docs)
    total = int(target.sum())
    law = 1.0 / np.arange(1, vocab + 1, dtype=np.float64) ** float(p["zipf_s"])
    # `total` independent tokens over the term law, sorted by term (the
    # multinomial counts); each goes to a passage drawn in proportion to
    # the passage's target length (a token slot drawn with replacement),
    # so a passage's length is the count of tokens it received
    term_count = rng.multinomial(total, law / law.sum())
    slot_doc = np.repeat(np.arange(docs, dtype=np.int32), target)
    perm = passage_ids(seed, docs, int(p.get("id_block", 0)))
    term_end = np.cumsum(term_count)
    # SLICES contiguous runs of whole terms, each drawn and sorted on a
    # thread of its own from its own child of `stats_seed` (NumPy's
    # draws, gathers and sorts release the GIL)
    cut_terms = np.r_[0, np.searchsorted(
        term_end, np.linspace(0, total, SLICES + 1)[1:-1]) + 1, vocab]

    def one_slice(i: int):
        t0, t1 = int(cut_terms[i]), int(cut_terms[i + 1])
        counts = term_count[t0:t1]
        m = int(counts.sum())
        if not m:
            return (np.empty(0, np.int64), np.empty(0, np.int32),
                    np.empty(0, np.int32))
        child = np.random.default_rng(stats + [i])
        doc = perm[slot_doc[child.integers(0, total, size=m)]]
        key = np.repeat(np.arange(t0, t1, dtype=np.int64) * docs, counts)
        key += doc
        key.sort()
        first = np.flatnonzero(np.r_[True, key[1:] != key[:-1]])
        tf = np.diff(np.r_[first, len(key)]).astype(np.int32)
        uniq = key[first]
        term = uniq // docs
        return term, (uniq - term * docs).astype(np.int32), tf

    with ThreadPoolExecutor(max_workers=8) as pool:
        parts = list(pool.map(one_slice, range(SLICES)))
    del slot_doc
    u_t = np.concatenate([a for a, _d, _f in parts])
    u_d = np.concatenate([d for _a, d, _f in parts])
    tfs_flat = np.concatenate([f for _a, _d, f in parts])
    del parts
    lengths = np.bincount(u_d, weights=tfs_flat, minlength=docs).astype(np.int64)

    term_df = np.bincount(u_t, minlength=vocab).astype(np.int32)
    term_total_tf = term_count.astype(np.int64)
    term_tile_count = ((term_df + TILE - 1) // TILE).astype(np.int32)
    term_tile_start = np.zeros(vocab, np.int32)
    np.cumsum(term_tile_count[:-1], out=term_tile_start[1:])
    n_tiles = int(term_tile_count.sum())
    term_post_start = np.zeros(vocab + 1, np.int64)
    np.cumsum(term_df.astype(np.int64), out=term_post_start[1:])
    slot = np.arange(len(u_t), dtype=np.int64)
    slot -= np.repeat(term_post_start[:-1], term_df)
    slot += np.repeat(term_tile_start.astype(np.int64) * TILE, term_df)
    del u_t

    doc_ids = np.full(n_tiles * TILE, INVALID_DOC, np.int32)
    tfs = np.zeros(n_tiles * TILE, np.int32)
    doc_ids[slot] = u_d
    tfs[slot] = tfs_flat
    norms = encode_norms(lengths.astype(np.int64))
    tile_norms = np.full(n_tiles * TILE, 255, np.uint8)
    tile_norms[slot] = norms[u_d]
    del slot
    doc_ids = doc_ids.reshape(n_tiles, TILE)
    tfs = tfs.reshape(n_tiles, TILE)
    tile_norms = tile_norms.reshape(n_tiles, TILE)

    width = len(str(vocab - 1))
    pf = PostingsField(
        # fixed width: sorted lexicographically, as a term dictionary is
        terms=[f"w{i:0{width}d}" for i in range(vocab)],
        term_df=term_df,
        term_total_tf=term_total_tf,
        term_tile_start=term_tile_start,
        term_tile_count=term_tile_count,
        doc_ids=doc_ids,
        tfs=tfs,
        tile_max_tf=tfs.max(axis=1).astype(np.int32),
        tile_min_norm=tile_norms.min(axis=1).astype(np.uint8),
        norms=norms,
        stats=FieldStats(
            doc_count=docs,
            sum_total_term_freq=int(term_total_tf.sum()),
            sum_doc_freq=int(term_df.sum()),
        ),
    )
    segment = Segment(
        num_docs=docs,
        doc_ids=[str(i) for i in range(docs)],
        sources=[None] * docs,
        postings={field: pf},
        numerics={},
        ordinals={},
        vectors={},
    )
    return {
        "segment": segment,
        "mappings": {"properties": {field: {"type": "text"}}},
        "reference": {
            "field": field,
            "docs": docs,
            "lengths": lengths.astype(np.int64),
            "post_start": term_post_start,
            "post_doc": u_d,
            "post_tf": tfs_flat,
        },
        "body_context": {
            "field": field,
            "term_width": width,
            # the collection's unigram law: query words are drawn from it
            "term_total_tf": term_total_tf,
        },
    }
