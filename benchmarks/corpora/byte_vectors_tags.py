"""Seeded synthetic rows at the shapes of big-ann-benchmarks' filter
track (`yfcc-10M`: 192-dimension uint8 CLIP descriptors of YFCC100M
images, each with a bag of tags from a 200,386-tag vocabulary): one
`byte` `dense_vector` field and one multi-valued `keyword` field, as
the configuration's `corpus.args` state them.

The bags are the configuration's, drawn from `stats_seed` and the same
in every run, because the program's device layout is sized by them (the
tag field's tile count is a shape of its programs' operands): how many
tags a row carries, every tag's document frequency (a shifted power
law, its commonest tag capped at a stated share of the rows), which
tags share a row (`corpora/splade_impacts.py` `structure`: independent
draws, a row chosen in proportion to its own number of tags). `--seed`
decides which row id holds which bag (a permutation) and draws every
vector (components i.i.d. from a clipped normal about the middle of the
byte range, in chunks on a few threads, each chunk from its own child
of the seed).

The program gets what its engine holds after a refresh: the vectors as
int8 (uint8 - 128: a shift changes no Euclidean distance) in a
`VectorField`, the tags as a tiled `PostingsField` (term-major, doc ids
ascending, tf 1), laid out here as `zipf_text.py` lays out its own. The
plain reference gets neither: it is handed the uint8 rows and the raw
row-major (row, tag) stream (`bag_start`, `bag_tags`: bag i's tags;
`bag_row`: the row id that holds bag i under this seed), made from the
same draws by a sort of its own.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

from plugins import load_plugin

SLICES = 16  # part of the data's definition: do not change


def draw_bytes(rng, law: dict, shape) -> np.ndarray:
    """uint8 components: normal(`mean`, `sigma`), rounded, clipped to a
    byte."""
    x = rng.standard_normal(shape, dtype=np.float32)
    x *= np.float32(law["sigma"])
    x += np.float32(law["mean"])
    np.rint(x, out=x)
    np.clip(x, 0.0, 255.0, out=x)
    return x.astype(np.uint8)


def split_keys(keys: list, docs: int):
    """(tag int32[total], bag int32[total]) of the structure's sorted
    keys `tag x docs + bag`, slice after slice: term-major, a tag's
    bags ascending."""
    def one(key: np.ndarray):
        tag = key // docs
        return tag.astype(np.int32), (key - tag * docs).astype(np.int32)

    with ThreadPoolExecutor(max_workers=8) as pool:
        parts = list(pool.map(one, keys))
    return (np.concatenate([t for t, _b in parts]),
            np.concatenate([b for _t, b in parts]))


def row_major(tag: np.ndarray, bag: np.ndarray, docs: int):
    """(bag_start int64[docs + 1], bag_tags int32[total]): every bag's
    tags, ascending, from the term-major stream. SLICES runs of bags,
    each picked out of the stream and put in bag order by a stable sort
    (which keeps a bag's tags in the stream's order, ascending) on a
    thread of its own."""
    bag_start = np.zeros(docs + 1, np.int64)
    np.cumsum(np.bincount(bag, minlength=docs), out=bag_start[1:])
    bag_tags = np.empty(len(bag), np.int32)
    per = -(-docs // SLICES)

    def one_slice(i: int) -> None:
        lo, hi = i * per, min(docs, (i + 1) * per)
        mine = np.flatnonzero((bag >= lo) & (bag < hi))
        order = np.argsort(bag[mine], kind="stable")
        bag_tags[bag_start[lo]:bag_start[hi]] = tag[mine[order]]

    with ThreadPoolExecutor(max_workers=8) as pool:
        list(pool.map(one_slice, range(SLICES)))
    return bag_start, bag_tags


def build(config: dict, seed: int, docs: int) -> dict:
    from elasticsearch_tpu.index.segment import (
        INVALID_DOC,
        TILE,
        FieldStats,
        PostingsField,
        Segment,
        VectorField,
    )
    from elasticsearch_tpu.utils.smallfloat import encode_norms

    p = config["corpus"]["args"]
    field, dims, chunk = p["field"], int(p["dims"]), int(p["chunk_rows"])
    tp = p["tags"]
    n_tags = int(tp["vocab"])

    # ---- the bags: the configuration's, from `stats_seed`
    _used, keys = load_plugin("corpora", "splade_impacts").structure(
        {"stats_seed": p["stats_seed"], "nnz": tp["per_row"],
         "vocab": n_tags, "vocab_in_use": n_tags, "df_law": tp["df_law"]},
        docs)
    cuts = np.cumsum([0] + [len(k) for k in keys])
    post_tag, bag = split_keys(keys, docs)
    del keys
    bag_start, bag_tags = row_major(post_tag, bag, docs)
    # bag i lives in row bag_row[i] under this seed
    bag_row = np.random.default_rng([int(seed), 1]).permutation(docs).astype(
        np.int32)

    # ---- the program's postings: term-major, this seed's row ids. A
    # slice holds whole tags, so sorting each slice by (tag, row) sorts
    # the stream; the tags stay where they are, only the rows move
    post_doc = np.empty(len(bag), np.int32)

    def one_slice(i: int) -> None:
        lo, hi = cuts[i], cuts[i + 1]
        doc = bag_row[bag[lo:hi]]
        key = post_tag[lo:hi].astype(np.int64) * docs + doc  # distinct
        post_doc[lo:hi] = doc[np.argsort(key)]

    with ThreadPoolExecutor(max_workers=8) as pool:
        list(pool.map(one_slice, range(SLICES)))
    del bag
    term_df = np.bincount(post_tag, minlength=n_tags).astype(np.int32)
    del post_tag
    tile_count = ((term_df + TILE - 1) // TILE).astype(np.int32)
    tile_start = np.zeros(n_tags, np.int32)
    np.cumsum(tile_count[:-1], out=tile_start[1:])
    n_tiles = int(tile_count.sum())
    post_start = np.zeros(n_tags + 1, np.int64)
    np.cumsum(term_df, out=post_start[1:])
    slot = np.arange(len(post_doc), dtype=np.int64)
    slot += np.repeat(tile_start.astype(np.int64) * TILE - post_start[:-1],
                      term_df)
    doc_ids = np.full(n_tiles * TILE, INVALID_DOC, np.int32)
    tfs = np.zeros(n_tiles * TILE, np.int32)
    doc_ids[slot] = post_doc
    tfs[slot] = 1
    lengths = np.bincount(post_doc, minlength=docs).astype(np.int64)
    norms = encode_norms(lengths)
    tile_norms = np.full(n_tiles * TILE, 255, np.uint8)
    tile_norms[slot] = norms[post_doc]
    del slot, post_doc
    tfs = tfs.reshape(n_tiles, TILE)
    width = len(str(n_tags - 1))
    pf = PostingsField(
        # fixed width: sorted lexicographically, as a term dictionary is
        terms=[f"t{i:0{width}d}" for i in range(n_tags)],
        term_df=term_df,
        term_total_tf=term_df.astype(np.int64),
        term_tile_start=tile_start,
        term_tile_count=tile_count,
        doc_ids=doc_ids.reshape(n_tiles, TILE),
        tfs=tfs,
        tile_max_tf=tfs.max(axis=1).astype(np.int32),
        tile_min_norm=tile_norms.reshape(n_tiles, TILE).min(axis=1),
        norms=norms,
        stats=FieldStats(
            doc_count=int((lengths > 0).sum()),
            sum_total_term_freq=int(term_df.sum()),
            sum_doc_freq=int(term_df.sum()),
        ),
    )
    del tile_norms

    # ---- the vectors: uint8 for the reference, int8 for the program
    starts = list(range(0, docs, chunk))
    children = np.random.SeedSequence([int(seed), 2]).spawn(len(starts))
    rows_u8 = np.empty((docs, dims), np.uint8)
    rows_i8 = np.empty((docs, dims), np.int8)

    def fill(i: int) -> None:
        lo = starts[i]
        hi = min(docs, lo + chunk)
        x = draw_bytes(np.random.default_rng(children[i]), p["components"],
                       (hi - lo, dims))
        rows_u8[lo:hi] = x
        rows_i8[lo:hi] = (x ^ np.uint8(0x80)).view(np.int8)  # x - 128

    with ThreadPoolExecutor(max_workers=int(p.get("threads", 8))) as pool:
        list(pool.map(fill, range(len(starts))))

    segment = Segment(
        num_docs=docs,
        doc_ids=[str(i) for i in range(docs)],
        sources=[None] * docs,
        postings={tp["field"]: pf},
        numerics={},
        ordinals={},
        vectors={
            field: VectorField(
                vectors=rows_i8,
                exists=np.ones(docs, bool),
                similarity=p["similarity"],
            )
        },
    )
    return {
        "segment": segment,
        "mappings": {"properties": {
            field: {"type": "dense_vector", "dims": dims,
                    "element_type": "byte", "similarity": p["similarity"]},
            tp["field"]: {"type": "keyword"},
        }},
        "reference": {
            "field": field, "tag_field": tp["field"], "docs": docs,
            "tag_width": width, "vectors": rows_u8,
            "bag_start": bag_start, "bag_tags": bag_tags,
            "bag_row": bag_row,
        },
        "body_context": {
            "field": field, "tag_field": tp["field"], "dims": dims,
            "tag_width": width, "components": p["components"],
            # a query's tags are drawn from one stored bag
            "bag_start": bag_start, "bag_tags": bag_tags,
        },
    }
