"""`corpora/zipf_text_df.py`'s corpus (the seeded `body` text and each
term's document frequency, unchanged: that builder is loaded by name and
run on the configuration as it stands) with a multi-valued `keyword`
field of tags beside it, drawn as `corpora/byte_vectors_tags.py` draws
its bags: how many tags a passage carries, every tag's document
frequency (a shifted power law, its commonest tag capped at a stated
share of the passages) and which tags share a passage are the
configuration's, from the tags' own `stats_seed` and the same in every
run (the program's layout is sized by them: the tag field's tile count,
which tags hold a bit row); `--seed` decides which passage id holds
which bag (a permutation of its own, independent of the text's).

The program gets what its engine holds after a refresh: the tags as a
second tiled `PostingsField` of the segment (term-major, passage ids
ascending, tf 1), laid out as `byte_vectors_tags.py` lays out its own.
The plain reference gets the raw row-major (bag, tag) stream
(`bag_start`, `bag_tags`: bag i's tags; `bag_row`: the passage id that
holds bag i under this seed) beside the text's raw posting stream, and
nothing the program has made.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

from plugins import load_plugin


def tag_postings(tp: dict, seed: int, docs: int):
    """(the tag field's PostingsField, bag_start, bag_tags, bag_row,
    the width of a tag's name)."""
    from elasticsearch_tpu.index.segment import (
        INVALID_DOC,
        TILE,
        FieldStats,
        PostingsField,
    )
    from elasticsearch_tpu.utils.smallfloat import encode_norms

    bags = load_plugin("corpora", "byte_vectors_tags")
    n_tags = int(tp["vocab"])
    _used, keys = load_plugin("corpora", "splade_impacts").structure(
        {"stats_seed": tp["stats_seed"], "nnz": tp["per_row"],
         "vocab": n_tags, "vocab_in_use": n_tags, "df_law": tp["df_law"]},
        docs)
    cuts = np.cumsum([0] + [len(k) for k in keys])
    post_tag, bag = bags.split_keys(keys, docs)
    del keys
    bag_start, bag_tags = bags.row_major(post_tag, bag, docs)
    # bag i lives in passage bag_row[i] under this seed
    bag_row = np.random.default_rng([int(seed), 7]).permutation(docs).astype(
        np.int32)

    # term-major, this seed's passage ids: a slice holds whole tags, so
    # sorting each slice by (tag, passage) sorts the stream
    post_doc = np.empty(len(bag), np.int32)

    def one_slice(i: int) -> None:
        lo, hi = cuts[i], cuts[i + 1]
        doc = bag_row[bag[lo:hi]]
        key = post_tag[lo:hi].astype(np.int64) * docs + doc  # distinct
        post_doc[lo:hi] = doc[np.argsort(key)]

    with ThreadPoolExecutor(max_workers=8) as pool:
        list(pool.map(one_slice, range(len(cuts) - 1)))
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
    return pf, bag_start, bag_tags, bag_row, width


def build(config: dict, seed: int, docs: int) -> dict:
    out = load_plugin("corpora", "zipf_text_df").build(config, seed, docs)
    tp = config["corpus"]["args"]["tags"]
    pf, bag_start, bag_tags, bag_row, width = tag_postings(tp, seed, docs)
    out["segment"].postings[tp["field"]] = pf
    out["mappings"]["properties"][tp["field"]] = {"type": "keyword"}
    tags = {"tag_field": tp["field"], "tag_width": width,
            "bag_start": bag_start, "bag_tags": bag_tags}
    out["reference"] = {**out["reference"], **tags, "bag_row": bag_row}
    # a request's tag is drawn from one stored bag
    out["body_context"] = {**out["body_context"], **tags}
    return out
