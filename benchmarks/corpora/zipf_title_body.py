"""Seeded synthetic documents at MS MARCO document's shapes: two `text`
fields, `title` and `body`, one term dictionary (a word is the same string
in both), every token kept, as the configuration's `corpus.args` state
them. `zipf_text.py`'s method (tokens are independent draws from the term
law, a body's length is the count of tokens it received) at document
length, where a stop word's tf passes 255 in the long tail; a title's
words are a sample of its own document's body tokens, so the fields
agree as real titles do.

As there, the collection's statistics (each term's total frequency, the
multiset of body lengths, which tokens share a document, which of them
are the title) come from `stats_seed` and are the same in every run, and
`--seed` decides which document id holds which document, moving ids only
inside and between whole `id_block`s (`zipf_text.passage_ids`), so the
program's device layout and its compiled shapes do not move with the seed.

Returns one `Segment` holding both `PostingsField`s, the `mappings`, the
raw posting streams of both fields (global term numbers) for the plain
reference, which takes nothing the program has made, and the body's
unigram law as `body_context` (questions draw their words from it).
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from plugins import load_plugin

SLICES = 64  # part of the data's definition: do not change


def vocabulary(args: dict, docs: int) -> int:
    """Heaps' law on tokens, from the anchor collection's own point: both
    token counts are Anserini's (its analyzer drops stop words)."""
    tokens = docs * float(args["tokens_per_doc_at_anchor_analyzer"])
    return max(1000, int(round(
        args["vocab_at_anchor"]
        * (tokens / args["tokens_at_anchor"]) ** args["heaps_beta"])))


def _postings(keys: np.ndarray, docs: int, t0: int, t1: int) -> dict:
    """Sorted `term * docs + doc` keys of the terms t0..t1, one a token
    -> that slice of a field's raw stream and its statistics."""
    first = np.flatnonzero(np.r_[True, keys[1:] != keys[:-1]][:len(keys)])
    tf = np.diff(np.r_[first, len(keys)]).astype(np.int32)
    uniq = keys[first]
    term = uniq // docs
    doc = (uniq - term * docs).astype(np.int32)
    return {
        "doc": doc, "tf": tf,
        "df": np.bincount(term - t0, minlength=t1 - t0),
        "total_tf": np.bincount(term - t0, weights=tf, minlength=t1 - t0),
        "lengths": np.bincount(doc, weights=tf, minlength=docs),
    }


def _tiled_field(pool, parts: list, cut_terms, vocab: int, width: int,
                 docs: int):
    """One field's `PostingsField` (the layout the engine holds after a
    refresh: every present term's postings in tiles of its own) and the
    field's raw stream by global term number. Each slice of whole terms
    fills its own run of tiles, on a thread of its own."""
    from elasticsearch_tpu.index.segment import (
        INVALID_DOC,
        TILE,
        FieldStats,
        PostingsField,
    )
    from elasticsearch_tpu.utils.smallfloat import encode_norms

    lengths = np.sum([x["lengths"] for x in parts], axis=0).astype(np.int64)
    df_all = np.concatenate([x["df"] for x in parts]).astype(np.int64)
    total_all = np.concatenate([x["total_tf"] for x in parts]).astype(np.int64)
    post_start = np.zeros(vocab + 1, np.int64)
    np.cumsum(df_all, out=post_start[1:])
    tiles_all = (df_all + TILE - 1) // TILE  # 0 where the field lacks the term
    tile_start_all = np.zeros(vocab + 1, np.int64)
    np.cumsum(tiles_all, out=tile_start_all[1:])
    n_tiles = int(tile_start_all[-1])
    norms = encode_norms(lengths)
    doc_ids = np.empty((n_tiles, TILE), np.int32)
    tfs = np.empty((n_tiles, TILE), np.int32)
    tile_max_tf = np.empty(n_tiles, np.int32)
    tile_min_norm = np.empty(n_tiles, np.uint8)

    def fill(i: int) -> None:
        t0, t1 = int(cut_terms[i]), int(cut_terms[i + 1])
        a, b = int(tile_start_all[t0]), int(tile_start_all[t1])
        if a == b:
            return
        x, df = parts[i], df_all[t0:t1]
        slot = np.arange(len(x["doc"]), dtype=np.int64)
        slot -= np.repeat(post_start[t0:t1] - post_start[t0], df)
        slot += np.repeat((tile_start_all[t0:t1] - a) * TILE, df)
        d = doc_ids[a:b].reshape(-1)
        d[:] = INVALID_DOC
        d[slot] = x["doc"]
        f = tfs[a:b].reshape(-1)
        f[:] = 0
        f[slot] = x["tf"]
        tile_max_tf[a:b] = tfs[a:b].max(axis=1)
        tn = np.full((b - a) * TILE, 255, np.uint8)
        tn[slot] = norms[x["doc"]]
        tile_min_norm[a:b] = tn.reshape(b - a, TILE).min(axis=1)

    list(pool.map(fill, range(len(parts))))
    present = np.flatnonzero(df_all)  # the field's own term dictionary
    pf = PostingsField(
        # fixed width: sorted lexicographically, as a term dictionary is
        terms=[f"w{i:0{width}d}" for i in present.tolist()],
        term_df=df_all[present].astype(np.int32),
        term_total_tf=total_all[present],
        term_tile_start=tile_start_all[present].astype(np.int32),
        term_tile_count=tiles_all[present].astype(np.int32),
        doc_ids=doc_ids,
        tfs=tfs,
        tile_max_tf=tile_max_tf,
        tile_min_norm=tile_min_norm,
        norms=norms,
        stats=FieldStats(
            # Lucene's docCount: documents that have the field
            doc_count=int(np.count_nonzero(lengths)),
            sum_total_term_freq=int(total_all.sum()),
            sum_doc_freq=int(df_all.sum()),
        ),
    )
    raw = {"lengths": lengths, "post_start": post_start,
           "post_doc": np.concatenate([x["doc"] for x in parts]),
           "post_tf": np.concatenate([x["tf"] for x in parts])}
    return pf, raw, total_all


def build(config: dict, seed: int, docs: int) -> dict:
    from elasticsearch_tpu.index.segment import Segment

    zipf_text = load_plugin("corpora", "zipf_text")
    p = config["corpus"]["args"]
    vocab = vocabulary(p, docs)
    stats = [int(p["stats_seed"]), docs]
    rng = np.random.default_rng(stats)
    target = zipf_text.draw_lengths(rng, p["body_length"], docs)
    # the share of a document's body tokens that its title repeats
    title_share = np.minimum(
        1.0, zipf_text.draw_lengths(rng, p["title_length"], docs) / target)
    total = int(target.sum())
    law = 1.0 / np.arange(1, vocab + 1, dtype=np.float64) ** float(p["zipf_s"])
    term_count = rng.multinomial(total, law / law.sum())
    slot_doc = np.repeat(np.arange(docs, dtype=np.int32), target)
    perm = zipf_text.passage_ids(seed, docs, int(p.get("id_block", 0)))
    # SLICES contiguous runs of whole terms, about as many tokens each
    # (the most frequent terms are a slice each), drawn and sorted on a
    # thread of their own from their own child of `stats_seed`
    cut_terms = np.r_[0, np.searchsorted(
        np.cumsum(term_count), np.linspace(0, total, SLICES + 1)[1:-1]) + 1,
        vocab]
    cut_terms = np.maximum.accumulate(np.minimum(cut_terms, vocab))

    def one_slice(i: int):
        """Every token a draw of a body slot; a token is also a title
        word with its document's `title_share`."""
        t0, t1 = int(cut_terms[i]), int(cut_terms[i + 1])
        counts = term_count[t0:t1]
        m = int(counts.sum())
        child = np.random.default_rng(stats + [i])
        doc0 = slot_doc[child.integers(0, total, size=m)]
        in_title = child.random(m) < title_share[doc0]
        key = np.repeat(np.arange(t0, t1, dtype=np.int64) * docs, counts)
        key += perm[doc0]
        del doc0
        title_key = key[in_title]
        key.sort()
        title_key.sort()
        return (_postings(key, docs, t0, t1),
                _postings(title_key, docs, t0, t1))

    width = len(str(vocab - 1))
    with ThreadPoolExecutor(max_workers=min(12, os.cpu_count() or 8)) as pool:
        parts = list(pool.map(one_slice, range(SLICES)))
        del slot_doc
        body, body_raw, unigram = _tiled_field(
            pool, [b for b, _t in parts], cut_terms, vocab, width, docs)
        title, title_raw, _ = _tiled_field(
            pool, [t for _b, t in parts], cut_terms, vocab, width, docs)
    del parts
    fields = {"title": title, "body": body}
    segment = Segment(
        num_docs=docs,
        doc_ids=[str(i) for i in range(docs)],
        sources=[None] * docs,
        postings=fields,
        numerics={},
        ordinals={},
        vectors={},
    )
    return {
        "segment": segment,
        "mappings": {"properties": {f: {"type": "text"} for f in fields}},
        "reference": {"docs": docs,
                      "fields": {"title": title_raw, "body": body_raw}},
        "body_context": {
            "term_width": width,
            # the body's unigram law: query words are drawn from it
            "term_total_tf": unigram,
        },
    }
