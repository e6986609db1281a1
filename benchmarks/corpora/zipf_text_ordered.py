"""Seeded synthetic passages WITH WORD ORDER at MS MARCO passage's
published shapes: `corpora/zipf_text.py`'s text law (passage lengths,
Heaps-scaled vocabulary, Zipf term law, every token kept) drawn as token
SEQUENCES, so that a passage holds phrases and the segment positions.

Order comes from a stated collocation law (the configuration's
`corpus.args.collocations`; every number of it is `assumed`): a
first-order chain. Every term may have ONE fixed partner; after a token
whose term has a partner, the partner follows with the term's own
probability `q`, else the slot holds an independent draw. A partner's
partner makes chains of three. The chain is built so that the term law
stays the configuration's: a term's independent draws are thinned by
what its collocations bring it (`base = (pi - inflow) / (1 - share)`),
and no term takes more than `inflow_cap` of its occurrences from
collocations (the `q` of the terms that point at it are scaled down).

- function words (the `function_ranks` most frequent terms): each has a
  partner drawn among them, `function_q` ("of the", "to be");
- content words (ranks up to `content_ranks`): a share `content_share`
  of them has a partner, a MORE frequent term at rank
  floor(rank x U(`partner_rank_lo`, 1)), followed with a probability
  drawn from U(`content_q`) ("united states": where q > 0.5 the phrase
  is most of the rarer word's occurrences).

The collection's statistics come from `stats_seed` (the same in every
run, as `zipf_text.py` argues); `--seed` decides which passage id holds
which passage, whole `id_block`s trading places.

What the program's engine holds after a refresh is built here from the
token stream: the tiled `PostingsField` with its columnar positions
(`term_pos_start` / `pos_offsets` / `pos_data`, the layout
`SegmentBuilder._attach_positions` leaves) and, through the program's
OWN function (`index/segment.plane_from_occurrences`, the half of
`build_positions_plane` that lays a forward stream out: not a copy of
it, and absent from a program that serves no phrase), the positions
plane its phrase kernel reads. The plain reference gets the raw token
stream (passage -> term ids in order) and nothing the program has made.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

from plugins import load_plugin

CHUNKS = 16  # part of the data's definition: do not change
THREADS = 8


def collocation_law(rng, pi: np.ndarray, c: dict):
    """(partner int32[V] (-1: none), q float64[V], base law float64[V],
    share of token slots that continue a collocation)."""
    vocab = len(pi)
    partner = np.full(vocab, -1, np.int64)
    q = np.zeros(vocab, np.float64)
    f = int(c["function_ranks"])
    shift = rng.integers(1, f, size=f)  # never itself
    partner[:f] = (np.arange(f) + shift) % f
    q[:f] = float(c["function_q"])
    top = min(int(c["content_ranks"]), vocab)
    ranks = np.arange(f, top)  # 0-based term id = rank - 1
    has = rng.random(len(ranks)) < float(c["content_share"])
    lo = float(c["partner_rank_lo"])
    to = np.floor((ranks + 1) * rng.uniform(lo, 1.0, len(ranks))).astype(
        np.int64) - 1
    to = np.clip(to, 0, ranks - 1)  # a more frequent term, never itself
    qlo, qhi = c["content_q"]
    qs = rng.uniform(float(qlo), float(qhi), len(ranks))
    partner[ranks[has]] = to[has]
    q[ranks[has]] = qs[has]
    # no term takes more than `inflow_cap` of its law from collocations
    held = np.flatnonzero(partner >= 0)
    inflow = np.bincount(partner[held], weights=pi[held] * q[held],
                         minlength=vocab)
    scale = np.minimum(1.0, float(c["inflow_cap"]) * pi
                       / np.maximum(inflow, 1e-300))
    q[held] *= scale[partner[held]]
    inflow = np.bincount(partner[held], weights=pi[held] * q[held],
                         minlength=vocab)
    share = float((pi * q).sum())
    base = (pi - inflow) / (1.0 - share)
    return partner.astype(np.int32), q, base / base.sum(), share


def draw_tokens(stats: list, target: np.ndarray, pi, base, partner, q):
    """The token stream in passage order (int32[sum(target)]) and each
    passage's first slot (int64[docs + 1]). A passage's first token
    follows the term law itself; a later slot continues its
    predecessor's collocation with the predecessor's `q`, else holds a
    draw from the thinned law. Passages are cut into CHUNKS runs, each
    drawn on a thread from its own child of `stats_seed`."""
    docs = len(target)
    doc_start = np.zeros(docs + 1, np.int64)
    np.cumsum(target, out=doc_start[1:])
    cdf_pi, cdf_base = np.cumsum(pi), np.cumsum(base)
    cdf_pi /= cdf_pi[-1]
    cdf_base /= cdf_base[-1]
    cuts = np.linspace(0, docs, CHUNKS + 1).astype(np.int64)
    tok = np.empty(int(doc_start[-1]), np.int32)

    def one(i: int) -> None:
        d0, d1 = int(cuts[i]), int(cuts[i + 1])
        s0, s1 = int(doc_start[d0]), int(doc_start[d1])
        if s1 == s0:
            return
        child = np.random.default_rng(stats + [i])
        first = np.zeros(s1 - s0, bool)
        first[doc_start[d0:d1] - s0] = True
        u = child.random(s1 - s0)
        x = np.searchsorted(cdf_base, u, side="right")
        x[first] = np.searchsorted(cdf_pi, u[first], side="right")
        x = np.minimum(x, len(pi) - 1).astype(np.int32)
        go = child.random(s1 - s0)
        go[first] = 2.0  # a first slot continues nothing
        t = x.copy()
        while True:  # to the fixed point: a chain settles left to right
            prev = np.empty_like(t)
            prev[0] = 0
            prev[1:] = t[:-1]
            new = np.where(go < q[prev], partner[prev], x)
            if np.array_equal(new, t):
                break
            t = new
        tok[s0:s1] = t

    with ThreadPoolExecutor(max_workers=THREADS) as pool:
        list(pool.map(one, range(CHUNKS)))
    return tok, doc_start


def build_postings(tok, doc_start, target, perm, vocab: int, docs: int):
    """The tiled `PostingsField` with its columnar positions, from ONE
    sort of (term, passage id, position) keys over the token stream."""
    from elasticsearch_tpu.index.segment import (
        INVALID_DOC,
        TILE,
        FieldStats,
        PostingsField,
    )
    from elasticsearch_tpu.utils.smallfloat import encode_norms

    total = len(tok)
    key = np.repeat(perm.astype(np.uint64) << np.uint64(8), target)
    pos = np.arange(total, dtype=np.int64)
    pos -= np.repeat(doc_start[:-1], target)
    key |= pos.astype(np.uint64)
    del pos
    key |= tok.astype(np.uint64) << np.uint64(28)
    key.sort()
    pos_data = (key & np.uint64(0xFF)).astype(np.int32)
    key >>= np.uint64(8)  # (term, passage id)
    new = np.empty(total, bool)
    new[0] = True
    np.not_equal(key[1:], key[:-1], out=new[1:])
    first = np.flatnonzero(new)  # a posting's first occurrence
    del new
    post = key[first]
    del key
    u_t = (post >> np.uint64(20)).astype(np.int64)
    u_d = (post & np.uint64(0xFFFFF)).astype(np.int32)
    del post
    pos_offsets = np.append(first, total).astype(np.int64)
    tfs_flat = np.diff(pos_offsets).astype(np.int32)

    term_df = np.bincount(u_t, minlength=vocab).astype(np.int32)
    term_total_tf = np.bincount(tok, minlength=vocab).astype(np.int64)
    term_tile_count = ((term_df + TILE - 1) // TILE).astype(np.int32)
    term_tile_start = np.zeros(vocab, np.int32)
    np.cumsum(term_tile_count[:-1], out=term_tile_start[1:])
    n_tiles = int(term_tile_count.sum())
    term_post_start = np.zeros(vocab + 1, np.int64)
    np.cumsum(term_df.astype(np.int64), out=term_post_start[1:])
    slot = np.arange(len(u_t), dtype=np.int64)
    slot += np.repeat(
        term_tile_start.astype(np.int64) * TILE - term_post_start[:-1],
        term_df)
    del u_t

    lengths = np.empty(docs, np.int64)
    lengths[perm] = target  # by passage id
    doc_ids = np.full(n_tiles * TILE, INVALID_DOC, np.int32)
    tfs = np.zeros(n_tiles * TILE, np.int32)
    doc_ids[slot] = u_d
    tfs[slot] = tfs_flat
    norms = encode_norms(lengths)
    tile_norms = np.full(n_tiles * TILE, 255, np.uint8)
    tile_norms[slot] = norms[u_d]
    del slot, u_d
    doc_ids = doc_ids.reshape(n_tiles, TILE)
    tfs = tfs.reshape(n_tiles, TILE)
    tile_norms = tile_norms.reshape(n_tiles, TILE)
    width = len(str(vocab - 1))
    return PostingsField(
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
            sum_total_term_freq=int(total),
            sum_doc_freq=int(term_df.sum()),
        ),
        # the columnar positions a refresh leaves (_attach_positions)
        term_pos_start=term_post_start[:-1].copy(),
        pos_offsets=pos_offsets,
        pos_data=pos_data,
    )


def build_plane(tok, doc_start, target, perm):
    """The device layout of the program's phrase kernel, by the
    program's OWN function, from the forward stream put in passage-id
    order (what the function would have turned the CSR back into)."""
    from elasticsearch_tpu.index.segment import plane_from_occurrences

    by_id = np.argsort(perm, kind="stable")  # the passage of each id
    lens = target[by_id]
    first = np.cumsum(lens) - lens
    pos = np.arange(len(tok), dtype=np.int64)
    pos -= np.repeat(first, lens)
    at = pos + np.repeat(doc_start[:-1][by_id], lens)
    doc = np.repeat(np.arange(len(perm), dtype=np.int32), lens)
    return plane_from_occurrences(doc, pos, tok[at])


def build(config: dict, seed: int, docs: int) -> dict:
    # first of all: a program that holds no positions plane (it serves
    # no phrase) fails here, at once, before a token is drawn
    from elasticsearch_tpu.index.segment import (  # noqa: F401
        Segment,
        plane_from_occurrences,
    )

    text = load_plugin("corpora", "zipf_text")
    p = config["corpus"]["args"]
    field = p["field"]
    vocab = max(1000, int(round(
        p["vocab_at_source"] * (docs / p["source_docs"]) ** p["heaps_beta"])))
    if vocab >= 1 << 20 or docs > 1 << 20 or p["length"]["max"] > 256:
        raise ValueError("the packed sort keys hold 20 bits of term, 20 of "
                         "passage and 8 of position")
    stats = [int(p["stats_seed"]), docs]
    rng = np.random.default_rng(stats)
    target = text.draw_lengths(rng, p["length"], docs)
    law = 1.0 / np.arange(1, vocab + 1, dtype=np.float64) ** float(p["zipf_s"])
    pi = law / law.sum()
    partner, q, base, share = collocation_law(rng, pi, p["collocations"])
    tok, doc_start = draw_tokens(stats, target, pi, base, partner, q)
    perm = text.passage_ids(seed, docs, int(p.get("id_block", 0)))
    # three views of the one stream, each on a thread of its own (NumPy's
    # sorts, gathers and repeats release the GIL): what a refresh leaves,
    # the device layout, and the census the body generator bins by
    body = config["body"]
    gen = load_plugin("bodies", body["generator"])
    with ThreadPoolExecutor(max_workers=3) as pool:
        postings = pool.submit(
            build_postings, tok, doc_start, target, perm, vocab, docs)
        plane = pool.submit(build_plane, tok, doc_start, target, perm)
        census = pool.submit(
            gen.census, tok, doc_start, gen.census_floor(body["args"], docs))
        pf = postings.result()
        # kept where the program looks for it at a field's first phrase
        pf._positions_plane = plane.result() or False
        runs = census.result()
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
            # the raw token stream: passage p holds
            # tokens[doc_start[p]:doc_start[p + 1]] and answers to the id
            # passage_id[p]
            "tokens": tok,
            "doc_start": doc_start,
            "passage_id": perm,
            "vocab": vocab,
        },
        "body_context": {
            "field": field,
            "term_width": len(str(vocab - 1)),
            "docs": docs,
            "vocab": vocab,
            # the stream the generator's phrases are runs of, and its
            # census of them (every distinct run with the passages that
            # hold it: `bodies/phrase_classes.census`, made here beside
            # the other two sorts)
            "tokens": tok,
            "doc_start": doc_start,
            "census": runs,
            # the law as built, for the record (PERF.md section 4)
            "collocation_share": share,
            "partner": partner,
            "q": q,
        },
    }
