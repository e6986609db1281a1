"""Hybrid bodies in upstream's retriever form (~7.9 KB each): an `rrf`
retriever over a `standard` retriever (a `match` on the text field) and a
`knn` retriever (a 768-d query vector, six decimals), fused over
`rank_window_size` candidates a leg with `rank_constant`, page `size`, no
source.

The words are the ones `bodies/match_terms.py` draws (the configuration's
words histogram, the collection's unigram law), the vector the one
`bodies/knn_vector.py` draws and encodes; both generators are loaded by
name and their bodies taken apart, so the two draws are exactly the other
two configurations' and independent of each other: a query's legs share
hits by chance only (the configuration's `assumed`).
"""

from __future__ import annotations

import json

import numpy as np

from plugins import load_plugin


def make(context: dict, args: dict, rng: np.random.Generator, n: int) -> list:
    text_ctx, vec_ctx = context["text"], context["vector"]
    texts = load_plugin("bodies", "match_terms").make(
        text_ctx, {"size": args["size"],
                   "words_histogram": args["words_histogram"]}, rng, n)
    knns = load_plugin("bodies", "knn_vector").make(
        vec_ctx, {"k": args["k"], "num_candidates": args["num_candidates"],
                  "size": args["size"], "decimals": args["decimals"]}, rng, n)
    knn_head = ('{"knn":{"field":%s,"query_vector":'
                % json.dumps(vec_ctx["field"])).encode()
    tail = (',"k":%d,"num_candidates":%d}}],"rank_window_size":%d,'
            '"rank_constant":%d}},"size":%d,"_source":false}'
            % (args["k"], args["num_candidates"], args["rank_window_size"],
               args["rank_constant"], args["size"])).encode()
    out = []
    for text, knn in zip(texts, knns):
        query = json.loads(text)["query"]
        vector = knn[knn.index(b"["):knn.rindex(b"]") + 1]
        head = ('{"retriever":{"rrf":{"retrievers":[{"standard":{"query":%s}},'
                % json.dumps(query, separators=(",", ":"))).encode()
        out.append(head + knn_head + vector + tail)
    return out
