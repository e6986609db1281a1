"""`sparse_vector` bodies shaped as SPLADE-encoded MS MARCO dev queries
are (big-ann-benchmarks' sparse track): the number of weighted tokens
from the configuration's law (mean ~49), the tokens of one query
distinct and drawn by posting mass (a token's share of the collection's
non-zeros), each weight from the corpus's own weight law at that token
(`corpora/splade_impacts.py`), written with `decimals` digits as an
inference service's JSON would carry them. What Elasticsearch's
`SparseVectorQueryBuilder` takes when the application expands the
question itself: `{"query": {"sparse_vector": {"field": ...,
"query_vector": {token: weight, ...}}}}`, `size` hits, no source."""

from __future__ import annotations

import json

import numpy as np

from plugins import load_plugin


def make(context: dict, args: dict, rng: np.random.Generator, n: int) -> list:
    corpus = load_plugin("corpora", "splade_impacts")
    ks = corpus.draw_counts(rng, args["nnz"], n)
    cdf = np.cumsum(context["term_df"], dtype=np.float64)
    spare = 16  # draws beyond a query's tokens, to replace repeats
    draws = np.searchsorted(
        cdf, rng.random((n, int(ks.max()) + spare)) * cdf[-1], side="right")
    weights = corpus.draw_weights(
        rng, context["term_weight_mean"][draws], args["weights"])
    decimals = int(args["decimals"])
    floor = 10.0 ** -decimals
    names = context["terms"]
    width = context["term_width"]
    out = []
    for row, ws, k in zip(draws.tolist(), weights.tolist(), ks.tolist()):
        vector = {}
        for t, w in zip(row, ws):
            if len(vector) == k:
                break
            # distinct tokens in drawn order, every weight positive
            vector.setdefault(f"t{int(names[t]):0{width}d}",
                              max(floor, round(w, decimals)))
        body = {"query": {"sparse_vector": {"field": context["field"],
                                            "query_vector": vector}},
                "size": args["size"], "_source": False}
        out.append(json.dumps(body, separators=(",", ":")).encode())
    return out
