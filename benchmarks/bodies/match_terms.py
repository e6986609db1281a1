"""Match bodies shaped as MS MARCO's queries are: the number of words
from the configuration's histogram (mean ~6), each word drawn from the
collection's own unigram law, stop-word class included (the standard
analyzer keeps "what", "is", "the", "of", and `match` ORs them in), the
words of one query distinct."""

from __future__ import annotations

import json

import numpy as np


def make(context: dict, args: dict, rng: np.random.Generator, n: int) -> list:
    hist = args["words_histogram"]
    sizes = np.array(sorted(int(k) for k in hist))
    share = np.array([hist[str(k)] for k in sizes], np.float64)
    ks = rng.choice(sizes, size=n, p=share / share.sum())
    cdf = np.cumsum(context["term_total_tf"], dtype=np.float64)
    spare = 4  # draws beyond a query's words, to replace repeats
    draws = np.searchsorted(
        cdf, rng.random((n, int(sizes.max()) + spare)) * cdf[-1], side="right")
    width = context["term_width"]
    out = []
    for row, k in zip(draws.tolist(), ks.tolist()):
        words = list(dict.fromkeys(row))[:k]  # distinct, in drawn order
        text = " ".join(f"w{t:0{width}d}" for t in words)
        body = {"query": {"match": {context["field"]: text}},
                "size": args["size"], "_source": False}
        out.append(json.dumps(body, separators=(",", ":")).encode())
    return out
