"""`multi_match` bodies over the configuration's fields, shaped as MS
MARCO's questions are: `match_terms.py`'s own words (the number of words
from the configuration's histogram, each drawn from the body's unigram
law, stop-word class included, the words of one query distinct), sent at
the query's default type (`best_fields`) with the configuration's
`tie_breaker`."""

from __future__ import annotations

import json

import numpy as np

from plugins import load_plugin


def make(context: dict, args: dict, rng: np.random.Generator, n: int) -> list:
    drawn = load_plugin("bodies", "match_terms").make(
        {**context, "field": "words"}, args, rng, n)
    out = []
    for raw in drawn:
        body = {"query": {"multi_match": {
                    "query": json.loads(raw)["query"]["match"]["words"],
                    "fields": list(args["fields"]),
                    "tie_breaker": args["tie_breaker"]}},
                "size": args["size"], "_source": False}
        out.append(json.dumps(body, separators=(",", ":")).encode())
    return out
