"""Typo-tolerant search-box bodies: the passage cell's question (the
number of words from the configuration's histogram, each word drawn from
the collection's own unigram law, the words of one question distinct) as
a `match` with `fuzziness: AUTO`, the form a search box with typo
tolerance on sends for EVERY question, misspelled or not. Each word of
three letters or more is misspelled with probability `typo_rate` by one
edit (substitute, insert, delete, transpose: a quarter each, the place
and the letter uniform), so about one question in eight holds a
misspelling. Words are written as the corpus spells them
(`body_context["spellings"]`, by frequency rank)."""

from __future__ import annotations

import json
import string

import numpy as np

from plugins import load_plugin

one_edit = load_plugin("corpora", "zipf_text_spelled").one_edit


def make(context: dict, args: dict, rng: np.random.Generator, n: int) -> list:
    hist = args["words_histogram"]
    sizes = np.array(sorted(int(k) for k in hist))
    share = np.array([hist[str(k)] for k in sizes], np.float64)
    ks = rng.choice(sizes, size=n, p=share / share.sum())
    cdf = np.cumsum(context["term_total_tf"], dtype=np.float64)
    spare = 4  # draws beyond a query's words, to replace repeats
    width = int(sizes.max()) + spare
    draws = np.searchsorted(cdf, rng.random((n, width)) * cdf[-1],
                            side="right")
    typo = rng.random((n, width)) < float(args["typo_rate"])
    kind = rng.integers(0, 4, size=(n, width))
    at = rng.random((n, width))
    letter = rng.integers(0, 26, size=(n, width))
    spelled = context["spellings"]
    out = []
    for i, k in enumerate(ks.tolist()):
        ranks = list(dict.fromkeys(draws[i].tolist()))[:k]  # distinct
        words = []
        for c, r in enumerate(ranks):
            w = spelled[r]
            if typo[i, c] and len(w) >= 3:
                w = one_edit(w, int(kind[i, c]), float(at[i, c]),
                             string.ascii_lowercase[letter[i, c]])
            words.append(w)
        body = {"query": {"match": {context["field"]: {
            "query": " ".join(words), "fuzziness": args["fuzziness"]}}},
            "size": args["size"], "_source": False}
        out.append(json.dumps(body, separators=(",", ":")).encode())
    return out
