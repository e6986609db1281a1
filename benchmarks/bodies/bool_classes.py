"""`bool` bodies in luceneutil's Boolean task classes
(`tasks/wikimedium.10M.nostopwords.tasks`): two required terms
(`AndHighHigh`, `AndHighMed`, `AndHighLow`: `+a +b`), two optional terms
(`OrHighHigh`, `OrHighMed`, `OrHighLow`: `a b`) and a required term
beside a required disjunction (`AndHighOrMedMed`: `+high +(med med)`,
`AndMedOrHighHigh`: `+med +(high high)`), the eight in equal shares.

A term's class is its document frequency on this shard as a share of the
shard's passages (`args["df_share"]`: High, Med, Low as [from, below)),
the `args["stop_terms"]` most frequent terms left out (they stand for the
stop list the `nostopwords` task file leaves out). The terms of one
request are distinct and drawn uniformly inside their class. In the
Elasticsearch DSL a required term is a `term` clause under `must`, an
optional one under `should`, and the disjunction a two-word `match`
(operator or) under `must`. Nothing in a body names its class:
`class_of` tells it from the body's shape and its terms' classes.
"""

from __future__ import annotations

import json

import numpy as np

# name -> (occurrence of the two outer terms, classes of the terms in the
# order they are written: the outer terms, then the inner disjunction's)
CLASSES = {
    "AndHighHigh": ("must", ("High", "High")),
    "AndHighMed": ("must", ("High", "Med")),
    "AndHighLow": ("must", ("High", "Low")),
    "OrHighHigh": ("should", ("High", "High")),
    "OrHighMed": ("should", ("High", "Med")),
    "OrHighLow": ("should", ("High", "Low")),
    "AndHighOrMedMed": ("must", ("High", "Med", "Med")),
    "AndMedOrHighHigh": ("must", ("Med", "High", "High")),
}


def class_terms(context: dict, args: dict) -> dict:
    """{"High" | "Med" | "Low": the term ids of that class}."""
    df = np.asarray(context["term_df"], np.int64)
    docs = int(context["docs"])
    stop = np.zeros(len(df), bool)
    stop[np.argsort(-df, kind="stable")[:int(args["stop_terms"])]] = True
    out = {}
    for name, (lo, hi) in args["df_share"].items():
        inside = (df >= lo * docs) & ~stop
        if hi is not None:
            inside &= df < hi * docs
        out[name] = np.flatnonzero(inside)
    return out


def class_of(body: dict, field: str, terms: dict) -> str:
    """The task class of one body this generator made, `terms` being
    `class_terms` of the same context and arguments."""
    def of_term(t: int) -> str:
        for name, ids in terms.items():  # ids ascend
            i = int(np.searchsorted(ids, t))
            if i < len(ids) and ids[i] == t:
                return name
        raise ValueError(f"term {t} is of no class")

    (occur, clauses), = body["query"]["bool"].items()
    words = []
    for clause in clauses:
        (_kind, inner), = clause.items()
        words += inner[field].split()
    shape = (occur, tuple(of_term(int(w[1:])) for w in words))
    return next(name for name, s in CLASSES.items() if s == shape)


def make(context: dict, args: dict, rng: np.random.Generator, n: int) -> list:
    terms = class_terms(context, args)
    field, width = context["field"], context["term_width"]
    names = sorted(CLASSES)
    out = []
    for name in rng.choice(names, size=n):  # equal shares
        occur, classes = CLASSES[name]
        ids: list = []
        for cls in classes:  # distinct terms, each uniform in its class
            t = int(rng.choice(terms[cls]))
            while t in ids:
                t = int(rng.choice(terms[cls]))
            ids.append(t)
        words = [f"w{t:0{width}d}" for t in ids]
        if len(words) == 2:
            clauses = [{"term": {field: w}} for w in words]
        else:
            clauses = [{"term": {field: words[0]}},
                       {"match": {field: " ".join(words[1:])}}]
        body = {"query": {"bool": {occur: clauses}},
                "size": args["size"], "_source": False}
        out.append(json.dumps(body, separators=(",", ":")).encode())
    return out
