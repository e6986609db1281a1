"""`bool` bodies in luceneutil's negated and filtered Boolean task
classes beside two plain ones (`tasks/wikimedium.10M.nostopwords.tasks`
and the `Filtered*` classes of the nightly task file), the ten in equal
shares:

  OrHighNotHigh, OrHighNotMed, OrHighNotLow   `high -x`: an optional
      High term and a prohibited one of the named class;
  OrNotHighLow                                `low -high`;
  FilteredOrHighHigh, FilteredOrHighMed       `a b` under a filter;
  FilteredAndHighHigh, FilteredAndHighMed     `+a +b` under a filter;
  OrHighMed, AndHighMed                       `bodies/bool_classes.py`'s
      forms, unchanged.

A term's class is its document frequency on this shard
(`bodies/bool_classes.py` `class_terms`, loaded by name: the same cuts
and stop terms as the plain Boolean cell's). The terms of one request
are distinct and drawn uniformly inside their class. In the
Elasticsearch DSL an optional term is a `term` clause under `should`, a
required one under `must`, a prohibited one under `must_not`; the filter
is a `term` on the keyword field under `filter`, its tag drawn uniformly
from ONE stored passage's own tags (a bag uniform among those that hold
a tag), so a tag is asked for in proportion to the passages that carry
it and at least one passage passes every filter. A should-only `bool`
beside a `filter` states `minimum_should_match: 1` (the default there is
0: every passing passage would match, at score 0). Nothing in a body
names its class: `class_of` tells it from the body's shape and its
terms' classes.
"""

from __future__ import annotations

import json

import numpy as np

from plugins import load_plugin

# name -> (occurrence of the scoring terms, their classes, the class of
# the prohibited term or None, whether a tag filters)
CLASSES = {
    "OrHighNotHigh": ("should", ("High",), "High", False),
    "OrHighNotMed": ("should", ("High",), "Med", False),
    "OrHighNotLow": ("should", ("High",), "Low", False),
    "OrNotHighLow": ("should", ("Low",), "High", False),
    "FilteredOrHighHigh": ("should", ("High", "High"), None, True),
    "FilteredOrHighMed": ("should", ("High", "Med"), None, True),
    "FilteredAndHighHigh": ("must", ("High", "High"), None, True),
    "FilteredAndHighMed": ("must", ("High", "Med"), None, True),
    "OrHighMed": ("should", ("High", "Med"), None, False),
    "AndHighMed": ("must", ("High", "Med"), None, False),
}


def class_terms(context: dict, args: dict) -> dict:
    return load_plugin("bodies", "bool_classes").class_terms(context, args)


def class_of(body: dict, field: str, terms: dict) -> str:
    """The task class of one body this generator made, `terms` being
    `class_terms` of the same context and arguments."""
    def of_term(word: str) -> str:
        t = int(word[1:])
        for name, ids in terms.items():  # ids ascend
            i = int(np.searchsorted(ids, t))
            if i < len(ids) and ids[i] == t:
                return name
        raise ValueError(f"term {t} is of no class")

    q = body["query"]["bool"]
    occur = "must" if "must" in q else "should"
    shape = (
        occur,
        tuple(of_term(c["term"][field]) for c in q[occur]),
        of_term(q["must_not"][0]["term"][field]) if "must_not" in q else None,
        "filter" in q,
    )
    return next(name for name, s in CLASSES.items() if s == shape)


def make(context: dict, args: dict, rng: np.random.Generator, n: int) -> list:
    terms = class_terms(context, args)
    field, width = context["field"], context["term_width"]
    start, tags = context["bag_start"], context["bag_tags"]
    sizes = np.diff(start)
    names = sorted(CLASSES)
    out = []
    for name in rng.choice(names, size=n):  # equal shares
        occur, classes, negated, filtered = CLASSES[name]
        ids: list = []
        for cls in classes + ((negated,) if negated else ()):
            t = int(rng.choice(terms[cls]))  # distinct, uniform in its class
            while t in ids:
                t = int(rng.choice(terms[cls]))
            ids.append(t)
        words = [f"w{t:0{width}d}" for t in ids]
        q = {occur: [{"term": {field: w}} for w in words[:len(classes)]]}
        if negated:
            q["must_not"] = [{"term": {field: words[-1]}}]
        if filtered:
            if occur == "should":
                q["minimum_should_match"] = 1
            bag = int(rng.integers(len(sizes)))
            while not sizes[bag]:
                bag = int(rng.integers(len(sizes)))
            tag = int(rng.choice(tags[start[bag]:start[bag + 1]]))
            q["filter"] = [{"term": {
                context["tag_field"]: f"t{tag:0{context['tag_width']}d}"}}]
        body = {"query": {"bool": q}, "size": args["size"], "_source": False}
        out.append(json.dumps(body, separators=(",", ":")).encode())
    return out
