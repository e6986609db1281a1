"""Filtered kNN bodies shaped as the queries of big-ann-benchmarks'
filter track are (`yfcc-10M`: a uint8 vector and one or two tags that
must all be present): a `knn` section over the `byte` field with a
`filter` of `term` clauses on the tag field, `{"knn": {"field": ...,
"query_vector": [192 ints], "k": ..., "num_candidates": ..., "filter":
{"bool": {"filter": [{"term": {tags: "t000017"}}, ...]}}}, "size": ...,
"_source": false}`, ~0.9 KB.

The vector's components follow the corpus's own law (a clipped normal
about the middle of the byte range) and are written as the integers
uint8 - 128 that the `byte` field stores. The tags are one stored bag's
own (as the source built its queries from the query image's own tags):
a bag drawn uniformly among those that hold enough tags, then one tag
of it (with probability `one_tag_share`) or two distinct ones, drawn
uniformly; so a tag is asked for in proportion to the rows that carry
it, and at least one row passes every filter."""

from __future__ import annotations

import json

import numpy as np

from plugins import load_plugin


def make(context: dict, args: dict, rng: np.random.Generator, n: int) -> list:
    corpus = load_plugin("corpora", "byte_vectors_tags")
    q = corpus.draw_bytes(rng, context["components"],
                          (n, int(context["dims"]))).astype(np.int16) - 128
    want = np.where(rng.random(n) < float(args["one_tag_share"]), 1, 2)
    start, tags = context["bag_start"], context["bag_tags"]
    sizes = np.diff(start)
    width = context["tag_width"]
    head = ('{"knn":{"field":%s,"k":%d,"num_candidates":%d,"query_vector":['
            % (json.dumps(context["field"]), args["k"],
               args["num_candidates"]))
    out = []
    for row, k in zip(q.tolist(), want.tolist()):
        bag = int(rng.integers(len(sizes)))
        while sizes[bag] < k:
            bag = int(rng.integers(len(sizes)))
        picked = rng.choice(tags[start[bag]:start[bag + 1]], size=k,
                            replace=False)
        clauses = [{"term": {context["tag_field"]: f"t{int(t):0{width}d}"}}
                   for t in picked]
        out.append((head + ",".join(map(str, row)) + '],"filter":'
                    + json.dumps({"bool": {"filter": clauses}},
                                 separators=(",", ":"))
                    + '},"size":%d,"_source":false}' % args["size"]).encode())
    return out
