"""The Boolean deployment's reference, bodies and control, without a chip:

- `references/bm25_bool.py` against a hand-worked corpus of a dozen
  passages, class by class: which passages each of luceneutil's eight
  shapes matches (written out by hand below), their order and their scores
  (a scalar loop over the published formula);
- `bodies/bool_classes.py` at `rehearse_docs`: every class's terms lie in
  its document-frequency band with the stop terms left out, the eight
  classes come in equal shares, a request's terms are distinct, and
  `class_of` tells a body's class back from its shape;
- the plain reference in bfloat16 comes out NOT correct under the
  comparison that decides `correct`, by pages as well as by scores; in
  full precision correct;
- `selfcheck.py` `check_forms` holds `BENCHMARK.json` and the new files to
  the contract's forms.

    python3 -m pytest benchmarks/tests -q        (not part of tier-1)
"""

import json
import math
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [HERE, os.path.dirname(HERE)]

from compare import compare_all, reference_body  # noqa: E402
from plugins import load_json, load_plugin  # noqa: E402
from selfcheck import check_forms, small_cell  # noqa: E402

CONFIG = "msmarco-bool-wand"
K1, B = 1.2, 0.75

# a dozen passages over five words: w0 and w1 frequent, w2 and w3 less,
# w4 rare; w9 pads lengths
PASSAGES = [
    "w0 w1 w2",        # 0
    "w0 w1",           # 1
    "w0 w0 w3 w9",     # 2
    "w1 w2 w3",        # 3
    "w0 w4",           # 4
    "w1 w9 w9 w9",     # 5
    "w0 w1 w3",        # 6
    "w2 w9",           # 7
    "w0 w9",           # 8
    "w1 w1 w4 w2",     # 9
    "w9 w9",           # 10
    "w0 w2 w3 w1 w1",  # 11
]


def term(w):
    return {"term": {"body": w}}


# shape -> (query, the passages that match, by hand)
SHAPES = {
    "AndHighHigh": ({"must": [term("w0"), term("w1")]}, {0, 1, 6, 11}),
    "AndHighMed": ({"must": [term("w0"), term("w2")]}, {0, 11}),
    "AndHighLow": ({"must": [term("w1"), term("w4")]}, {9}),
    "AndHighLow_no_hit": ({"must": [term("w3"), term("w4")]}, set()),
    "OrHighHigh": ({"should": [term("w0"), term("w1")]},
                   {0, 1, 2, 3, 4, 5, 6, 8, 9, 11}),
    "OrHighMed": ({"should": [term("w0"), term("w3")]},
                  {0, 1, 2, 3, 4, 6, 8, 11}),
    "OrHighLow": ({"should": [term("w1"), term("w4")]},
                  {0, 1, 3, 4, 5, 6, 9, 11}),
    # passage 3 holds both words of the disjunction and not w0: no hit
    "AndHighOrMedMed": ({"must": [term("w0"),
                                  {"match": {"body": "w2 w3"}}]},
                        {0, 2, 6, 11}),
    "AndMedOrHighHigh": ({"must": [term("w2"),
                                   {"match": {"body": "w0 w1"}}]},
                         {0, 3, 9, 11}),
    "nested_bool_is_the_match": ({"must": [term("w2"), {"bool": {
        "should": [term("w0"), term("w1")]}}]}, {0, 3, 9, 11}),
    "should_beside_must_only_scores": ({"must": [term("w4")],
                                        "should": [term("w0")]}, {4, 9}),
}


def raw_postings():
    tokens = [[int(w[1:]) for w in p.split()] for p in PASSAGES]
    n, vocab = len(tokens), 10
    post_start, post_doc, post_tf = [0], [], []
    for t in range(vocab):
        for d, ws in enumerate(tokens):
            if t in ws:
                post_doc.append(d)
                post_tf.append(ws.count(t))
        post_start.append(len(post_doc))
    return {"field": "body", "docs": n,
            "lengths": np.array([len(ws) for ws in tokens], np.int64),
            "post_start": np.array(post_start, np.int64),
            "post_doc": np.array(post_doc, np.int32),
            "post_tf": np.array(post_tf, np.int32)}


def scalar_score(words, d):
    """BM25 of the `words` passage `d` holds, by the published formula
    (lengths under 24 tokens are their own SmallFloat byte)."""
    tokens = [p.split() for p in PASSAGES]
    n = len(tokens)
    avgdl = sum(map(len, tokens)) / n
    total = 0.0
    for w in words:
        tf = tokens[d].count(w)
        if tf:
            df = sum(w in ws for ws in tokens)
            idf = math.log(1.0 + (n - df + 0.5) / (df + 0.5))
            total += idf * tf / (
                tf + K1 * (1.0 - B + B * len(tokens[d]) / avgdl))
    return total


def words_of(query):
    out = []
    for clauses in query.values():
        for clause in clauses:
            (kind, inner), = clause.items()
            if kind == "bool":
                out += words_of(inner)
            else:
                out += inner["body"].split()
    return out


@pytest.fixture(scope="module")
def reference():
    return load_plugin("references", "bm25_bool").Reference(
        raw_postings(), {"guarantees": {"bm25_k1": K1, "bm25_b": B}})


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_reference_on_the_hand_worked_corpus(reference, shape):
    query, want = SHAPES[shape]
    got = reference.answer({"query": {"bool": query}, "size": 12})
    hits = [(int(h["_id"]), h["_score"]) for h in got["hits"]["hits"]]
    assert {d for d, _s in hits} == want
    assert got["hits"]["total"] == {"value": len(want), "relation": "eq"}
    scored = {d: scalar_score(words_of(query), d) for d in want}
    for d, s in hits:
        assert abs(s - scored[d]) <= 1e-12 * max(1.0, scored[d])
    # score descending, then passage ascending
    assert [d for d, _s in hits] == sorted(want, key=lambda d: (-scored[d], d))
    # a page shorter than the matches is their head
    page = reference.answer({"query": {"bool": query}, "size": 2})
    assert [int(h["_id"]) for h in page["hits"]["hits"]] == [
        d for d, _s in hits[:2]]


@pytest.mark.parametrize("query", [
    {"bool": {"must_not": [{"term": {"body": "w0"}}]}},
    {"bool": {"must": [{"term": {"body": "w0"}}],
              "minimum_should_match": 1}},
    {"bool": {"must": [{"match_phrase": {"body": "w0 w1"}}]}},
    {"bool": {"must": [{"term": {"title": "w0"}}]}},
    {"bool": {"must": [{"bool": {"must": [{"bool": {"must": [
        {"term": {"body": "w0"}}]}}]}}]}},
    {"match": {"body": "w0"}},
], ids=["must_not", "minimum_should_match", "phrase", "another_field",
        "two_levels", "no_bool"])
def test_reference_raises_outside_its_semantics(reference, query):
    with pytest.raises(ValueError):
        reference.answer({"query": query, "size": 10})


def test_classes_at_rehearse_docs():
    config = load_json("configs", f"{CONFIG}.json")
    passage = load_json("configs", "msmarco-passage-bm25.json")
    assert config["corpus"]["args"] == passage["corpus"]["args"]
    docs, args = int(config["rehearse_docs"]), config["body"]["args"]
    corpus = load_plugin("corpora", config["corpus"]["builder"]).build(
        config, 1, docs)
    ctx = corpus["body_context"]
    df = ctx["term_df"]
    assert (df == np.bincount(  # from the raw posting stream
        np.repeat(np.arange(len(df)), df), minlength=len(df))).all()
    assert int(df.sum()) == len(corpus["reference"]["post_doc"])
    gen = load_plugin("bodies", config["body"]["generator"])
    terms = gen.class_terms(ctx, args)
    stop = set(np.argsort(-df, kind="stable")[:args["stop_terms"]].tolist())
    for name, (lo, hi) in args["df_share"].items():
        ids = terms[name]
        assert len(ids) >= 30 and not stop & set(ids.tolist()), name
        assert (df[ids] >= lo * docs).all()
        assert hi is None or (df[ids] < hi * docs).all()
    raw = gen.make(ctx, args, np.random.default_rng(4), 4000)
    bodies = [json.loads(b) for b in raw]
    assert all(b["size"] == 10 and b["_source"] is False
               and set(b) == {"query", "size", "_source"} for b in bodies)
    classes = [gen.class_of(b, ctx["field"], terms) for b in bodies]
    share = {c: classes.count(c) / len(classes) for c in gen.CLASSES}
    assert all(0.10 < s < 0.15 for s in share.values()), share
    for body, cls in zip(bodies, classes):
        (occur, clauses), = body["query"]["bool"].items()
        assert occur == ("should" if cls.startswith("Or") else "must")
        words = [w for c in clauses for v in c.values()
                 for w in v["body"].split()]
        assert len(set(words)) == len(words) == len(gen.CLASSES[cls][1])
        assert [next(iter(c)) for c in clauses] == (
            ["term", "term"] if len(words) == 2 else ["term", "match"])


@pytest.mark.parametrize("seed", [1, 2147483900, 3000000007])
def test_lower_precision_fails_and_full_precision_passes(seed):
    config, ref, bodies = small_cell(CONFIG, 20_000, seed, 128)
    g = config["guarantees"]
    refs = ref.answer_many([reference_body(g["rule"], b) for b in bodies])
    sound = compare_all(g, bodies, ref.answer_many(bodies), refs)
    assert sound["correct"], sound
    control = compare_all(
        g, bodies, ref.answer_many(bodies, precision="lower"), refs)
    assert not control["correct"], control
    value, _rel, limit = control["numbers"]["score_rel_max"]
    assert value > 10 * limit, control
    assert control["numbers"]["page_mismatches"][0] >= 1, control
    assert control["numbers"]["total_mismatches"][0] == 0, control


def test_forms_hold_with_the_new_files():
    check_forms()
    with open(os.path.join(os.path.dirname(HERE), "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"]
                if w["name"] == f"{CONFIG}.solo")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "solo", 1)
    listed = {m["name"] for kind in ("end_to_end", "per_layer")
              for m in bench[kind] if cell["name"] in m.get("workloads", [])}
    assert listed == {
        "latency_p50_ms", "latency_p95_ms", "fan_out_ms", "shard_search_ms",
        "rare_slots_scattered_share", "serve_fallback_share",
        "dense_rows_held_share", "serve_kernel_ms", "serve_fused_roofline"}
    spec = load_json("layer_metrics", "unplanned_query_share.json")
    assert spec["reader"] == "count_ratio"
    reader = load_plugin("readers", "count_ratio")
    counts = {"thread_pool.search.completed": 200}
    assert reader.read({"counts": counts}, spec["args"]) is None  # the parent
    counts[spec["args"]["num"]] = 0
    assert reader.read({"counts": counts}, spec["args"]) == 0.0
    counts[spec["args"]["num"]] = 50
    assert reader.read({"counts": counts}, spec["args"]) == 25.0
