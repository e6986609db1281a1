"""The filtered and negated Boolean deployment's files, without a chip:

- `references/bm25_bool_filtered.py` against a hand-worked corpus of a
  dozen tagged passages, shape by shape: which passages each of
  luceneutil's negated and filtered shapes matches (written out by hand
  below), their order and their scores (a scalar loop over the published
  formula): a `filter` and a `must_not` mask and do not score;
- `corpora/zipf_text_df_tags.py`: the text is `zipf_text_df`'s to the
  posting, the tags follow the stated law, move with their bags and keep
  their document frequencies whatever the seed, and the program's tiled
  tag field holds the same (passage, tag) pairs as the raw bags the
  reference is handed;
- `bodies/bool_filter_classes.py` at `rehearse_docs`: ten classes in
  equal shares, the text terms in their document-frequency bands, a tag
  asked for in proportion to its postings, `class_of` tells a body's
  class back;
- the plain reference in bfloat16 comes out NOT correct under the
  comparison that decides `correct` (by scores; the masks do not move, so
  totals stay equal); in full precision correct;
- the new metrics' readers on made-up counters; `selfcheck.py`
  `check_forms`;
- the rehearsal of the new cell runs whole on the CPU (a child process)
  and is `correct`, its control is not; with the filter ignored and with
  the `must_not` ignored underneath the timed path it is not.

    python3 -m pytest benchmarks/tests -q        (not part of tier-1)
"""

import json
import math
import os
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [HERE, ROOT]

from compare import compare_all, reference_body  # noqa: E402
from plugins import load_json, load_plugin  # noqa: E402
from selfcheck import check_forms, small_cell  # noqa: E402

CONFIG = "msmarco-filtered-bool"
CELL = f"{CONFIG}.solo"
K1, B = 1.2, 0.75

# a dozen passages over five words (w9 pads lengths), each with a bag of
# tags (three tags; a passage may carry several, or none)
PASSAGES = [
    ("w0 w1 w2", (0,)),        # 0
    ("w0 w1", (1,)),           # 1
    ("w0 w0 w3 w9", (0, 1)),   # 2
    ("w1 w2 w3", (2,)),        # 3
    ("w0 w4", (0,)),           # 4
    ("w1 w9 w9 w9", (1,)),     # 5
    ("w0 w1 w3", (0, 2)),      # 6
    ("w2 w9", (1,)),           # 7
    ("w0 w9", (2,)),           # 8
    ("w1 w1 w4 w2", (0,)),     # 9
    ("w9 w9", ()),             # 10
    ("w0 w2 w3 w1 w1", (1,)),  # 11
]


def term(w):
    return {"term": {"body": w}}


def tag(v):
    return {"term": {"tag": f"t{v}"}}


# shape -> (query, the passages that match, by hand, the words that score)
SHAPES = {
    "OrHighNotHigh": ({"should": [term("w0")], "must_not": [term("w1")]},
                      {2, 4, 8}, ["w0"]),
    "OrHighNotLow": ({"should": [term("w0")], "must_not": [term("w4")]},
                     {0, 1, 2, 6, 8, 11}, ["w0"]),
    "not_a_match_of_two_words_is_neither": (
        {"should": [term("w0")],
         "must_not": [{"match": {"body": "w1 w3"}}]}, {4, 8}, ["w0"]),
    "FilteredAndHighHigh": ({"must": [term("w0"), term("w1")],
                             "filter": [tag(1)]}, {1, 11}, ["w0", "w1"]),
    "FilteredAnd_no_hit": ({"must": [term("w3"), term("w4")],
                            "filter": [tag(0)]}, set(), ["w3", "w4"]),
    "FilteredOrHighMed": (
        {"should": [term("w0"), term("w3")], "minimum_should_match": 1,
         "filter": [tag(0)]}, {0, 2, 4, 6}, ["w0", "w3"]),
    "Filtered_msm_2": (
        {"should": [term("w0"), term("w3")], "minimum_should_match": 2,
         "filter": [tag(0)]}, {2, 6}, ["w0", "w3"]),
    "filter_no_passage_holds": ({"must": [term("w0")],
                                 "filter": [tag(7)]}, set(), ["w0"]),
    "two_filters_need_both_tags": (
        {"must": [term("w0")], "filter": [tag(0), tag(1)]}, {2}, ["w0"]),
    "terms_filter_is_any_of_its_tags": (
        {"must": [term("w0")],
         "filter": [{"terms": {"tag": ["t1", "t2"]}}]},
        {1, 2, 6, 8, 11}, ["w0"]),
    "filter_and_must_not": ({"must": [term("w0")], "filter": [tag(0)],
                             "must_not": [term("w3")]}, {0, 4}, ["w0"]),
    "should_beside_a_filter_only_scores": (
        {"should": [term("w4")], "filter": [tag(0)]},
        {0, 2, 4, 6, 9}, ["w4"]),
    "should_beside_must_only_scores": (
        {"must": [term("w2")], "should": [term("w0")],
         "must_not": [term("w3")]}, {0, 7, 9}, ["w2", "w0"]),
}


def raw_data():
    tokens = [[int(w[1:]) for w in p.split()] for p, _t in PASSAGES]
    n, vocab = len(tokens), 10
    post_start, post_doc, post_tf = [0], [], []
    for t in range(vocab):
        for d, ws in enumerate(tokens):
            if t in ws:
                post_doc.append(d)
                post_tf.append(ws.count(t))
        post_start.append(len(post_doc))
    bags = [tags for _p, tags in PASSAGES]
    # bag i lives in passage bag_row[i]: stored out of order on purpose
    bag_row = np.array([5, 3, 0, 1, 2, 4, 11, 10, 9, 8, 7, 6], np.int32)
    stored = [bags[int(r)] for r in bag_row]
    return {"field": "body", "docs": n, "tag_field": "tag", "tag_width": 1,
            "bag_start": np.cumsum([0] + [len(b) for b in stored]).astype(
                np.int64),
            "bag_tags": np.array([t for b in stored for t in b], np.int32),
            "bag_row": bag_row,
            "lengths": np.array([len(ws) for ws in tokens], np.int64),
            "post_start": np.array(post_start, np.int64),
            "post_doc": np.array(post_doc, np.int32),
            "post_tf": np.array(post_tf, np.int32)}


def scalar_score(words, d):
    """BM25 of the `words` passage `d` holds, by the published formula
    (lengths under 24 tokens are their own SmallFloat byte)."""
    tokens = [p.split() for p, _t in PASSAGES]
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


@pytest.fixture(scope="module")
def reference():
    return load_plugin("references", "bm25_bool_filtered").Reference(
        raw_data(), {"guarantees": {"bm25_k1": K1, "bm25_b": B}})


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_reference_on_the_hand_worked_corpus(reference, shape):
    query, want, scoring_words = SHAPES[shape]
    got = reference.answer({"query": {"bool": query}, "size": 12})
    hits = [(int(h["_id"]), h["_score"]) for h in got["hits"]["hits"]]
    assert {d for d, _s in hits} == want
    assert got["hits"]["total"] == {"value": len(want), "relation": "eq"}
    # a filter and a must_not score nothing: only these words do
    scored = {d: scalar_score(scoring_words, d) for d in want}
    for d, s in hits:
        assert abs(s - scored[d]) <= 1e-12 * max(1.0, scored[d])
    assert [d for d, _s in hits] == sorted(want, key=lambda d: (-scored[d], d))
    page = reference.answer({"query": {"bool": query}, "size": 2})
    assert [int(h["_id"]) for h in page["hits"]["hits"]] == [
        d for d, _s in hits[:2]]


def test_reference_is_the_unfiltered_one_where_nothing_masks(reference):
    plain = load_plugin("references", "bm25_bool").Reference(
        raw_data(), {"guarantees": {"bm25_k1": K1, "bm25_b": B}})
    for q in ({"must": [term("w0"), term("w1")]},
              {"should": [term("w0"), term("w3")]},
              {"must": [term("w2"), {"match": {"body": "w0 w1"}}]}):
        body = {"query": {"bool": q}, "size": 12}
        assert reference.answer(body) == plain.answer(body)


@pytest.mark.parametrize("query", [
    {"bool": {"must_not": [term("w0")]}},
    {"bool": {"filter": [tag(0)]}},
    {"bool": {"must": [term("w0")],
              "filter": [{"range": {"tag": {"gte": "t0"}}}]}},
    {"bool": {"must": [term("w0")], "filter": [term("w1")]}},
    {"bool": {"must": [term("w0")],
              "must_not": [{"match_phrase": {"body": "w0 w1"}}]}},
    {"bool": {"must": [term("w0")], "must_not": [tag(0)]}},
    {"bool": {"must": [term("w0")], "filter": [tag(0)], "boost": 2.0}},
], ids=["must_not_alone", "filter_alone", "range_filter",
        "filter_on_the_text_field", "must_not_of_a_phrase",
        "must_not_of_a_tag", "a_key_it_does_not_know"])
def test_reference_raises_outside_its_semantics(reference, query):
    with pytest.raises(ValueError):
        reference.answer({"query": query, "size": 10})


@pytest.fixture(scope="module")
def rehearsal():
    config = load_json("configs", f"{CONFIG}.json")
    build = load_plugin("corpora", config["corpus"]["builder"]).build
    docs = int(config["rehearse_docs"])
    return config, docs, build(config, 1, docs), build(config, 2, docs)


def pairs(ref: dict) -> np.ndarray:
    """The sorted (tag, passage) keys of a reference's raw bags."""
    sizes = np.diff(ref["bag_start"])
    row = np.repeat(ref["bag_row"].astype(np.int64), sizes)
    return np.sort(ref["bag_tags"].astype(np.int64) * ref["docs"] + row)


def test_corpus_keeps_the_text_and_tags_the_passages(rehearsal):
    config, docs, one, two = rehearsal
    wand = load_json("configs", "msmarco-bool-wand.json")
    args = dict(config["corpus"]["args"])
    law = args.pop("tags")
    assert args == wand["corpus"]["args"]  # the text, unchanged
    text = load_plugin("corpora", "zipf_text_df").build(wand, 1, docs)
    for key in ("post_start", "post_doc", "post_tf", "lengths"):
        assert (one["reference"][key] == text["reference"][key]).all(), key
    assert (one["body_context"]["term_df"]
            == text["body_context"]["term_df"]).all()
    assert one["mappings"]["properties"] == {
        "body": {"type": "text"}, "tag": {"type": "keyword"}}
    ref = one["reference"]
    sizes = np.diff(ref["bag_start"])
    assert len(sizes) == docs and abs(sizes.mean() - 3.0) < 0.15
    df = np.bincount(ref["bag_tags"], minlength=law["vocab"])
    assert len(df) == law["vocab"] and df.min() >= 1
    top = np.sort(df)[::-1]
    assert abs(top[0] / docs - law["df_law"]["max_share"]) < 0.03
    assert top[9] < top[0] / 5 and top[99] < top[9] / 5  # ~ C / (rank + q)
    # the program's tiled field holds the raw bags' pairs, ids ascending
    pf = one["segment"].postings["tag"]
    assert int(pf.term_df.sum()) == int(sizes.sum()) == len(ref["bag_tags"])
    assert int(pf.tfs.max()) == 1 and (pf.term_df == df).all()
    key = pairs(ref)
    for tid in (int(np.argmax(df)), int(np.argsort(-df)[50]),
                int(np.argmin(df))):
        mine = key[(key // docs) == tid] % docs
        assert (pf.term_docs(pf.term_id(f"t{tid:04d}")) == mine).all()
    # another seed moves the bags to other passages: every tag's document
    # frequency stays (the layout's shapes, and which tags hold a bit
    # row, do not move)
    other = two["reference"]
    assert (other["bag_tags"] == ref["bag_tags"]).all()
    assert not (other["bag_row"] == ref["bag_row"]).all()
    assert (two["segment"].postings["tag"].term_tile_count
            == pf.term_tile_count).all()
    # independent of the words: a common word's passages carry the
    # commonest tag at its share of the shard
    t = int(np.argmax(np.diff(ref["post_start"])))
    holders = ref["post_doc"][ref["post_start"][t]:ref["post_start"][t + 1]]
    tagged = np.zeros(docs, bool)
    tagged[key[(key // docs) == int(np.argmax(df))] % docs] = True
    assert abs(tagged[holders].mean() - df.max() / docs) < 0.01


def test_classes_at_rehearse_docs(rehearsal):
    config, docs, corpus, _two = rehearsal
    ctx, args = corpus["body_context"], config["body"]["args"]
    wand = load_json("configs", "msmarco-bool-wand.json")
    assert args == wand["body"]["args"]  # the same cuts and stop terms
    gen = load_plugin("bodies", config["body"]["generator"])
    assert len(gen.CLASSES) == 10
    terms = gen.class_terms(ctx, args)
    raw = gen.make(ctx, args, np.random.default_rng(4), 5000)
    bodies = [json.loads(b) for b in raw]
    assert all(b["size"] == 10 and b["_source"] is False
               and set(b) == {"query", "size", "_source"} for b in bodies)
    assert 100 <= min(map(len, raw)) and max(map(len, raw)) <= 260
    classes = [gen.class_of(b, ctx["field"], terms) for b in bodies]
    share = {c: classes.count(c) / len(classes) for c in gen.CLASSES}
    assert all(0.08 < s < 0.12 for s in share.values()), share
    df = np.bincount(corpus["reference"]["bag_tags"], minlength=10_000)
    asked = np.zeros(10_000)
    for body, cls in zip(bodies, classes):
        q = body["query"]["bool"]
        occur, bands, negated, filtered = gen.CLASSES[cls]
        words = [c["term"]["body"] for c in q[occur]]
        if negated:
            words.append(q["must_not"][0]["term"]["body"])
        assert len(set(words)) == len(words)
        for w, band in zip(words, bands + ((negated,) if negated else ())):
            assert int(w[1:]) in terms[band]
        want = {occur} | ({"must_not"} if negated else set()) | (
            {"filter"} if filtered else set()) | (
            {"minimum_should_match"} if filtered and occur == "should"
            else set())
        assert set(q) == want, cls
        if filtered:
            (f,) = q["filter"]
            asked[int(f["term"]["tag"][1:])] += 1
    # a tag is asked for in proportion to the passages that carry it:
    # the commonest tags (those that hold a bit row at the deployment's
    # size, df >= docs / 128) take their postings' share of the filters
    common = df >= docs / 128
    assert abs(asked[common].sum() / asked.sum()
               - df[common].sum() / df.sum()) < 0.05
    assert asked[df == 0].sum() == 0


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
    assert control["numbers"]["total_mismatches"][0] == 0, control


NEW_METRICS = ("bool_filter_mask_ms", "bool_filter_pass_share",
               "bool_filter_bitset_terms_share",
               "bool_excluded_tiles_per_req", "bool_filtered_fallback_share")


def test_new_metrics_read_the_new_counters_and_nothing_at_the_parent():
    parent = {"thread_pool.search.completed": 132,
              "knn_filtered.searches": 0}
    counts = {**parent, "serve_filtered.searches": 80,
              "serve_filtered.mask_launches": 40,
              "serve_filtered.filter_terms": 40,
              "serve_filtered.bitset_terms": 17,
              "serve_filtered.filter_tiles": 300,
              "serve_filtered.rows_scanned": 40_000_000,
              "serve_filtered.rows_passed": 3_000_000,
              "serve_filtered.excluded_terms": 40,
              "serve_filtered.excluded_tiles": 500,
              "serve_filtered.fallbacks": 0}
    want = {"bool_filter_pass_share": 7.5,
            "bool_filter_bitset_terms_share": 42.5,
            "bool_excluded_tiles_per_req": 6.25,
            "bool_filtered_fallback_share": 0.0}
    for name in NEW_METRICS:
        spec = load_json("layer_metrics", f"{name}.json")
        read = load_plugin("readers", spec["reader"]).read
        obs = {"counts": counts, "spans_ms": {"filter_mask": [0.05, 0.07, 0.3]}}
        at_parent = {"counts": parent, "spans_ms": {}}
        assert read(at_parent, spec["args"]) is None, name
        if name in want:
            assert read(obs, spec["args"]) == pytest.approx(want[name]), name
        else:
            assert read(obs, spec["args"]) == 0.07


def test_forms_hold_with_the_new_files():
    check_forms()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "solo", 1)
    config = load_json("configs", f"{CONFIG}.json")
    assert next(c for c in bench["configs"] if c["name"] == CONFIG)[
        "source"] == config["source"]
    listed = {m["name"] for kind in ("end_to_end", "per_layer")
              for m in bench[kind] if CELL in m.get("workloads", [])}
    assert listed >= {
        "latency_p50_ms", "latency_p95_ms", "fan_out_ms", "shard_search_ms",
        "plan_ms", "fan_out_handover_ms", "fan_out_inline_share",
        "serve_fallback_share", "dense_rows_held_share", "serve_kernel_ms",
        "serve_fused_roofline", "rare_slots_scattered_share", *NEW_METRICS}
    for name in NEW_METRICS:
        (m,) = [m for m in bench["per_layer"] if m["name"] == name]
        assert m["workloads"] == [CELL]
    assert len(config["source"]) <= 200 and "\n" not in config["source"]
    assert set(config["reduced"]) == {"docs", "ingest", "fields"}
    assert config["docs"] == 1_000_000 and len(config["assumed"]) >= 6
    assert config["body"]["args"]["fields"] == ["body"]


DRIVER = r"""
import json, sys
sys.path[:0] = [{bench!r}, {root!r}]
import run
dropped = {dropped!r}
if dropped:
    # the occurrence is ignored where the plan is made: the timed path
    # answers as if the request had not carried it
    from elasticsearch_tpu.search import dsl
    real = dsl.parse_query

    def ignoring(body, *args, **kwargs):
        q = real(body, *args, **kwargs)
        if isinstance(q, dsl.BoolQuery):
            setattr(q, dropped, [])
        return q

    dsl.parse_query = ignoring
result = run.run_cell({cell!r}, seed=2147483748, seconds=4.0, trace=False,
                      rehearse=True)
print("RESULT " + json.dumps(result))
"""


def drive(dropped: str) -> dict:
    code = DRIVER.format(bench=HERE, root=ROOT, dropped=dropped, cell=CELL)
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("ES_TPU_")}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run([sys.executable, "-c", code], env=env, text=True,
                       stdout=subprocess.PIPE, timeout=900)
    assert p.returncode == 0, p.stdout[-2000:]
    line = [ln for ln in p.stdout.splitlines() if ln.startswith("RESULT ")]
    return json.loads(line[-1][len("RESULT "):])


@pytest.mark.parametrize("dropped", ["", "filter", "must_not"],
                         ids=["sound", "filter_ignored", "must_not_ignored"])
def test_rehearsal_is_correct_and_a_broken_path_is_caught(dropped):
    result = drive(dropped)
    assert result["attempted"] > 0 and result["failed"] == 0, result
    assert result["checks"]["answers_checked"][0] >= 32, result
    assert result["correct"] is (not dropped), result
    if dropped:
        # an excluded word's passages are few among a common word's: the
        # totals always tell, the first ten ranks only now and then
        assert result["checks"]["total_mismatches"][0] > 0, result
        if dropped == "filter":
            assert result["checks"]["page_mismatches"][0] > 0, result


def test_rehearsal_runs_whole_and_its_control_fails():
    """`run.py --rehearse --control 1 --trace 1` of the new cell, as the
    sandbox can run it: exit 3, no result line, the would-be result
    `correct` with nothing failed or built in the window, the control NOT
    correct, the new counters' metrics read, both mask forms engaged."""
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("ES_TPU_") and k != "PYTHONHASHSEED"}
    env["JAX_PLATFORMS"] = "cpu"
    run = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload", CELL,
         "--seed", "2147483999", "--seconds", "4", "--trace", "1",
         "--rehearse", "--control", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert run.returncode == 3, run.stderr[-2000:]
    assert "control correct = False" in run.stdout
    line = next(ln for ln in run.stderr.splitlines()
                if ln.startswith("REHEARSAL on "))
    result = json.loads(line[line.index("no result: ") + len("no result: "):])
    assert result["correct"] is True and result["failed"] == 0
    m = {k: v["value"] for k, v in result["metrics"].items()}
    assert m["unplanned_query_share"] == 0.0
    assert m["serve_fallback_share"] == 0.0
    assert m["bool_filtered_fallback_share"] == 0.0
    assert m["fan_out_inline_share"] == 100.0
    assert m["launch_width"] == 1.0 and m["host_syncs_per_req"] == 1.0
    assert 0.0 < m["bool_filter_bitset_terms_share"] < 100.0
    assert 0.0 < m["bool_filter_pass_share"] < 100.0
    assert m["bool_excluded_tiles_per_req"] > 0
    assert m["bool_filter_mask_ms"] > 0
