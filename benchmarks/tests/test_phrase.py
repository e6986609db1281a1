"""The phrase deployment's reference, bodies, corpus and control, without
a chip:

- `references/bm25_phrase.py` against a hand-worked corpus of a dozen
  passages: which passages hold each phrase and how often (written out
  by hand below), their order and their scores (a scalar loop over the
  published formula: phrase frequency as tf, summed idf);
- `corpora/zipf_text_ordered.py` at `rehearse_docs`: the text law is
  `msmarco-passage-bm25`'s (lengths, vocabulary, and the document
  frequencies of the 500 most frequent terms within 5% of
  `corpora/zipf_text.py`'s), a position for every token in the layout a
  refresh leaves, the collocation law planted what it states;
- `bodies/phrase_classes.py`: every class's phrases are runs of stored
  tokens held by a number of passages inside the class's band, the five
  classes come in equal shares, no word stands twice;
- the plain reference in bfloat16 comes out NOT correct under the
  comparison that decides `correct`; in full precision correct;
- a whole rehearsal of the cell (`run.run_cell(..., rehearse=True)`, a
  process of its own) is `correct`, and with the positions plane shifted
  by one position underneath the program it is not;
- `selfcheck.py` `check_forms` holds `BENCHMARK.json` and the new files
  to the contract's forms; the roofline reader's arithmetic.

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
sys.path[:0] = [HERE, os.path.dirname(HERE)]

from compare import compare_all, reference_body  # noqa: E402
from plugins import load_json, load_plugin  # noqa: E402
from selfcheck import check_forms, small_cell  # noqa: E402

CONFIG = "msmarco-phrase"
K1, B = 1.2, 0.75

PASSAGES = [
    "w0 w1 w2",              # 0
    "w0 w1 w0 w1",           # 1: "w0 w1" twice
    "w1 w0 w3",              # 2: the other order
    "w0 w3 w1",              # 3: both words, not adjacent
    "w2 w0 w1 w4",           # 4
    "w9 w9 w9",              # 5
    "w0 w1 w2 w9 w0 w1 w2",  # 6: "w0 w1 w2" twice
    "w1 w2",                 # 7
    "w4 w0",                 # 8: ends in w0 ...
    "w1 w9",                 # 9: ... and the next passage begins with w1
    "w0 w0 w1",              # 10
    "w3 w4 w0 w1 w2 w3",     # 11
]

# phrase -> {passage: frequency}, by hand
PHRASES = {
    "w0 w1": {0: 1, 1: 2, 4: 1, 6: 2, 10: 1, 11: 1},
    "w1 w0": {1: 1, 2: 1},
    "w0 w1 w2": {0: 1, 6: 2, 11: 1},
    "w1 w2": {0: 1, 6: 2, 7: 1, 11: 1},
    "w3 w4": {11: 1},
    "w4 w0": {8: 1, 11: 1},
    "w2 w4": {},
    "w9 w9": {5: 2},
}


def raw_stream():
    tokens = [[int(w[1:]) for w in p.split()] for p in PASSAGES]
    start = np.r_[0, np.cumsum([len(t) for t in tokens])].astype(np.int64)
    return {"field": "body", "docs": len(tokens), "vocab": 10,
            "tokens": np.asarray([w for t in tokens for w in t], np.int32),
            "doc_start": start,
            "passage_id": np.arange(len(tokens), dtype=np.int64)}


def scalar_score(words, d, f):
    tokens = [p.split() for p in PASSAGES]
    n = len(tokens)
    avgdl = sum(map(len, tokens)) / n
    idf = 0.0
    for w in words:
        df = sum(w in ws for ws in tokens)
        idf += math.log(1.0 + (n - df + 0.5) / (df + 0.5))
    return idf * f / (f + K1 * (1.0 - B + B * len(tokens[d]) / avgdl))


@pytest.fixture(scope="module")
def reference():
    return load_plugin("references", "bm25_phrase").Reference(
        raw_stream(), {"guarantees": {"bm25_k1": K1, "bm25_b": B}})


@pytest.mark.parametrize("phrase", sorted(PHRASES))
def test_reference_on_the_hand_worked_corpus(reference, phrase):
    want = PHRASES[phrase]
    got = reference.answer(
        {"query": {"match_phrase": {"body": phrase}}, "size": 12})
    hits = [(int(h["_id"]), h["_score"]) for h in got["hits"]["hits"]]
    assert {d for d, _s in hits} == set(want)
    assert got["hits"]["total"] == {"value": len(want), "relation": "eq"}
    scored = {d: float(np.float32(scalar_score(phrase.split(), d, f)))
              for d, f in want.items()}
    for d, s in hits:
        assert abs(s - scored[d]) <= 1e-12 * max(1.0, scored[d])
    assert [d for d, _s in hits] == sorted(
        want, key=lambda d: (-scored[d], d))
    page = reference.answer(
        {"query": {"match_phrase": {"body": phrase}}, "size": 2})
    assert [int(h["_id"]) for h in page["hits"]["hits"]] == [
        d for d, _s in hits[:2]]


def test_twice_scores_above_once_in_the_reference(reference):
    got = reference.answer(
        {"query": {"match_phrase": {"body": "w0 w1"}}, "size": 12})
    score = {int(h["_id"]): h["_score"] for h in got["hits"]["hits"]}
    assert score[1] > score[10] > 0  # 4 tokens twice above 3 tokens once


@pytest.fixture(scope="module")
def rehearsal():
    config = load_json("configs", f"{CONFIG}.json")
    docs = int(config["rehearse_docs"])
    corpus = load_plugin("corpora", config["corpus"]["builder"]).build(
        config, 1, docs)
    return config, docs, corpus


def test_corpus_keeps_the_text_law_and_holds_positions(rehearsal):
    config, docs, corpus = rehearsal
    passage = load_json("configs", "msmarco-passage-bm25.json")
    args = config["corpus"]["args"]
    for key in passage["corpus"]["args"]:
        assert args[key] == passage["corpus"]["args"][key], key
    ref = corpus["reference"]
    lengths = np.diff(ref["doc_start"])
    assert lengths.min() >= 8 and lengths.max() <= 256
    assert abs(lengths.mean() - args["length"]["mean"]) < 1.0
    vocab = round(args["vocab_at_source"]
                  * (docs / args["source_docs"]) ** args["heaps_beta"])
    assert ref["vocab"] == vocab
    pf = corpus["segment"].postings["body"]
    assert len(pf.terms) == vocab
    # the bag-of-words corpus of the same law: the frequent terms'
    # document frequencies agree within 5%
    bag = load_plugin("corpora", "zipf_text").build(passage, 1, docs)
    theirs = bag["segment"].postings["body"].term_df[:500].astype(np.float64)
    mine = pf.term_df[:500].astype(np.float64)
    # at this size the rarer of the 500 are held by ~1,000 passages and
    # two independent draws differ by sampling alone: the band is 5% and
    # three standard deviations of that
    room = 0.05 + 3.0 * np.sqrt(2.0 / theirs)
    assert (np.abs(mine / theirs - 1.0) < room).all()
    assert abs(mine[:50].sum() / theirs[:50].sum() - 1.0) < 0.02
    # a position for every token, in the layout a refresh leaves
    total = int(lengths.sum())
    assert len(pf.pos_data) == total == int(pf.term_total_tf.sum())
    assert len(pf.pos_offsets) == int(pf.term_df.sum()) + 1
    assert (np.diff(pf.pos_offsets) == pf.tfs[pf.doc_ids >= 0]).all()
    ids = ref["passage_id"]
    for p in (0, docs // 2, docs - 1):
        toks = ref["tokens"][ref["doc_start"][p]: ref["doc_start"][p + 1]]
        for t in set(toks.tolist()):
            assert pf.doc_positions(t, int(ids[p])).tolist() == (
                np.flatnonzero(toks == t).tolist())
    # a second seed moves passages, not the collection's statistics
    other = load_plugin("corpora", config["corpus"]["builder"]).build(
        config, 2, 20_000)
    again = load_plugin("corpora", config["corpus"]["builder"]).build(
        config, 3, 20_000)
    assert (other["reference"]["tokens"] == again["reference"]["tokens"]).all()
    assert not (other["reference"]["passage_id"]
                == again["reference"]["passage_id"]).all()
    assert (other["segment"].postings["body"].term_df
            == again["segment"].postings["body"].term_df).all()


def test_collocation_law_is_planted_as_stated(rehearsal):
    config, docs, corpus = rehearsal
    ctx, ref = corpus["body_context"], corpus["reference"]
    law = config["corpus"]["args"]["collocations"]
    partner, q = ctx["partner"], ctx["q"]
    assert (partner[:law["function_ranks"]] >= 0).all()
    assert (partner[:law["function_ranks"]] < law["function_ranks"]).all()
    held = np.flatnonzero(partner >= 0)
    assert (partner[held] != held).all()
    content = held[held >= law["function_ranks"]]
    assert (partner[content] < content).all()  # a more frequent term
    assert q.max() <= law["content_q"][1] and 0.05 < ctx[
        "collocation_share"] < 0.2
    tok, start = ref["tokens"], ref["doc_start"]
    follows = np.ones(len(tok), bool)
    follows[start[1:-1] - 1] = False  # a passage's last token
    follows[-1] = False
    nxt = np.r_[tok[1:], -1]
    counts = np.bincount(tok, minlength=ref["vocab"])
    checked = 0
    for t in held[np.argsort(-q[held] * counts[held])][:20]:
        at = np.flatnonzero((tok == t) & follows)
        if len(at) < 300:
            continue
        rate = float((nxt[at] == partner[t]).mean())
        chance = counts[partner[t]] / len(tok)
        # the partner follows with q, plus the independent draws' chance
        assert abs(rate - (q[t] + (1 - q[t]) * chance)) < 0.05 + 3 * math.sqrt(
            0.25 / len(at)), (t, rate, q[t])
        checked += 1
    assert checked >= 5


def test_classes_at_rehearse_docs(rehearsal):
    config, docs, corpus = rehearsal
    args = config["body"]["args"]
    ctx = corpus["body_context"]
    gen = load_plugin("bodies", config["body"]["generator"])
    phrases = gen.class_phrases(ctx, args)
    ref = load_plugin("references", config["reference"]).Reference(
        corpus["reference"], config)
    for name, (cls, words) in gen.CLASSES.items():
        pool = phrases[name]
        lo, hi = args["df_share"][cls]
        assert len(pool) >= 200 and pool.shape[1] == words, (name, len(pool))
        for row in pool[:: max(1, len(pool) // 8)][:8]:
            held, _f = ref.phrase_freq(row.tolist())  # counted again, plainly
            assert len(held) >= lo * docs, (name, row, len(held))
            assert hi is None or len(held) < hi * docs, (name, row, len(held))
            assert len(set(row.tolist())) == words
    raw = gen.make(ctx, args, np.random.default_rng(4), 2000)
    bodies = [json.loads(b) for b in raw]
    assert all(b["size"] == 10 and b["_source"] is False
               and set(b) == {"query", "size", "_source"} for b in bodies)
    assert all(70 <= len(b) <= 110 for b in raw)
    classes = [gen.class_of(b, ctx["field"], phrases) for b in bodies]
    share = {c: classes.count(c) / len(classes) for c in gen.CLASSES}
    assert all(0.17 < s < 0.23 for s in share.values()), share
    # High two-word phrases of both kinds: two frequent words, and a
    # content collocation that is most of its rarer word's passages
    high = phrases["HighPhrase"]
    assert ((high < 50).all(axis=1)).any()
    df = np.asarray(corpus["segment"].postings["body"].term_df)
    planted = high[(ctx["partner"][high[:, 0]] == high[:, 1])
                   & (high[:, 0] >= 50)]
    strong = 0
    for row in planted[:40]:
        held, _f = ref.phrase_freq(row.tolist())
        strong += len(held) > 0.5 * df[row].min()
    assert strong >= 1


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


DRIVER = r"""
import json, sys
sys.path[:0] = [{bench!r}, {root!r}]
import run
if {broken}:
    import numpy as np
    from elasticsearch_tpu.index import segment
    real = segment.plane_from_occurrences

    def shifted(doc, pos, term):
        # every token one position late in the plane the kernel reads
        plane = real(doc, pos, term)
        plane.mats = [np.roll(m, 1, axis=0) for m in plane.mats]
        return plane

    segment.plane_from_occurrences = shifted
result = run.run_cell("msmarco-phrase.solo", seed=11, seconds=4.0,
                      trace=False, rehearse=True)
print("RESULT " + json.dumps(result))
"""


def drive(broken: bool) -> dict:
    code = DRIVER.format(bench=HERE, root=os.path.dirname(HERE),
                         broken=broken)
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run([sys.executable, "-c", code], env=env, text=True,
                       stdout=subprocess.PIPE, timeout=900)
    assert p.returncode == 0, p.stdout[-2000:]
    line = [ln for ln in p.stdout.splitlines() if ln.startswith("RESULT ")]
    return json.loads(line[-1][len("RESULT "):])


@pytest.mark.parametrize("broken", [False, True])
def test_rehearsal_is_correct_and_a_shifted_plane_is_caught(broken):
    result = drive(broken)
    assert result["attempted"] > 0 and result["failed"] == 0, result
    assert result["checks"]["answers_checked"][0] >= 16, result
    assert result["correct"] is (not broken), result
    if broken:
        assert (result["checks"]["page_mismatches"][0] > 0
                or result["checks"]["total_mismatches"][0] > 0), result


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
        "plan_ms", "fan_out_handover_ms", "fan_out_inline_share",
        "phrase_plan_ms", "phrase_kernel_ms", "phrase_occurrences_per_req",
        "phrase_candidates_per_req", "phrase_fallback_share",
        "phrase_scan_roofline"}
    conf = load_json("configs", f"{CONFIG}.json")
    assert len(conf["source"]) <= 200 and "\n" not in conf["source"]


def test_roofline_reader_applies_the_least_bytes_to_the_traced_launches():
    spec = load_json("layer_metrics", "phrase_scan_roofline.json")
    reader = load_plugin("readers", spec["reader"])
    assert reader.least_bytes(1000, 1_000_000, 500) == 4000 + 500
    assert reader.least_bytes(600_000, 1_000_000, 0) == 125_000
    obs = {"profile": {"modules": {"jit_phrase_topk": (10, 0.010)}},
           "counts": {"phrase.least_bytes": 819_000 * 40,
                      "phrase.launches": 40},
           "device": {"kind": "TPU v5 lite"},
           "peaks": load_json("peaks.json")["by_device_kind"],
           "rehearsal": False}
    # 10 launches x 819 kB over 819 GB/s = 10 us of the 10 ms they took
    assert reader.read(obs, spec["args"]) == pytest.approx(0.1)
    for missing in ({"profile": {"modules": {}}},
                    {"counts": {"phrase.launches": 40}},
                    {"counts": {}}):
        assert reader.read({**obs, **missing}, spec["args"]) is None
    with pytest.raises(KeyError):
        reader.read({**obs, "device": {"kind": "TPU v9"}}, spec["args"])
