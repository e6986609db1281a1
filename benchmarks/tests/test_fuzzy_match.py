"""The typo-tolerant deployment's reference, bodies, corpus and control,
without a chip:

- `references/bm25_fuzzy_match.py` against a hand-worked dictionary: which
  terms each word keeps, in which order and under which boosts (written
  out by hand below), the blended idf, the scores (a scalar loop over the
  published formula), a transposition counted once;
- `corpora/zipf_text_spelled.py`: the postings law is
  `msmarco-passage-bm25`'s (the raw stream is the same arrays), the
  dictionary is sorted, distinct and lower-case, lengths by rank as the
  law states; `bodies/match_fuzzy_terms.py`: the passage cell's question,
  about one in eight with a misspelling;
- the plain reference in bfloat16 comes out NOT correct under the
  comparison that decides `correct`; in full precision correct;
- a whole rehearsal of the cell (`run.run_cell(..., rehearse=True)`, a
  process of its own) is `correct`, and with the expansions cut to the
  FIRST 50 candidates in dictionary order underneath the program (the
  parent's rule) it is not, by `page_mismatches`;
- `selfcheck.py` `check_forms` holds `BENCHMARK.json` and the new files
  to the contract's forms.

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

CONFIG = "msmarco-fuzzy-match"
K1, B = 1.2, 0.75

# the dictionary by frequency rank, and the passages (term ranks)
SPELLED = ["the", "then", "hte", "search", "saerch", "serach", "he", "box",
           "them", "thee", "sea"]
PASSAGES = [[0, 0, 3, 7], [1, 6], [2, 4], [5, 5, 5], [8, 0], [9, 10, 7],
            [3, 3, 1, 0], [6]]

# word -> [(kept term, distance)], best first, by hand (AUTO)
KEPT = {
    "the": [("the", 0), ("hte", 1), ("thee", 1), ("them", 1), ("then", 1),
            ("he", 1)],  # 1 | 1 - 1/3 four times, by term | 1 - 1/2
    "teh": [("the", 1)],  # one transposition; `hte` is two edits away
    "search": [("search", 0), ("saerch", 1), ("serach", 1)],
    "serach": [("serach", 0), ("search", 1), ("saerch", 2)],
    "he": [("he", 0)],  # two letters: itself
    "xy": [],
    "boxx": [("box", 1)],
}


def raw_stream():
    docs = len(PASSAGES)
    posts = sorted((t, d, p.count(t)) for d, p in enumerate(PASSAGES)
                   for t in set(p))
    df = np.bincount([t for t, _d, _f in posts], minlength=len(SPELLED))
    return {"field": "body", "docs": docs,
            "lengths": np.array([len(p) for p in PASSAGES], np.int64),
            "post_start": np.r_[0, np.cumsum(df)].astype(np.int64),
            "post_doc": np.array([d for _t, d, _f in posts], np.int32),
            "post_tf": np.array([f for _t, _d, f in posts], np.int32),
            "spellings": SPELLED}


@pytest.fixture(scope="module")
def reference():
    return load_plugin("references", "bm25_fuzzy_match").Reference(
        raw_stream(), {"guarantees": {"bm25_k1": K1, "bm25_b": B},
                       "shapes": {"max_expansions": 50}})


@pytest.mark.parametrize("word", sorted(KEPT))
def test_kept_terms_on_the_hand_worked_dictionary(reference, word):
    ranks, weights = reference.kept(word, "AUTO")
    assert [SPELLED[r] for r in ranks] == [t for t, _d in KEPT[word]]
    if not KEPT[word]:
        return
    n = len(PASSAGES)
    df = max(sum(SPELLED.index(t) in p for p in PASSAGES)
             for t, _d in KEPT[word])
    idf = math.log(1.0 + (n - df + 0.5) / (df + 0.5))
    for w, (t, d) in zip(weights, KEPT[word]):
        boost = 1.0 if d == 0 else float(
            np.float32(1.0) - np.float32(d) / np.float32(min(len(word), len(t))))
        assert w == pytest.approx(boost * idf, rel=1e-12)


def test_scores_on_the_hand_worked_corpus(reference):
    body = {"query": {"match": {"body": {"query": "teh serach",
                                         "fuzziness": "AUTO"}}}, "size": 8}
    got = reference.answer(body)
    avgdl = sum(map(len, PASSAGES)) / len(PASSAGES)
    want = {}
    for word in ("teh", "serach"):
        ranks, weights = reference.kept(word, "AUTO")
        for r, w in zip(ranks.tolist(), weights.tolist()):
            for d, p in enumerate(PASSAGES):
                if r in p:
                    tf = p.count(r)
                    want[d] = want.get(d, 0.0) + w * tf / (
                        tf + K1 * (1.0 - B + B * len(p) / avgdl))
    hits = [(int(h["_id"]), h["_score"]) for h in got["hits"]["hits"]]
    assert [d for d, _s in hits] == sorted(want, key=lambda d: (-want[d], d))
    for d, s in hits:
        assert s == pytest.approx(want[d], rel=1e-12)
    assert got["hits"]["total"] == {"value": len(want), "relation": "eq"}


def test_the_full_table_counts_a_transposition_once():
    table = load_plugin("references", "bm25_fuzzy_match").osa_table
    cp = lambda s: np.frombuffer(s.encode("utf-32-le"), np.uint32).astype(  # noqa: E731
        np.int32)
    pairs = [("ab", "ba", 1), ("teh", "the", 1), ("abcd", "acbd", 1),
             ("ca", "ac", 1), ("abc", "cab", 2), ("kitten", "sittin", 2)]
    for a, b, d in pairs:
        assert table(cp(a), cp(b)[None, :])[len(a), len(b), 0] == d, (a, b)


@pytest.fixture(scope="module")
def small():
    return small_cell(CONFIG, 20_000, 5, 48)


def test_corpus_keeps_the_postings_law_and_spells_its_terms():
    config = load_json("configs", f"{CONFIG}.json")
    passage = load_json("configs", "msmarco-passage-bm25.json")
    for key, value in passage["corpus"]["args"].items():
        assert config["corpus"]["args"][key] == value, key
    docs = 20_000
    spelled = load_plugin("corpora", "zipf_text_spelled").build(
        config, 3, docs)
    plain = load_plugin("corpora", "zipf_text").build(passage, 3, docs)
    for key in ("post_start", "post_doc", "post_tf", "lengths"):
        np.testing.assert_array_equal(
            spelled["reference"][key], plain["reference"][key])
    words = spelled["reference"]["spellings"]
    pf = spelled["segment"].postings["body"]
    assert pf.terms == sorted(set(words)) and len(pf.terms) == len(words)
    assert all(w.isalpha() and w.islower() and w.isascii() for w in words)
    # the dictionary moved, the tiles did not
    df = np.diff(plain["reference"]["post_start"])
    for rank in range(0, len(words), 997):
        tid = pf.term_id(words[rank])
        assert pf.term_df[tid] == df[rank]
        assert pf.term_tile_start[tid] == plain["segment"].postings[
            "body"].term_tile_start[rank]
    lens = np.array([len(w) for w in words])
    assert lens[:10].mean() < 3.5 < 6.0 < lens[-2000:].mean()
    # the same dictionary on every seed
    again = load_plugin("corpora", "zipf_text_spelled").build(config, 4, docs)
    assert again["reference"]["spellings"] == words


def test_bodies_are_the_passage_cells_question_with_typos(small):
    config, _ref, _bodies = small
    corpus = load_plugin("corpora", "zipf_text_spelled").build(
        config, 5, 20_000)
    raw = load_plugin("bodies", "match_fuzzy_terms").make(
        corpus["body_context"], config["body"]["args"],
        np.random.default_rng([23, 3, 0]), 2000)
    known = set(corpus["reference"]["spellings"])
    n_words, typos, with_typo = [], 0, 0
    for b in raw:
        body = json.loads(b)
        spec = body["query"]["match"]["body"]
        assert spec["fuzziness"] == "AUTO" and body["size"] == 10
        words = spec["query"].split()
        n_words.append(len(words))
        bad = sum(w not in known for w in words)
        typos += bad
        with_typo += bad > 0
    assert 5.7 < np.mean(n_words) < 6.4 and min(n_words) >= 2
    assert max(n_words) <= 12
    # 0.025 a word of three letters or more, ~12% of questions (a typo
    # that lands on another word of the dictionary is not counted here)
    assert 0.06 < with_typo / len(raw) < 0.16, with_typo / len(raw)
    assert len(max(raw, key=len)) < 400


@pytest.mark.parametrize("seed", [1, 2147483900, 3000000007])
def test_lower_precision_fails_and_full_precision_passes(seed):
    config, ref, bodies = small_cell(CONFIG, 20_000, seed, 64)
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
    from elasticsearch_tpu.models import fuzzy
    real = fuzzy.class_ranks

    def first_by_spelling(m, k):
        # every candidate in one class: the selection keeps the FIRST 50
        # in dictionary order, the parent's rule
        ranks = real(m, k)
        ranks[ranks > 0] = 0
        return ranks

    fuzzy.class_ranks = first_by_spelling
result = run.run_cell("msmarco-fuzzy-match.solo", seed=11, seconds=4.0,
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
def test_rehearsal_is_correct_and_the_first_50_by_spelling_are_caught(broken):
    result = drive(broken)
    assert result["attempted"] > 0 and result["failed"] == 0, result
    assert result["checks"]["answers_checked"][0] >= 8, result
    assert result["correct"] is (not broken), result
    if broken:
        assert result["checks"]["page_mismatches"][0] > 0, result


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
    assert {"latency_p50_ms", "latency_p95_ms", "launch_ms", "download_ms",
            "fuzzy_expand_kernel_ms", "fuzzy_expand_ms", "fuzzy_plan_ms",
            "fuzzy_terms_per_req", "fuzzy_saturated_share",
            "fuzzy_overflow_share", "fuzzy_expand_roofline"} <= listed
    assert bench["workloads"][-1] == cell and bench["configs"][-1][
        "name"] == CONFIG
    config = load_json("configs", f"{CONFIG}.json")
    assert config["docs"] == 1_000_000 and config["architecture"] is None
    assert config["shapes"]["max_expansions"] == 50
