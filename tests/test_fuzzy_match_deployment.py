"""The typo-tolerant search-box deployment (`benchmarks/configs/
msmarco-fuzzy-match.json`) at a small size: `match` with `fuzziness: AUTO`
over ~20,000 seeded passages of the benchmark's own spelled corpus
builder, served over HTTP through the batcher's `fuzzy` family (the
expansion program, then the fused program at the family's slot budgets)
and held to the benchmark's own plain reference (`benchmarks/references/
bm25_fuzzy_match.py`) by the benchmark's own rule (`benchmarks/compare.py`,
`exact`).

What the deployment forced, each held here: the distance counts a
transposition once, the best 50 are kept with ties by term, a word's
terms share one blended idf, a short word is itself, a question of more
dense rows than `FUSED_H` scores all of them, an unknown `match` key is
a 400, the device's expansion is the oracle's, and both steps have spans
and counters.
"""

import http.client
import json
import os
import random
import sys

import numpy as np
import pytest

from elasticsearch_tpu.cluster import ClusterService
from elasticsearch_tpu.models import fuzzy as fuzzy_model
from elasticsearch_tpu.ops import fuzzy as fuzzy_ops
from elasticsearch_tpu.ops import scoring
from elasticsearch_tpu.search import batcher as batcher_mod
from elasticsearch_tpu.search import dsl, executor_jax
from elasticsearch_tpu.search.batcher import extract_fuzzy_plan

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from compare import compare_one, reference_body  # noqa: E402
from plugins import load_json, load_plugin  # noqa: E402
from run import place_segment  # noqa: E402

DOCS, SEED, N_BODIES = 20000, 11, 16
AUTO = fuzzy_model.FuzzyParams()


def osa_plain(a: str, b: str, transpositions: bool = True) -> int:
    """The optimal string alignment distance as a plain table."""
    d = [[max(i, j) if 0 in (i, j) else 0 for j in range(len(b) + 1)]
         for i in range(len(a) + 1)]
    for i in range(1, len(a) + 1):
        for j in range(1, len(b) + 1):
            d[i][j] = min(d[i - 1][j] + 1, d[i][j - 1] + 1,
                          d[i - 1][j - 1] + (a[i - 1] != b[j - 1]))
            if (transpositions and i > 1 and j > 1 and a[i - 1] == b[j - 2]
                    and a[i - 2] == b[j - 1]):
                d[i][j] = min(d[i][j], d[i - 2][j - 2] + 1)
    return d[-1][-1]


def distances(word: str, terms, transpositions: bool = True) -> np.ndarray:
    plane = fuzzy_model.build_term_plane(sorted(terms))
    return fuzzy_model.osa_within(
        fuzzy_model.code_points(word), plane.chars, plane.lens,
        transpositions)


# ---------------------------------------------------------------------------
# the distance, the selection, the blend
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("alphabet,seed", [("ab", 1), ("abc", 2),
                                           ("abcdefgh", 3), ("aé中𝒳", 4)])
def test_the_banded_distance_is_the_plain_tables_on_random_pairs(
        alphabet, seed):
    rng = random.Random(seed)
    terms = sorted({"".join(rng.choice(alphabet)
                            for _ in range(rng.randint(1, 9)))
                    for _ in range(400)})
    for _ in range(12):
        word = "".join(rng.choice(alphabet)
                       for _ in range(rng.randint(1, 9)))
        for swaps in (True, False):
            want = [min(osa_plain(word, t, swaps), fuzzy_model.CAP)
                    for t in terms]
            assert distances(word, terms, swaps).tolist() == want, word


@pytest.mark.parametrize("a,b,d", [
    ("ab", "ba", 1), ("abcd", "acbd", 1), ("ca", "abc", 3), ("the", "teh", 1),
    ("the", "hte", 1), ("the", "then", 1), ("the", "he", 1), ("abc", "abc", 0),
    ("kitten", "sitting", 3), ("search", "saerhc", 2), ("a", "b", 1),
])
def test_hand_made_pairs_and_a_transposition_is_one_edit(a, b, d):
    assert distances(a, [b])[0] == min(d, fuzzy_model.CAP)
    assert osa_plain(a, b) == d


def test_a_control_that_counts_a_transposition_twice_fails():
    # plain Levenshtein is that control: `ab` -> `ba` reads 2
    assert distances("ab", ["ba"], transpositions=False)[0] == 2
    assert distances("ab", ["ba"])[0] == 1
    terms = sorted(["there", "three", "threw", "thre"])
    plane = fuzzy_model.build_term_plane(terms)
    with_swaps = fuzzy_model.expand_word(plane, terms, "three", 1)[0]
    without = fuzzy_model.expand_word(
        plane, terms, "three", 1, transpositions=False)[0]
    assert [terms[i] for i in with_swaps] == [
        "three", "there", "threw", "thre"]  # 1, 0.8, 0.8, 0.75
    assert "there" not in [terms[i] for i in without]


def test_the_tie_at_place_50_goes_to_the_smaller_term():
    # 60 terms one substitution away from `abcd`: all boost 0.75
    near = sorted({"abc" + c for c in "efghijklmnopqrstuvwxyz"}
                  | {"ab" + c + "d" for c in "efghijklmnopqrstuvwxyz"}
                  | {"a" + c + "cd" for c in "efghijklmnopqrstuv"})
    terms = sorted(near + ["abcd", "zzzz"])
    plane = fuzzy_model.build_term_plane(terms)
    ids, boosts, dist = fuzzy_model.expand_word(plane, terms, "abcd", 1)
    kept = [terms[i] for i in ids]
    assert len(kept) == 50 and kept[0] == "abcd" and boosts[0] == 1.0
    assert kept[1:] == near[:49]  # ties by term ascending: `near[49:]` lost
    assert set(boosts[1:].tolist()) == {0.75} and dist[1:].tolist() == [1] * 49


def test_a_closer_spelling_outranks_a_smaller_term():
    terms = sorted(["aaaaaa", "aaaaab", "aaaabb", "zaaaaa"])
    plane = fuzzy_model.build_term_plane(terms)
    ids, boosts, _d = fuzzy_model.expand_word(
        plane, terms, "aaaaaa", 2, max_expansions=3)
    assert [terms[i] for i in ids] == ["aaaaaa", "aaaaab", "zaaaaa"]
    np.testing.assert_array_equal(
        boosts, np.float32([1.0, 1.0 - np.float32(1) / np.float32(6)] * 2)[
            [0, 1, 3]])


def test_boosts_and_class_ranks_agree_on_the_order():
    for m in range(1, 31):
        for k in (1, 2):
            ranks = fuzzy_model.class_ranks(m, k).reshape(3, 3)
            cells = [(d, s) for d in range(3) for s in range(3)
                     if ranks[d, s] >= 0]
            boost = {c: float(fuzzy_model.boosts_of(
                np.array([c[0]]), m, np.array([m - c[1]]))[0]) for c in cells}
            for a in cells:
                assert boost[a] > 0
                for b in cells:
                    assert (boost[a] > boost[b]) == (ranks[a] < ranks[b])


def test_blended_idf_is_the_largest_dfs():
    got = fuzzy_model.blended_idf(1000, np.array([3, 250, 40]))
    assert got == np.float32(np.log(1.0 + (1000 - 250 + 0.5) / 250.5))
    w = fuzzy_model.term_weights(2.0, got, np.float32([1.0, 0.75]))
    assert w.dtype == np.float32 and w[1] == np.float32(2.0) * got * np.float32(0.75)


@pytest.mark.parametrize("fuzziness,lens,edits", [
    ("AUTO", (1, 2, 3, 5, 6, 12), (0, 0, 1, 1, 2, 2)),
    ("AUTO:4,7", (3, 4, 6, 7), (0, 1, 1, 2)),
    ("0", (1, 9), (0, 0)), ("1", (1, 9), (1, 1)), (2, (2, 9), (2, 2)),
])
def test_fuzziness_forms(fuzziness, lens, edits):
    assert tuple(fuzzy_model.edits_for(fuzziness, m) for m in lens) == edits


@pytest.mark.parametrize("bad", ["AUTO:6,3", "3", "1.5", "fast", "AUTO:a,b"])
def test_a_bad_fuzziness_is_a_parse_error(bad):
    with pytest.raises(dsl.QueryParseError):
        dsl.parse_query({"match": {"body": {"query": "x", "fuzziness": bad}}})


def test_a_term_longer_than_the_plane_is_still_found():
    long_word = "a" * 40
    terms = sorted([long_word, "a" * 39 + "b", "abc"])
    plane = fuzzy_model.build_term_plane(terms)
    assert plane.long_ids.tolist() == [0, 1]
    ids, boosts, dist = fuzzy_model.expand_word(plane, terms, long_word, 2)
    assert [terms[i] for i in ids] == [long_word, "a" * 39 + "b"]
    assert dist.tolist() == [0, 1]


# ---------------------------------------------------------------------------
# the parser
# ---------------------------------------------------------------------------


def test_match_takes_the_four_fuzzy_keys():
    q = dsl.parse_query({"match": {"body": {
        "query": "helo wrld", "fuzziness": "AUTO:3,6", "prefix_length": 1,
        "max_expansions": 20, "fuzzy_transpositions": False,
        "lenient": True, "zero_terms_query": "none", "_name": "box"}}})
    assert q.fuzzy == fuzzy_model.FuzzyParams("AUTO:3,6", 1, 20, False)
    assert dsl.parse_query({"match": {"body": "helo"}}).fuzzy is None


@pytest.mark.parametrize("key", ["fuzzines", "slop", "type", "cutoff"])
def test_an_unknown_match_key_is_a_parse_error(key):
    with pytest.raises(dsl.QueryParseError, match=key):
        dsl.parse_query({"match": {"body": {"query": "x", key: 1}}})


def test_the_planner_takes_what_the_family_serves():
    from elasticsearch_tpu.analysis import AnalysisRegistry
    from elasticsearch_tpu.index.mapping import Mappings

    mappings = Mappings({"properties": {
        "body": {"type": "text"}, "tag": {"type": "keyword"}}})
    analysis = AnalysisRegistry()

    def plan(q):
        return extract_fuzzy_plan(dsl.parse_query(q), mappings, analysis)

    got = plan({"match": {"body": {"query": "Helo wrld", "fuzziness": "AUTO"}}})
    assert got.words == ("helo", "wrld") and got.params == AUTO
    assert plan({"fuzzy": {"body": {"value": "helo"}}}).words == ("helo",)
    for turned_away in (
        {"match": {"body": "helo"}},
        {"match": {"body": {"query": "a b", "fuzziness": 1,
                            "operator": "and"}}},
        {"match": {"body": {"query": "a b c", "fuzziness": 1,
                            "minimum_should_match": 2}}},
        {"match": {"body": {"query": "helo", "fuzziness": 1,
                            "prefix_length": 1}}},
        {"match": {"tag": {"query": "helo", "fuzziness": 1}}},
        {"match": {"body": {"query": "x" * 31, "fuzziness": 1}}},
        {"match": {"body": {"query": " ".join(["w"] * 17), "fuzziness": 1}}},
        {"match": {"body": {"query": "helo", "fuzziness": 1,
                            "max_expansions": 500}}},
    ):
        assert plan(turned_away) is None, turned_away


# ---------------------------------------------------------------------------
# the deployment, served
# ---------------------------------------------------------------------------


def call(port: int, path: str, body: dict, method: str = "POST",
         expect: int = 200) -> dict:
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=300)
    try:
        conn.request(method, path, body=json.dumps(body).encode(),
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        payload = resp.read()
        assert resp.status == expect, (resp.status, payload[:400])
        return json.loads(payload)
    finally:
        conn.close()


class Deployment:
    def __init__(self):
        from elasticsearch_tpu.rest.server import ElasticsearchTpuServer

        self.config = load_json("configs", "msmarco-fuzzy-match.json")
        self.corpus = load_plugin(
            "corpora", self.config["corpus"]["builder"]).build(
            self.config, SEED, DOCS)
        self.ref = load_plugin(
            "references", self.config["reference"]).Reference(
            self.corpus["reference"], self.config)
        gen = load_plugin("bodies", self.config["body"]["generator"])
        raw = gen.make(self.corpus["body_context"],
                       self.config["body"]["args"],
                       np.random.default_rng([55, 9]), N_BODIES)
        self.bodies = [json.loads(b) for b in raw]
        self.spelled = self.corpus["reference"]["spellings"]
        self.server = ElasticsearchTpuServer(port=0)
        self.server.start_background()
        self.port = self.server.port
        self.index = self.config["index"]
        call(self.port, f"/{self.index}",
             {"settings": self.config["settings"],
              "mappings": self.corpus["mappings"]}, "PUT")
        place_segment(self.svc, self.corpus["segment"])

    @property
    def svc(self):
        return self.server.cluster.indices[self.index]

    def body(self, text: str, **more) -> dict:
        return {"query": {"match": {"body": {
            "query": text, "fuzziness": "AUTO", **more}}},
            "size": 10, "_source": False}

    def search(self, body: dict) -> dict:
        return call(self.port, f"/{self.index}/_search", body)

    def held(self, body: dict, served: dict) -> dict:
        g = self.config["guarantees"]
        (expected,) = self.ref.answer_many(
            [reference_body(g["rule"], body)])
        got = compare_one(g["rule"], g["score_rtol"], body, served, expected)
        assert got["page_ok"], got["why"]
        assert got["total_ok"], (served["hits"]["total"],
                                 expected["hits"]["total"])
        assert got["score_rel"] <= g["score_rtol"], got["score_rel"]
        return expected

    def node(self) -> dict:
        from elasticsearch_tpu.rest.actions import RestActions

        _status, body = RestActions(self.server.cluster).nodes_stats(
            None, {}, {})
        return body["nodes"]["node-0"]

    def close(self):
        self.server.close()


@pytest.fixture(scope="module")
def dep():
    # a small segment rides the fused program, as the cell's does
    floor, executor_jax.FUSED_MIN_DOCS = executor_jax.FUSED_MIN_DOCS, 1024
    d = Deployment()
    yield d
    d.close()
    executor_jax.FUSED_MIN_DOCS = floor


def test_the_spelled_dictionary_is_sorted_and_keeps_the_postings(dep):
    pf = dep.corpus["segment"].postings["body"]
    assert pf.terms == sorted(set(dep.spelled))
    ref = dep.corpus["reference"]
    df = np.diff(ref["post_start"])
    for rank in (0, 7, 500, len(dep.spelled) - 1):
        tid = pf.term_id(dep.spelled[rank])
        assert pf.term_df[tid] == df[rank]
    lens = np.array([len(w) for w in dep.spelled])
    weight = 1.0 / np.arange(1, len(lens) + 1)
    assert 4.0 < (lens * weight).sum() / weight.sum() < 6.0  # by token
    assert 6.0 < lens.mean() < 9.5  # by type
    assert all(w.isalpha() and w.islower() for w in dep.spelled[:2000])


def test_the_configurations_bodies_are_served_as_the_reference_answers(dep):
    before = dep.node()["fuzzy"]
    words = 0
    for body in dep.bodies:
        spec = body["query"]["match"]["body"]
        assert spec["fuzziness"] == "AUTO" and body["size"] == 10
        words += len(spec["query"].split())
        dep.held(body, dep.search(body))
    after = dep.node()["fuzzy"]
    n = len(dep.bodies)
    assert after["requests"] - before["requests"] == n
    assert after["words"] - before["words"] == words
    assert after["overflows"] == before["overflows"]
    assert after["fallbacks"] == before["fallbacks"]
    assert after["score_launches"] - before["score_launches"] == n
    # every word of three letters or more is expanded, typo or not
    assert after["terms_kept"] - before["terms_kept"] > 3 * words
    assert after["least_bytes"] > before["least_bytes"]
    assert dep.node()["pipeline"]["batching"]["unplanned_queries"] == 0


def test_the_bfloat16_reference_differs_by_more_than_the_limit(dep):
    g = dep.config["guarantees"]
    worst, broken = 0.0, 0
    for body in dep.bodies:
        asked = reference_body(g["rule"], body)
        full, low = (dep.ref.answer_many([asked], precision=p)[0]
                     for p in ("full", "lower"))
        got = compare_one(g["rule"], g["score_rtol"], body,
                          {"hits": {**low["hits"],
                                    "hits": low["hits"]["hits"][:10]}}, full)
        worst = max(worst, got["score_rel"])
        broken += not got["page_ok"]
    assert worst > 100 * g["score_rtol"], worst
    assert broken >= 1


def test_a_misspelled_word_finds_what_the_right_spelling_finds(dep):
    word = next(w for w in dep.spelled[200:] if len(w) >= 6)
    typo = word[:2] + word[3] + word[2] + word[4:]  # one transposition
    assert typo != word and typo not in set(dep.spelled)
    served = dep.search(dep.body(typo))
    dep.held(dep.body(typo), served)
    plain = dep.search({"query": {"match": {"body": word}}, "size": 10,
                        "_source": False})
    assert served["hits"]["total"]["value"] >= plain["hits"]["total"]["value"]
    ex = dep.svc._executor(dep.svc.local_shard(0))
    assert word in ex.fuzzy_terms("body", typo, AUTO)
    # the parent's rule (no `fuzziness`) finds nothing for the typo
    assert dep.search({"query": {"match": {"body": typo}},
                       "size": 10})["hits"]["total"]["value"] == 0


def test_a_word_of_one_or_two_letters_expands_to_itself(dep):
    short = [w for w in dep.spelled[:40] if len(w) <= 2][:2]
    assert len(short) == 2
    fuzzy = dep.search(dep.body(" ".join(short)))
    plain = dep.search({"query": {"match": {"body": " ".join(short)}},
                        "size": 10, "_source": False})
    assert fuzzy["hits"]["total"] == plain["hits"]["total"]
    assert ([h["_id"] for h in fuzzy["hits"]["hits"]]
            == [h["_id"] for h in plain["hits"]["hits"]])
    np.testing.assert_allclose(
        [h["_score"] for h in fuzzy["hits"]["hits"]],
        [h["_score"] for h in plain["hits"]["hits"]], rtol=1e-6)
    ex = dep.svc._executor(dep.svc.local_shard(0))
    assert ex.fuzzy_terms("body", short[0], AUTO) == [short[0]]


def test_a_question_of_more_dense_rows_than_fused_h_scores_all_of_them(dep):
    ex = dep.svc._executor(dep.svc.local_shard(0))
    hot = ex.fused_parts(0, "body")["hot_rank"]
    pf = dep.corpus["segment"].postings["body"]
    hot_terms = {pf.terms[t] for t in hot}
    # the commonest words of three letters and more, a dozen of them
    words = [w for w in dep.spelled[:60] if len(w) >= 3][:12]
    kept_hot = sum(t in hot_terms for w in words
                   for t in ex.fuzzy_terms("body", w, AUTO))
    assert kept_hot > scoring.FUSED_H, kept_hot
    before = dep.node()["fuzzy"]
    body = dep.body(" ".join(words))
    dep.held(body, dep.search(body))
    after = dep.node()["fuzzy"]
    assert after["hot_terms"] - before["hot_terms"] == kept_hot
    assert after["overflows"] == before["overflows"]
    fused = dep.node()["thread_pool"]["search"]
    assert fused["fused_overflow_jobs"] == 0


def test_a_plan_past_a_budget_is_served_whole_and_counted(dep, monkeypatch):
    monkeypatch.setattr(scoring, "FUZZY_H", 2)  # the plan's check alone
    words = [w for w in dep.spelled[:60] if len(w) >= 3][:6]
    before = dep.node()["fuzzy"]
    body = dep.body(" ".join(words))
    dep.held(body, dep.search(body))
    after = dep.node()["fuzzy"]
    assert after["overflows"] - before["overflows"] == 1


def test_the_device_expansion_is_the_oracles(dep):
    ex = dep.svc._executor(dep.svc.local_shard(0))
    pf = dep.corpus["segment"].postings["body"]
    fz = ex.fuzzy_parts(0, "body")
    rng = np.random.default_rng(5)
    words = [dep.spelled[i] for i in rng.integers(0, 5000, 24)]
    words += [w[:-1] + "q" for w in words[:6] if len(w) >= 3]
    words = [w for w in dict.fromkeys(words) if len(w) >= 3][:16]
    sent = [(fuzzy_model.code_points(w), AUTO.edits(w)) for w in words]
    out = np.asarray(fuzzy_ops.expand_async(fz["plane"], sent, 16, 50, True))
    got = fuzzy_ops.decode(out, len(sent), 50)
    saturated = 0
    for w, (cp, k), (ords, dist) in zip(words, sent, got):
        ids, _b, d = fuzzy_model.expand_word(pf.term_plane(), pf.terms, w, k)
        np.testing.assert_array_equal(ords, ids, err_msg=w)
        np.testing.assert_array_equal(dist, d, err_msg=w)
        saturated += len(ids) == 50
        # and the plain table's, term by term
        for t in {pf.terms[i] for i in ids[:5]}:
            assert osa_plain(w, t) <= k
    assert saturated >= 1  # the cut was exercised


def test_a_wide_dictionary_is_selected_from_block_maxima_and_exactly():
    """Past 8 x 50 blocks of 128 x 128 columns the program selects its
    50 from block maxima (`scoring._block_topk`, the passage shard's
    form): the kept ordinals and distances are still the oracle's."""
    law = load_json("configs", "msmarco-fuzzy-match.json")[
        "corpus"]["args"]["spelling"]
    spelled = load_plugin("corpora", "zipf_text_spelled").spell(
        70000, 23, law)
    terms = sorted(spelled)
    plane = fuzzy_model.build_term_plane(terms, pad_to=fuzzy_model.PLANE_PAD)
    dev = fuzzy_ops.DeviceTermPlane(plane, len(terms))
    width = dev.lens.shape[0]
    assert width // (128 * 128) * 128 >= 8 * 50
    words = [w for w in spelled[:300] if 3 <= len(w) <= 9][:6]
    words += [words[0][::-1], words[3][1:] + "q"]
    sent = [(fuzzy_model.code_points(w), AUTO.edits(w)) for w in words]
    out = np.asarray(fuzzy_ops.expand_async(dev, sent, 16, 50, True))
    saturated = 0
    for w, (_cp, k), (ords, dist) in zip(
            words, sent, fuzzy_ops.decode(out, len(sent), 50)):
        ids, _b, d = fuzzy_model.expand_word(plane, terms, w, k)
        np.testing.assert_array_equal(ords, ids, err_msg=w)
        np.testing.assert_array_equal(dist, d, err_msg=w)
        saturated += len(ids) == 50
    assert saturated >= 1


def served_of(td) -> dict:
    """A TopDocs as the REST layer would report it (small totals)."""
    return {"hits": {
        "total": {"value": td.total, "relation": td.relation},
        "hits": [{"_id": h.doc_id, "_score": h.score} for h in td.hits]}}


@pytest.mark.parametrize("rows", [8, 32])
def test_one_group_of_one_word_at_three_fuzzinesses_serves_each_its_own(
        dep, rows):
    """The fuzziness is not in the family's key: jobs of one launch may
    ask one word at 0, AUTO (two edits here) and 1 edits, and each is
    answered with its own expansion (a control that shares the first
    job's, as the expansion keyed by the word alone did, fails)."""
    word = next(w for w in dep.spelled[150:] if len(w) >= 7)
    typo = word[1] + word[0] + word[2:-1]  # a transposition and a deletion
    other = next(w for w in dep.spelled[100:] if len(w) == 5)
    svc = dep.svc
    ex = svc._executor(svc.local_shard(0))
    bodies = [dep.body(f"{typo} {other}", fuzziness=f)
              for f in (0, "AUTO", 1, "AUTO:3,9", 2)]
    jobs = []
    for body in bodies:
        q = dsl.parse_query(body["query"])
        plan = extract_fuzzy_plan(q, svc.mappings, svc.analysis)
        assert plan is not None
        jobs.append(batcher_mod._Job(ex, plan, 10, kind="fuzzy", query=q))
    share = batcher_mod.FAMILIES["fuzzy"].share
    assert len({share(j.plan) for j in jobs}) == 1
    assert [j.plan.params.edits(typo) for j in jobs] == [0, 2, 1, 1, 2]
    b = svc._batcher
    before = dict(b.fuzzy)
    b._collect_fuzzy_group(jobs, 16, b._dispatch_fuzzy_group(
        jobs, 16, rows=rows))
    after = b.fuzzy
    assert after["launches"] == before["launches"] + 1
    # (typo, 1), (typo, 2), (other, 1), (other, 2): a pair once a launch
    assert after["words_expanded"] == before["words_expanded"] + 4
    assert after["overflows"] == before["overflows"]
    totals = []
    for body, job in zip(bodies, jobs):
        dep.held(body, served_of(job.result))
        totals.append(job.result.total)
    # the typo is no term: at 0 edits it finds nothing, at 1 less than at
    # 2; AUTO gives the five-letter word one edit where `2` gives it two
    assert totals[0] < totals[2] == totals[3] < totals[1] <= totals[4]


@pytest.mark.parametrize("form", ["fuzzy", "and", "msm", "prefix", "bool",
                                  "levenshtein", "two_edits"])
def test_both_backends_answer_the_oracles_pages(dep, form):
    """What the family turns away (and the `fuzzy` query it takes) is
    the oracle's on either backend."""
    w1 = next(w for w in dep.spelled[100:] if len(w) == 5)
    w2 = next(w for w in dep.spelled[150:] if len(w) >= 7)
    t1 = w1[:2] + "q" + w1[3:]
    t2 = w2[1] + w2[0] + w2[2:-1]  # a transposition and a deletion
    query = {
        "fuzzy": {"fuzzy": {"body": {"value": t1, "boost": 2.0}}},
        "and": {"match": {"body": {"query": f"{t1} {w2}", "fuzziness": "AUTO",
                                   "operator": "and"}}},
        "msm": {"match": {"body": {"query": f"{t1} {w2} zzzzzzzz",
                                   "fuzziness": "AUTO",
                                   "minimum_should_match": 2}}},
        "prefix": {"match": {"body": {"query": f"{t1} {t2}",
                                      "fuzziness": "AUTO",
                                      "prefix_length": 1}}},
        "bool": {"bool": {"must": [
            {"match": {"body": {"query": t1, "fuzziness": 1}}}],
            "should": [{"match": {"body": w2}}]}},
        "levenshtein": {"match": {"body": {
            "query": t2, "fuzziness": "AUTO", "fuzzy_transpositions": False}}},
        "two_edits": {"match": {"body": {"query": t2, "fuzziness": 2}}},
    }[form]
    served = dep.search({"query": query, "size": 10, "_source": False})
    from elasticsearch_tpu.search.executor import NumpyExecutor

    oracle = NumpyExecutor(dep.svc._executor(dep.svc.local_shard(0)).reader)
    want = oracle.search(dsl.parse_query(query), size=10)
    assert served["hits"]["total"]["value"] == want.total
    assert want.total > 0 or form in ("and", "levenshtein")
    assert ([h["_id"] for h in served["hits"]["hits"]]
            == [h.doc_id for h in want.hits])
    np.testing.assert_allclose(
        [h["_score"] for h in served["hits"]["hits"]],
        [h.score for h in want.hits], rtol=2e-6)


def test_an_unknown_match_key_is_a_400_over_http(dep):
    got = call(dep.port, f"/{dep.index}/_search",
               {"query": {"match": {"body": {"query": "x", "fuzzines": 1}}}},
               expect=400)
    assert "fuzzines" in json.dumps(got)


def test_highlighting_marks_the_kept_terms(dep):
    ex = dep.svc._executor(dep.svc.local_shard(0))
    from elasticsearch_tpu.search.highlight import extract_highlight_terms

    word = next(w for w in dep.spelled[100:] if len(w) == 5)
    typo = word[:2] + "q" + word[3:]
    q = dsl.parse_query({"match": {"body": {"query": typo,
                                            "fuzziness": "AUTO"}}})
    marked = extract_highlight_terms(
        q, dep.svc.mappings, dep.svc.analysis, expand=ex.fuzzy_terms)["body"]
    assert word in marked and typo not in marked
    assert marked == set(ex.fuzzy_terms("body", typo, AUTO))
    alone = extract_highlight_terms(q, dep.svc.mappings, dep.svc.analysis)
    assert alone["body"] == {typo}


def test_the_two_steps_have_spans_and_they_tile_dispatch(dep):
    import time

    body = dep.body(" ".join(w for w in dep.spelled[20:400:60]))
    dep.search(body)
    spans = {}
    for _ in range(50):
        traces = call(dep.port, "/_internal/traces?n=4", None, "GET")["traces"]
        for tr in traces:
            names = {sp["name"]: sp for sp in tr["spans"]}
            if "fuzzy_expand" in names:
                spans = names
        if spans:
            break
        time.sleep(0.05)
    assert {"fuzzy_expand", "fuzzy_plan", "dispatch"} <= set(spans)
    d, e, p = spans["dispatch"], spans["fuzzy_expand"], spans["fuzzy_plan"]
    assert e["parent_id"] == d["id"] == p["parent_id"]
    assert d["tags"]["family"] == "fuzzy" and d["tags"]["terms_kept"] > 0
    assert e["tags"]["launches"] == 1 and e["tags"]["words"] >= 1
    assert p["tags"]["terms_kept"] == d["tags"]["terms_kept"]
    assert e["start_ns"] + e["duration_ns"] <= p["start_ns"] + 1000
    assert e["duration_ns"] + p["duration_ns"] <= d["duration_ns"]


def test_the_readers_reckon_the_programs_least_work(dep):
    reader = load_plugin("readers", "fuzzy_expand_roofline")
    by_len = np.bincount([len(w) for w in dep.spelled])
    nbytes, cells = fuzzy_ops.least_work([3, 8], [1, 2], by_len)
    reach3 = by_len[2:5]
    reach8 = by_len[6:11]
    assert nbytes == (
        reader.least_bytes(reach3.sum(), (reach3 * np.arange(2, 5)).sum())
        + reader.least_bytes(reach8.sum(), (reach8 * np.arange(6, 11)).sum()))
    assert cells * reader.OPS_PER_CELL == (
        reader.least_ops(reach3.sum(), 3, 1)
        + reader.least_ops(reach8.sum(), 8, 2))
    obs = {"profile": {"modules": {"jit_fuzzy_expand": (10, 0.05)}},
           "counts": {"fuzzy.least_bytes": 4e8, "fuzzy.least_cells": 2e9,
                      "fuzzy.launches": 20},
           "device": {"kind": "TPU v5 lite"}, "rehearsal": False,
           "peaks": load_json("peaks.json")["by_device_kind"]}
    args = load_json("layer_metrics", "fuzzy_expand_roofline.json")["args"]
    share = reader.read(obs, args)
    assert share == pytest.approx(100 * 10 / 20 * 4e8 / 819e9 / 0.05)
    assert reader.read({**obs, "counts": {}}, args) is None


def test_two_segments_are_served_by_the_oracles_rewrite():
    """The device path holds one scoring segment; a shard of several is
    the unbatched executor's, counted, and still right."""
    cluster = ClusterService()
    try:
        cluster.create_index("two", {
            "settings": {"number_of_shards": 1, "search.backend": "jax"},
            "mappings": {"properties": {"body": {"type": "text"}}}})
        idx = cluster.indices["two"]
        idx.index_doc("1", {"body": "search engines rank passages"})
        idx.refresh()
        idx.index_doc("2", {"body": "a saerch box forgives a typo"})
        idx.index_doc("3", {"body": "nothing to see"})
        idx.refresh()
        query = {"match": {"body": {"query": "serach",
                                    "fuzziness": "AUTO"}}}
        got = idx.search({"query": query})
        assert [h["_id"] for h in got["hits"]["hits"]] == ["1", "2"]
        from elasticsearch_tpu.search.executor import NumpyExecutor

        ex = idx._executor(idx.local_shard(0))
        want = NumpyExecutor(ex.reader).search(dsl.parse_query(query))
        np.testing.assert_allclose(
            [h["_score"] for h in got["hits"]["hits"]],
            [h.score for h in want.hits], rtol=2e-6)
        # one blended idf over the SHARD's dictionary: `search` (one
        # transposition) outranks `saerch` (two edits) by its boost alone
        assert ex.fuzzy_terms("body", "serach", AUTO) == ["search", "saerch"]
    finally:
        cluster.close() if hasattr(cluster, "close") else None
