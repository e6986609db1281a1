"""The late-interaction rescoring deployment's files, without a chip:

- `msmarco-colbert-rescore`'s builder puts the passage configuration's
  text (under another field name) and a byte `rank_vectors` plane into
  one segment: a passage's token vectors follow its own word count, the
  plane has one size on every seed, the bytes follow the stated law;
- its bodies are the passage cell's questions with a `rescore` of window
  1,000 over 32 unit query vectors of 128 floats, ~41 KB each;
- its plain reference re-ranks `bm25_match`'s window by float64 MaxSim
  over each candidate's own rows, and with bfloat16 query rows and
  products comes out NOT correct under the comparison that decides
  `correct`, by the score limit;
- the per-layer readers return nothing (and do not raise) where the
  program has no such counter;
- `selfcheck.py` passes with the new files; a `--rehearse` run of the
  cell on the CPU exits 3 with `correct` true, the control breaching and
  every scoped metric that needs no device present; the same run with
  the rescore skipped underneath reads `correct` false.

    python3 -m pytest benchmarks/tests -q        (not part of tier-1)
"""

import json
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
from selfcheck import small_cell  # noqa: E402

CONFIG = "msmarco-colbert-rescore"
CELL = "msmarco-colbert-rescore.solo"


def test_one_segment_holds_the_text_and_the_byte_token_plane():
    config = load_json("configs", f"{CONFIG}.json")
    args, docs = config["corpus"]["args"], 20_000
    build = load_plugin("corpora", config["corpus"]["builder"]).build
    corpus = build(config, 5, docs)
    seg = corpus["segment"]
    assert set(seg.postings) == {"text"} and set(seg.multi_vectors) == {"tok"}
    assert corpus["mappings"]["properties"]["tok"] == {
        "type": "rank_vectors", "element_type": "byte", "dims": 128,
        "similarity": "dot_product"}
    # the text is what the passage configuration builds
    passage = load_json("configs", "msmarco-passage-bm25.json")
    assert {**passage["corpus"]["args"], "field": "text"} == args["text"]
    alone = load_plugin("corpora", "zipf_text").build(passage, 5, docs)
    for key in ("lengths", "post_start", "post_doc", "post_tf"):
        assert (corpus["reference"]["text"][key]
                == alone["reference"][key]).all(), key
    # a passage's vectors follow its own words: round(1.2 w) + 2, <= 180
    mvf = seg.multi_vectors["tok"]
    words = corpus["reference"]["text"]["lengths"]
    counts = np.diff(mvf.tok_offsets)
    assert (counts == np.minimum(np.rint(1.2 * words) + 2, 180)).all()
    assert 66 < counts.mean() < 72 and counts.max() <= 180
    assert mvf.tok_vectors.dtype == np.int8
    assert mvf.tok_vectors.shape == (int(counts.sum()), 128)
    # the law: zero mean, sd 127 / sqrt(128), whole numbers in +-127
    rows = mvf.tok_vectors[:200_000].astype(np.float64)
    assert abs(rows.mean()) < 0.05 and 11.0 < rows.std() < 11.5
    assert np.abs(mvf.tok_vectors).max() <= 127
    assert 120 < np.linalg.norm(rows, axis=1).mean() < 134
    # the reference is handed the same bytes and offsets, as arrays
    ref = corpus["reference"]
    assert ref["tok_rows"] is mvf.tok_vectors
    assert (ref["tok_offsets"] == mvf.tok_offsets).all()
    # another seed: the same plane size, other bytes
    other = build(config, 6, docs)["segment"].multi_vectors["tok"]
    assert other.tok_vectors.shape == mvf.tok_vectors.shape
    assert not (other.tok_vectors[:1000] == mvf.tok_vectors[:1000]).all()


def test_bodies_are_the_passage_questions_under_a_rescore_of_the_window():
    config, _ref, bodies = small_cell(CONFIG, 20_000, 4, 100)
    for b in bodies:
        assert set(b) == {"query", "size", "_source", "rescore"}
        assert b["size"] == 10 and b["_source"] is False
        words = b["query"]["match"]["text"].split()
        assert 2 <= len(words) <= 12 and len(set(words)) == len(words)
        r = b["rescore"]
        assert r["window_size"] == 1000
        assert r["query"]["query_weight"] == 0
        assert r["query"]["rescore_query_weight"] == 1
        rv = r["query"]["rescore_query"]["rank_vectors"]
        q = np.asarray(rv["query_vectors"])
        assert rv["field"] == "tok" and q.shape == (32, 128)
        assert np.abs(np.linalg.norm(q, axis=1) - 1.0).max() < 1e-4
    corpus = load_plugin("corpora", config["corpus"]["builder"]).build(
        config, 4, 20_000)
    a = config["body"]["args"]
    ours = load_plugin("bodies", config["body"]["generator"]).make(
        corpus["body_context"], a, np.random.default_rng(8), 50)
    theirs = load_plugin("bodies", "match_terms").make(
        corpus["body_context"]["text"],
        {"size": a["size"], "words_histogram": a["words_histogram"]},
        np.random.default_rng(8), 50)
    assert ([json.loads(b)["query"] for b in ours]
            == [json.loads(b)["query"] for b in theirs])
    assert 40_500 < np.mean([len(b) for b in ours]) < 42_000  # ~41 KB


def test_reference_reranks_the_bm25_window_by_each_candidates_own_rows():
    config, ref, bodies = small_cell(CONFIG, 20_000, 6, 6)
    for body in bodies:
        body = {**body, "rescore": {**body["rescore"], "window_size": 50}}
        got = ref.answer(body)
        first = ref.text.answer({"query": body["query"], "size": 50})
        assert got["hits"]["total"] == first["hits"]["total"]
        q = np.asarray(body["rescore"]["query"]["rescore_query"]
                       ["rank_vectors"]["query_vectors"], np.float64)
        scored = []
        for rank, h in enumerate(first["hits"]["hits"]):
            d = int(h["_id"])
            rows = ref.rows[ref.offsets[d]:ref.offsets[d + 1]]
            sims = q @ rows.astype(np.float64).T
            scored.append((-sims.max(axis=1).sum(), rank, h["_id"]))
        best = sorted(scored)[:10]
        assert [h["_id"] for h in got["hits"]["hits"]] == [
            b[2] for b in best]
        assert [h["_score"] for h in got["hits"]["hits"]] == pytest.approx(
            [-b[0] for b in best], rel=1e-12)
        # a window shorter than the page leaves the tail in BM25's order
        short = ref.answer({**body, "rescore": {**body["rescore"],
                                                "window_size": 4}})
        assert [h["_id"] for h in short["hits"]["hits"][4:]] == [
            h["_id"] for h in first["hits"]["hits"][4:10]]


@pytest.mark.parametrize("seed", [1, 2147483900])
def test_lower_precision_fails_by_scores_and_full_precision_passes(seed):
    config, ref, bodies = small_cell(CONFIG, 20_000, seed, 24)
    g = config["guarantees"]
    refs = ref.answer_many([reference_body(g["rule"], b) for b in bodies])
    sound = compare_all(g, bodies, ref.answer_many(bodies), refs)
    assert sound["correct"], sound
    control = compare_all(
        g, bodies, ref.answer_many(bodies, precision="lower"), refs)
    assert not control["correct"], control
    assert control["numbers"]["score_rel_max"][0] > 10 * g["score_rtol"]
    assert control["numbers"]["total_mismatches"][0] == 0


def test_new_readers_give_nothing_where_the_program_counts_nothing():
    roof = load_plugin("readers", "maxsim_gather_roofline")
    spec = load_json("layer_metrics", "maxsim_gather_roofline.json")
    obs = {"profile": {"modules": {}}, "counts": {}, "device": {"kind": "x"},
           "peaks": {}, "rehearsal": False,
           "config": {"corpus": {"args": {"dims": 128}}}}
    assert roof.read(obs, spec["args"]) is None
    obs["profile"]["modules"] = {"jit__maxsim_rescore": (10, 0.05)}
    assert roof.read(obs, spec["args"]) is None  # launches, no counters
    obs["counts"] = {"rescore.tokens_scored": 69_000 * 20,
                     "rescore.windows_docs": 1000 * 20,
                     "rescore.launches": 20}
    obs["device"]["kind"] = "TPU v5 lite"
    obs["peaks"] = load_json("peaks.json")["by_device_kind"]
    share = roof.read(obs, spec["args"])
    # 8.84e6 B a launch at 819e9 B/s = 10.8 us of the 5 ms it took
    assert share == pytest.approx(
        100 * (69_000 * 128 + 8000) / 819e9 / 5e-3, rel=1e-9)
    assert roof.least_bytes(69_000, 1000, 128) == 69_000 * 128 + 8000
    for name in ("rerank_tokens_per_req", "rerank_padded_share",
                 "rerank_skipped_share", "window_ties_refilled_share",
                 "rescore_ms", "rerank_plan_ms", "maxsim_kernel_ms",
                 "first_stage_kernel_ms"):
        spec = load_json("layer_metrics", f"{name}.json")
        empty = {"counts": {}, "spans_ms": {}, "profile": {"modules": {}}}
        assert load_plugin("readers", spec["reader"]).read(
            empty, spec["args"]) is None, name


def test_selfcheck_passes_with_the_new_files():
    p = subprocess.run([sys.executable, os.path.join(HERE, "selfcheck.py")],
                       text=True, capture_output=True, timeout=900)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]


DRIVER = r"""
import json, sys
sys.path[:0] = [{bench!r}, {root!r}]
import run
if {broken}:
    # the rescore skipped underneath: every request answers its
    # first-stage ranking with HTTP 200
    from elasticsearch_tpu.common import settings
    settings.rerank_mode = lambda: "off"
result = run.run_cell({cell!r}, seed=3000000053, seconds=6.0, trace=True,
                      rehearse=True, control=not {broken})
print("RESULT " + json.dumps(result))
"""


def drive(broken: bool):
    code = DRIVER.format(bench=HERE, root=ROOT, broken=broken, cell=CELL)
    env = {k: v for k, v in os.environ.items() if not k.startswith("ES_TPU_")}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                       text=True, stdout=subprocess.PIPE, timeout=1500)
    assert p.returncode == 0, p.stdout[-3000:]
    line = [ln for ln in p.stdout.splitlines() if ln.startswith("RESULT ")]
    return json.loads(line[-1][len("RESULT "):]), p.stdout


def test_rehearsal_of_the_cell_is_correct_and_the_control_breaches():
    result, said = drive(False)
    assert result["correct"] is True and result["failed"] == 0, result
    assert result["checks"]["answers_checked"][0] >= 8, result
    for number in ("total_mismatches", "page_mismatches",
                   "programs_built_in_window"):
        assert result["checks"][number][0] == 0, result["checks"]
    assert result["checks"]["score_rel_max"][0] < 1e-6
    assert "control correct = False" in said
    m = result["metrics"]
    for metric in ("rescore_ms", "rerank_plan_ms", "rerank_tokens_per_req",
                   "rerank_padded_share", "rerank_skipped_share",
                   "window_ties_refilled_share", "fan_out_ms",
                   "shard_search_ms", "plan_ms", "launch_ms", "download_ms",
                   "unpack_ms", "rare_slots_scattered_share"):
        assert metric in m, metric
    assert m["rerank_skipped_share"]["value"] == 0.0
    # the window, not the page, is rescored
    assert 55_000 < m["rerank_tokens_per_req"]["value"] < 75_000
    assert 55 < m["rerank_padded_share"]["value"] < 70


def test_a_skipped_rescore_reads_correct_false():
    result, _said = drive(True)
    assert result["attempted"] > 0 and result["failed"] == 0, result
    assert result["correct"] is False, result
    assert result["checks"]["page_mismatches"][0] > 0, result
    assert result["metrics"]["rerank_skipped_share"]["value"] == 100.0
