"""The hybrid deployment's files, without a chip:

- `msmarco-hybrid-rrf`'s builder puts BOTH fields into one segment at
  `rehearse_docs`, each exactly what the configuration it is taken from
  builds (the passage configuration's postings under another field name,
  the kNN configuration's rows), and hands the reference both payloads;
- its bodies are upstream's retriever form at the stated window, k and
  rank constant, the words and the vector the two existing generators'
  own draws;
- its plain reference fuses the two existing references' legs by the
  published rule, reports the union's `hits.total`, and in bfloat16 comes
  out NOT correct under the comparison that decides `correct` (by pages:
  RRF scores are exact rationals of the ranks), in full precision correct;
- `selfcheck.py` passes with the new files, and a `--rehearse` run of the
  cell on the CPU exits 3 having compared 64 answers.

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

CONFIG = "msmarco-hybrid-rrf"
CELL = "msmarco-hybrid-rrf.solo"


def test_one_segment_holds_both_fields_at_rehearse_docs():
    config = load_json("configs", f"{CONFIG}.json")
    args, docs = config["corpus"]["args"], int(config["rehearse_docs"])
    corpus = load_plugin("corpora", config["corpus"]["builder"]).build(
        config, 5, docs)
    seg = corpus["segment"]
    assert seg.num_docs == docs
    assert set(seg.postings) == {"text"} and set(seg.vectors) == {"vec"}
    assert set(corpus["mappings"]["properties"]) == {"text", "vec"}
    assert seg.vectors["vec"].vectors.shape == (docs, args["dims"])
    assert seg.vectors["vec"].vectors.dtype == np.float16
    # each field is what the configuration it is taken from builds
    passage = load_json("configs", "msmarco-passage-bm25.json")
    alone = load_plugin("corpora", "zipf_text").build(passage, 5, docs)
    for key in ("lengths", "post_start", "post_doc", "post_tf"):
        assert (corpus["reference"]["text"][key]
                == alone["reference"][key]).all(), key
    assert ({**passage["corpus"]["args"], "field": "text"} == args["text"])
    knn = load_json("configs", "msmarco-knn768.json")
    assert ({**args["vector"], "dims": args["dims"]}
            == knn["corpus"]["args"])
    rows = load_plugin("corpora", "unit_vectors").build(knn, 5, 4096)
    assert (corpus["reference"]["vector"]["vectors"][:4096]
            == rows["reference"]["vectors"]).all()
    ref = corpus["reference"]
    assert ref["docs"] == ref["text"]["docs"] == ref["vector"]["docs"] == docs
    ctx = corpus["body_context"]
    assert ctx["text"]["field"] == "text" and ctx["vector"]["field"] == "vec"


def test_bodies_are_the_retriever_form_over_the_two_generators_draws():
    config, _ref, bodies = small_cell(CONFIG, 20_000, 4, 200)
    a = config["body"]["args"]
    for b in bodies:
        assert set(b) == {"retriever", "size", "_source"}
        assert b["size"] == 10 and b["_source"] is False
        rrf = b["retriever"]["rrf"]
        assert rrf["rank_window_size"] == 100 and rrf["rank_constant"] == 60
        standard, knn = rrf["retrievers"]
        words = standard["standard"]["query"]["match"]["text"].split()
        assert 2 <= len(words) <= 12 and len(set(words)) == len(words)
        knn = knn["knn"]
        assert (knn["field"], knn["k"], knn["num_candidates"]) == (
            "vec", 100, 100)
        assert len(knn["query_vector"]) == 768
        assert abs(np.linalg.norm(knn["query_vector"]) - 1.0) < 1e-4
    # the words are `match_terms`' own draws from the same generator state
    corpus = load_plugin("corpora", config["corpus"]["builder"]).build(
        config, 4, 20_000)
    ours = load_plugin("bodies", config["body"]["generator"]).make(
        corpus["body_context"], a, np.random.default_rng(8), 50)
    theirs = load_plugin("bodies", "match_terms").make(
        corpus["body_context"]["text"],
        {"size": a["size"], "words_histogram": a["words_histogram"]},
        np.random.default_rng(8), 50)
    assert [json.loads(b)["retriever"]["rrf"]["retrievers"][0]["standard"]
            ["query"] for b in ours] == [json.loads(b)["query"]
                                         for b in theirs]
    assert 7_800 < np.mean([len(b) for b in ours]) < 8_100  # ~7.9 KB


def test_reference_fuses_the_two_references_legs_and_counts_the_union():
    config, ref, bodies = small_cell(CONFIG, 20_000, 6, 24)
    for body, got in zip(bodies, ref.answer_many(bodies)):
        rrf = body["retriever"]["rrf"]
        text = ref.text.answer(
            {"query": rrf["retrievers"][0]["standard"]["query"],
             "size": 100})["hits"]["hits"]
        (knn,) = ref.knn.answer_many(
            [{"knn": rrf["retrievers"][1]["knn"], "size": 100}])
        knn = knn["hits"]["hits"]
        assert len(knn) == 100  # the window, not the 64 a page of 10 needs
        score: dict = {}
        for leg in (text, knn):
            for rank, h in enumerate(leg, 1):
                score[h["_id"]] = score.get(h["_id"], 0.0) + 1.0 / (60 + rank)
        best = sorted(score.items(), key=lambda kv: (-kv[1], int(kv[0])))[:10]
        assert [(h["_id"], h["_score"]) for h in got["hits"]["hits"]] == best
        plane = ref.text_plane(rrf["retrievers"][0]["standard"]["query"])
        union = len(set(np.flatnonzero(plane).tolist())
                    | {int(h["_id"]) for h in knn})
        assert got["hits"]["total"] == (
            {"value": union, "relation": "eq"} if union <= 10_000
            else {"value": 10_000, "relation": "gte"})


@pytest.mark.parametrize("seed", [1, 2147483900, 3000000007])
def test_lower_precision_fails_by_pages_and_full_precision_passes(seed):
    config, ref, bodies = small_cell(CONFIG, 20_000, seed, 64)
    g = config["guarantees"]
    refs = ref.answer_many([reference_body(g["rule"], b) for b in bodies])
    sound = compare_all(g, bodies, ref.answer_many(bodies), refs)
    assert sound["correct"], sound
    control = compare_all(
        g, bodies, ref.answer_many(bodies, precision="lower"), refs)
    assert not control["correct"], control
    # bfloat16 leg scores reorder ranks inside a leg: pages differ (and
    # now and then a total, where the kNN leg holds other passages)
    assert control["numbers"]["page_mismatches"][0] >= 5, control


def test_selfcheck_passes_with_the_new_files():
    p = subprocess.run([sys.executable, os.path.join(HERE, "selfcheck.py")],
                       text=True, capture_output=True, timeout=900)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]


def test_rehearsal_of_the_cell_compares_64_answers_and_exits_3():
    env = {k: v for k, v in os.environ.items() if not k.startswith("ES_TPU_")}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload", CELL,
         "--seed", "3000000011", "--seconds", "10", "--trace", "1",
         "--rehearse", "--control", "1"],
        env=env, cwd=ROOT, text=True, capture_output=True, timeout=1500)
    assert p.returncode == 3, p.stdout[-3000:] + p.stderr[-3000:]
    said = p.stdout
    assert "check answers_checked = 64 " in said, said[-3000:]
    for number in ("total_mismatches", "page_mismatches",
                   "programs_built_in_window"):
        assert f"check {number} = 0 " in said, said[-3000:]
    assert "control correct = False" in said
    line = [ln for ln in p.stderr.splitlines() if ln.startswith("REHEARSAL")]
    result = json.loads(line[-1].split("no result: ", 1)[1])
    assert result["correct"] is True and result["failed"] == 0
    for metric in ("front_ms", "coordinator_ms", "rrf_ms", "leg_text_ms",
                   "leg_knn_ms", "rrf_fuse_ms", "legs_overlap_ms",
                   "host_syncs_per_req", "h2d_bytes_per_req",
                   "d2h_bytes_per_req", "rare_slots_scattered_share"):
        assert metric in result["metrics"], metric
    assert result["metrics"]["rrf_device_fused_share"]["value"] == 100.0
    # a hybrid request answers through the retriever path alone
    assert "fan_out_ms" not in result["metrics"]
    assert "shard_search_ms" not in result["metrics"]
