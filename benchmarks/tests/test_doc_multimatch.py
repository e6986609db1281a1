"""The document deployment's data and its control, without a chip:

- `msmarco-doc-multimatch`'s data have the shapes its file states (mean
  body and title lengths, the Heaps-scaled vocabulary shared by both
  fields, every title word one of its own document's body tokens, mean
  words a question, the stop-word class among them), the collection's
  statistics stay fixed across seeds, and at `rehearse_docs` at least one
  term's largest tf passes 255 (what the deployment forces on the
  program's dense hot-term rows);
- the plain reference in bfloat16 comes out NOT correct under the
  comparison that decides `correct`, in full precision correct.

    python3 -m pytest benchmarks/tests -q        (not part of tier-1)
"""

import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [HERE, os.path.dirname(HERE)]

from compare import compare_all, reference_body  # noqa: E402
from plugins import load_json, load_plugin  # noqa: E402
from selfcheck import small_cell  # noqa: E402

CONFIG = "msmarco-doc-multimatch"


def build(seed: int, docs: int) -> dict:
    config = load_json("configs", f"{CONFIG}.json")
    return load_plugin("corpora", config["corpus"]["builder"]).build(
        config, seed, docs)


def test_shapes_at_rehearse_docs():
    config = load_json("configs", f"{CONFIG}.json")
    args, docs = config["corpus"]["args"], int(config["rehearse_docs"])
    corpus = build(1, docs)
    raw, ctx = corpus["reference"]["fields"], corpus["body_context"]
    body, title = raw["body"], raw["title"]
    assert abs(body["lengths"].mean() / args["body_length"]["mean"] - 1) < 0.02
    assert abs(title["lengths"].mean() / args["title_length"]["mean"] - 1) < 0.02
    assert int(body["post_tf"].sum()) == int(body["lengths"].sum())
    vocab = round(args["vocab_at_anchor"] * (
        docs * args["tokens_per_doc_at_anchor_analyzer"]
        / args["tokens_at_anchor"]) ** args["heaps_beta"])
    assert len(ctx["term_total_tf"]) == vocab
    assert len(body["post_start"]) == len(title["post_start"]) == vocab + 1
    seg = corpus["segment"]
    assert set(seg.postings) == {"title", "body"}
    # one dictionary: a title's term is the same string as the body's
    assert set(seg.postings["title"].terms) <= set(seg.postings["body"].terms)
    # every title word is one of its own document's body tokens: each
    # (term, doc) of the title is a (term, doc) of the body, at no higher tf
    def keys(f):
        term = np.repeat(np.arange(vocab, dtype=np.int64),
                         np.diff(f["post_start"]))
        return term * docs + f["post_doc"]
    at = np.searchsorted(keys(body), keys(title))
    assert (keys(body)[at] == keys(title)).all()
    assert (title["post_tf"] <= body["post_tf"][at]).all()
    # what the deployment forces: a stop word's tf passes 255 in the tail
    over = np.flatnonzero(body["post_tf"] > 255)
    assert len(over) > 100 and int(body["post_tf"].max()) < 65536
    assert np.searchsorted(body["post_start"], over[0], side="right") == 1
    raw_bodies = load_plugin("bodies", config["body"]["generator"]).make(
        ctx, config["body"]["args"], np.random.default_rng(4), 4000)
    asked = [json.loads(b) for b in raw_bodies]
    assert all(b["query"]["multi_match"]["fields"] == ["title", "body"]
               and b["query"]["multi_match"]["tie_breaker"] == 0.3
               and "type" not in b["query"]["multi_match"] for b in asked)
    words = [b["query"]["multi_match"]["query"].split() for b in asked]
    mean_words = sum(map(len, words)) / len(words)
    assert 5.7 < mean_words < 6.3, mean_words  # MS MARCO: ~6 words
    assert all(len(set(w)) == len(w) for w in words)
    top = {f"w{t:0{ctx['term_width']}d}" for t in range(5)}
    share = sum(t in top for w in words for t in w) / sum(map(len, words))
    assert 0.12 < share < 0.19, share  # the five most frequent terms: ~15%


def test_statistics_fixed_across_seeds():
    a, b = (build(seed, 20_000)["reference"]["fields"] for seed in (1, 2))
    for f in ("title", "body"):
        assert (a[f]["post_start"] == b[f]["post_start"]).all()
        assert sorted(a[f]["lengths"]) == sorted(b[f]["lengths"])
        assert not (a[f]["lengths"] == b[f]["lengths"]).all()


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
