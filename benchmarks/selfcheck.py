"""Checks of the yardstick itself; no chip, a few seconds:

    python3 benchmarks/selfcheck.py

- the trace reducer on the recorded `sample_trace/sample.xplane.pb` (five
  2048^3 bf16 products of 90.1 us each on a TPU v5e, 20 ms pauses
  between them) gives the busy time, launches and gaps it is known to have;
- percentile arithmetic on fixed lists;
- the comparison rule accepts equal pages and rejects pages scored with
  bf16-rounded products (kNN) or bf16 scores (text), at a small size;
- the text configuration's data have the shapes its file states from the
  source (mean passage length, Heaps-scaled vocabulary, mean words a
  query, stop-word class among the query words);
- `BENCHMARK.json` and the data files keep to the contract's forms.
"""

from __future__ import annotations

import json
import os
import re
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [HERE, ROOT]

from plugins import load_json, load_plugin as plugin  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def check_trace_reducer() -> None:
    from tracereduce import merge, reduce_trace

    assert merge([[5, 7], [0, 2], [1, 3], [7, 8]]) == [[0, 3], [5, 8]]
    r = reduce_trace(os.path.join(HERE, "sample_trace", "sample.xplane.pb"))
    assert r["devices"] == 1
    assert abs(r["busy_s"] - 450.62e-6) < 0.05e-6, r["busy_s"]
    assert r["modules"]["jit__lambda"][0] == 5
    assert abs(r["modules"]["jit__lambda"][1] - 450.656e-6) < 0.05e-6
    assert abs(r["span_s"] - 86.681652e-3) < 1e-6, r["span_s"]
    long_gaps = [s for _n, s in r["idle_gaps"] if s > 1e-3]
    assert len(long_gaps) == 4 and all(21.4e-3 < s < 21.7e-3
                                       for s in long_gaps), long_gaps
    assert r["idle_gaps"][0][0].startswith("unknown.before.jit__lambda")
    assert r["device_ops"][0][0].startswith("%convolution_reduce_fusion")
    # busy + idle = span, to the nanosecond
    idle = r["span_s"] - r["busy_s"]
    assert abs(idle - 86.231032e-3) < 1e-6, idle


def check_percentiles() -> None:
    from stats import median, percentile

    xs = [15.0, 20.0, 35.0, 40.0, 50.0]
    assert median(xs) == 35.0
    assert percentile(xs, 0) == 15.0 and percentile(xs, 100) == 50.0
    assert abs(percentile(xs, 40) - 29.0) < 1e-12
    assert abs(percentile(xs, 95) - 48.0) < 1e-12
    rng = np.random.default_rng(0).random(1001).tolist()
    for q in (5, 50, 95, 99):
        assert abs(percentile(rng, q) - float(np.percentile(rng, q))) < 1e-12


def small_cell(config_name: str, docs: int, seed: int, n_bodies: int):
    config = load_json("configs", f"{config_name}.json")
    corpus = plugin("corpora", config["corpus"]["builder"]).build(
        config, seed, docs)
    ref = plugin("references", config["reference"]).Reference(
        corpus["reference"], config)
    raw = plugin("bodies", config["body"]["generator"]).make(
        corpus["body_context"], config["body"]["args"],
        np.random.default_rng([seed, 9]), n_bodies)
    return config, ref, [json.loads(b) for b in raw]


def check_compare() -> None:
    from compare import compare_all, reference_body

    for name in ("msmarco-knn768", "msmarco-passage-bm25"):
        config, ref, bodies = small_cell(name, 20_000, 3, 48)
        g = config["guarantees"]
        refs = ref.answer_many([reference_body(g["rule"], b) for b in bodies])
        same = compare_all(g, bodies, ref.answer_many(bodies), refs)
        assert same["correct"], (name, same)
        low = compare_all(g, bodies,
                          ref.answer_many(bodies, precision="lower"), refs)
        assert not low["correct"], (name, low)
        assert low["numbers"]["score_rel_max"][0] > 10 * g["score_rtol"], low
        # a page with one id swapped for a doc that is not a tie
        served = ref.answer_many(bodies)
        served[0]["hits"]["hits"][0]["_id"] = "not-a-doc"
        bad = compare_all(g, bodies, served, refs)
        assert not bad["correct"] and bad["numbers"]["page_mismatches"][0] >= 1


def check_shapes() -> None:
    """The widths of the text deployment against the numbers its file
    takes from the source, at the rehearsal's size."""
    config = load_json("configs", "msmarco-passage-bm25.json")
    args, docs = config["corpus"]["args"], 20_000
    corpus = plugin("corpora", config["corpus"]["builder"]).build(
        config, 1, docs)
    ref, ctx = corpus["reference"], corpus["body_context"]
    assert abs(ref["lengths"].mean() - args["length"]["mean"]) < 1.0
    assert int(ref["post_tf"].sum()) == int(ref["lengths"].sum())
    vocab = round(args["vocab_at_source"]
                  * (docs / args["source_docs"]) ** args["heaps_beta"])
    assert len(ctx["term_total_tf"]) == vocab, (len(ctx["term_total_tf"]), vocab)
    # a second seed moves passages, not the collection's statistics
    other = plugin("corpora", config["corpus"]["builder"]).build(
        config, 2, docs)["reference"]
    assert (np.diff(other["post_start"]) == np.diff(ref["post_start"])).all()
    assert not (other["lengths"] == ref["lengths"]).all()
    raw = plugin("bodies", config["body"]["generator"]).make(
        ctx, config["body"]["args"], np.random.default_rng(4), 4000)
    words = [json.loads(b)["query"]["match"]["body"].split() for b in raw]
    mean_words = sum(map(len, words)) / len(words)
    assert 5.7 < mean_words < 6.3, mean_words  # MS MARCO dev: ~6 words
    assert all(len(set(w)) == len(w) for w in words)
    top = {f"w{t:0{ctx['term_width']}d}" for t in range(50)}
    stop_share = (sum(t in top for w in words for t in w)
                  / sum(map(len, words)))
    assert 0.25 < stop_share < 0.45, stop_share  # the stop-word class is in


def check_forms() -> None:
    path = os.path.join(ROOT, "BENCHMARK.json")
    assert os.path.getsize(path) <= 64 * 1024
    with open(path) as f:
        b = json.load(f)
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= b["run_seconds"] <= 51 and len(b["command"]) <= 32
    for p in b["paths"]:
        assert PATH.match(p) and ".." not in p and not p.startswith("/")

    def line(s):
        return 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s

    names = set()
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}, c
        assert NAME.match(c["name"]) and line(c["source"]) and line(c["why"])
        assert c["file"].startswith(tuple(p + "/" for p in b["paths"]))
        assert len(c["reduced"]) <= 16 and all(NAME.match(k)
                                               for k in c["reduced"])
        with open(os.path.join(ROOT, c["file"])) as f:
            conf = json.load(f)
        assert conf["name"] == c["name"] and conf["source"] == c["source"]
        assert set(c["reduced"]) == set(conf["reduced"])
        for kind, key in (("corpora", conf["corpus"]["builder"]),
                          ("references", conf["reference"]),
                          ("bodies", conf["body"]["generator"])):
            assert os.path.exists(os.path.join(HERE, kind, f"{key}.py"))
        names.add(c["name"])
    cells, four = set(), 0
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}, w
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in names and w["chips"] in (1, 4) and line(w["why"])
        mix = load_json("traffic", f"{w['traffic']}.json")
        assert os.path.exists(os.path.join(HERE, "loops", f"{mix['loop']}.py"))
        assert (w["config"], w["traffic"]) not in cells
        cells.add((w["config"], w["traffic"]))
        four += w["chips"] == 4
    assert four <= max(1, len(b["workloads"]) // 2)
    assert {c for c, _t in cells} == names, "a configuration has no cell"
    cell_names = {w["name"] for w in b["workloads"]}
    e2e = set()
    for m in b["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}, m
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
        assert m["name"] not in e2e
        e2e.add(m["name"])
    assert "setup_s" in e2e
    seen = set()
    for m in b["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}, m
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["source"] in SOURCES and m["moves"] in e2e and line(m["layer"])
        assert set(m.get("workloads", cell_names)) <= cell_names
        assert m["name"] not in seen | e2e
        seen.add(m["name"])
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
        with open(os.path.join(HERE, "layer_metrics",
                               f"{m['name']}.json")) as f:
            spec = json.load(f)
        assert spec["layer"] == m["layer"] and spec["unit"] == m["unit"]
        assert spec["moves"] == m["moves"]
        assert os.path.exists(
            os.path.join(HERE, "readers", f"{spec['reader']}.py"))
    for base, _dirs, files in os.walk(HERE):
        if "__pycache__" in base:
            continue
        for name in files:
            rel = os.path.relpath(os.path.join(base, name), ROOT)
            assert PATH.match(rel), f"file name outside the contract: {rel}"
    with open(os.path.join(HERE, "peaks.json")) as f:
        peaks = json.load(f)["by_device_kind"]
    assert peaks["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9


def main() -> int:
    for check in (check_trace_reducer, check_percentiles, check_forms,
                  check_shapes, check_compare):
        check()
        print(f"ok  {check.__name__}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
