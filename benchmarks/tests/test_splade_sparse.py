"""The learned-sparse deployment's files, without a chip:

- `references/impact_sum.py` against a hand-worked corpus of eight
  passages: which passages a weighted-token query matches, their order
  and their scores under both stored formats (a scalar loop over the
  stated int8 formula), `hits.total` on both sides of 10,000 (a counting
  corpus);
- the plain reference in bfloat16 comes out NOT correct under the
  comparison that decides `correct`, by pages or by scores; in full
  precision correct;
- the readers this configuration brings on made-up observations: the
  bytes function of `impact_scan_roofline`, a parent without the
  counters reads nothing;
- `selfcheck.py` `check_forms` holds `BENCHMARK.json` and the new files
  to the contract's forms;
- the rehearsal of the new cell runs whole on the CPU at `rehearse_docs`
  (a child process: server, load generator, profiler window, reference,
  control) and its control reads `correct` false.

    python3 -m pytest benchmarks/tests -q        (not part of tier-1)
"""

import json
import os
import re
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

CONFIG = "msmarco-splade-sparse"
CELL = f"{CONFIG}.solo"

# eight passages over four tokens, float32 weights
PASSAGES = [
    {7: 2.0, 9: 0.5},            # 0
    {7: 1.0},                    # 1
    {9: 3.0, 11: 0.25},          # 2
    {7: 0.013, 11: 1.5},         # 3: 0.013 stores as q = 1 of scale 2/127
    {11: 0.75},                  # 4
    {7: 0.004, 9: 1.0, 11: 0.1},  # 5: 0.004 stores as 0, and still matches
    {30: 1.0},                   # 6
    {9: 0.5},                    # 7
]


def raw_postings():
    terms = sorted({t for p in PASSAGES for t in p})
    start, doc, w = [0], [], []
    for t in terms:
        for d, p in enumerate(PASSAGES):
            if t in p:
                doc.append(d)
                w.append(p[t])
        start.append(len(doc))
    return {"field": "splade", "docs": len(PASSAGES),
            "terms": np.array(terms, np.int64),
            "post_start": np.array(start, np.int64),
            "post_doc": np.array(doc, np.int32),
            "post_w": np.array(w, np.float32)}


def stored(token: int, weight: float, fmt: str) -> np.float32:
    if fmt == "float32":
        return np.float32(weight)
    top = max(np.float32(p[token]) for p in PASSAGES if token in p)
    scale = np.float32(top) / np.float32(127.0)
    return np.float32(np.rint(np.float32(weight) / scale)) * scale


def body(vector: dict, size: int = 10) -> dict:
    return {"query": {"sparse_vector": {
        "field": "splade",
        "query_vector": {f"t{t:05d}": w for t, w in vector.items()}}},
        "size": size, "_source": False}


@pytest.mark.parametrize("fmt", ["int8", "float32"])
@pytest.mark.parametrize("vector", [
    {7: 1.5}, {7: 1.0, 9: 2.0}, {9: 0.3, 11: 1.1, 30: 4.0},
    {7: 0.5, 9: 0.5, 11: 0.5, 30: 0.5, 12345: 9.0}],
    ids=["one_token", "two", "three", "a_token_no_passage_holds"])
def test_reference_on_the_hand_worked_corpus(fmt, vector):
    ref = load_plugin("references", "impact_sum").Reference(
        raw_postings(), {"guarantees": {"stored": fmt}})
    got = ref.answer(body(vector, size=8))
    want = {}
    for d, p in enumerate(PASSAGES):
        shared = sorted(t for t in vector if t in p)
        if shared:
            s = np.float32(0.0)
            for t in shared:  # token order, float32 throughout
                s = np.float32(s + np.float32(vector[t]) * stored(t, p[t], fmt))
            want[d] = float(s)
    hits = [(int(h["_id"]), h["_score"]) for h in got["hits"]["hits"]]
    assert dict(hits) == want
    assert got["hits"]["total"] == {"value": len(want), "relation": "eq"}
    assert [d for d, _s in hits] == sorted(want, key=lambda d: (-want[d], d))
    if fmt == "int8" and 7 in vector and len(vector) == 1:
        # passage 5 holds token 7 at a weight that stores as 0: a hit
        assert want[5] == 0.0 and 5 in dict(hits)
    page = ref.answer(body(vector, size=2))
    assert [int(h["_id"]) for h in page["hits"]["hits"]] == [
        d for d, _s in hits[:2]]


@pytest.mark.parametrize("holders", [9_999, 10_000, 10_001])
def test_reference_total_on_both_sides_of_the_threshold(holders):
    n = 12_000
    data = {"field": "splade", "docs": n, "terms": np.array([5], np.int64),
            "post_start": np.array([0, holders], np.int64),
            "post_doc": np.arange(holders, dtype=np.int32),
            "post_w": np.linspace(3.5, 0.1, holders).astype(np.float32)}
    ref = load_plugin("references", "impact_sum").Reference(
        data, {"guarantees": {"stored": "int8"}})
    total = ref.answer(body({5: 1.0}))["hits"]["total"]
    assert total == ({"value": holders, "relation": "eq"} if holders <= 10_000
                     else {"value": 10_000, "relation": "gte"})


@pytest.mark.parametrize("query", [
    {"match": {"splade": "t00007"}},
    {"sparse_vector": {"field": "other", "query_vector": {"t00007": 1.0}}},
    {"sparse_vector": {"field": "splade", "query_vector": {"t00007": 1.0},
                       "boost": 2.0}},
    {"sparse_vector": {"field": "splade", "inference_id": "elser",
                       "query": "what is a tpu"}},
], ids=["no_sparse_vector", "another_field", "boost", "inference"])
def test_reference_raises_outside_its_semantics(query):
    ref = load_plugin("references", "impact_sum").Reference(
        raw_postings(), {"guarantees": {"stored": "int8"}})
    with pytest.raises((ValueError, KeyError)):
        ref.answer({"query": query, "size": 10})


@pytest.mark.parametrize("seed", [1, 2147483900, 3000000007])
def test_lower_precision_fails_and_full_precision_passes(seed):
    config, ref, bodies = small_cell(CONFIG, 20_000, seed, 96)
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


def test_readers_on_made_up_observations():
    roof = load_plugin("readers", "impact_scan_roofline")
    assert roof.tile_bytes("int8") == 128 * (4 + 1 + 16) == 2688
    assert roof.tile_bytes("float32") == 128 * (4 + 4 + 16)
    spec = load_json("layer_metrics", "impact_scan_roofline.json")
    obs = {"profile": {"modules": {"jit__impact_chunk_add": [40, 0.040]}},
           "counts": {"sparse.chunk_launches": 400,
                      "sparse.tiles_scored": 190_000},
           "device": {"kind": "TPU v5 lite"}, "rehearsal": False,
           "peaks": load_json("peaks.json")["by_device_kind"],
           "config": {"guarantees": {"stored": "int8"}}}
    share = roof.read(obs, spec["args"])
    # 40 traced launches of 475 tiles each, 2,688 B a tile, over 40 ms
    assert abs(share - 100 * (40 * 475 * 2688 / 819e9) / 0.040) < 1e-9
    assert 0 < share < 100
    parent = {**obs, "counts": {}}  # a program without the counters
    assert roof.read(parent, spec["args"]) is None
    assert roof.read({**obs, "profile": {"modules": {}}}, spec["args"]) is None
    with pytest.raises(KeyError):
        roof.read({**obs, "device": {"kind": "TPU v9"}}, spec["args"])
    share_of = load_plugin("readers", "count_ratio_of_sum")
    spec = load_json("layer_metrics", "impact_tiles_pruned_share.json")
    counts = {"sparse.tiles_scored": 900, "sparse.tiles_pruned": 100}
    assert share_of.read({"counts": counts}, spec["args"]) == 10.0
    assert share_of.read({"counts": {"sparse.tiles_scored": 0,
                                     "sparse.tiles_pruned": 0}},
                         spec["args"]) is None
    assert share_of.read({"counts": {}}, spec["args"]) is None
    spec = load_json("layer_metrics", "sparse_fallback_share.json")
    assert share_of.read({"counts": {"sparse.searches": 50,
                                     "sparse.fallbacks": 0}},
                         spec["args"]) == 0.0


def test_forms_hold_with_the_new_files():
    check_forms()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "solo", 1)
    assert bench["workloads"][-1] is cell and bench["configs"][-1]["name"] == CONFIG
    listed = {m["name"] for kind in ("end_to_end", "per_layer")
              for m in bench[kind] if CELL in m.get("workloads", [])}
    assert listed == {
        "latency_p50_ms", "latency_p95_ms", "fan_out_ms", "shard_search_ms",
        "plan_ms", "fan_out_handover_ms", "fan_out_inline_share",
        "impact_kernel_ms", "impact_launches_per_req", "impact_tiles_per_req",
        "impact_tiles_pruned_share", "sparse_theta_ms",
        "sparse_fallback_share", "impact_scan_roofline"}
    config = load_json("configs", f"{CONFIG}.json")
    assert set(config["reduced"]) == {"docs", "ingest", "fields"}
    assert "index.sparse.quantization" not in config["settings"]
    assert config["corpus"]["args"]["vocab"] == 30_522


def test_rehearsal_runs_whole_and_its_control_fails():
    """`run.py --rehearse --control 1 --trace 1` of the new cell, as the
    sandbox can run it: exit 3, no result line, the would-be result
    `correct` with nothing failed or built in the window, the control
    NOT correct, the counters' metrics read."""
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
    assert re.search(r"recall of the stored impacts' top pages .* mean 0\.9|"
                     r"mean 1\.0", run.stdout)
    line = next(ln for ln in run.stderr.splitlines()
                if ln.startswith("REHEARSAL on "))
    result = json.loads(line[line.index("no result: ") + len("no result: "):])
    assert result["correct"] is True and result["failed"] == 0
    m = {k: v["value"] for k, v in result["metrics"].items()}
    assert m["unplanned_query_share"] == 0.0
    assert m["sparse_fallback_share"] == 0.0
    assert m["fan_out_inline_share"] == 100.0
    assert m["impact_launches_per_req"] >= 1.0
    assert m["impact_tiles_per_req"] > 100
    assert 0.0 <= m["impact_tiles_pruned_share"] < 100.0
    assert m["sparse_theta_ms"] > 0 and m["host_syncs_per_req"] >= 1.0
