"""The filtered vector-search deployment's files, without a chip:

- `references/l2_filtered_knn.py` against a hand-worked corpus of eight
  rows: which rows a filter of one or two tags passes, their order and
  their scores as whole-number distances give them, pages shorter than
  `size`, the hit past a full page that `compare.py` asks for;
- the plain reference accumulating in bfloat16 comes out NOT correct
  under the comparison that decides `correct`, by pages or by scores; in
  full precision correct;
- the corpus builder's two layouts hold the same (row, tag) pairs, and
  the int8 rows are the uint8 rows less 128;
- the reader this configuration brings on made-up observations: the
  bytes function of `knn_filtered_roofline`, a parent without the
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

CONFIG = "yfcc10m-filtered-knn"
CELL = f"{CONFIG}.solo"

# eight bags over five tags; bag i lives in row ROW_OF[i]
BAGS = [[1, 2], [2], [1, 2, 3], [3], [1], [], [2, 3], [1, 2]]
ROW_OF = [5, 0, 7, 2, 1, 6, 3, 4]
ROWS_U8 = np.array([[10 * r + c for c in range(4)] for r in range(8)],
                   np.uint8)


def raw_stream() -> dict:
    start = np.zeros(len(BAGS) + 1, np.int64)
    np.cumsum([len(b) for b in BAGS], out=start[1:])
    return {"field": "vec", "tag_field": "tags", "docs": 8, "tag_width": 6,
            "vectors": ROWS_U8, "bag_start": start,
            "bag_tags": np.array([t for b in BAGS for t in b], np.int32),
            "bag_row": np.array(ROW_OF, np.int32)}


def body(tags, q_u8, k=10, size=10) -> dict:
    return {"knn": {"field": "vec", "k": k, "num_candidates": 100,
                    "query_vector": [int(x) - 128 for x in q_u8],
                    "filter": {"bool": {"filter": [
                        {"term": {"tags": f"t{t:06d}"}} for t in tags]}}},
            "size": size, "_source": False}


@pytest.mark.parametrize("tags", [[1], [2], [1, 2], [1, 3], [3, 2], [4]],
                         ids=lambda t: "+".join(map(str, t)))
def test_reference_on_the_hand_worked_corpus(tags):
    ref = load_plugin("references", "l2_filtered_knn").Reference(
        raw_stream(), {})
    q = [33, 30, 35, 31]
    (got,) = ref.answer_many([body(tags, q)])
    rows = sorted(ROW_OF[i] for i, b in enumerate(BAGS)
                  if set(tags) <= set(b))
    d2 = {r: sum((int(a) - b) ** 2 for a, b in zip(ROWS_U8[r], q))
          for r in rows}
    want = sorted(rows, key=lambda r: (d2[r], r))
    hits = got["hits"]["hits"]
    assert [int(h["_id"]) for h in hits] == want
    assert [h["_score"] for h in hits] == [
        float(np.float32(1.0 / (1.0 + d2[r]))) for r in want]
    assert got["hits"]["total"] == {"value": len(rows), "relation": "eq"}


def test_reference_page_and_the_hit_past_it():
    ref = load_plugin("references", "l2_filtered_knn").Reference(
        raw_stream(), {})
    q = [0, 1, 2, 3]  # row 0's own vector: rows in id order
    page, past, short = ref.answer_many([
        body([2], q, k=3, size=3), body([2], q, k=3, size=4),
        body([2], q, k=3, size=10)])
    ids = [int(h["_id"]) for h in past["hits"]["hits"]]
    assert ids == [0, 3, 4, 5]  # tag 2: rows 0, 3, 4, 5, 7
    assert [int(h["_id"]) for h in page["hits"]["hits"]] == ids[:3]
    # a larger page holds the k winners and nothing else
    assert [int(h["_id"]) for h in short["hits"]["hits"]] == ids[:3]
    for got in (page, past, short):
        assert got["hits"]["total"] == {"value": 3, "relation": "eq"}


@pytest.mark.parametrize("filt", [
    {"range": {"tags": {"gte": "t1"}}},
    {"term": {"other": "t000001"}},
    {"bool": {"must_not": [{"term": {"tags": "t000001"}}]}},
], ids=["range", "another_field", "must_not"])
def test_reference_raises_outside_its_semantics(filt):
    ref = load_plugin("references", "l2_filtered_knn").Reference(
        raw_stream(), {})
    b = body([1], [0, 0, 0, 0])
    b["knn"]["filter"] = filt
    with pytest.raises(ValueError):
        ref.answer_many([b])


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


@pytest.mark.parametrize("seed", [3, 2147483999])
def test_the_builders_two_layouts_hold_the_same_pairs(seed):
    config = load_json("configs", f"{CONFIG}.json")
    docs = 20_000
    corpus = load_plugin("corpora", config["corpus"]["builder"]).build(
        config, seed, docs)
    ref, pf = corpus["reference"], corpus["segment"].postings["tags"]
    assert len(pf.terms) == config["corpus"]["args"]["tags"]["vocab"]
    bag = np.repeat(np.arange(docs), np.diff(ref["bag_start"]))
    stream = np.unique(ref["bag_row"][bag].astype(np.int64) * 10**6
                       + ref["bag_tags"])
    assert len(stream) == len(ref["bag_tags"])  # a bag holds a tag once
    tiles = np.repeat(np.arange(len(pf.terms)), pf.term_tile_count)
    tag = np.repeat(tiles, 128).reshape(-1, 128)
    held = pf.doc_ids >= 0
    program = np.sort(pf.doc_ids[held].astype(np.int64) * 10**6 + tag[held])
    assert (program == stream).all()
    # a term's doc ids ascend inside its tile range
    t = int(np.argmax(pf.term_df))
    lo, n = int(pf.term_tile_start[t]), int(pf.term_tile_count[t])
    ids = pf.doc_ids[lo:lo + n].ravel()[:int(pf.term_df[t])]
    assert (np.diff(ids) > 0).all() and n > 40
    vf = corpus["segment"].vectors["vec"]
    assert vf.vectors.dtype == np.int8 and ref["vectors"].dtype == np.uint8
    assert (vf.vectors.astype(np.int16) + 128 == ref["vectors"]).all()
    # another seed: other rows hold the bags, the bags are the same
    other = load_plugin("corpora", config["corpus"]["builder"]).build(
        config, seed + 1, docs)["reference"]
    assert (other["bag_tags"] == ref["bag_tags"]).all()
    assert not (other["bag_row"] == ref["bag_row"]).all()
    assert not (other["vectors"] == ref["vectors"]).all()


def test_reader_on_made_up_observations():
    roof = load_plugin("readers", "filtered_scan_roofline")
    assert roof.bytes_passed(1000, 192, 1) == 192_000
    spec = load_json("layer_metrics", "knn_filtered_roofline.json")
    obs = {"profile": {"modules": {"jit_knn_topk_filtered": [30, 0.150],
                                   "jit_knn_filter_mask": [30, 0.090]}},
           "counts": {"knn_filtered.rows_passed": 18_000_000,
                      "knn_filtered.mask_launches": 100},
           "device": {"kind": "TPU v5 lite"}, "rehearsal": False,
           "peaks": load_json("peaks.json")["by_device_kind"],
           "config": load_json("configs", f"{CONFIG}.json")}
    share = roof.read(obs, spec["args"])
    # 30 traced scans of 180,000 passing rows each, 192 B a row, over
    # the 240 ms both programs took
    assert abs(share - 100 * (30 * 180_000 * 192 / 819e9) / 0.240) < 1e-9
    assert 0 < share < 100
    # every row passing every request: still under 100
    full = {**obs, "counts": {"knn_filtered.rows_passed": 10**9,
                              "knn_filtered.mask_launches": 100}}
    assert 0 < roof.read(full, spec["args"]) < 100
    assert roof.read({**obs, "counts": {}}, spec["args"]) is None  # parent
    assert roof.read({**obs, "profile": {"modules": {}}}, spec["args"]) is None
    with pytest.raises(KeyError):
        roof.read({**obs, "device": {"kind": "TPU v9"}}, spec["args"])
    assert roof.read({**obs, "device": {"kind": "cpu"}, "rehearsal": True},
                     spec["args"]) is None


def test_forms_hold_with_the_new_files():
    check_forms()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "solo", 1)
    assert bench["workloads"][-1] is cell
    assert bench["configs"][-1]["name"] == CONFIG
    listed = {m["name"] for kind in ("end_to_end", "per_layer")
              for m in bench[kind] if CELL in m.get("workloads", [])}
    assert listed == {
        "latency_p50_ms", "latency_p95_ms", "fan_out_ms", "shard_search_ms",
        "plan_ms", "fan_out_handover_ms", "fan_out_inline_share",
        "filter_mask_ms", "filter_tiles_per_req", "filter_pass_share",
        "knn_filtered_fallback_share", "knn_byte_scan_ms",
        "knn_filtered_roofline"}
    config = load_json("configs", f"{CONFIG}.json")
    assert set(config["reduced"]) == {"ingest", "fields"}
    assert config["docs"] == 10_000_000
    args = config["corpus"]["args"]
    assert (args["dims"], args["similarity"], args["tags"]["vocab"]) == (
        192, "l2_norm", 200_386)


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
    line = next(ln for ln in run.stderr.splitlines()
                if ln.startswith("REHEARSAL on "))
    result = json.loads(line[line.index("no result: ") + len("no result: "):])
    assert result["correct"] is True and result["failed"] == 0
    m = {k: v["value"] for k, v in result["metrics"].items()}
    assert m["unplanned_query_share"] == 0.0
    assert m["knn_filtered_fallback_share"] == 0.0
    assert m["fan_out_inline_share"] == 100.0
    assert m["filter_tiles_per_req"] >= 1.0
    assert 0.0 < m["filter_pass_share"] < 100.0
    assert m["filter_mask_ms"] > 0 and m["host_syncs_per_req"] == 1.0
    assert m["h2d_bytes_per_req"] > 0 and m["d2h_bytes_per_req"] == 128.0
