"""Step 0 of the filtered kNN family's lead route (PR 50): what decides
`scoring.KNN_LEAD_SCAN_ROWS` / `KNN_LEAD_VERIFY_TILES_MAX`, kept so the
table above them can be taken again.

`--count` (CPU, no device): the filtered cell's own bags and query set
(`benchmarks/configs/yfcc10m-filtered-knn.json`, the window's stream as
`benchmarks/run.py` draws it) -> per request the clauses' tiles, whether
each holds a bit row (`dense_row_min_df`), the lead clause and whether
the request leads under the rule at the given limits; prints the share
that leads and the quartiles of the lead's tiles.

default (the chip: `chiprun -- python scripts/probe_knn_lead.py`):
synthetic operands at the cell's shapes (10M x 192 int8 rows, their norm
plane, ~0.95M postings tiles, a 122-row bit plane, one query row).
Device ms a launch (the profiler's `XLA Modules` line over `--reps`
launches) and the host's ms around one launch of `knn_topk_lead` at
leads of C rows, bare, with one bit-row clause verified and with a
second small tile range verified, beside one launch of today's mask +
scan.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

TILE = 128


def count(args) -> None:
    sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
    from plugins import load_json, load_plugin

    from elasticsearch_tpu.search.executor_jax import dense_row_min_df

    config = load_json("configs", "yfcc10m-filtered-knn.json")
    docs = int(args.docs or config["docs"])
    p = config["corpus"]["args"]
    tp = p["tags"]
    n_tags = int(tp["vocab"])
    corpus = load_plugin("corpora", "byte_vectors_tags")
    _used, keys = load_plugin("corpora", "splade_impacts").structure(
        {"stats_seed": p["stats_seed"], "nnz": tp["per_row"],
         "vocab": n_tags, "vocab_in_use": n_tags, "df_law": tp["df_law"]},
        docs)
    post_tag, bag = corpus.split_keys(keys, docs)
    del keys
    bag_start, bag_tags = corpus.row_major(post_tag, bag, docs)
    df = np.bincount(post_tag, minlength=n_tags)
    tiles_of = (df + TILE - 1) // TILE
    has_row = df >= dense_row_min_df(docs)
    width = len(str(n_tags - 1))
    context = {"field": p["field"], "tag_field": tp["field"],
               "dims": int(p["dims"]), "tag_width": width,
               "components": p["components"],
               "bag_start": bag_start, "bag_tags": bag_tags}
    body_conf = config["body"]
    body_mod = load_plugin("bodies", body_conf["generator"])
    base = int(body_conf["query_set_seed"])
    print(f"docs {docs}; tags holding a bit row {int(has_row.sum())}; "
          f"limits lead <= {args.lead_max} tiles, verified <= "
          f"{args.verify_max} tiles")
    for stream, name in ((3, "window"), (4, "warm-up")):
        lead_tiles, routes = [], {"lead": 0, "all_bit_rows": 0,
                                  "lead_too_long": 0, "other_too_long": 0}
        n_req = 0
        for c in range(args.chunks):
            rng = np.random.default_rng([base, stream, c])
            for body in body_mod.make(context, body_conf["args"], rng, 100):
                clauses = json.loads(body)["knn"]["filter"]["bool"]["filter"]
                tids = [int(c_["term"][tp["field"]][1:]) for c_ in clauses]
                n_req += 1
                postings = sorted(
                    int(tiles_of[t]) for t in tids if not has_row[t])
                if not postings:
                    routes["all_bit_rows"] += 1
                elif postings[0] > args.lead_max:
                    routes["lead_too_long"] += 1
                elif any(t > args.verify_max for t in postings[1:]):
                    routes["other_too_long"] += 1
                else:
                    routes["lead"] += 1
                    lead_tiles.append(postings[0])
        q = np.percentile(lead_tiles, [25, 50, 75, 90, 100])
        print(f"{name} stream, {n_req} requests: "
              + ", ".join(f"{k} {v} ({100.0 * v / n_req:.1f}%)"
                          for k, v in routes.items()))
        print(f"  lead tiles of the leading requests: p25 {q[0]:.0f}, "
              f"p50 {q[1]:.0f}, p75 {q[2]:.0f}, p90 {q[3]:.0f}, "
              f"max {q[4]:.0f}; slots scored a leading request, mean "
              f"{TILE * float(np.mean(lead_tiles)):.0f}")


def probe(args) -> None:
    import shutil
    import tempfile

    import jax
    import jax.numpy as jnp

    sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
    from tracereduce import find_xplane, reduce_trace

    from elasticsearch_tpu.ops import scoring

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse:
        raise SystemExit(f"no chip: {dev.platform}")
    if args.chunk:  # read when the program is traced: before its first use
        scoring.KNN_LEAD_CHUNK = args.chunk
    n, d = int(args.docs or 10_000_000), 192
    n_tiles = int(args.tiles or 950_000)
    k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(50), 4)
    vectors = jax.random.randint(k1, (n, d), -128, 128, jnp.int8)
    norms = scoring.knn_row_norms(vectors)
    cand = jnp.ones(n, jnp.bool_)
    # postings: each tile 128 ascending doc ids drawn over the whole set,
    # as a rare tag's are (the gathers' addresses are what costs)
    doc_ids = jnp.sort(
        jax.random.randint(k2, (n_tiles, TILE), 0, n, jnp.int32), axis=1)
    plane = jax.random.bits(
        k3, (122, scoring.filter_bit_words(n)), jnp.uint32)
    q = jax.random.randint(k4, (1, d), -128, 128, jnp.int32).astype(
        jnp.float32)
    kc = 112
    blocks = scoring.rows_on_lanes(vectors)
    jax.block_until_ready((vectors, norms, doc_ids, plane))

    def ms(fn) -> dict:
        """Device ms a launch (the profiler's `XLA Modules` line: every
        module's time over the launches) and the host's ms around one
        launch and its `block_until_ready`."""
        jax.block_until_ready(fn())  # compile, first use
        host = []
        for _ in range(args.reps):
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            host.append((time.perf_counter() - t0) * 1e3)
        out = {"host_ms": round(statistics.median(host), 4)}
        log = tempfile.mkdtemp(prefix="probe_knn_lead_")
        try:
            with jax.profiler.trace(log):
                jax.block_until_ready([fn() for _ in range(args.reps)])
            red = reduce_trace(find_xplane(log), rehearsal=args.rehearse)
            out["device_ms"] = round(1e3 * sum(
                sec for _n, sec in red["modules"].values()) / args.reps, 4)
            out["top_ops"] = [[name, round(1e3 * sec / args.reps, 4)]
                              for name, sec in red["device_ops"][:4]]
        finally:
            shutil.rmtree(log, ignore_errors=True)
        return out

    S = scoring.FILTER_SLOT_BUCKETS[0]
    table = {"device": dev.device_kind, "docs": n, "tiles": n_tiles,
             "kc": kc, "reps": args.reps, "rows_on_lanes": blocks,
             "chunk": scoring.KNN_LEAD_CHUNK, "lead": []}

    def lead_plan(tiles: int, other: str) -> np.ndarray:
        """The plan `pack_lead_plans` writes for a lead of `tiles` tiles
        (longer, here, than its rule admits) and one other clause: a bit
        row, or a range of KNN_LEAD_VERIFY_TILES_MAX tiles."""
        plan = np.zeros((1, 3 * S + 1), np.int32)
        plan[0, 0], plan[0, S], plan[0, 2 * S] = 0, tiles, 1
        if other == "bit_row":
            plan[0, 1], plan[0, 2 * S + 1] = 7, scoring.FILTER_BIT_OPENS
        elif other == "range_64":
            plan[0, 1], plan[0, S + 1] = (
                n_tiles // 2, scoring.KNN_LEAD_VERIFY_TILES_MAX)
            plan[0, 2 * S + 1] = 1
        plan[0, 3 * S] = 2 if other else 1
        return plan

    for rows in args.rows:
        tiles = max(rows // TILE, 1)
        for other in args.others:
            if other == "range_64" and tiles > 64:
                continue  # the lead is the shorter of two ranges
            plan = lead_plan(tiles, other)
            line = {"rows": rows, "tiles": tiles, "other": other or "none",
                    **ms(lambda: scoring.knn_topk_lead(
                        q, vectors, norms, cand, doc_ids, plane, plan,
                        similarity="l2_norm", k=kc, blocks=blocks,
                        interpret=args.rehearse and blocks))}
            table["lead"].append(line)
            print(json.dumps(line), flush=True)

    # today's two launches for a rare tag: the mask (scatter of 8 tiles
    # into the 40 MB plane), then the scan under it
    plan = np.zeros((1, 3 * S + 1), np.int32)
    plan[0, 0], plan[0, S], plan[0, 2 * S], plan[0, 3 * S] = 0, 8, 1, 1

    def scan():
        mask, _p = scoring.knn_filter_mask(doc_ids, cand, plan, plane)
        return scoring.knn_topk_filtered(
            q, vectors, mask, "l2_norm", kc, norms)

    table["mask_scan"] = ms(scan)
    table["mask"] = ms(
        lambda: scoring.knn_filter_mask(doc_ids, cand, plan, plane))
    print(json.dumps({k: v for k, v in table.items() if k != "lead"}),
          flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(
            ROOT, "chiprun_out",
            f"probe_knn_lead_chunk{scoring.KNN_LEAD_CHUNK}.json"), "w") as f:
        json.dump(table, f, indent=1)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--count", action="store_true")
    ap.add_argument("--docs", type=int, default=0)
    ap.add_argument("--tiles", type=int, default=0)
    ap.add_argument("--chunks", type=int, default=30)
    ap.add_argument("--lead-max", type=int, default=305,
                    help="knn_lead_tiles_max(docs) at 10M rows")
    ap.add_argument("--verify-max", type=int, default=64)
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--chunk", type=int, default=0,
                    help="lead tiles a trip (default: KNN_LEAD_CHUNK)")
    ap.add_argument("--rows", type=int, nargs="+",
                    default=[128, 1024, 8192, 32768, 78080])
    ap.add_argument("--others", nargs="+",
                    default=["", "bit_row", "range_64"])
    ap.add_argument("--rehearse", action="store_true",
                    help="run the probe off the chip (no number counts)")
    args = ap.parse_args()
    count(args) if args.count else probe(args)


if __name__ == "__main__":
    main()
