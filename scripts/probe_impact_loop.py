"""Step 0 of the sparse family's looped tile pass (PR 52): what decides
`impact.TILE_STEP` / `TILE_CAP`, kept so the table beside them can be
taken again.

The chip (`chiprun -- python scripts/probe_impact_loop.py`): synthetic
operands at the sparse cell's shapes (1M documents, ~1M impact tiles of
128 int8 postings, one query row whose cold terms hold contiguous tile
ranges). Device ms a scoring's tile pass (the profiler's `XLA Modules`
line over `--reps` scorings) and the host's ms around it, for tiles in
use `--tiles`: the looped program (`impact._impact_tile_loop`) at each
`--steps` tiles a trip and each `--caps` plan width, beside the program
it replaces, launches of a fixed 512 tiles each with three host
operands (kept here as `_chunk512`, the reference the looped program's
planes are held to, bit for bit, on whatever device this runs). `chain`
lines time a whole scoring as the batcher launches it (the fill, the
tile pass, `_finalize`, the packed download).

`--cell N`: no timing; the sparse cell's own corpus at its own size
(`benchmarks/configs/msmarco-splade-sparse.json`, built from `--seed`)
and the first N bodies of its question set, each scored at one row by
both programs over the int8 column, once with every term on its tiles
and once with the terms under the row threshold alone (what the cell's
tile pass is handed): the planes and `_finalize`'s page compared bit
for bit.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import shutil
import statistics
import sys
import tempfile
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

TILE = 128
OLD_CHUNK = 512


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--docs", type=int, default=1_000_000)
    ap.add_argument("--n-tiles", type=int, default=1_000_000)
    ap.add_argument("--rows", type=int, default=1)
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--tiles", type=int, nargs="+",
                    default=[1, 128, 512, 1264, 2048, 4096])
    ap.add_argument("--steps", type=int, nargs="+", default=[128, 256, 512])
    ap.add_argument("--caps", type=int, nargs="+", default=[4096])
    ap.add_argument("--cell", type=int, default=0,
                    help="compare N bodies of the sparse cell, bit for bit")
    ap.add_argument("--seed", type=int, default=2147520052)
    ap.add_argument("--no-chain", action="store_true",
                    help="the tile pass alone, no whole scoring")
    ap.add_argument("--float32", action="store_true",
                    help="a float32 values plane (default: int8)")
    ap.add_argument("--rehearse", action="store_true",
                    help="run the probe off the chip (no number counts)")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
    from tracereduce import find_xplane, reduce_trace

    from elasticsearch_tpu.ops import impact
    from elasticsearch_tpu.ops.scoring import _to_host

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse:
        raise SystemExit(f"no chip: {dev.platform}")
    n, n_tiles, rows = args.docs, args.n_tiles, args.rows
    if args.cell:
        from plugins import load_json, load_plugin

        config = load_json("configs", "msmarco-splade-sparse.json")
        n = int(config["docs"]) if not args.rehearse else n
        corpus = load_plugin("corpora", config["corpus"]["builder"]).build(
            config, args.seed, n)
        sf = corpus["segment"].sparse[corpus["body_context"]["field"]]
        doc_ids, values, n_tiles, rows = sf.doc_ids, sf.qweights, sf.n_tiles, 1
    else:
        k1, k2 = jax.random.split(jax.random.PRNGKey(52))
        # impact-ordered tiles: a tile's documents come in no order of id
        doc_ids = jax.random.randint(k1, (n_tiles, TILE), -1, n, jnp.int32)
        if args.float32:
            values = jax.random.uniform(k2, (n_tiles, TILE), jnp.float32)
        else:
            values = jax.random.randint(
                k2, (n_tiles, TILE), -127, 128, jnp.int8)
    sc = impact.ImpactScorer(doc_ids, values, n)
    jax.block_until_ready((sc.doc_ids, sc.values))

    @functools.partial(jax.jit, donate_argnums=(2, 3))
    def _chunk512(doc_ids, values, acc, cnt, ti, tw, tv):
        rows_d = doc_ids[ti]
        valid = (rows_d >= 0) & tv[:, :, None]
        tgt, s = impact.impact_tile_contrib(
            rows_d, values[ti], tw[:, :, None], valid, acc.shape[1] - 1)
        acc = jax.vmap(lambda a, d, v: a.at[d.ravel()].add(v.ravel()))(
            acc, tgt, s)
        cnt = jax.vmap(
            lambda c, d, v: c.at[d.ravel()].add(v.ravel().astype(jnp.int32))
        )(cnt, tgt, valid)
        return acc, cnt

    def lists(tiles: int, seed: int):
        """Per query row the tile ids of ~38-tile term ranges, `tiles`
        in all at row 0 and fewer below, and a weight a term."""
        rng = np.random.default_rng([52, seed])
        tl, wl = [], []
        for j in range(rows):
            want = max(tiles - 37 * j, 0)
            t, w = [], []
            while sum(map(len, t)) < want:
                c = min(int(rng.integers(1, 76)), want - sum(map(len, t)))
                s0 = int(rng.integers(0, n_tiles - c))
                t.append(np.arange(s0, s0 + c, dtype=np.int64))
                w.append(np.full(c, rng.random() + 0.01, np.float32))
            tl.append(np.concatenate(t) if t else np.zeros(0, np.int64))
            wl.append(np.concatenate(w) if w else np.zeros(0, np.float32))
        return tl, wl

    def stage512(tl, wl):
        t_max = max(len(t) for t in tl)
        out = []
        for c0 in range(0, t_max, OLD_CHUNK):
            ti = np.zeros((rows, OLD_CHUNK), np.int32)
            tw = np.zeros((rows, OLD_CHUNK), np.float32)
            tv = np.zeros((rows, OLD_CHUNK), bool)
            for j in range(rows):
                m = len(tl[j][c0:c0 + OLD_CHUNK])
                ti[j, :m] = tl[j][c0:c0 + OLD_CHUNK]
                tw[j, :m] = wl[j][c0:c0 + OLD_CHUNK]
                tv[j, :m] = True
            out.append((ti, tw, tv))
        return out

    def pass512(acc, cnt, staged):
        for ti, tw, tv in staged:
            acc, cnt = _chunk512(sc.doc_ids, sc.values, acc, cnt, ti, tw, tv)
        return acc, cnt

    def looped(step: int):
        return jax.jit(
            functools.partial(impact._impact_tile_loop, step=step),
            donate_argnums=(2, 3))

    def stage_loop(tl, wl, cap: int):
        impact.TILE_CAP, keep = cap, impact.TILE_CAP
        try:
            return sc.stage_chunks(rows, tl, wl)
        finally:
            impact.TILE_CAP = keep

    def pass_loop(fn, acc, cnt, plans):
        for plan in plans:
            acc, cnt = fn(sc.doc_ids, sc.values, acc, cnt, plan)
        return acc, cnt

    def ms(run, chain: bool) -> dict:
        """Device ms a call of `run` (every module's time on the
        profiler's `XLA Modules` line, the fill's excepted unless
        `chain`) and the host's ms around one call, to its end."""
        jax.block_until_ready(run())  # compile, first use
        host = []
        for _ in range(args.reps):
            t0 = time.perf_counter()
            jax.block_until_ready(run())
            host.append((time.perf_counter() - t0) * 1e3)
        out = {"host_ms": round(statistics.median(host), 4)}
        log = tempfile.mkdtemp(prefix="probe_impact_loop_")
        try:
            with jax.profiler.trace(log):
                for _ in range(args.reps):
                    jax.block_until_ready(run())
            red = reduce_trace(find_xplane(log), rehearsal=args.rehearse)
            out["device_ms"] = round(1e3 * sum(
                sec for name, (_n, sec) in red["modules"].items()
                if chain or "_impact_zeros" not in name) / args.reps, 4)
            out["launches"] = round(sum(
                c for name, (c, _s) in red["modules"].items()
                if "_impact_zeros" not in name) / args.reps, 2)
            out["top_ops"] = [[name, round(1e3 * sec / args.reps, 4)]
                              for name, sec in red["device_ops"][:6]]
        finally:
            shutil.rmtree(log, ignore_errors=True)
        return out

    table = {"device": dev.device_kind, "docs": n, "n_tiles": n_tiles,
             "rows": rows, "reps": args.reps,
             "values": str(sc.values.dtype), "lines": []}

    def emit(line: dict) -> None:
        table["lines"].append(line)
        print(json.dumps(line), flush=True)

    def same_bits(got, want) -> bool:
        return all(np.array_equal(np.asarray(g).view(np.int32),
                                  np.asarray(w).view(np.int32))
                   for g, w in zip(got, want))

    if args.cell:
        from elasticsearch_tpu.search.executor_jax import dense_row_min_df

        body_conf = config["body"]
        bodies = load_plugin("bodies", body_conf["generator"]).make(
            corpus["body_context"], body_conf["args"],
            # the window's stream, its first chunk, as `run.py` draws it
            np.random.default_rng([int(body_conf["query_set_seed"]), 3, 0]),
            100)[:args.cell]
        cold_df = dense_row_min_df(n)
        out = {"program": "cell", "docs": n, "n_tiles": int(n_tiles),
               "bodies": 0, "scorings": 0, "tiles_max": 0, "launches_max": 0,
               "planes_bit_equal": 0, "pages_bit_equal": 0}
        for body in bodies:
            vector = json.loads(body)["query"]["sparse_vector"]["query_vector"]
            tids, tws, _b, starts, counts = impact.impact_tile_lists(
                sf, list(vector), list(vector.values()), True)
            cold = counts * TILE < cold_df + TILE  # df under the threshold
            out["bodies"] += 1
            for keep in (np.ones(len(tids), bool), cold):
                tl = [impact.term_tiles(starts[keep], counts[keep])]
                wl = [np.repeat(tws[keep], counts[keep])]
                want = pass512(*sc.new_acc(1), stage512(tl, wl))
                plans = sc.stage_chunks(1, tl, wl)
                got = sc.add_chunks(*sc.new_acc(1), plans)
                out["scorings"] += 1
                out["tiles_max"] = max(out["tiles_max"], len(tl[0]))
                out["launches_max"] = max(out["launches_max"], len(plans))
                out["planes_bit_equal"] += same_bits(
                    [g[:, :n] for g in got], [w[:, :n] for w in want])
                out["pages_bit_equal"] += same_bits(
                    sc.finalize(*got, 16), sc.finalize(*want, 16))
        emit(out)
        return

    fns = {step: looped(step) for step in args.steps}
    for ti, tiles in enumerate(args.tiles):
        tl, wl = lists(tiles, ti)
        staged = stage512(tl, wl)
        want = jax.device_get(pass512(*sc.new_acc(rows), staged))
        emit({"program": "chunk512", "tiles": tiles,
              **ms(lambda: pass512(*sc.new_acc(rows), staged), False)})
        for cap in args.caps:
            plans = stage_loop(tl, wl, cap)
            for step, fn in fns.items():
                got = jax.device_get(
                    pass_loop(fn, *sc.new_acc(rows), plans))
                same = same_bits([g[:, :n] for g in got],
                                 [w[:, :n] for w in want])
                emit({"program": "loop", "tiles": tiles, "cap": cap,
                      "step": step, "bit_equal_to_chunk512": same,
                      **ms(lambda: pass_loop(
                          fn, *sc.new_acc(rows), plans), False)})

    # a whole scoring, as the batcher launches it: stage on the host,
    # fill, tile pass, `_finalize`, the packed triple downloaded
    def chain(tile_pass, stage):
        def run():
            acc, cnt = tile_pass(*sc.new_acc(rows), stage())
            return [_to_host(x) for x in sc.finalize_device(acc, cnt, 16)]
        return run

    for ti, tiles in enumerate(args.tiles):
        if tiles < OLD_CHUNK or args.no_chain:
            continue
        tl, wl = lists(tiles, ti)
        emit({"program": "chain.chunk512", "tiles": tiles,
              **ms(chain(pass512, lambda: stage512(tl, wl)), True)})
        for cap in args.caps:
            for step, fn in fns.items():
                emit({"program": "chain.loop", "tiles": tiles, "cap": cap,
                      "step": step,
                      **ms(chain(functools.partial(pass_loop, fn),
                                 lambda: stage_loop(tl, wl, cap)), True)})

    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    name = (f"probe_impact_loop_rows{rows}_{sc.values.dtype}_"
            f"{'_'.join(map(str, args.tiles))}.json")
    with open(os.path.join(ROOT, "chiprun_out", name), "w") as f:
        json.dump(table, f, indent=1)


if __name__ == "__main__":
    main()
