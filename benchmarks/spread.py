"""Runs one cell several times (a new process each, as the driver does)
and prints what the bounds are set from: per set and metric the median
and the spread (distance between the first and third quartile of
`statistics.quantiles(values, n=4)`, as a share of the median).

    python3 benchmarks/spread.py --workload <cell> --seeds 11,12,13 --sets 2

Every run's result line and `check`/`phases` lines are appended to
`--out` (default `chiprun_out/spread.<cell>.jsonl`). This process never
imports JAX, so the chip belongs to each run in turn.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def one_run(workload: str, seed: int, seconds: float, trace: int,
            extra: list) -> dict:
    cmd = [sys.executable, os.path.join(HERE, "run.py"), "--workload",
           workload, "--seed", str(seed), "--seconds", str(seconds),
           "--trace", str(trace), *extra]
    t = time.time()
    p = subprocess.run(cmd, cwd=ROOT, stdout=subprocess.PIPE, text=True)
    lines = p.stdout.strip().splitlines()
    rec = {"seed": seed, "trace": trace, "rc": p.returncode,
           "wall_s": time.time() - t,
           "notes": [ln for ln in lines if " check " in ln or " control "
                     in ln or " phases " in ln or " warm round" in ln
                     or "window counts" in ln or "requests," in ln
                     or " end to end " in ln or "first use" in ln]}
    try:
        rec["result"] = json.loads(lines[-1])
    except (IndexError, ValueError):
        rec["result"] = None
        rec["tail"] = lines[-5:]
    return rec


def spread(values: list) -> float:
    q1, _q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--sets", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--control", type=int, default=0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        seconds = args.seconds or json.load(f)["run_seconds"]
    seeds = [int(s) for s in args.seeds.split(",")]
    out = args.out or os.path.join(
        ROOT, "chiprun_out", f"spread.{args.workload}.jsonl")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    extra = ["--control", "1"] if args.control else []
    bad = 0
    for s in range(args.sets):
        values: dict = {}
        for seed in seeds:
            rec = one_run(args.workload, seed, seconds, args.trace, extra)
            rec["set"] = s
            with open(out, "a") as f:
                f.write(json.dumps(rec) + "\n")
            res = rec["result"]
            if rec["rc"] != 0 or not res or not res["correct"]:
                bad += 1
                print(f"set {s} seed {seed}: rc {rec['rc']} NOT CORRECT "
                      f"{rec.get('tail')} {rec['notes']}", flush=True)
                continue
            print(f"set {s} seed {seed} ({rec['wall_s']:.0f}s): " + ", ".join(
                f"{k} {v['value']:.4g}" for k, v in res["metrics"].items())
                + f"; failed {res['failed']}/{res['attempted']}", flush=True)
            for ln in rec["notes"]:
                if "score_rel_max" in ln or "recall_min" in ln \
                        or " control " in ln or " phases " in ln:
                    print("   ", ln, flush=True)
            for k, v in res["metrics"].items():
                values.setdefault(k, []).append(v["value"])
            for ln in rec["notes"]:  # values the cell is not asked for
                if " end to end " in ln:
                    for k, v in json.loads(ln.split(" end to end ")[1]).items():
                        if k not in res["metrics"]:
                            values.setdefault(k + " (not reported)",
                                              []).append(v)
        for k, vs in values.items():
            if len(vs) >= 2:
                print(f"SET {s} {k}: median {statistics.median(vs):.6g} "
                      f"spread {100 * spread(vs):.2f}% of median "
                      f"(n={len(vs)}, min {min(vs):.6g}, max {max(vs):.6g})",
                      flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
