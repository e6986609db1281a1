#!/usr/bin/env bash
# Tiny-corpus bench smoke: pre-push sanity for the serving pipeline.
# Runs the full bench.py harness (~20k docs, CPU by default), asserts
# every recall gate >= 0.99, and prints the per-config latencies.
# Fast enough for local use.
set -euo pipefail
cd "$(dirname "$0")/.."

export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"
export BENCH_N_DOCS="${BENCH_N_DOCS:-20000}"
export BENCH_VOCAB="${BENCH_VOCAB:-8000}"
export BENCH_DIMS="${BENCH_DIMS:-64}"
export BENCH_N_QUERIES="${BENCH_N_QUERIES:-96}"
export BENCH_THREADS="${BENCH_THREADS:-16}"

log="${TMPDIR:-/tmp}/bench_smoke.log"
json_out="${TMPDIR:-/tmp}/bench_smoke.json"
if ! python bench.py >"$json_out" 2>"$log"; then
    echo "bench.py failed; last stderr lines:" >&2
    tail -40 "$log" >&2
    exit 1
fi

python - "$json_out" <<'PY'
import json
import sys

with open(sys.argv[1]) as f:
    r = json.load(f)
bad = [
    (name, cfg["recall"])
    for name, cfg in r["configs"].items()
    if "recall" in cfg and cfg["recall"] < 0.99
]
assert not bad, f"recall gate < 0.99: {bad}"

print(f"headline: {r['value']} {r['unit']} (vs_baseline {r['vs_baseline']})")
print("--- per config ---")
for name in ("match", "bool", "multi_match", "knn", "hybrid_rrf"):
    c = r["configs"][name]
    print(
        f"{name:12s} qps={c['qps']:<8} p50={c['p50_ms']}ms "
        f"p50_batch1={c['p50_batch1_ms']}ms"
    )
print(f"pipeline     depth={r['pipeline']['depth']}")
print("SMOKE OK")
PY
