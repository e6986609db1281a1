"""One cell of the benchmark, once, in a new process.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own, found by the name `BENCHMARK.json`
gives (README.md). A run: require the chip -> build the cell's one field
from `--seed` -> start the in-process server at a node's shipped defaults,
`PUT /<index>` over HTTP, place the prebuilt segment -> warm with the
cell's own load until a round compiles nothing -> `setup_s` ends ->
measure for `--seconds` from a separate load-generator process -> check a
seeded sample of the window's own answers against the plain reference ->
print each number compared beside its limit, then one JSON line.

`--trace 0` reports the end-to-end metrics, `--trace 1` the per-layer
metrics (one profiler window, the per-request trace ring, `_nodes/stats`
deltas). With no TPU the command exits non-zero and prints no result;
`--rehearse` (the sandbox: any platform, the configuration's
`rehearse_docs`) prints no result line either and exits 3.
"""

from __future__ import annotations

import os
import sys
import time

# One interpreter state for every run: with Python's per-process hash
# randomization the same cell read 3.4% apart from process to process on
# the chip (p50, same seed), with it fixed 0.7% (PERF.md section 6, PR 23).
# The first process's start still counts in `setup_s`.
if __name__ == "__main__" and os.environ.get("PYTHONHASHSEED") != "0":
    os.environ["PYTHONHASHSEED"] = "0"
    os.environ["BENCH_T_PROCESS_START"] = repr(time.time())
    os.execv(sys.executable, [sys.executable] + sys.argv)
T_PROCESS_START = float(os.environ.pop("BENCH_T_PROCESS_START", time.time()))

import argparse  # noqa: E402
import http.client  # noqa: E402
import json  # noqa: E402
import logging  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import threading  # noqa: E402
from concurrent.futures import ThreadPoolExecutor  # noqa: E402

import numpy as np  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
RUN_DIR = os.path.join(ROOT, ".bench_run")  # git-ignored scratch of a run
POOL_CHUNK = 100  # bodies per seeded chunk: a longer pool extends a shorter
WARM_POOL = 1000  # bodies of the warm-up's pool (its rounds may repeat them)
POOL_ROOM = 1.6  # the window's pool, over what the warm-up's rate would use
PROFILE_START_S, PROFILE_SECONDS = 1.0, 3.0
MAX_WARM_ROUNDS = 10
FIRST_USE_BODIES = 64  # sent one at a time before the first loaded round

for p in (ROOT, HERE):
    if p not in sys.path:
        sys.path.insert(0, p)

from plugins import load_json, load_plugin  # noqa: E402


def say(msg: str) -> None:
    print(f"[bench +{time.time() - T_PROCESS_START:7.1f}s] {msg}", flush=True)


class CompileWatch:
    """Copied from `chip_smoke.py`: backend compilations, their seconds and
    persistent-cache hits/misses, through `jax.monitoring`'s own events."""

    def __init__(self):
        import jax.monitoring as mon

        self.compiles, self.compile_s = 0, 0.0
        self.cache_hits = self.cache_misses = 0
        mon.register_event_duration_secs_listener(self._dur)
        mon.register_event_listener(self._evt)

    def _dur(self, event, duration, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += float(duration)

    def _evt(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def programs(self) -> int:
        """Programs built or fetched: any of them inside the window means
        a shape the warm-up did not reach."""
        return self.compiles + self.cache_hits


class Http:
    def __init__(self, port: int):
        self.conn = http.client.HTTPConnection("127.0.0.1", port, timeout=900)

    def call(self, method: str, path: str, body=None):
        data = None if body is None else json.dumps(body).encode()
        headers = {"Content-Type": "application/json"} if data else {}
        self.conn.request(method, path, body=data, headers=headers)
        resp = self.conn.getresponse()
        payload = resp.read()
        if resp.status >= 300:
            raise RuntimeError(
                f"{method} {path} -> HTTP {resp.status}: {payload[:600]!r}")
        return json.loads(payload) if payload else None


def find_cell(bench: dict, workload: str):
    cell = next((w for w in bench["workloads"] if w["name"] == workload), None)
    if cell is None:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")
    if not any(c["name"] == cell["config"] for c in bench["configs"]):
        raise SystemExit(f"workload {workload!r} names an unlisted config")
    return cell


def cell_metrics(bench: dict, section: str, workload: str) -> list:
    return [m for m in bench[section]
            if "workloads" not in m or workload in m["workloads"]]


def place_segment(svc, segment) -> None:
    """The seeded, prebuilt segment becomes the shard's one segment, as
    `bench.py` `make_service` does (ingest is bypassed: configs'
    `reduced.ingest`)."""
    n = segment.num_docs
    eng = svc.shards[0]
    eng.segments = [segment]
    eng.live_docs = [None]
    eng.seg_versions = [np.ones(n, np.int64)]
    eng.seg_seqnos = [np.arange(n, dtype=np.int64)]
    eng.seg_names = ["seg_0_0"]
    eng._next_seq = n
    eng.change_generation += 1


def make_pool(body_mod, context: dict, body_conf: dict, seed: int,
              stream: int, n: int) -> list:
    """The first `n` bodies of one seeded stream. Each chunk comes from its
    own child of the stream's seed (on a few threads: the encoders are
    NumPy passes that release the GIL) and is put in an order drawn from
    `--seed`. The stream's seed is `--seed`, or the configuration's
    `query_set_seed` where the queries are a fixed set, as a query file
    is: a window then sends the same requests chunk by chunk on every seed,
    in another order inside each chunk, so the seed does not change the
    work (with a question's cost spread over two decades, windows of
    ~800 questions drawn afresh read 6% apart; PERF.md section 6)."""
    base = int(body_conf.get("query_set_seed", seed))

    def one(c: int) -> list:
        rng = np.random.default_rng([base, stream, c])
        bodies = body_mod.make(context, body_conf["args"], rng, POOL_CHUNK)
        order = np.random.default_rng([int(seed), stream, c]).permutation(
            len(bodies))
        return [bodies[i] for i in order]

    with ThreadPoolExecutor(max_workers=8) as pool:
        return [b for chunk in pool.map(one, range(math.ceil(n / POOL_CHUNK)))
                for b in chunk][:n]


def write_pool(path: str, bodies: list) -> None:
    offsets = np.zeros(len(bodies) + 1, np.int64)
    np.cumsum([len(b) for b in bodies], out=offsets[1:])
    np.savez(path, data=np.frombuffer(b"".join(bodies), np.uint8),
             offsets=offsets)


class Load:
    """One run of `loadgen.py` (a separate process, no JAX)."""

    def __init__(self, port: int, path: str, pool: str, traffic: str,
                 seconds: float, out: str, keep: str = None):
        cmd = [sys.executable, os.path.join(HERE, "loadgen.py"),
               "--port", str(port), "--path", path, "--pool", pool,
               "--traffic", traffic, "--seconds", repr(float(seconds)),
               "--out", out]
        if keep:
            cmd += ["--keep", keep]
        self.out = out
        self.proc = subprocess.Popen(
            cmd, stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
        self._expect("READY")

    def _expect(self, word: str) -> str:
        line = self.proc.stdout.readline()
        if not line.startswith(word):
            self.kill()
            raise RuntimeError(f"loadgen said {line!r}, expected {word}")
        return line

    def start(self) -> float:
        """-> the window's start, unix seconds, on the generator's clock."""
        self.proc.stdin.write("go\n")
        self.proc.stdin.flush()
        return float(self._expect("START").split()[1])

    def finish(self) -> dict:
        rc = self.proc.wait(timeout=600)
        if rc != 0:
            raise RuntimeError(f"loadgen exited with {rc}")
        with np.load(self.out) as z:
            res = {k: z[k] for k in z.files}
        with open(self.out + ".answers.json") as f:
            res["answers"] = {int(k): v for k, v in json.load(f).items()}
        return res

    def kill(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
        self.proc.wait()


def node_numbers(http: Http) -> dict:
    """Every number of the node's `_nodes/stats` under its dotted path
    (`thread_pool.search.launches`, `admission.queue_delay_ewma_ms`, ...):
    a reader names the counters or gauges it wants in its metric's file."""
    out: dict = {}

    def walk(prefix: str, value) -> None:
        if isinstance(value, dict):
            for k, v in value.items():
                walk(f"{prefix}.{k}" if prefix else str(k), v)
        elif isinstance(value, (int, float)) and not isinstance(value, bool):
            out[prefix] = value

    walk("", next(iter(http.call("GET", "/_nodes/stats")["nodes"].values())))
    return out


def span_samples(traces: dict) -> dict:
    """{span name: [ms]} over the deduplicated traces polled in a window."""
    out: dict = {}
    for tr in traces.values():
        for sp in tr["spans"]:
            out.setdefault(sp["name"], []).append(sp["duration_ns"] / 1e6)
    return out


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             rehearse: bool = False, control: bool = False) -> dict:
    """Drives one cell and returns the result object (module docstring)."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = find_cell(bench, workload)
    config = load_json("configs", f"{cell['config']}.json")
    traffic_path = os.path.join(HERE, "traffic", f"{cell['traffic']}.json")
    traffic = load_json(traffic_path)
    if not os.path.exists(os.path.join(HERE, "loops", f"{traffic['loop']}.py")):
        raise SystemExit(f"traffic loop {traffic['loop']!r} has no generator "
                         "under benchmarks/loops/")
    overrides = sorted(k for k in os.environ if k.startswith("ES_TPU_"))
    if overrides:
        raise SystemExit(f"runs at a node's shipped defaults; unset {overrides}")

    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    if not rehearse and (device["platform"] != "tpu"
                         or device["count"] < cell["chips"]):
        raise SystemExit(
            f"{workload} needs {cell['chips']} TPU chip(s); JAX found {device}")
    from elasticsearch_tpu.common.compile_cache import configure_compile_cache

    cache_dir = configure_compile_cache()  # before anything compiles
    # every program goes to the persistent cache, however fast it compiled,
    # so that only a checkout's first run of a cell compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    watch = CompileWatch()
    say(f"device {device}; compile cache {cache_dir}")

    n_docs = int(config["rehearse_docs"] if rehearse else config["docs"])
    run_dir = os.path.join(RUN_DIR, workload)
    shutil.rmtree(run_dir, ignore_errors=True)
    os.makedirs(run_dir)

    t = time.time()
    corpus = load_plugin("corpora", config["corpus"]["builder"]).build(
        config, seed, n_docs)
    say(f"corpus: {n_docs} docs built in {time.time() - t:.1f}s")
    phases = {"import_and_device_s": t - T_PROCESS_START,
              "corpus_s": time.time() - t}

    body_conf = config["body"]
    body_mod = load_plugin("bodies", body_conf["generator"])
    warm_seconds = float(traffic["warm_seconds"])
    t = time.time()
    # a fixed pool warms up: a round that outruns it sends bodies again,
    # which no cache of the program's answers from memory
    warm_path = os.path.join(run_dir, "warm_pool.npz")
    pool_path = os.path.join(run_dir, "pool.npz")
    warm_pool = make_pool(body_mod, corpus["body_context"], body_conf, seed,
                          4, WARM_POOL)
    write_pool(warm_path, warm_pool)
    phases["bodies_s"] = time.time() - t

    from elasticsearch_tpu.rest.server import ElasticsearchTpuServer

    server = ElasticsearchTpuServer(port=0)
    server.start_background()
    load = None
    try:
        http = Http(server.port)
        index = config["index"]
        http.call("PUT", f"/{index}", {"settings": config["settings"],
                                       "mappings": corpus["mappings"]})
        svc = server.cluster.indices[index]
        place_segment(svc, corpus["segment"])
        search_path = f"/{index}/_search"

        # ---- warm-up. First a few bodies one at a time: what the program
        # builds at first use (device layout, block index, programs) then
        # has no request queued behind it, which admission would read as
        # congestion. Then the cell's own load, until a round compiles
        # nothing.
        t = time.time()
        for body in warm_pool[:FIRST_USE_BODIES]:
            http.call("POST", search_path, json.loads(body))
        if not svc._batcher.wait_warm_idle(timeout=900.0):
            raise RuntimeError("bucket warm-up still running after 900 s")
        phases["first_use_s"] = time.time() - t
        say(f"first use: {FIRST_USE_BODIES} bodies one at a time in "
            f"{phases['first_use_s']:.1f}s, {watch.programs()} programs "
            "built or fetched")
        warm_rate, rounds = 0.0, []
        for rnd in range(MAX_WARM_ROUNDS):
            before = watch.programs()
            load = Load(server.port, search_path, warm_path, traffic_path,
                        warm_seconds, os.path.join(run_dir, "warm.npz"))
            load.start()
            res = load.finish()
            load = None
            if not svc._batcher.wait_warm_idle(timeout=900.0):
                raise RuntimeError("bucket warm-up still running after 900 s")
            ok = int((res["status"] == 200).sum())
            warm_rate = ok / warm_seconds
            rounds.append(watch.programs() - before)
            say(f"warm round {rnd}: {ok} answers of {len(res['status'])}, "
                f"{rounds[-1]} programs built or fetched")
            if rounds[-1] == 0 and ok > 0:
                break
        else:
            raise RuntimeError(f"still compiling after {rounds} warm rounds")
        phases["warm_s"] = time.time() - t
        phases["warm_rounds"] = len(rounds)
        phases["compile_s"] = watch.compile_s
        phases["cache_hits"], phases["cache_misses"] = (
            watch.cache_hits, watch.cache_misses)

        # the window's pool, sized from the rate the warm-up just read: no
        # body repeats inside a window, however fast the program is
        t = time.time()
        pool = make_pool(
            body_mod, corpus["body_context"], body_conf, seed, 3,
            max(int(traffic["check_answers"]),
                math.ceil(POOL_ROOM * warm_rate * seconds)))
        write_pool(pool_path, pool)
        phases["bodies_s"] += time.time() - t
        keep = (np.random.default_rng([int(seed), 5]).random(len(pool))
                < min(1.0, 16.0 * traffic["check_answers"] / len(pool)))
        keep_path = os.path.join(run_dir, "keep.npy")
        np.save(keep_path, keep)

        # ---- the window
        load = Load(server.port, search_path, pool_path, traffic_path,
                    seconds, os.path.join(run_dir, "window.npz"), keep_path)
        http.call("DELETE", "/_internal/traces")
        before_counts = node_numbers(http)
        before_programs = watch.programs()
        t_start = load.start()
        setup_s = t_start - T_PROCESS_START
        observed = {}
        if trace:
            observed = observe_window(http, run_dir, t_start, seconds,
                                      rehearse)
        res = load.finish()
        load = None
        after_counts = node_numbers(http)
        programs_in_window = watch.programs() - before_programs
        device["memory_peak_bytes"] = int(max(
            (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
            for d in devices))
    finally:
        if load is not None:
            load.kill()
        server.close()
        for name in ("pool.npz", "warm_pool.npz"):  # the bulk of a run's files
            if os.path.exists(os.path.join(run_dir, name)):
                os.remove(os.path.join(run_dir, name))

    # ---- reduce the window (client side)
    t_end = t_start + seconds
    ok = res["status"] == 200
    latency_ms = (res["t_end"] - res["t_send"])[ok] * 1e3
    in_window = int((ok & (res["t_end"] <= t_end)).sum())
    attempted, failed = int(len(ok)), int((~ok).sum())
    counts = {k: v - before_counts[k] for k, v in after_counts.items()
              if k in before_counts}
    by_bucket = "pipeline.batching.launches_by_bucket."
    buckets = {k[len(by_bucket):]: v for k, v in counts.items()
               if k.startswith(by_bucket) and v}
    say(f"{attempted} requests, {failed} failed, {in_window} answered inside "
        f"the {seconds}s window; latency samples {len(latency_ms)}; "
        f"pool_wrapped {int(res['pool_wrapped'][0])}; launches by bucket "
        f"{buckets}")

    # ---- correctness: a seeded sample of the window's own answers
    check = check_answers(config, corpus, pool, res, seed,
                          int(traffic["check_answers"]), control)
    check["numbers"]["programs_built_in_window"] = (programs_in_window, "<=", 0)
    from compare import within

    correct = bool(len(latency_ms)) and all(
        within(v) for v in check["numbers"].values())
    for name, (value, relation, limit) in check["numbers"].items():
        say(f"check {name} = {value!r} (limit {relation} {limit!r})")
    for why in check["breaches"]:
        say(f"check breach: {why}")
    for name, (value, relation, limit) in check.get("control", {}).items():
        say(f"control {name} = {value!r} (limit {relation} {limit!r})")
    if control:
        say(f"control correct = {check['control_correct']}")

    from stats import median, percentile

    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": {}, "device": device, "phases": phases,
              "checks": check["numbers"]}
    if not len(latency_ms):
        return result
    if not trace:
        values = {
            "throughput_qps": in_window / seconds,
            "latency_p50_ms": median(latency_ms),
            "latency_p95_ms": percentile(latency_ms, 95.0),
            "setup_s": setup_s,
        }
        # every value, also those BENCHMARK.json does not ask of this cell
        say("end to end " + json.dumps(values))
        for m in cell_metrics(bench, "end_to_end", workload):
            result["metrics"][m["name"]] = {
                "value": float(values[m["name"]]), "unit": m["unit"]}
        return result

    # ---- per-layer metrics: one small reader each, found by name
    prof = observed["profile"]
    device["busy_s"], device["window_s"] = prof["busy_s"], prof["window_s"]
    answered = ok & (res["t_end"] >= prof["t0"]) & (res["t_end"] <= prof["t1"])
    result["breakdown"] = {"device_ops": prof["device_ops"][:10],
                           "idle_gaps": prof["idle_gaps"][:5]}
    obs = {
        "latency_ms": latency_ms.tolist(),
        "spans_ms": span_samples(observed["traces"]),
        "counts": counts,  # deltas over the window, by dotted path
        "gauges": after_counts,  # as read when the window closed
        # requests a second while the profiler ran: a rate on the host's
        # clock, kept apart from the device's busy share on the trace's
        "profile": {**prof, "requests_per_s":
                    int(answered.sum()) / (prof["t1"] - prof["t0"])},
        "config": config, "docs": n_docs, "device": device,
        "peaks": load_json("peaks.json")["by_device_kind"],
        "rehearsal": rehearse,
    }
    shown = {k: v for k, v in counts.items()
             if v and k.startswith(("thread_pool.search.", "admission."))}
    say(f"window counts {shown}; traced window (first to last "
        f"device operation) {prof['window_s']:.3f}s busy {prof['busy_s']:.3f}s,"
        f" {obs['profile']['requests_per_s']:.1f} requests/s meanwhile, "
        f"modules {prof['modules']}; traces {len(observed['traces'])}")
    for m in cell_metrics(bench, "per_layer", workload):
        spec = load_json("layer_metrics", f"{m['name']}.json")
        value = load_plugin("readers", spec["reader"]).read(
            obs, spec.get("args", {}))
        if value is not None:  # a reader that finds nothing returns nothing
            result["metrics"][m["name"]] = {
                "value": float(value), "unit": m["unit"]}
    return result


def observe_window(http: Http, run_dir: str, t_start: float,
                   seconds: float, rehearsal: bool) -> dict:
    """The traced run's own observations, taken while the load runs: the
    per-request trace ring polled once a second, and one profiler window.
    Its length `window_s` and its busy time are both the trace's own, on
    the profiler's clock: first device operation's start to the last's
    end, so no operation lies outside the window it is divided by. `t0`,
    `t1` (host clock) only say which requests were answered meanwhile."""
    import jax

    from tracereduce import find_xplane, reduce_trace

    traces: dict = {}
    stop = threading.Event()

    def poll() -> None:
        poller = Http(http.conn.port)
        while not stop.wait(1.0):
            for tr in poller.call("GET", "/_internal/traces?n=256")["traces"]:
                traces[tr["trace_id"]] = tr

    thread = threading.Thread(target=poll, daemon=True)
    thread.start()
    trace_dir = os.path.join(run_dir, "profile")
    span = min(PROFILE_SECONDS, max(0.5, seconds - 2 * PROFILE_START_S))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    time.sleep(max(0.0, t_start + PROFILE_START_S - time.time()))
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    t0 = time.time()
    time.sleep(span)
    t1 = time.time()
    jax.profiler.stop_trace()
    time.sleep(max(0.0, t_start + seconds - time.time()))
    stop.set()
    thread.join(timeout=30)
    prof = reduce_trace(find_xplane(trace_dir), rehearsal=rehearsal)
    prof.update(t0=t0, t1=t1, window_s=prof["span_s"])
    return {"traces": traces, "profile": prof}


def check_answers(config: dict, corpus: dict, pool: list, res: dict,
                  seed: int, n: int, control: bool) -> dict:
    from compare import compare_all, reference_body

    have = sorted(res["answers"])
    order = np.random.default_rng([int(seed), 6]).permutation(len(have))
    chosen = [have[i] for i in order[:n]]
    bodies = [json.loads(pool[i]) for i in chosen]
    served = [json.loads(res["answers"][i]) for i in chosen]
    guarantees = config["guarantees"]
    t = time.time()
    ref = load_plugin("references", config["reference"]).Reference(
        corpus["reference"], config)
    refs = ref.answer_many(
        [reference_body(guarantees["rule"], b) for b in bodies])
    out = compare_all(guarantees, bodies, served, refs)
    say(f"reference answered {len(bodies)} requests in {time.time() - t:.2f}s")
    if control:
        low = ref.answer_many(bodies, precision="lower")
        c = compare_all(guarantees, bodies, low, refs)
        out["control"], out["control_correct"] = c["numbers"], c["correct"]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="any platform, rehearse_docs, no result line, exit 3")
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="also put the lower-precision reference in the "
                         "program's place and print what the check reads")
    args = ap.parse_args(argv)
    logging.basicConfig(stream=sys.stderr, level=logging.WARNING)
    result = run_cell(args.workload, args.seed, args.seconds,
                      bool(args.trace), rehearse=args.rehearse,
                      control=bool(args.control))
    say("phases " + json.dumps(result.pop("phases")))
    del result["checks"]  # printed above, one line each
    if args.rehearse:
        print("REHEARSAL on " + json.dumps(result["device"])
              + " - not a chip run, no result: " + json.dumps(result),
              file=sys.stderr)
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
