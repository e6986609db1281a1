#!/usr/bin/env python3
"""chip_smoke.py — the served search path, once, on the attached TPU.

The quickest proof that the program still starts, compiles, fits and
answers correctly on the chip. ONE process, no child processes, no
`ES_TPU_*` overrides (the defaults a node ships with: admission on,
bucket warm-up on, background refresh on, device segment build `auto`),
all data made from `--seed`.

    python chip_smoke.py               # one chip (what the driver runs)
    python chip_smoke.py --multichip   # four chips: mesh vs per-shard only

Phases of the default run:

  front door  an in-process `ElasticsearchTpuServer`, spoken to over
              HTTP: a `search.backend: jax` index and a `numpy` twin,
              the same seeded documents `_bulk`ed into both through the
              write path, then every plan family `_search`ed on both
              and compared.
  real size   the one-chip share of MS MARCO passage that bench.py
              builds (1,000,000 docs, 50k terms, 768-d fp16): the five
              BASELINE families through `IndexService.search`, i.e.
              through admission and the batcher, compared with the
              NumPy service on the same segment.
  counters    after each phase `_nodes/stats` and the module registries
              must show that the device path did the work and that
              nothing gave way (no fallback, no degrade, no failed
              warm-up launch).

THE COMPARISON RULE (written once, `compare()` applies it to every
family; `FAMILY_RULES` holds the per-family numbers and reasons):

  * `hits.total` must be equal, always. One exception, for the families
    whose default device path prunes (`PRUNING_FAMILIES`: the learned-
    sparse scorer skips block-max tiles it therefore never counts, and
    hybrid carries such a leg): there `"relation": "gte"` is a lower
    bound and must not exceed the oracle's count.
  * EXACT families (BM25 text, filters, aggregations — the same fp32
    formula on both sides): the same number of hits, the same ids in
    the same order — except that hits whose ORACLE scores tie within
    the tolerance may swap places — and position-wise scores within
    `rtol` (1e-5: a handful of fp32 ulps of re-association and the
    chip's non-IEEE divide; the v5e showed <= 1.8e-7). The oracle is
    asked for one hit past the page: only if that hit ties with the
    page's last group may the page boundary cut the group differently;
    otherwise the last group's ids are compared like any other.
    Aggregation trees must be equal (ints exactly, floats to 1e-6).
  * APPROXIMATE families (everything that crosses the MXU or a
    quantized plane): recall of the oracle's page >= the family's floor,
    every hit both sides return scores within the family's `rtol`, and
    — where misses can only come from arithmetic (kNN, sparse, rescore)
    — a hit only one page has must sit within 2*rtol of the other
    page's last score: a near-tie the stated difference can flip.
    Each bound is a small factor over what the v5e showed, so that a
    leg that degrades (say, to bf16 products) fails. Reasons: a matmul
    re-associates its fp32 sum with the launch shape (<= d*2^-24, so
    near-tied neighbours may swap and the k-th may change), and at the
    MXU's default precision an fp32 contraction may run as bf16 passes
    — the maxsim einsum does (4.0e-3 measured), the f32 x fp16 kNN
    matmul does not (1.1e-7 measured), so each has the bound its
    arithmetic gives; the learned-sparse column is served from its int8
    twin by default (per-term symmetric scales, error <= 1/254 per
    weight; 3.8e-3 measured); IVF probes 8 of ~2*sqrt(N) clusters by
    design. An RRF score is a function of the doc's rank in each leg
    and of nothing else: a doc at the same final rank on both sides
    must score the same (1e-4), one whose final rank differs moved in
    a leg, 1/(60+r) ~ 1.6% a rank, and is held to three ranks' worth.

Exit status: 0 and a last stdout line
`{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}`
only when every phase passed on a TPU. No accelerator -> non-zero at
once, no such line. Every phase's exception propagates.

`--rehearse` runs the same phases on whatever platform JAX finds (the
CPU in the sandbox) at whatever `--docs/--real-docs` say. It is for
finding wrong paths and arguments before a chip call; it prints no
result line and exits 3 even when everything passed.
"""

from __future__ import annotations

import argparse
import http.client
import json
import logging
import os
import statistics
import sys
import time

import numpy as np

# the one kNN score tolerance, shared with the tier-1 parity tests
from elasticsearch_tpu.ops.scoring import KNN_SCORE_RTOL

T_START = time.perf_counter()
FACTS: dict = {}

# family -> (class, recall floor, score rtol, why). "measured" is the
# v5e, chip_smoke.py at its defaults (seed 42, 25,000 / 1,000,000 docs).
FAMILY_RULES = {
    "match": ("exact", 1.0, 1e-5, "fp32 BM25, same formula"),
    "bool": ("exact", 1.0, 1e-5, "fp32 BM25, same formula"),
    "multi_match": ("exact", 1.0, 1e-5, "fp32 BM25F, same formula"),
    "filtered_bool": ("exact", 1.0, 1e-5, "fp32 BM25 under a bitset"),
    "aggs": ("exact", 1.0, 1e-5, "integer bucket counts"),
    "knn": ("approx", 0.9, KNN_SCORE_RTOL,
            "fp32 accumulation over d=768 in another order: <= d*2^-24 = "
            "4.6e-5 worst case (measured: 1.1e-7, recall 1.0, i.e. this "
            "f32 x fp16 matmul does NOT run as single bf16 passes; if it "
            "ever does, scores move by ~1e-3 and this fails - wanted)"),
    "ivf_knn": ("approx", 0.8, KNN_SCORE_RTOL,
                "probes nprobe=8 of ~2*sqrt(N) cells by design, and the "
                "build splits a true cluster over several cells (measured: "
                "recall 1.0; 0.8 for one query in 24 on the CPU); the "
                "scores it does return are fp32 dots as above (6.9e-8)"),
    "sparse_vector": ("approx", 0.8, 1e-2,
                      "served from the int8 impact twin (default): "
                      "<= 1/254 per weight (measured: 3.8e-3), which flips "
                      "near-ties at the page boundary (measured: recall "
                      "1.0; 0.8 for one query at other doc counts)"),
    "rescore_maxsim": ("approx", 0.9, 1.5e-2,
                       "the maxsim einsum DOES run at the MXU's default "
                       "precision (bf16 products, <= 2^-8 each), and the "
                       "blended score sums signed token maxima, so the "
                       "error is relative to their magnitudes, not to the "
                       "sum (measured: 4.0e-3, recall 1.0)"),
    "hybrid_rrf": ("approx", 0.8, 1e-4,
                   "a doc at the same final rank on both sides has the "
                   "same leg ranks and so the same score (measured: "
                   "5.8e-8); equal ranks in different legs tie exactly and "
                   "the page boundary cuts such a tie either way, and the "
                   "IVF leg misses by design (measured: recall 0.9)"),
}
# a hybrid doc whose FINAL rank differs between the pages moved in a leg:
# a step of 1/(60+r) ~ 1.6% per rank; three ranks' worth is admitted
RRF_MOVED_RTOL = 5e-2

# families whose default device path block-max-prunes and may therefore
# report hits.total as a "gte" lower bound
PRUNING_FAMILIES = {"sparse_vector", "hybrid_rrf"}

# approximate families whose every miss must be a near-tie at the page
# boundary (the others miss by design: IVF leaves clusters unprobed, RRF
# steps by whole ranks)
BOUNDARY_EXPLAINS_MISSES = {"knn", "sparse_vector", "rescore_maxsim"}


def say(msg: str) -> None:
    print(f"[smoke +{time.perf_counter() - T_START:7.1f}s] {msg}", flush=True)


def fact(key: str, value) -> None:
    FACTS[key] = value
    say(f"FACT {key} = {json.dumps(value)}")


class SmokeFailure(AssertionError):
    pass


def require(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# ---------------------------------------------------------------------------
# the comparison rule
# ---------------------------------------------------------------------------


def _hits(resp):
    return [(h["_id"], float(h["_score"])) for h in resp["hits"]["hits"]]


def _close(a: float, b: float, rtol: float) -> bool:
    return abs(a - b) <= rtol * max(abs(a), abs(b), 1e-30)


def _agg_equal(a, b, path="aggregations"):
    if isinstance(a, dict) and isinstance(b, dict):
        require(set(a) == set(b), f"{path}: keys {sorted(a)} != {sorted(b)}")
        for k in a:
            _agg_equal(a[k], b[k], f"{path}.{k}")
    elif isinstance(a, list) and isinstance(b, list):
        require(len(a) == len(b), f"{path}: {len(a)} != {len(b)} entries")
        for i, (x, y) in enumerate(zip(a, b)):
            _agg_equal(x, y, f"{path}[{i}]")
    elif isinstance(a, float) or isinstance(b, float):
        require(
            a is not None and b is not None and _close(a, b, 1e-6),
            f"{path}: {a} != {b}",
        )
    else:
        require(a == b, f"{path}: {a!r} != {b!r}")


def oracle_body(family: str, body: dict) -> dict:
    """What the oracle is asked: for an exact family one hit past the
    page, so the rule can tell a tie the page boundary cut from a wrong
    last hit."""
    size = body.get("size", 10)
    if FAMILY_RULES[family][0] == "exact" and size > 0:
        return {**body, "size": size + 1}
    return body


def compare(family: str, body: dict, jx: dict, oracle: dict,
            seen: dict) -> None:
    """THE rule (module docstring). `jx` answers `body`, `oracle`
    answers `oracle_body(family, body)`.
    `seen[family]` accumulates the worst observed score delta and
    recall, printed as facts."""
    cls, floor, rtol, _why = FAMILY_RULES[family]
    s = seen.setdefault(family, {"max_rel": 0.0, "min_recall": 1.0, "n": 0})
    s["n"] += 1
    tj, to = jx["hits"]["total"], oracle["hits"]["total"]
    require(
        tj == to
        or (family in PRUNING_FAMILIES and tj["relation"] == "gte"
            and tj["value"] <= to["value"]),
        f"{family}: hits.total {tj} disagrees with {to}",
    )
    if "aggregations" in oracle or "aggregations" in jx:
        _agg_equal(jx.get("aggregations"), oracle.get("aggregations"))
    hj, ho = _hits(jx), _hits(oracle)

    def note_rel(a, b):
        if b:
            s["max_rel"] = max(s["max_rel"], abs(a - b) / abs(b))

    if cls == "exact":
        page = body.get("size", 10)
        ho, past = ho[:page], ho[page:]  # past: the oracle's next hit
        require(len(hj) == len(ho), f"{family}: {len(hj)} hits != {len(ho)}")
        for (_, a), (_, b) in zip(hj, ho):
            note_rel(a, b)
            require(
                _close(a, b, rtol),
                f"{family}: score {a} != {b} beyond rtol {rtol}",
            )
        # tie groups of the oracle page: ids must match as sets — the
        # last group too, unless the oracle's next hit ties with it
        # (then the page boundary may cut that tie either way)
        i = 0
        while i < len(ho):
            j = i + 1
            while j < len(ho) and _close(ho[j][1], ho[i][1], rtol):
                j += 1
            cut = (j == len(ho) and past
                   and _close(past[0][1], ho[i][1], rtol))
            if not cut:
                require(
                    {d for d, _ in hj[i:j]} == {d for d, _ in ho[i:j]},
                    f"{family}: ids differ at ranks {i}..{j - 1}: "
                    f"{hj[i:j]} vs {ho[i:j]}",
                )
            i = j
        return
    require(len(hj) == len(ho), f"{family}: {len(hj)} hits != {len(ho)}")
    jm, om = dict(hj), dict(ho)
    if om:
        recall = len(set(jm) & set(om)) / len(om)
        s["min_recall"] = min(s["min_recall"], recall)
        require(
            recall >= floor,
            f"{family}: recall {recall:.3f} under the floor {floor} "
            f"({sorted(jm)} vs {sorted(om)})",
        )
    rank_j = {d: r for r, (d, _) in enumerate(hj)}
    rank_o = {d: r for r, (d, _) in enumerate(ho)}
    for d in set(jm) & set(om):
        note_rel(jm[d], om[d])
        tol = rtol
        if family == "hybrid_rrf" and rank_j[d] != rank_o[d]:
            tol = RRF_MOVED_RTOL
        require(
            _close(jm[d], om[d], tol),
            f"{family}: doc {d} score {jm[d]} vs {om[d]} beyond {tol}",
        )
    if family in BOUNDARY_EXPLAINS_MISSES and hj and ho:
        # a hit only one page has must be a near-tie with the OTHER
        # page's last score: within rtol on each side of the cut
        for mine, other, other_last, who in (
            (jm, om, ho[-1][1], "device"),
            (om, jm, hj[-1][1], "oracle"),
        ):
            for d in set(mine) - set(other):
                require(
                    _close(mine[d], other_last, 2 * rtol),
                    f"{family}: only the {who} page has doc {d} "
                    f"(score {mine[d]}) and it is no near-tie with the "
                    f"other page's last score {other_last}",
                )


def report_seen(phase: str, seen: dict) -> None:
    for fam, s in seen.items():
        cls, floor, rtol, why = FAMILY_RULES[fam]
        fact(
            f"{phase}.agree.{fam}",
            {
                "class": cls, "queries": s["n"],
                "max_rel_score_delta": s["max_rel"],
                "min_recall": s["min_recall"],
                "rtol": rtol, "recall_floor": floor, "why": why,
            },
        )


# ---------------------------------------------------------------------------
# compilation accounting (jax.monitoring; nothing is inferred from time)
# ---------------------------------------------------------------------------


class CompileWatch:
    """Counts backend compilations (and their seconds) and persistent-
    cache hits/misses through JAX's own monitoring events."""

    def __init__(self):
        import jax.monitoring as mon

        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
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

    def mark(self):
        return (self.compiles, self.compile_s, self.cache_hits,
                self.cache_misses)

    def since(self, m) -> dict:
        return {
            "compilations": self.compiles - m[0],
            "compile_seconds": round(self.compile_s - m[1], 2),
            "persistent_cache_hits": self.cache_hits - m[2],
            "persistent_cache_misses": self.cache_misses - m[3],
        }


# ---------------------------------------------------------------------------
# facts about the device
# ---------------------------------------------------------------------------


def measure_round_trip() -> None:
    """Host<->device transfer, measured directly: ops/scoring.py's fused
    design and ROADMAP S1 assume ~100 ms and ~16 MB/s, figures of
    hardware that is gone."""
    import jax

    dev = jax.devices()[0]

    def trip(host, reps):
        up, down = [], []
        for _ in range(reps):
            t0 = time.perf_counter()
            d = jax.device_put(host, dev)
            d.block_until_ready()
            t1 = time.perf_counter()
            back = np.asarray(d)
            t2 = time.perf_counter()
            assert back.nbytes == host.nbytes
            up.append(t1 - t0)
            down.append(t2 - t1)
            del d
        return statistics.median(up), statistics.median(down)

    small = np.zeros(1, np.float32)
    trip(small, 3)  # first touch of the transfer path
    up, down = trip(small, 50)
    fact("round_trip.4_bytes", {
        "device_put_block_ms_median": up * 1e3,
        "device_get_ms_median": down * 1e3, "readings": 50,
    })
    big = np.ones(64 * 1024 * 1024 // 4, np.float32)
    up, down = trip(big, 5)
    fact("round_trip.64_MiB", {
        "device_put_block_ms_median": up * 1e3,
        "device_get_ms_median": down * 1e3,
        "host_to_device_MB_per_s": big.nbytes / up / 1e6,
        "device_to_host_MB_per_s": big.nbytes / down / 1e6,
        "readings": 5,
    })


def memory_facts(label: str) -> None:
    import jax

    from elasticsearch_tpu.common.memory import hbm_ledger

    led = hbm_ledger.stats()
    per_dev = []
    for d in jax.devices():
        ms = d.memory_stats() or {}
        per_dev.append({
            "id": d.id,
            "bytes_in_use": ms.get("bytes_in_use"),
            "peak_bytes_in_use": ms.get("peak_bytes_in_use"),
            "bytes_limit": ms.get("bytes_limit"),
        })
    fact(f"memory.{label}", {
        "devices": per_dev,
        "hbm_ledger_bytes": led["estimated_size_in_bytes"],
        "hbm_ledger_limit": led["limit_size_in_bytes"],
        "hbm_ledger_by_category": led["by_category"],
    })


def require_ledger_clean(where: str) -> None:
    from elasticsearch_tpu.common.memory import hbm_ledger

    led = hbm_ledger.stats()
    require(led["tripped"] == 0, f"{where}: HBM breaker tripped {led}")
    require(
        led["degraded_allocations"] == 0,
        f"{where}: HBM ledger degraded an allocation {led}",
    )


def require_admission_clean(where: str) -> dict:
    """Admission is on (a node's default) and let every request of the
    smoke through whole: nothing shed with a 429, nothing browned out."""
    from elasticsearch_tpu.search.admission import admission

    st = admission.stats()
    require(st["enabled"], f"{where}: admission control is off")
    require(st["shed_rejected"] == 0 and st["brownouts"] == 0,
            f"{where}: admission shed or degraded smoke traffic: {st}")
    return {k: st[k] for k in ("enabled", "admitted", "shed_rejected",
                               "brownouts", "queue_delay_ewma_ms")}


def require_warm_clean(svc, where: str) -> None:
    b = svc._batcher
    require(b.wait_warm_idle(timeout=900.0), f"{where}: warm-up still running")
    n = b.stats["warmup_failures"]
    require(n == 0, f"{where}: {n} bucket warm-up launch(es) failed")


def serve_and_compare(phase, bodies, ask_jax, ask_oracle, work, svc,
                      watch: CompileWatch) -> None:
    """Both passes of one phase. First pass, family by family: every
    body goes to the jax side and the oracle and is held to THE rule;
    `work(family)` (a counter of device work) must have moved; the
    bucket warm-up the family set off must finish without a failed
    launch; compile seconds are printed per family. Second pass: the
    same bodies again must compile nothing."""
    seen: dict = {}
    moved = {}
    for family, fam_bodies in bodies.items():
        m = watch.mark()
        t0 = time.perf_counter()
        w0 = work(family)
        for body in fam_bodies:
            compare(family, body, ask_jax(body),
                    ask_oracle(oracle_body(family, body)), seen)
        require_warm_clean(svc, f"{phase} / {family}")
        moved[family] = work(family) - w0
        require(moved[family] > 0,
                f"{phase} / {family}: no device work was counted")
        fact(f"{phase}.compile.{family}", {
            **watch.since(m),
            "first_pass_wall_seconds": time.perf_counter() - t0,
        })
    fact(f"{phase}.device_work_by_family", moved)
    report_seen(phase, seen)
    m = watch.mark()
    for family, fam_bodies in bodies.items():
        for body in fam_bodies:
            compare(family, body, ask_jax(body),
                    ask_oracle(oracle_body(family, body)), {})
    second = watch.since(m)
    fact(f"{phase}.compile.second_pass", second)
    require(second["compilations"] == 0,
            f"{phase}: the second pass of the same queries compiled: "
            f"{second}")


# ---------------------------------------------------------------------------
# phase 1: the front door
# ---------------------------------------------------------------------------

DIMS = 768
IVF_DIMS = 128
TOK_DIMS = 32
BODY_VOCAB, TITLE_VOCAB, SPARSE_VOCAB = 20_000, 5_000, 300
IVF_CENTERS = 64
DAY0 = 1_700_000_000_000


class Http:
    def __init__(self, port: int):
        self.conn = http.client.HTTPConnection("127.0.0.1", port, timeout=900)

    def call(self, method: str, path: str, body=None, ndjson: bytes = None):
        headers = {}
        data = None
        if ndjson is not None:
            data, headers["Content-Type"] = ndjson, "application/x-ndjson"
        elif body is not None:
            data = json.dumps(body).encode()
            headers["Content-Type"] = "application/json"
        self.conn.request(method, path, body=data, headers=headers)
        resp = self.conn.getresponse()
        payload = resp.read()
        out = json.loads(payload) if payload else None
        require(
            resp.status < 300,
            f"{method} {path} -> HTTP {resp.status}: {str(out)[:600]}",
        )
        return out


class Corpus:
    """Seeded front-door documents, made in batches (vectorized where
    numpy can; the JSON is encoded once and sent to both indices)."""

    def __init__(self, seed: int):
        self.rng = np.random.default_rng(seed)
        zipf = 1.0 / np.arange(1, BODY_VOCAB + 1)
        self.body_p = zipf / zipf.sum()
        zt = 1.0 / np.arange(1, TITLE_VOCAB + 1)
        self.title_p = zt / zt.sum()
        zs = 1.0 / np.arange(1, SPARSE_VOCAB + 1)
        self.sparse_p = zs / zs.sum()
        self.body_words = np.array([f"w{i:05d}" for i in range(BODY_VOCAB)])
        self.title_words = np.array([f"t{i:05d}" for i in range(TITLE_VOCAB)])
        self.centers = self.rng.normal(size=(IVF_CENTERS, IVF_DIMS))
        self.body_df = np.zeros(BODY_VOCAB, np.int64)
        self.title_df = np.zeros(TITLE_VOCAB, np.int64)
        self.n = 0
        self.ivf_rows = []  # kept for "find my neighbours" queries

    def batch(self, size: int) -> bytes:
        rng = self.rng
        blen = rng.integers(15, 35, size=size)
        tlen = rng.integers(3, 9, size=size)
        btok = rng.choice(BODY_VOCAB, size=int(blen.sum()), p=self.body_p)
        ttok = rng.choice(TITLE_VOCAB, size=int(tlen.sum()), p=self.title_p)
        vec = rng.normal(size=(size, DIMS))
        vec /= np.linalg.norm(vec, axis=1, keepdims=True)
        vec = np.round(vec, 4)
        asg = rng.integers(0, IVF_CENTERS, size=size)
        ivf = self.centers[asg] + 0.5 * rng.normal(size=(size, IVF_DIMS))
        ivf /= np.linalg.norm(ivf, axis=1, keepdims=True)
        ivf = np.round(ivf, 4)
        self.ivf_rows.append(ivf[: max(1, size // 50)].copy())
        pop = rng.integers(0, 100, size=size)
        day = DAY0 + rng.integers(0, 30, size=size) * 86_400_000
        cat = rng.integers(0, 16, size=size)
        ntok = rng.integers(2, 7, size=size)
        toks = np.round(rng.normal(size=(int(ntok.sum()), TOK_DIMS)), 3)
        nsp = rng.integers(3, 9, size=size)
        lines = []
        bo = to = ko = 0
        for i in range(size):
            b = btok[bo: bo + blen[i]]
            t = ttok[to: to + tlen[i]]
            bo += blen[i]
            to += tlen[i]
            self.body_df[np.unique(b)] += 1
            self.title_df[np.unique(t)] += 1
            sp = rng.choice(
                SPARSE_VOCAB, size=int(nsp[i]), replace=False, p=self.sparse_p
            )
            sw = np.round(rng.uniform(0.1, 3.0, size=len(sp)), 3)
            doc = {
                "title": " ".join(self.title_words[t]),
                "body": " ".join(self.body_words[b]),
                "cat": f"cat{cat[i]:02d}",
                "popularity": int(pop[i]),
                "day": int(day[i]),
                "vec": vec[i].tolist(),
                "vec_ivf": ivf[i].tolist(),
                "ml": {f"tok{int(a):04d}": float(w) for a, w in zip(sp, sw)},
                "toks": toks[ko: ko + ntok[i]].tolist(),
            }
            ko += ntok[i]
            lines.append(json.dumps({"index": {"_id": str(self.n + i)}}))
            lines.append(json.dumps(doc))
        self.n += size
        return ("\n".join(lines) + "\n").encode()

    # ---- queries over what was indexed ----

    def _mid(self, df, words, lo_rank=30, hi_rank=2000):
        order = np.argsort(-df)
        cands = order[lo_rank: min(hi_rank, int((df > 0).sum()))]
        return words[cands]

    def text_queries(self, n: int, field: str = "body"):
        words = (
            self._mid(self.body_df, self.body_words) if field == "body"
            else self._mid(self.title_df, self.title_words, 10, 800)
        )
        out = []
        for _ in range(n):
            k = int(self.rng.integers(2, 5))
            out.append(list(self.rng.choice(words, size=k, replace=False)))
        return out

    def unit_vectors(self, n: int):
        v = self.rng.normal(size=(n, DIMS))
        v /= np.linalg.norm(v, axis=1, keepdims=True)
        return np.round(v, 4).tolist()

    def ivf_queries(self, n: int):
        rows = np.concatenate(self.ivf_rows)
        pick = self.rng.choice(len(rows), size=n, replace=False)
        q = rows[pick] + 0.05 * self.rng.normal(size=(n, IVF_DIMS))
        q /= np.linalg.norm(q, axis=1, keepdims=True)
        return np.round(q, 4).tolist()

    def sparse_queries(self, n: int):
        out = []
        for _ in range(n):
            k = int(self.rng.integers(2, 6))
            sp = self.rng.choice(
                SPARSE_VOCAB, size=k, replace=False, p=self.sparse_p
            )
            out.append({
                f"tok{int(a):04d}": float(np.round(w, 3))
                for a, w in zip(sp, self.rng.uniform(0.5, 2.0, size=k))
            })
        return out

    def token_queries(self, n: int):
        return [
            np.round(self.rng.normal(size=(4, TOK_DIMS)), 3).tolist()
            for _ in range(n)
        ]


MAPPINGS = {
    "properties": {
        "title": {"type": "text"},
        "body": {"type": "text"},
        "cat": {"type": "keyword"},
        "popularity": {"type": "integer"},
        "day": {"type": "date"},
        "vec": {"type": "dense_vector", "dims": DIMS, "similarity": "cosine"},
        # served by the IVF tier on the jax index (index.knn.type: ivf is
        # index-wide; `vec` opts back out per request with "exact": true)
        "vec_ivf": {
            "type": "dense_vector", "dims": IVF_DIMS, "similarity": "cosine",
        },
        "ml": {"type": "sparse_vector"},
        "toks": {
            "type": "rank_vectors", "dims": TOK_DIMS,
            "similarity": "dot_product",
        },
    }
}
JAX_INDEX, NP_INDEX = "smoke-jax", "smoke-numpy"
BULK_DOCS = 1000  # documents per _bulk request
# What the chip's machine ingests into both indices in about two minutes
# (25-28k in three timed calls). Fixed, not timed, so that the documents
# — and with them every answer the rule judges — are the same from run
# to run; well over the IVF tier's 4,096-doc floor.
FRONT_DOOR_DOCS = 25_000


def front_door_bodies(corpus: Corpus, n: int) -> dict:
    """family -> request bodies (the same body goes to both indices)."""
    tq = corpus.text_queries(4 * n)
    tt = corpus.text_queries(n, "title")
    qv = corpus.unit_vectors(2 * n)
    iq = corpus.ivf_queries(2 * n)
    sq = corpus.sparse_queries(2 * n)
    kq = corpus.token_queries(n)
    b: dict = {}
    b["match"] = [
        {"query": {"match": {"body": " ".join(t)}}, "size": 10}
        for t in tq[:n]
    ]
    b["bool"] = [
        {"query": {"bool": {
            "must": [{"term": {"body": t[0]}}],
            "should": [{"match": {"body": " ".join(t[1:])}}],
        }}, "size": 10}
        for t in tq[n: 2 * n]
    ]
    b["multi_match"] = [
        {"query": {"multi_match": {
            "query": f"{' '.join(a)} {' '.join(c[:2])}",
            "fields": ["title^2", "body"], "tie_breaker": 0.3,
        }}, "size": 10}
        for a, c in zip(tt, tq[2 * n: 3 * n])
    ]
    b["filtered_bool"] = [
        {"query": {"bool": {
            "must": [{"match": {"body": " ".join(t)}}],
            "filter": [
                {"term": {"cat": f"cat{i % 16:02d}"}},
                {"range": {"popularity": {"gte": 20}}},
            ],
        }}, "size": 10}
        for i, t in enumerate(tq[3 * n: 4 * n])
    ]
    b["knn"] = [
        {"knn": {"field": "vec", "query_vector": v, "k": 10,
                 "num_candidates": 100},
         "size": 10, "exact": True, "_source": False}
        for v in qv[:n]
    ]
    b["ivf_knn"] = [
        {"knn": {"field": "vec_ivf", "query_vector": v, "k": 10,
                 "num_candidates": 100},
         "size": 10, "_source": False}
        for v in iq[:n]
    ]
    b["sparse_vector"] = [
        {"query": {"sparse_vector": {"field": "ml", "query_vector": s}},
         "size": 10, "_source": False}
        for s in sq[:n]
    ]
    b["rescore_maxsim"] = [
        {"query": {"match": {"body": " ".join(t)}}, "size": 10,
         "_source": False,
         "rescore": {"window_size": 50, "query": {
             "rescore_query": {
                 "rank_vectors": {"field": "toks", "query_vectors": q}},
             "query_weight": 0.5, "rescore_query_weight": 2.0}}}
        for t, q in zip(tq[:n], kq)
    ]
    b["aggs"] = [
        {"size": 0, "request_cache": False,
         "query": {"match": {"body": " ".join(t)}},
         "aggs": {
             "cats": {"terms": {"field": "cat"}},
             "by_day": {"date_histogram": {
                 "field": "day", "fixed_interval": "1d"}},
         }}
        for t in tq[n: 2 * n]
    ]
    # the rrf knn leg rides the index's IVF tier (a body-level "exact"
    # does not reach retriever legs), so it queries the clustered field
    b["hybrid_rrf"] = [
        {"retriever": {"rrf": {"retrievers": [
            {"standard": {"query": {"match": {"body": " ".join(t)}}}},
            {"knn": {"field": "vec_ivf", "query_vector": v, "k": 20,
                     "num_candidates": 100}},
            {"standard": {"query": {"sparse_vector": {
                "field": "ml", "query_vector": s}}}},
        ], "rank_constant": 60}},
         "size": 10, "_source": False}
        for t, v, s in zip(tq[2 * n: 3 * n], iq[n:], sq[n:])
    ]
    return b


def node_stats(http: Http) -> dict:
    return http.call("GET", "/_nodes/stats")["nodes"]["node-0"]


def device_work(http: Http, jsvc, family: str) -> int:
    """The counter that moves when the device serves `family`: batcher
    launches (`_nodes/stats` thread_pool.search.launches; the numpy twin
    never launches) — except the filtered bool, which JaxExecutor.
    search_plan_filtered serves in the request thread (fused kernels
    under a cached DEVICE bitset, not a batcher job): there it is the
    jax index's filter-bitset cache lookups."""
    if family == "filtered_bool":
        from elasticsearch_tpu.search.query_cache import filter_cache

        fc = filter_cache.stats_for_index(jsvc.uuid)
        return fc["hit_count"] + fc["miss_count"]
    return node_stats(http)["thread_pool"]["search"]["launches"]


def phase_front_door(args, watch: CompileWatch) -> None:
    from elasticsearch_tpu.native import native_available
    from elasticsearch_tpu.rest.server import ElasticsearchTpuServer

    fact("native_codec_loaded", bool(native_available()))
    mark = watch.mark()
    server = ElasticsearchTpuServer(port=0)
    server.start_background()
    try:
        http = Http(server.port)
        base = {"number_of_shards": 1, "number_of_replicas": 0}
        http.call("PUT", f"/{JAX_INDEX}", {
            "settings": {**base, "search.backend": "jax", "knn.type": "ivf"},
            "mappings": MAPPINGS,
        })
        http.call("PUT", f"/{NP_INDEX}", {
            "settings": {**base, "search.backend": "numpy"},
            "mappings": MAPPINGS,
        })

        # ---- the write path ----
        corpus = Corpus(args.seed)
        t_jax = t_np = 0.0
        t0 = time.perf_counter()
        while corpus.n < args.docs:
            payload = corpus.batch(min(BULK_DOCS, args.docs - corpus.n))
            for index in (JAX_INDEX, NP_INDEX):
                t1 = time.perf_counter()
                r = http.call("POST", f"/{index}/_bulk", ndjson=payload)
                dt = time.perf_counter() - t1
                require(not r["errors"], f"_bulk into {index} had errors")
                if index == JAX_INDEX:
                    t_jax += dt
                else:
                    t_np += dt
        elapsed = time.perf_counter() - t0
        fact("front_door.ingest", {
            "docs_per_index": corpus.n,
            "jax_index_docs_per_s": corpus.n / t_jax,
            "numpy_index_docs_per_s": corpus.n / t_np,
            "wall_seconds_both_indices_and_generation": elapsed,
        })
        for index in (JAX_INDEX, NP_INDEX):
            http.call("POST", f"/{index}/_refresh")
        before_merge = node_stats(http)["ingest"]
        # one segment per index before any query: the two indices were
        # refreshed by their own background threads, so their segment
        # geometry differs; and a background-refresh segment under the
        # IVF floor (4,096 docs) would rightly be counted in
        # exact_fallbacks/small_segment_exact. The merge is itself the
        # largest device build of the run.
        t1 = time.perf_counter()
        for index in (JAX_INDEX, NP_INDEX):
            http.call("POST", f"/{index}/_forcemerge?max_num_segments=1")
            http.call("POST", f"/{index}/_refresh")
        fact("front_door.forcemerge_seconds", time.perf_counter() - t1)
        for index in (JAX_INDEX, NP_INDEX):
            n = http.call("GET", f"/{index}/_count")["count"]
            require(n == corpus.n, f"{index} holds {n} docs, sent {corpus.n}")
        ing = node_stats(http)["ingest"]
        fact("front_door.ingest_counters", {
            k: ing[k] for k in (
                "refreshes", "device_builds", "host_builds", "fallbacks",
                "degraded", "generations_discarded",
            )
        })
        require(
            before_merge["device_builds"] > 0,
            f"no refresh segment was built on the device: {before_merge}",
        )
        require(ing["device_builds"] > before_merge["device_builds"],
                "the merged segment was not built on the device")
        require(ing["fallbacks"] == 0, f"device build fell back: {ing}")
        require(ing["degraded"] == 0, f"device build degraded: {ing}")
        fact("front_door.compile.ingest", watch.since(mark))

        # ---- every plan family, over HTTP, on both indices ----
        bodies = front_door_bodies(corpus, args.queries)
        jsvc = server.cluster.indices[JAX_INDEX]
        ann_before_queries = node_stats(http)["knn"]["ann"]
        serve_and_compare(
            "front_door", bodies,
            lambda body: http.call("POST", f"/{JAX_INDEX}/_search", body),
            lambda body: http.call("POST", f"/{NP_INDEX}/_search", body),
            lambda family: device_work(http, jsvc, family),
            jsvc, watch,
        )

        # ---- no fallback went unseen ----
        require_warm_clean(jsvc, "front door")
        st = node_stats(http)
        fact("front_door.counters", {
            "batching": {
                k: st["pipeline"]["batching"][k]
                for k in ("launches_by_bucket", "warmup_failures",
                          "express_lane_hits", "worker_compile_ms")
            },
            "mesh": st["pipeline"]["mesh"],
            "aggs": st["aggs"],
            "ann": st["knn"]["ann"],
            "rescore": {k: v for k, v in st["rescore"].items()
                        if k != "windows"},
            "sparse": st["sparse"],
            "hbm": {k: st["breakers"]["hbm"][k]
                    for k in ("tripped", "degraded_allocations")},
            "admission": {k: st["admission"][k]
                          for k in ("enabled", "admitted", "shed_rejected",
                                    "brownouts")},
        })
        pb = st["pipeline"]["batching"]
        require(pb["warmup_failures"] == 0, f"warm-up launches failed: {pb}")
        mesh = st["pipeline"]["mesh"]
        require(mesh["fallbacks"] == 0 and mesh["degraded"] == 0,
                f"mesh path gave way: {mesh}")
        require(st["aggs"]["fallbacks"] == 0, f"device aggs fell back: "
                f"{st['aggs']}")
        require(st["aggs"]["device_routed"] > 0,
                f"no aggregation ran on the device: {st['aggs']}")
        ann = st["knn"]["ann"]
        require(ann["exact_fallbacks"] == 0, f"IVF probe fell back: {ann}")
        # small_segment_exact is rightly non-zero by now for a reason that
        # has nothing to do with the device: during ingest every
        # background-refresh segment (under the 4,096-doc IVF floor) was
        # counted once by the post-swap prewarm. What must hold is that
        # no QUERY met such a segment: the count has not moved since the
        # force-merge.
        require(
            ann["small_segment_exact"]
            == ann_before_queries["small_segment_exact"],
            f"a query met a segment under the IVF floor: {ann} "
            f"(before the queries: {ann_before_queries})",
        )
        require(ann["ann_searches"] > 0, f"no IVF probe ran: {ann}")
        rs = st["rescore"]
        require(rs["fallbacks"] == 0 and rs["skipped"] == 0,
                f"rescore gave way: {rs}")
        require(rs["device_rescores"] > 0, f"no device rescore ran: {rs}")
        sp = st["sparse"]
        require(sp["fallbacks"] == 0, f"sparse path fell back: {sp}")
        require(sp["searches"] > 0, f"no impact-tile scoring ran: {sp}")
        require(st["admission"]["enabled"]
                and st["admission"]["shed_rejected"] == 0,
                f"_nodes/stats admission block: {st['admission']}")
        require_admission_clean("front door")
        require_ledger_clean("front door")
        memory_facts("after_front_door")
        fact("front_door.compile.total", watch.since(mark))
    finally:
        server.close()


# ---------------------------------------------------------------------------
# phase 2: real size
# ---------------------------------------------------------------------------

BASELINE_FAMILIES = ("match", "bool", "multi_match", "knn", "hybrid_rrf")


def _median_latency_ms(svc, bodies) -> dict:
    ms = []
    for body in bodies:
        t0 = time.perf_counter()
        svc.search(body)
        ms.append((time.perf_counter() - t0) * 1e3)
    return {"median_ms": statistics.median(ms), "min_ms": min(ms),
            "max_ms": max(ms), "requests": len(ms)}


def phase_real_size(args, watch: CompileWatch) -> None:
    import bench  # the corpus and service builders are imported, not copied
    from elasticsearch_tpu.search import ann, sparse

    bench.N_DOCS = args.real_docs
    bench.N_QUERIES = max(64, 4 * args.queries)
    bench.N_QUERIES_SECONDARY = bench.N_QUERIES
    bench.SEED = args.seed
    mark = watch.mark()
    t0 = time.perf_counter()
    seg_jax, seg_np, body_df, title_df = bench.build_corpus()
    fact("real_size.build_corpus_seconds", time.perf_counter() - t0)
    svc = bench.make_service(seg_jax, "jax")
    oracle = bench.make_service(seg_np, "numpy")
    try:
        bodies = bench.build_bodies(body_df, title_df)
        n = args.queries
        serve_and_compare(
            "real_size",
            {f: bodies[f][:n] for f in BASELINE_FAMILIES},
            svc.search, oracle.search,
            lambda _family: svc._batcher.stats["launches"],
            svc, watch,
        )

        # single-request latency, warm, one request in flight (fresh
        # bodies of shapes that are compiled by now)
        m = watch.mark()
        fact("real_size.single_request_latency.match",
             _median_latency_ms(svc, bodies["match"][n: n + 20]))
        fact("real_size.single_request_latency.knn",
             _median_latency_ms(svc, bodies["knn"][n: n + 20]))
        fact("real_size.single_request_latency.compilations_meanwhile",
             watch.since(m)["compilations"])

        require_warm_clean(svc, "real size")
        bs = svc._batcher.batching_stats()
        fact("real_size.counters", {
            "batcher": {k: svc._batcher.stats[k] for k in (
                "launches", "jobs", "fused_jobs", "pruned_jobs",
                "fused_overflow_jobs", "sparse_jobs", "rejected",
                "shed_dead_jobs", "warmup_failures")},
            "launches_by_bucket": bs["launches_by_bucket"],
            "worker_compile_ms": bs["worker_compile_ms"],
            "ann": ann.stats_snapshot(),
            "sparse": sparse.stats_snapshot(),
        })
        require(svc._batcher.stats["rejected"] == 0
                and svc._batcher.stats["shed_dead_jobs"] == 0,
                "the batcher rejected or shed smoke traffic")
        require(sparse.stats_snapshot()["fallbacks"] == 0,
                "sparse leg fell back")
        require(ann.stats_snapshot()["exact_fallbacks"] == 0,
                "IVF probe fell back")
        fact("real_size.admission", require_admission_clean("real size"))
        require_ledger_clean("real size")
        memory_facts("after_real_size")
        fact("real_size.compile.total", watch.since(mark))
    finally:
        svc.close()
        oracle.close()


# ---------------------------------------------------------------------------
# --multichip: the SPMD mesh path vs the per-shard fan-out, and nothing else
# ---------------------------------------------------------------------------


def phase_multichip(args, watch: CompileWatch) -> None:
    import jax

    import bench

    n_dev = len(jax.devices())
    require(n_dev >= 4, f"--multichip needs four devices, JAX found {n_dev}")
    bench.N_DOCS = args.real_docs
    bench.MESH_SHARDS = 4
    bench.MESH_DOCS = args.real_docs
    bench.SEED = args.seed
    mark = watch.mark()
    t0 = time.perf_counter()
    # parity here is mesh vs per-shard on the SAME index: no NumPy twin
    svc, _none, body_df = bench.build_mesh_services(oracle=False)
    fact("multichip.build_seconds", time.perf_counter() - t0)
    try:
        texts = bench.make_query_texts(body_df, args.queries, seed=23)
        rng = np.random.default_rng(args.seed + 5)
        qv = rng.normal(size=(args.queries, bench.DIMS)).astype(np.float32)
        qv /= np.linalg.norm(qv, axis=1, keepdims=True)
        bodies = [
            ("match", {"query": {"match": {"body": t}}, "size": 10})
            for t in texts
        ] + [
            ("knn", {"knn": {"field": "vec",
                             "query_vector": [float(x) for x in v],
                             "k": 10, "num_candidates": 100}, "size": 10})
            for v in qv
        ]
        mex = svc.mesh_executor()
        require(os.environ.get("ES_TPU_MESH") is None, "ES_TPU_MESH is set")
        # Requests go back to back, as a client would send them: nothing
        # here waits for a bucket warm-up to finish. On this cold process
        # the per-shard path's four jobs a request queue behind first-use
        # compiles and warm-up ladders for tens of seconds; admission
        # (on) must not read that as load — checked below.
        mesh_resps = []
        for _fam, body in bodies:  # mesh: auto engages (>=2 devices+shards)
            r0 = mex.stats["routed"]
            mesh_resps.append(svc.search(body))
            require(mex.stats["routed"] == r0 + 1,
                    f"request did not take the mesh path: {body.keys()}")
        snap_ids = tuple(mex._snapshot.device_ids)
        stats = mex.stats_snapshot()
        fact("multichip.mesh", {**stats, "device_ids": list(snap_ids)})
        fact("multichip.compile.mesh", watch.since(mark))
        # the same requests through the per-shard fan-out, same process
        # (common/settings.mesh_mode reads the variable per call)
        m = watch.mark()
        os.environ["ES_TPU_MESH"] = "off"
        try:
            seq_resps = [svc.search(body) for _fam, body in bodies]
            # a JaxExecutor built with no device sits on the default one
            shard_devices = sorted({
                (ex.device or jax.devices()[0]).id
                for _gen, ex in svc._executors.values()
            })
        finally:
            os.environ.pop("ES_TPU_MESH", None)
        require(mex.stats["routed"] == len(bodies),
                "a per-shard request was mesh-routed")
        fact("multichip.compile.per_shard", watch.since(m))
        # the parity __graft_entry__.dryrun_multichip asserts
        worst = 0.0
        for (fam, body), a, b in zip(bodies, mesh_resps, seq_resps):
            ha, hb = _hits(a), _hits(b)
            require(ha, f"multichip / {fam}: no hits")
            require([d for d, _ in ha] == [d for d, _ in hb],
                    f"multichip / {fam}: mesh ranking != per-shard: "
                    f"{ha} vs {hb}")
            require(a["hits"]["total"] == b["hits"]["total"],
                    f"multichip / {fam}: totals differ")
            for (_, x), (_, y) in zip(ha, hb):
                worst = max(worst, abs(x - y) / abs(y))
                require(_close(x, y, 1e-5),
                        f"multichip / {fam}: score {x} vs {y} beyond 1e-5")
        fact("multichip.parity", {
            "requests": len(bodies), "ids_equal": True,
            "max_rel_score_delta": worst, "rtol": 1e-5,
        })
        stats = mex.stats_snapshot()
        require(stats["routed"] > 0, f"nothing was mesh-routed: {stats}")
        require(stats["fallbacks"] == 0 and stats["degraded"] == 0,
                f"the mesh path gave way: {stats}")
        require(len(set(snap_ids)) == 4,
                f"the mesh snapshot spans {snap_ids}, not four devices")
        # what the stacked kNN view really put on each device (code that
        # has never seen more than one real chip may have put everything
        # on the first), and what each device itself reports
        stacked = mex._knn_view(mex._snapshot, "vec")["vectors"]
        shard_bytes: dict = {}
        for sh in stacked.addressable_shards:
            shard_bytes[sh.device.id] = (
                shard_bytes.get(sh.device.id, 0) + int(sh.data.nbytes)
            )
        fact("multichip.stacked_vector_bytes_by_device", shard_bytes)
        require(
            len(shard_bytes) == 4 and all(v > 0 for v in shard_bytes.values()),
            f"the stacked vectors do not span four devices: {shard_bytes}",
        )
        in_use = {
            d.id: (d.memory_stats() or {}).get("bytes_in_use")
            for d in jax.devices()
        }
        fact("multichip.bytes_in_use_by_device", in_use)
        if jax.devices()[0].platform == "tpu":  # the CPU reports none
            require(all((in_use.get(i) or 0) > 0 for i in snap_ids),
                    f"a mesh device reports no bytes in use: {in_use}")
        # a fact, not a verdict (ROADMAP S2; cluster/indices.py builds
        # each shard's JaxExecutor with no device): not changed here
        fact("multichip.per_shard_path_device_ids", shard_devices)
        require_warm_clean(svc, "multichip")
        fact("multichip.admission", {
            **require_admission_clean("multichip"),
            # what the batcher kept out of the queue-delay signal
            "worker_compile_ms":
                svc._batcher.batching_stats()["worker_compile_ms"],
        })
        require_ledger_clean("multichip")
        memory_facts("after_multichip")
    finally:
        svc.close()


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=42)
    ap.add_argument("--multichip", action="store_true",
                    help="four chips: mesh vs per-shard parity, nothing else")
    ap.add_argument("--docs", type=int, default=FRONT_DOOR_DOCS,
                    help="front door: documents per index")
    ap.add_argument("--queries", type=int, default=4,
                    help="requests per plan family")
    ap.add_argument("--real-docs", type=int, default=1_000_000)
    ap.add_argument("--rehearse", action="store_true",
                    help="any platform, no result line, exit 3 (sandbox)")
    args = ap.parse_args(argv)

    overrides = sorted(k for k in os.environ if k.startswith("ES_TPU_"))
    require(not overrides,
            f"runs with a node's defaults; unset {overrides}")
    logging.basicConfig(stream=sys.stderr, level=logging.WARNING)

    import jax

    from elasticsearch_tpu.common.compile_cache import (
        CACHE_ENV,
        configure_compile_cache,
    )

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    if dev.platform != "tpu" and not args.rehearse:
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
              "this script only passes on the chip", file=sys.stderr)
        return 1
    cache_dir = configure_compile_cache()  # before anything compiles
    fact("device", device)
    fact("versions", {"jax": jax.__version__,
                      "jaxlib": __import__("jaxlib").__version__,
                      "python": sys.version.split()[0]})
    fact("compile_cache", {"dir": cache_dir,
                           "from_env": bool(os.environ.get(CACHE_ENV)),
                           "entries_at_start": len(os.listdir(cache_dir))
                           if os.path.isdir(cache_dir) else 0})
    watch = CompileWatch()
    if args.multichip:
        phase_multichip(args, watch)
    else:
        measure_round_trip()
        memory_facts("at_start")
        phase_front_door(args, watch)
        phase_real_size(args, watch)
    fact("compile.whole_run", watch.since((0, 0.0, 0, 0)))
    fact("wall_seconds", time.perf_counter() - T_START)
    out_dir = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "chiprun_out"
    )
    os.makedirs(out_dir, exist_ok=True)
    # a rehearsal never writes under the name of a chip run's facts
    name = ("rehearsal" if args.rehearse else "chip_smoke") + (
        "_multichip.json" if args.multichip else ".json")
    with open(os.path.join(out_dir, name), "w") as f:
        json.dump(FACTS, f, indent=1, default=str)
    if args.rehearse:
        print(f"chip_smoke: REHEARSAL passed on {device} — not a chip run, "
              "no result", file=sys.stderr)
        return 3
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
