"""Benchmark: the five BASELINE.md configs through the SERVING path at 1M docs.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N,
   "configs": {match, bool, multi_match, knn (exact baseline),
               ann_knn (IVF nprobe sweep + recall@10), hybrid_rrf}, ...}

What is measured (BASELINE.md config table / VERDICT round-3 #4, #5):
  - the REST/executor serving path — IndexService.search() end to end:
    JSON parse → micro-batching dispatcher → batched device kernels
    (fused single-round-trip text scoring, batched matmul kNN) →
    cross-segment merge → response assembly. NOT a standalone scorer.
  - 1,000,000-doc synthetic Zipf corpus with TWO text fields
    (title/body) and 768-d unit vectors (MS MARCO is unavailable in
    this zero-egress image; the power-law vocabulary reproduces its
    posting-list skew, the vector field its ANN config). Vectors are
    stored float16 and upcast on device: half the HBM and half the
    upload of fp32. (The choice was tuned to the transfer costs of
    hardware no longer present; on the attached chip the upload cost
    is "not measured" until chip_smoke.py's figures are in CHANGES.md.)
    The CPU oracle scores the SAME values in float32, so the recall
    gates compare identical inputs.
  - per config: QPS + p50/p99 under concurrent client threads, a
    recall gate vs the NumPy oracle, and the oracle's own QPS as the
    measured CPU denominator (vs_baseline).
  - baseline_kind documents the denominator honestly: the oracle is a
    dense vectorized NumPy scorer (it scores every live doc of every
    segment — no WAND skipping), run on the same serving path, plus a
    single-thread measurement for a GIL-free per-core number.
  - recall residue: device vs oracle score deltas on common hits are
    reported (max relative delta) — fp32 re-association at the k
    boundary, not ranking bugs.

All diagnostics go to stderr; stdout is exactly the one JSON line.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

import numpy as np


def log(*a):
    print(*a, file=sys.stderr, flush=True)


# env overrides exist for small-scale smoke runs (tests/CI); the real
# benchmark uses the defaults
N_DOCS = int(os.environ.get("BENCH_N_DOCS", 1_000_000))
VOCAB = int(os.environ.get("BENCH_VOCAB", 50_000))
TITLE_VOCAB = min(20_000, VOCAB)
DIMS = int(os.environ.get("BENCH_DIMS", 768))
N_QUERIES = int(os.environ.get("BENCH_N_QUERIES", 4096))
N_QUERIES_SECONDARY = max(N_QUERIES // 2, 1)
THREADS = int(os.environ.get("BENCH_THREADS", 192))  # enough in-flight
# requests to keep several fused batches in flight per dispatcher worker
# (see ops/scoring.py). The figure was tuned to the host<->device
# transfer costs of hardware no longer present; on the attached chip
# the right client count is "not measured".
ORACLE_THREADS = min(32, THREADS)  # the CPU oracle is GIL-bound; more
# threads only thrash
K = 10
SEED = 42
AVG_LEN = (15, 35)  # body length range (tokens)
TITLE_LEN = (3, 9)
# learned-sparse column (SPLADE-shaped expansions): a few hundred
# activated vocabulary entries, zipf-popular so hot terms span many
# impact tiles — the regime block-max pruning exists for
SPARSE_VOCAB = int(os.environ.get("BENCH_SPARSE_VOCAB", 300))
SPARSE_TERMS_PER_DOC = (3, 9)


# ---------------------------------------------------------------------------
# corpus + index construction (vectorized scaffolding, not measured)
# ---------------------------------------------------------------------------


def build_postings(rng, vocab, lengths, n_docs=None):
    from elasticsearch_tpu.index.segment import (
        INVALID_DOC,
        TILE,
        FieldStats,
        PostingsField,
    )
    from elasticsearch_tpu.utils.smallfloat import encode_norms

    n_docs = N_DOCS if n_docs is None else int(n_docs)
    probs = 1.0 / np.arange(1, vocab + 1)
    probs /= probs.sum()
    total = int(lengths.sum())
    log(f"sampling {total} tokens over {vocab} terms…")
    term_stream = rng.choice(vocab, size=total, p=probs).astype(np.int64)
    doc_of = np.repeat(np.arange(n_docs, dtype=np.int64), lengths)

    key = term_stream * n_docs + doc_of
    uniq, counts = np.unique(key, return_counts=True)
    u_t = (uniq // n_docs).astype(np.int64)
    u_d = (uniq % n_docs).astype(np.int32)
    tfs_flat = counts.astype(np.int32)
    log(f"{len(uniq)} postings")

    term_df = np.bincount(u_t, minlength=vocab).astype(np.int32)
    term_total_tf = np.bincount(u_t, weights=tfs_flat, minlength=vocab).astype(
        np.int64
    )
    term_tile_count = ((term_df + TILE - 1) // TILE).astype(np.int32)
    term_tile_start = np.zeros(vocab, np.int32)
    np.cumsum(term_tile_count[:-1], out=term_tile_start[1:])
    n_tiles = int(term_tile_count.sum())

    term_post_start = np.zeros(vocab, np.int64)
    np.cumsum(term_df[:-1].astype(np.int64), out=term_post_start[1:])
    rank = np.arange(len(u_t), dtype=np.int64) - term_post_start[u_t]
    slot = term_tile_start[u_t].astype(np.int64) * TILE + rank

    doc_ids = np.full(n_tiles * TILE, INVALID_DOC, np.int32)
    tfs = np.zeros(n_tiles * TILE, np.int32)
    doc_ids[slot] = u_d
    tfs[slot] = tfs_flat
    doc_ids = doc_ids.reshape(n_tiles, TILE)
    tfs = tfs.reshape(n_tiles, TILE)

    norms = encode_norms(lengths.astype(np.int64))
    tile_max_tf = tfs.max(axis=1).astype(np.int32)
    valid = doc_ids >= 0
    tile_norms = np.where(valid, norms[np.clip(doc_ids, 0, n_docs - 1)], 255)
    tile_min_norm = tile_norms.min(axis=1).astype(np.uint8)

    terms = [f"w{i:05d}" for i in range(vocab)]  # sorted lexicographically
    stats = FieldStats(
        doc_count=n_docs,
        sum_total_term_freq=int(term_total_tf.sum()),
        sum_doc_freq=int(term_df.sum()),
    )
    pf = PostingsField(
        terms=terms,
        term_df=term_df,
        term_total_tf=term_total_tf,
        term_tile_start=term_tile_start,
        term_tile_count=term_tile_count,
        doc_ids=doc_ids,
        tfs=tfs,
        tile_max_tf=tile_max_tf,
        tile_min_norm=tile_min_norm,
        norms=norms,
        stats=stats,
    )
    return pf, term_df


def _sparse_popularity():
    pop = 1.0 / np.arange(1, SPARSE_VOCAB + 1) ** 0.7
    return pop / pop.sum()


def build_sparse_column(rng, n_docs):
    """Impact-ordered learned-sparse column for the main corpus: per-doc
    term→weight maps laid out by the SAME host planner the real build
    path uses (segment.sparse_plan/sparse_from_plan), so the bench
    serves the production int8 + fp32 twin planes, not a replica."""
    from elasticsearch_tpu.index.segment import sparse_from_plan, sparse_plan

    pop = _sparse_popularity()
    nt = rng.integers(*SPARSE_TERMS_PER_DOC, size=n_docs)
    total = int(nt.sum())
    t_flat = rng.choice(SPARSE_VOCAB, size=total, p=pop).astype(np.int64)
    d_flat = np.repeat(np.arange(n_docs, dtype=np.int64), nt)
    w_flat = (rng.random(total) * 3 + 0.05).astype(np.float32)
    # dedupe (term, doc) pairs — a doc activates each expansion once
    key = t_flat * n_docs + d_flat
    _, first = np.unique(key, return_index=True)
    t_u, d_u, w_u = t_flat[first], d_flat[first], w_flat[first]
    order = np.argsort(t_u, kind="stable")
    t_u, d_u, w_u = t_u[order], d_u[order], w_u[order]
    bounds = np.searchsorted(t_u, np.arange(SPARSE_VOCAB + 1))
    inv = {}
    for tid in range(SPARSE_VOCAB):
        lo, hi = int(bounds[tid]), int(bounds[tid + 1])
        if hi > lo:
            inv[f"tok{tid:04d}"] = dict(
                zip(d_u[lo:hi].tolist(), w_u[lo:hi].tolist())
            )
    plan = sparse_plan(inv, pruning_ratio=0.0)
    return sparse_from_plan(plan, n_docs, np.ones(n_docs, bool))


def make_sparse_vectors(n, seed=23):
    """SPLADE-shaped query vectors over the sparse vocabulary."""
    rng = np.random.default_rng(seed)
    pop = _sparse_popularity()
    out = []
    for _ in range(n):
        k = int(rng.integers(2, 6))
        picked = rng.choice(SPARSE_VOCAB, size=k, replace=False, p=pop)
        out.append(
            {
                f"tok{int(t):04d}": float(np.round(rng.random() * 2 + 0.1, 4))
                for t in picked
            }
        )
    return out


def build_corpus():
    from elasticsearch_tpu.index.segment import (
        NumericField,
        OrdinalField,
        Segment,
        VectorField,
    )

    rng = np.random.default_rng(SEED)
    body_lengths = rng.integers(AVG_LEN[0], AVG_LEN[1], size=N_DOCS)
    title_lengths = rng.integers(TITLE_LEN[0], TITLE_LEN[1], size=N_DOCS)
    body_pf, body_df = build_postings(rng, VOCAB, body_lengths)
    title_pf, title_df = build_postings(rng, TITLE_VOCAB, title_lengths)

    log(f"sampling {N_DOCS}x{DIMS} unit vectors (float16)…")
    vecs = rng.normal(size=(N_DOCS, DIMS)).astype(np.float32)
    vecs /= np.linalg.norm(vecs, axis=1, keepdims=True)
    vecs16 = vecs.astype(np.float16)
    exists = np.ones(N_DOCS, bool)
    # numeric doc-value column for the agg/range-filter configs
    popularity = rng.integers(0, 100, size=N_DOCS).astype(np.float64)
    # dashboard-shape agg columns (cold_agg config): a 30-day date
    # column and a 16-way keyword column (single-valued ordinal CSR)
    day = (
        1_700_000_000_000
        + rng.integers(0, 30, size=N_DOCS).astype(np.int64) * 86_400_000
    ).astype(np.float64)
    cat_ords = rng.integers(0, 16, size=N_DOCS).astype(np.int32)
    cat_field = OrdinalField(
        ord_terms=[f"cat{j:02d}" for j in range(16)],
        ords=cat_ords,
        mv_ords=cat_ords.copy(),
        mv_offsets=np.arange(N_DOCS + 1, dtype=np.int32),
    )
    log(f"building sparse column ({SPARSE_VOCAB}-token vocab)…")
    sparse_field = build_sparse_column(rng, N_DOCS)

    def seg_with(vectors):
        return Segment(
            num_docs=N_DOCS,
            doc_ids=[str(i) for i in range(N_DOCS)],
            sources=[None] * N_DOCS,
            postings={"body": body_pf, "title": title_pf},
            numerics={
                "popularity": NumericField(
                    values=popularity, exists=exists.copy()
                ),
                "day": NumericField(values=day, exists=exists.copy()),
            },
            ordinals={"cat": cat_field},
            vectors={
                "vec": VectorField(
                    vectors=vectors,
                    exists=exists,
                    similarity="cosine",
                    unit_vectors=vectors,
                )
            },
            # one shared column: the jax path serves its int8 twin, the
            # numpy oracle scores the identical fp32 plane
            sparse={"ml": sparse_field},
        )

    # jax path uploads float16 (MXU accumulates fp32); the oracle scores
    # the same values upcast to float32 — identical inputs either way
    seg_jax = seg_with(vecs16)
    seg_np = seg_with(vecs16.astype(np.float32))
    return seg_jax, seg_np, body_df, title_df


def make_service(seg, backend: str):
    from elasticsearch_tpu.cluster.indices import IndexService

    svc = IndexService(
        f"bench-{backend}",
        settings={"number_of_shards": 1, "search.backend": backend},
        mappings_json={
            "properties": {
                "title": {"type": "text"},
                "body": {"type": "text"},
                "popularity": {"type": "integer"},
                "day": {"type": "date"},
                "cat": {"type": "keyword"},
                "vec": {
                    "type": "dense_vector",
                    "dims": DIMS,
                    "similarity": "cosine",
                },
                "ml": {"type": "sparse_vector"},
            }
        },
    )
    eng = svc.shards[0]
    eng.segments = [seg]
    eng.live_docs = [None]
    eng.seg_versions = [np.ones(N_DOCS, np.int64)]
    eng.seg_seqnos = [np.arange(N_DOCS, dtype=np.int64)]
    eng.seg_names = ["seg_0_0"]
    eng._next_seq = N_DOCS
    eng.change_generation += 1
    return svc


def _mid_freq_terms(term_df, lo=50, hi=8000):
    order = np.argsort(-term_df)
    return order[lo:min(len(order), hi)]


def make_query_texts(term_df, n, seed=7, lo=50, hi=8000):
    rng = np.random.default_rng(seed)
    cands = _mid_freq_terms(term_df, lo, hi)
    out = []
    for _ in range(n):
        k = int(rng.integers(2, 5))
        picked = rng.choice(len(cands), size=k, replace=False)
        out.append(" ".join(f"w{cands[int(i)]:05d}" for i in picked))
    return out


# ---------------------------------------------------------------------------
# the five BASELINE configs as body builders
# ---------------------------------------------------------------------------


def build_bodies(body_df, title_df):
    rng = np.random.default_rng(11)
    texts = make_query_texts(body_df, N_QUERIES)
    bodies = {}
    bodies["match"] = [
        {"query": {"match": {"body": t}}, "size": K} for t in texts
    ]
    # config 2: bool must (conjunction) + should (scoring disjunction)
    cands = _mid_freq_terms(body_df)
    bool_bodies = []
    for _ in range(N_QUERIES_SECONDARY):
        picked = rng.choice(len(cands), size=4, replace=False)
        t = [f"w{cands[int(i)]:05d}" for i in picked]
        bool_bodies.append(
            {
                "query": {
                    "bool": {
                        "must": [{"term": {"body": t[0]}}],
                        "should": [
                            {"match": {"body": f"{t[1]} {t[2]}"}},
                            {"match": {"body": t[3]}},
                        ],
                    }
                },
                "size": K,
            }
        )
    bodies["bool"] = bool_bodies
    # config 3: multi_match BM25F title/body
    t_texts = make_query_texts(title_df, N_QUERIES_SECONDARY, seed=13, hi=6000)
    bodies["multi_match"] = [
        {
            "query": {
                "multi_match": {
                    "query": t,
                    "fields": ["title^2", "body"],
                    "tie_breaker": 0.3,
                }
            },
            "size": K,
        }
        for t in t_texts
    ]
    # config 4: brute-force cosine kNN 768-d
    qv = rng.normal(size=(N_QUERIES_SECONDARY, DIMS)).astype(np.float32)
    qv /= np.linalg.norm(qv, axis=1, keepdims=True)
    bodies["knn"] = [
        {
            "knn": {
                "field": "vec",
                "query_vector": [float(x) for x in v],
                "k": K,
                "num_candidates": 100,
            },
            "size": K,
        }
        for v in qv
    ]
    # config: learned-sparse retrieval — SPLADE-shaped client-supplied
    # term→weight maps over the impact-ordered int8 postings (the numpy
    # oracle scores the identical fp32 plane exactly)
    sparse_qvs = make_sparse_vectors(N_QUERIES_SECONDARY)
    bodies["sparse_retrieval"] = [
        {
            "query": {"sparse_vector": {"field": "ml", "query_vector": sv}},
            "size": K,
            "_source": False,
        }
        for sv in sparse_qvs
    ]
    # config 5: hybrid BM25 + kNN + learned-sparse fused with RRF
    bodies["hybrid_rrf"] = [
        {
            "retriever": {
                "rrf": {
                    "retrievers": [
                        {
                            "standard": {
                                "query": {
                                    "multi_match": {
                                        "query": t,
                                        "fields": ["title", "body"],
                                    }
                                }
                            }
                        },
                        {
                            "knn": {
                                "field": "vec",
                                "query_vector": [float(x) for x in v],
                                "k": 20,
                                "num_candidates": 100,
                            }
                        },
                        {
                            "standard": {
                                "query": {
                                    "sparse_vector": {
                                        "field": "ml",
                                        "query_vector": sv,
                                    }
                                }
                            }
                        },
                    ],
                    "rank_constant": 60,
                }
            },
            "size": K,
            "_source": False,
        }
        for t, v, sv in zip(t_texts[:1024], qv[:1024], sparse_qvs[:1024])
    ]
    # config 6: filter-context bool (device filter-bitset cache). The
    # scoring part mirrors the bool config; the "warm" variant reuses a
    # small rotating filter set (bitsets cached across requests), the
    # "cold" variant gives every request a UNIQUE filter term so each
    # one pays full filter evaluation — the cold-vs-warm delta is the
    # cached-bitset win.
    n_f = N_QUERIES_SECONDARY
    filt_cands = _mid_freq_terms(body_df, lo=200, hi=4000)

    def filtered_body(i, filter_term):
        picked = rng.choice(len(cands), size=3, replace=False)
        t = [f"w{cands[int(j)]:05d}" for j in picked]
        return {
            "query": {
                "bool": {
                    "must": [{"term": {"body": t[0]}}],
                    "should": [{"match": {"body": f"{t[1]} {t[2]}"}}],
                    "filter": [
                        {"term": {"body": filter_term}},
                        {"range": {"popularity": {"gte": 20}}},
                    ],
                }
            },
            "size": K,
        }

    warm_filters = [
        f"w{filt_cands[int(i)]:05d}"
        for i in rng.choice(len(filt_cands), size=8, replace=False)
    ]
    bodies["filtered_bool"] = [
        filtered_body(i, warm_filters[i % len(warm_filters)])
        for i in range(n_f)
    ]
    bodies["filtered_bool_cold"] = [
        filtered_body(i, f"w{filt_cands[i % len(filt_cands)]:05d}")
        for i in range(n_f)
    ]
    # config 7: repeated size:0 agg requests (shard request cache) — a
    # small distinct set cycled, the steady-state shape of dashboard
    # traffic
    agg_texts = make_query_texts(body_df, 64, seed=17)
    bodies["repeated_agg"] = [
        {
            "size": 0,
            "query": {"match": {"body": t}},
            "aggs": {"pop_avg": {"avg": {"field": "popularity"}}},
        }
        for t in agg_texts
    ]
    # config 8: COLD agg traffic — every request is a unique dashboard
    # body (terms + date_histogram + stats, the classic Kibana shape)
    # with the request cache opted out, so each one pays the full agg
    # computation: host AggCollector vs the device segment-sum engine
    # is an apples-to-apples A/B on the same bodies.
    cold_agg_texts = make_query_texts(
        body_df, min(N_QUERIES_SECONDARY, 1024), seed=19
    )
    bodies["cold_agg"] = [
        {
            "size": 0,
            "request_cache": False,
            "query": {"match": {"body": t}},
            "aggs": {
                "by_day": {
                    "date_histogram": {
                        "field": "day", "fixed_interval": "1d",
                    }
                },
                "cats": {"terms": {"field": "cat"}},
                "pop": {"stats": {"field": "popularity"}},
            },
        }
        for t in cold_agg_texts
    ]
    return bodies


# ---------------------------------------------------------------------------
# measurement
# ---------------------------------------------------------------------------


def run_load(svc, bodies, threads=THREADS):
    """Concurrent closed-loop load; returns (qps, p50_ms, p99_ms,
    wall_s)."""
    lat = []
    lat_lock = threading.Lock()
    qi = [0]
    qlock = threading.Lock()

    def worker():
        local = []
        while True:
            with qlock:
                i = qi[0]
                if i >= len(bodies):
                    break
                qi[0] += 1
            t0 = time.perf_counter()
            r = svc.search(bodies[i])
            local.append(time.perf_counter() - t0)
            assert "hits" in r
        with lat_lock:
            lat.extend(local)

    ts = [threading.Thread(target=worker) for _ in range(threads)]
    t0 = time.perf_counter()
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    wall = time.perf_counter() - t0
    lat_ms = np.asarray(lat) * 1000.0
    return (
        len(bodies) / wall,
        float(np.percentile(lat_ms, 50)),
        float(np.percentile(lat_ms, 99)),
        wall,
    )


def run_open_loop(
    svc, bodies, rate_qps, duration_s, slo_ms, seed=101, max_workers=256
):
    """Open-loop load: Poisson arrivals at `rate_qps` for `duration_s`,
    independent of completions — the traffic shape closed-loop QPS
    numbers hide. Under overload a closed loop politely slows its own
    generator; an open loop keeps arriving, so collapse shows up as
    unbounded queueing unless the node sheds. Returns offered/completed/
    shed counts, goodput (completed-within-SLO per second), and
    accepted-request latency percentiles."""
    from concurrent.futures import ThreadPoolExecutor

    from elasticsearch_tpu.common.memory import CircuitBreakingException
    from elasticsearch_tpu.search.admission import EsOverloadedError
    from elasticsearch_tpu.search.batcher import EsRejectedExecutionError

    rng = np.random.default_rng(seed)
    results = []
    rlock = threading.Lock()

    def one(body):
        t0 = time.perf_counter()
        try:
            r = svc.search(body)
            ok = "hits" in r
            shed = False
        except (
            EsOverloadedError, EsRejectedExecutionError,
            CircuitBreakingException,
        ):
            ok, shed = False, True
        dt_ms = (time.perf_counter() - t0) * 1000.0
        with rlock:
            results.append((ok, shed, dt_ms))

    pool = ThreadPoolExecutor(
        max_workers=max_workers, thread_name_prefix="open-loop"
    )
    # the in-process arrival generator competes for the GIL with every
    # worker thread; a finer switch interval keeps the offered rate
    # honest under load (restored afterwards)
    import sys

    prev_switch = sys.getswitchinterval()
    sys.setswitchinterval(0.001)
    offered = 0
    t_start = time.perf_counter()
    next_t = 0.0
    try:
        while True:
            now = time.perf_counter() - t_start
            if now >= duration_s:
                break
            if now < next_t:
                time.sleep(min(next_t - now, 0.01))
                continue
            pool.submit(one, bodies[offered % len(bodies)])
            offered += 1
            next_t += float(rng.exponential(1.0 / rate_qps))
    finally:
        sys.setswitchinterval(prev_switch)
    pool.shutdown(wait=True)
    wall = time.perf_counter() - t_start
    ok_lat = np.asarray([dt for ok, _, dt in results if ok])
    shed = sum(1 for _, s, _ in results if s)
    errors = len(results) - len(ok_lat) - shed
    within_slo = int((ok_lat <= slo_ms).sum()) if len(ok_lat) else 0
    return {
        "offered": offered,
        "offered_qps": round(offered / wall, 1),
        "completed": int(len(ok_lat)),
        "completed_qps": round(len(ok_lat) / wall, 1),
        "shed_429": int(shed),
        "errors": int(errors),
        "within_slo": within_slo,
        "goodput_qps": round(within_slo / wall, 1),
        "slo_ms": float(slo_ms),
        "accepted_p50_ms": (
            round(float(np.percentile(ok_lat, 50)), 2) if len(ok_lat) else None
        ),
        "accepted_p99_ms": (
            round(float(np.percentile(ok_lat, 99)), 2) if len(ok_lat) else None
        ),
        "wall_s": round(wall, 2),
    }


def batching_window(b0, b1):
    """Continuous-batching numbers over one measured window from two
    QueryBatcher.batching_stats() snapshots: per-bucket launch hit
    rates, the average padded launch width, occupancy (padding waste),
    and express-lane hits."""
    hist = {}
    for k in set(b0["launches_by_bucket"]) | set(b1["launches_by_bucket"]):
        d = b1["launches_by_bucket"].get(k, 0) - b0["launches_by_bucket"].get(
            k, 0
        )
        if d > 0:
            hist[k] = d
    launches = sum(hist.values())
    jobs = b1["occupancy_jobs"] - b0["occupancy_jobs"]
    slots = b1["occupancy_slots"] - b0["occupancy_slots"]
    return {
        "launches": launches,
        "avg_launch_width": round(slots / launches, 2) if launches else 0.0,
        "avg_occupancy": round(jobs / slots, 4) if slots else 0.0,
        "bucket_hit_rates": {
            k: round(v / launches, 4) for k, v in sorted(
                hist.items(), key=lambda kv: int(kv[0])
            )
        },
        "express_lane_hits": (
            b1["express_lane_hits"] - b0["express_lane_hits"]
        ),
    }


def leg_p50s(svc):
    """Per-leg p50/p99 (ms) from the index's bounded rrf leg-latency
    reservoirs — the per-request number next to the cumulative
    bm25_leg_ms/knn_leg_ms averages."""
    out = {}
    with svc._rrf_lock:
        samples = {k: list(v) for k, v in svc.rrf_leg_samples.items()}
    for leg, vals in samples.items():
        if vals:
            arr = np.asarray(vals)
            out[f"{leg}_leg_p50_ms"] = round(float(np.percentile(arr, 50)), 2)
            out[f"{leg}_leg_p99_ms"] = round(float(np.percentile(arr, 99)), 2)
    return out


def batch1_p50(svc, bodies, n=32):
    """Single-inflight latency (bench honesty: pipelining gains must not
    hide latency regressions behind batching) — p50 over n sequential
    requests with exactly one in flight."""
    _, p50, _, _ = run_load(svc, bodies[: max(1, n)], threads=1)
    return p50


def recall_gate(svc_jax, svc_oracle, bodies, n=12, k=1000):
    """recall@k of the device path vs the oracle + max relative score
    delta on common hits (the fp re-association residue, bounded)."""
    recalls = []
    max_rel = 0.0
    for body in bodies[:n]:
        if "retriever" in body:
            big = {**body, "size": 100}
        else:
            big = {**body, "size": k, "_source": False}
            if "knn" in big:
                big["knn"] = {**big["knn"], "k": 100, "num_candidates": 1000}
        jx = svc_jax.search(big)["hits"]["hits"]
        ora = svc_oracle.search(big)["hits"]["hits"]
        jmap = {h["_id"]: h["_score"] for h in jx}
        omap = {h["_id"]: h["_score"] for h in ora}
        common = set(jmap) & set(omap)
        if omap:
            recalls.append(len(common) / len(omap))
        else:
            # both empty = agreement; device-only hits = disagreement
            recalls.append(1.0 if not jmap else 0.0)
        for d in common:
            if omap[d]:
                max_rel = max(
                    max_rel, abs(jmap[d] - omap[d]) / abs(omap[d])
                )
    return float(np.mean(recalls)), float(max_rel)


# ---------------------------------------------------------------------------
# mesh scaling sweep: the live search path as ONE SPMD program across
# 1/2/4/8 devices (parallel/mesh_executor.py). Its own multi-shard index
# — each shard an independent segment — so the sweep exercises the real
# stacked-entry layout, not a re-labeled single shard.
# ---------------------------------------------------------------------------

MESH_SHARDS = int(os.environ.get("BENCH_MESH_SHARDS", 8))
MESH_DOCS = int(os.environ.get("BENCH_MESH_DOCS", N_DOCS))


def build_mesh_services(oracle: bool = True):
    """(jax service, numpy oracle service, aggregate body df); without
    `oracle` the fp32 twin is neither built nor returned (None)."""
    from elasticsearch_tpu.cluster.indices import IndexService
    from elasticsearch_tpu.index.segment import Segment, VectorField

    rng = np.random.default_rng(SEED + 17)
    per = max(MESH_DOCS // MESH_SHARDS, 1)
    segs_jax, segs_np = [], []
    df_total = np.zeros(VOCAB, np.int64)
    for s in range(MESH_SHARDS):
        lengths = rng.integers(AVG_LEN[0], AVG_LEN[1], size=per)
        pf, df = build_postings(rng, VOCAB, lengths, n_docs=per)
        df_total += df
        vecs = rng.normal(size=(per, DIMS)).astype(np.float32)
        vecs /= np.linalg.norm(vecs, axis=1, keepdims=True)
        v16 = vecs.astype(np.float16)
        ids = [f"{s}-{i}" for i in range(per)]
        exists = np.ones(per, bool)

        def seg_of(vmat):
            return Segment(
                num_docs=per,
                doc_ids=ids,
                sources=[None] * per,
                postings={"body": pf},
                numerics={},
                ordinals={},
                vectors={
                    "vec": VectorField(
                        vectors=vmat, exists=exists,
                        similarity="cosine", unit_vectors=vmat,
                    )
                },
            )

        segs_jax.append(seg_of(v16))
        if oracle:
            segs_np.append(seg_of(v16.astype(np.float32)))

    def svc_of(segs, backend):
        svc = IndexService(
            f"bench-mesh-{backend}",
            settings={
                "number_of_shards": MESH_SHARDS,
                "search.backend": backend,
            },
            mappings_json={
                "properties": {
                    "body": {"type": "text"},
                    "vec": {
                        "type": "dense_vector",
                        "dims": DIMS,
                        "similarity": "cosine",
                    },
                }
            },
        )
        for sid, eng in enumerate(svc.shards):
            eng.segments = [segs[sid]]
            eng.live_docs = [None]
            eng.seg_versions = [np.ones(per, np.int64)]
            eng.seg_seqnos = [np.arange(per, dtype=np.int64)]
            eng.seg_names = [f"seg_{sid}_0"]
            eng._next_seq = per
            eng.change_generation += 1
        return svc

    return (
        svc_of(segs_jax, "jax"),
        svc_of(segs_np, "numpy") if oracle else None,
        df_total,
    )


def mesh_sweep(svc, svc_oracle, body_df):
    """Scaling sweep over 1/2/4/8 devices: per-device QPS, scaling
    efficiency vs the 1-device mesh, the sequential
    fan-out baseline, and recall/float-exactness gates."""
    import jax

    n_avail = len(jax.devices())
    dev_counts = [d for d in (1, 2, 4, 8) if d <= n_avail]
    texts = make_query_texts(body_df, N_QUERIES_SECONDARY, seed=23)
    match_bodies = [
        {"query": {"match": {"body": t}}, "size": K} for t in texts
    ]
    rngq = np.random.default_rng(29)
    qv = rngq.normal(size=(N_QUERIES_SECONDARY, DIMS)).astype(np.float32)
    qv /= np.linalg.norm(qv, axis=1, keepdims=True)
    knn_bodies = [
        {
            "knn": {
                "field": "vec",
                "query_vector": [float(x) for x in v],
                "k": K,
                "num_candidates": 100,
            },
            "size": K,
        }
        for v in qv
    ]
    mex = svc.mesh_executor()

    # sequential (per-shard fan-out) baseline on the SAME index
    os.environ["ES_TPU_MESH"] = "off"
    for b in match_bodies[:4] + knn_bodies[:4]:
        svc.search(b)
    seq_match_qps, seq_match_p50, _, _ = run_load(svc, match_bodies)
    seq_knn_qps, seq_knn_p50, _, _ = run_load(svc, knn_bodies)
    log(
        f"[mesh] sequential fan-out ({MESH_SHARDS} shards): "
        f"match={seq_match_qps:.1f} QPS p50={seq_match_p50:.2f}ms  "
        f"knn={seq_knn_qps:.1f} QPS p50={seq_knn_p50:.2f}ms"
    )

    sweep = []
    exact = True
    try:
        os.environ["ES_TPU_MESH"] = "force"
        for nd in dev_counts:
            os.environ["ES_TPU_MESH_DEVICES"] = str(nd)
            mex.close()  # next search rebuilds the stack on nd devices
            for b in match_bodies[:4] + knn_bodies[:4]:
                svc.search(b)  # warm/compile the nd-device programs
            routed0 = mex.stats["routed"]
            m_qps, m_p50, _, _ = run_load(svc, match_bodies)
            k_qps, k_p50, _, _ = run_load(svc, knn_bodies)
            assert mex.stats["routed"] > routed0, "sweep did not mesh-route"
            sweep.append(
                {
                    "devices": nd,
                    "match_qps": round(m_qps, 1),
                    "match_p50_ms": round(m_p50, 2),
                    "knn_qps": round(k_qps, 1),
                    "knn_p50_ms": round(k_p50, 2),
                    "match_qps_per_device": round(m_qps / nd, 1),
                    "knn_qps_per_device": round(k_qps / nd, 1),
                }
            )
            log(
                f"[mesh] {nd} device(s): match={m_qps:.1f} QPS "
                f"p50={m_p50:.2f}ms  knn={k_qps:.1f} QPS p50={k_p50:.2f}ms"
            )
        base = sweep[0]
        for entry in sweep:
            entry["scaling_match"] = (
                round(entry["match_qps"] / base["match_qps"], 3)
                if base["match_qps"]
                else None
            )
            entry["scaling_knn"] = (
                round(entry["knn_qps"] / base["knn_qps"], 3)
                if base["knn_qps"]
                else None
            )
            entry["scaling_efficiency_match"] = round(
                (entry["scaling_match"] or 0.0) / entry["devices"], 3
            )
            entry["scaling_efficiency_knn"] = round(
                (entry["scaling_knn"] or 0.0) / entry["devices"], 3
            )
        # gates at the widest mesh: recall vs the CPU oracle and
        # float-exactness vs the sequential path on the same service
        recall_m, rel_m = recall_gate(svc, svc_oracle, match_bodies, n=8)
        recall_k, rel_k = recall_gate(svc, svc_oracle, knn_bodies, n=6)
        for b in match_bodies[:4] + knn_bodies[:2]:
            rm = svc.search(b)
            os.environ["ES_TPU_MESH"] = "off"
            rs = svc.search(b)
            os.environ["ES_TPU_MESH"] = "force"
            if [(h["_id"], h["_score"]) for h in rm["hits"]["hits"]] != [
                (h["_id"], h["_score"]) for h in rs["hits"]["hits"]
            ]:
                exact = False
    finally:
        os.environ["ES_TPU_MESH"] = "off"
        os.environ.pop("ES_TPU_MESH_DEVICES", None)
    top = sweep[-1]
    log(
        f"[mesh] scaling at {top['devices']} devices: "
        f"match {top['scaling_match']}x knn {top['scaling_knn']}x "
        f"(recall match={recall_m:.4f} knn={recall_k:.4f}, "
        f"float_exact={exact})"
    )
    return {
        "n_shards": MESH_SHARDS,
        "n_docs": MESH_DOCS,
        "devices_available": n_avail,
        "sweep": sweep,
        "seq_match_qps": round(seq_match_qps, 1),
        "seq_knn_qps": round(seq_knn_qps, 1),
        "speedup_vs_sequential_match": (
            round(top["match_qps"] / seq_match_qps, 2)
            if seq_match_qps
            else None
        ),
        "speedup_vs_sequential_knn": (
            round(top["knn_qps"] / seq_knn_qps, 2) if seq_knn_qps else None
        ),
        "recall_match": round(recall_m, 4),
        "recall_knn": round(recall_k, 4),
        "max_score_rel_delta_match": float(f"{rel_m:.3e}"),
        "max_score_rel_delta_knn": float(f"{rel_k:.3e}"),
        "float_exact_vs_sequential": exact,
        "mesh_stats": mex.stats_snapshot(),
    }


# ---------------------------------------------------------------------------
# ann_knn config: IVF probed search vs the exact brute-force baseline,
# nprobe sweep with recall@10 reported next to QPS (ISSUE 9)
# ---------------------------------------------------------------------------

ANN_DOCS = int(os.environ.get("BENCH_ANN_DOCS", min(N_DOCS, 1_000_000)))
ANN_CENTERS = int(os.environ.get("BENCH_ANN_CENTERS", 512))
ANN_QUERIES = min(N_QUERIES_SECONDARY, 1024)


def build_ann_services():
    """(ivf service, exact service, query vectors) over a shared
    clustered-vector segment (mixture of ANN_CENTERS Gaussian centers,
    float16 rows like the main corpus)."""
    from elasticsearch_tpu.cluster.indices import IndexService
    from elasticsearch_tpu.index.segment import Segment, VectorField

    rng = np.random.default_rng(SEED + 31)
    log(f"[ann_knn] sampling {ANN_DOCS}x{DIMS} clustered vectors…")
    centers = rng.normal(size=(ANN_CENTERS, DIMS)).astype(np.float32)
    asg = rng.integers(0, ANN_CENTERS, size=ANN_DOCS)
    vecs = centers[asg] + 0.5 * rng.normal(size=(ANN_DOCS, DIMS)).astype(
        np.float32
    )
    vecs /= np.linalg.norm(vecs, axis=1, keepdims=True)
    vecs16 = vecs.astype(np.float16)
    exists = np.ones(ANN_DOCS, bool)
    seg = Segment(
        num_docs=ANN_DOCS,
        doc_ids=[str(i) for i in range(ANN_DOCS)],
        sources=[None] * ANN_DOCS,
        postings={},
        numerics={},
        ordinals={},
        vectors={
            "vec": VectorField(
                vectors=vecs16, exists=exists,
                similarity="cosine", unit_vectors=vecs16,
            )
        },
    )

    def svc_of(name, extra):
        svc = IndexService(
            name,
            settings={
                "number_of_shards": 1, "search.backend": "jax", **extra,
            },
            mappings_json={
                "properties": {
                    "vec": {
                        "type": "dense_vector", "dims": DIMS,
                        "similarity": "cosine",
                    }
                }
            },
        )
        eng = svc.shards[0]
        eng.segments = [seg]
        eng.live_docs = [None]
        eng.seg_versions = [np.ones(ANN_DOCS, np.int64)]
        eng.seg_seqnos = [np.arange(ANN_DOCS, dtype=np.int64)]
        eng.seg_names = ["seg_0_0"]
        eng._next_seq = ANN_DOCS
        eng.change_generation += 1
        return svc

    nlist = int(
        os.environ.get("BENCH_ANN_NLIST", max(64, int(np.sqrt(ANN_DOCS)) * 2))
    )
    svc_ivf = svc_of("bench-ann-ivf", {"knn.type": "ivf", "knn.nlist": nlist})
    svc_exact = svc_of("bench-ann-exact", {})
    # queries: perturbed corpus rows (the "find my neighbors" shape)
    picks = rng.choice(ANN_DOCS, size=ANN_QUERIES, replace=False)
    qv = vecs[picks] + 0.05 * rng.normal(size=(ANN_QUERIES, DIMS)).astype(
        np.float32
    )
    qv /= np.linalg.norm(qv, axis=1, keepdims=True)
    return svc_ivf, svc_exact, qv, nlist


def run_ann_config(configs):
    from elasticsearch_tpu.search import ann as ann_mod

    svc_ivf, svc_exact, qv, nlist = build_ann_services()
    try:
        def knn_bodies(nprobe=None):
            out = []
            for v in qv:
                sec = {
                    "field": "vec",
                    "query_vector": [float(x) for x in v],
                    "k": K,
                    "num_candidates": 100,
                }
                if nprobe is not None:
                    sec["nprobe"] = nprobe
                out.append({"knn": sec, "size": K, "_source": False})
            return out

        def recall_at_k(bodies_a, n=24):
            recs = []
            for ba in bodies_a[:n]:
                be = {k: v for k, v in ba.items() if k != "knn"}
                be["knn"] = {
                    k: v for k, v in ba["knn"].items() if k != "nprobe"
                }
                a = {
                    h["_id"]
                    for h in svc_ivf.search(ba)["hits"]["hits"]
                }
                e = {
                    h["_id"]
                    for h in svc_exact.search(be)["hits"]["hits"]
                }
                recs.append(len(a & e) / max(1, len(e)))
            return float(np.mean(recs))

        log("[ann_knn] warmup/compile (k-means build + probe kernels)…")
        tb = time.perf_counter()
        for b in knn_bodies()[:4]:
            svc_ivf.search(b)
        for b in knn_bodies()[:4]:
            svc_exact.search(b)
        log(f"[ann_knn] warm ({time.perf_counter()-tb:.1f}s)")
        exact_qps, exact_p50, _, _ = run_load(svc_exact, knn_bodies())
        log(f"[ann_knn] exact baseline: {exact_qps:.1f} QPS "
            f"p50={exact_p50:.2f}ms")
        sweep = {}
        for nprobe in (4, 8, 16, 32):
            bl = knn_bodies(nprobe)
            svc_ivf.search(bl[0])
            stats0 = ann_mod.stats_snapshot()
            qps, p50, p99, _ = run_load(svc_ivf, bl)
            rec = recall_at_k(bl)
            stats1 = ann_mod.stats_snapshot()
            sweep[str(nprobe)] = {
                "qps": round(qps, 1),
                "p50_ms": round(p50, 2),
                "p99_ms": round(p99, 2),
                "recall_at_10": round(rec, 4),
                "speedup_vs_exact": (
                    round(qps / exact_qps, 2) if exact_qps else None
                ),
                "clusters_scanned": (
                    stats1["clusters_scanned"] - stats0["clusters_scanned"]
                ),
                "clusters_total": (
                    stats1["clusters_total"] - stats0["clusters_total"]
                ),
            }
            log(
                f"[ann_knn] nprobe={nprobe}: {qps:.1f} QPS "
                f"p50={p50:.2f}ms recall@10={rec:.4f} "
                f"({sweep[str(nprobe)]['speedup_vs_exact']}x exact)"
            )
        # headline: the default-nprobe row (index setting default 8)
        head = sweep["8"]
        snap = ann_mod.stats_snapshot()
        return {
            "kind": "ivf",
            "n_docs": ANN_DOCS,
            "nlist": nlist,
            "qps": head["qps"],
            "p50_ms": head["p50_ms"],
            "p99_ms": head["p99_ms"],
            "recall_at_10": head["recall_at_10"],
            "speedup_vs_exact": head["speedup_vs_exact"],
            "exact_baseline_qps": round(exact_qps, 1),
            "exact_baseline_p50_ms": round(exact_p50, 2),
            "nprobe_sweep": sweep,
            "ann_stats": {
                k: snap[k]
                for k in (
                    "builds", "build_ms", "ledger_bytes",
                    "exact_fallbacks", "small_segment_exact",
                )
            },
        }
    finally:
        svc_ivf.close()
        svc_exact.close()


# ---------------------------------------------------------------------------
# rag_rerank config: the end-to-end RAG scenario — filtered hybrid
# retrieval (bm25 + kNN under a keyword filter, RRF-fused) → device
# late-interaction rerank → fetch (ISSUE 10)
# ---------------------------------------------------------------------------

RR_DOCS = int(os.environ.get("BENCH_RERANK_DOCS", min(N_DOCS, 200_000)))
RR_DIMS = int(os.environ.get("BENCH_RERANK_DIMS", 64))
RR_TOKENS = int(os.environ.get("BENCH_RERANK_TOKENS", 4))
RR_QUERIES = min(N_QUERIES_SECONDARY, 512)
RR_EVAL = int(os.environ.get("BENCH_RERANK_EVAL", 24))


def build_rerank_services():
    """(jax service, numpy oracle service, query texts, query token
    matrices, doc token tensor) over a shared corpus carrying text +
    dense vectors + a rank_vectors token column. Doc token rows are
    drawn around per-doc topic centers and queries around the same
    centers, so the second stage has real signal to reorder on."""
    from elasticsearch_tpu.cluster.indices import IndexService
    from elasticsearch_tpu.index.segment import (
        MultiVectorField,
        OrdinalField,
        Segment,
        VectorField,
    )

    rng = np.random.default_rng(SEED + 57)
    log(f"[rag_rerank] building {RR_DOCS}-doc corpus "
        f"({RR_TOKENS}x{RR_DIMS} tokens/doc)…")
    lengths = rng.integers(AVG_LEN[0], AVG_LEN[1], size=RR_DOCS)
    body_pf, body_df = build_postings(rng, 20_000, lengths, n_docs=RR_DOCS)
    centers = rng.normal(size=(64, RR_DIMS)).astype(np.float32)
    topic = rng.integers(0, 64, size=RR_DOCS)
    vecs = centers[topic][:, :RR_DIMS] + 0.6 * rng.normal(
        size=(RR_DOCS, RR_DIMS)
    ).astype(np.float32)
    vecs /= np.linalg.norm(vecs, axis=1, keepdims=True)
    toks = centers[topic][:, None, :] + 0.8 * rng.normal(
        size=(RR_DOCS, RR_TOKENS, RR_DIMS)
    ).astype(np.float32)
    cat_ords = rng.integers(0, 8, size=RR_DOCS).astype(np.int32)
    cat_field = OrdinalField(
        ord_terms=[f"cat{j}" for j in range(8)],
        ords=cat_ords,
        mv_ords=cat_ords.copy(),
        mv_offsets=np.arange(RR_DOCS + 1, dtype=np.int32),
    )
    # keyword postings for the term-filter legs (tf=1 per doc)
    from elasticsearch_tpu.index.segment import SegmentBuilder

    cat_inv = {
        f"cat{j}": {
            int(d): 1 for d in np.nonzero(cat_ords == j)[0]
        }
        for j in range(8)
    }
    cat_pf = SegmentBuilder._build_postings(
        cat_inv, np.ones(RR_DOCS, np.int64), RR_DOCS, RR_DOCS
    )
    exists = np.ones(RR_DOCS, bool)
    mvf = MultiVectorField(
        tok_vectors=toks.reshape(-1, RR_DIMS).astype(np.float32),
        tok_offsets=(
            np.arange(RR_DOCS + 1, dtype=np.int64) * RR_TOKENS
        ).astype(np.int32),
        exists=exists.copy(),
        similarity="dot_product",
    )
    seg = Segment(
        num_docs=RR_DOCS,
        doc_ids=[str(i) for i in range(RR_DOCS)],
        sources=[{"cat": f"cat{int(c)}"} for c in cat_ords],
        postings={"body": body_pf, "cat": cat_pf},
        numerics={},
        ordinals={"cat": cat_field},
        vectors={
            "vec": VectorField(
                vectors=vecs, exists=exists, similarity="cosine",
                unit_vectors=vecs,
            )
        },
        multi_vectors={"toks": mvf},
    )

    def svc_of(name, backend):
        svc = IndexService(
            name,
            settings={"number_of_shards": 1, "search.backend": backend},
            mappings_json={
                "properties": {
                    "body": {"type": "text"},
                    "cat": {"type": "keyword"},
                    "vec": {
                        "type": "dense_vector", "dims": RR_DIMS,
                        "similarity": "cosine",
                    },
                    "toks": {
                        "type": "rank_vectors", "dims": RR_DIMS,
                        "similarity": "dot_product",
                    },
                }
            },
        )
        eng = svc.shards[0]
        eng.segments = [seg]
        eng.live_docs = [None]
        eng.seg_versions = [np.ones(RR_DOCS, np.int64)]
        eng.seg_seqnos = [np.arange(RR_DOCS, dtype=np.int64)]
        eng.seg_names = ["seg_0_0"]
        eng._next_seq = RR_DOCS
        # the rescore phase resolves fused candidates back to
        # (segment, doc) identity through the engine's id locations
        eng._locations = {str(i): (0, i) for i in range(RR_DOCS)}
        eng.change_generation += 1
        return svc

    texts = make_query_texts(body_df, RR_QUERIES, seed=23, hi=6000)
    # query tokens drawn around corpus topic centers (the "rerank has
    # signal" regime); 3 tokens per query
    qtopic = rng.integers(0, 64, size=RR_QUERIES)
    qtoks = centers[qtopic][:, None, :] + 0.6 * rng.normal(
        size=(RR_QUERIES, 3, RR_DIMS)
    ).astype(np.float32)
    qvec = centers[qtopic] + 0.4 * rng.normal(
        size=(RR_QUERIES, RR_DIMS)
    ).astype(np.float32)
    qvec /= np.linalg.norm(qvec, axis=1, keepdims=True)
    return (
        svc_of("bench-rerank", "jax"),
        svc_of("bench-rerank-np", "numpy"),
        texts, qtoks, qvec, toks, cat_ords,
    )


def _ndcg_at_10(ranked_ids, grades):
    dcg = 0.0
    for i, doc in enumerate(ranked_ids[:10]):
        g = grades.get(doc, 0)
        dcg += (2**g - 1) / np.log2(i + 2)
    ideal = sorted(grades.values(), reverse=True)[:10]
    idcg = sum((2**g - 1) / np.log2(i + 2) for i, g in enumerate(ideal))
    return dcg / idcg if idcg > 0 else 0.0


def run_rerank_config():
    from elasticsearch_tpu.models import rerank as rerank_model

    svc, svc_np, texts, qtoks, qvec, doc_toks, cat_ords = (
        build_rerank_services()
    )
    try:
        def body_of(i, rescore=True, source=False):
            b = {
                "retriever": {"rrf": {
                    "rank_window_size": 100,
                    "retrievers": [
                        {"standard": {
                            "query": {"match": {"body": texts[i]}},
                            "filter": {
                                "term": {"cat": f"cat{i % 8}"}
                            },
                        }},
                        {"knn": {
                            "field": "vec",
                            "query_vector": [float(x) for x in qvec[i]],
                            "k": 50, "num_candidates": 200,
                            "filter": {"term": {"cat": f"cat{i % 8}"}},
                        }},
                    ],
                }},
                "size": K,
                "_source": bool(source),
            }
            if rescore:
                b["rescore"] = {
                    "window_size": 100,
                    "query": {
                        "rescore_query": {"rank_vectors": {
                            "field": "toks",
                            "query_vectors": qtoks[i].tolist(),
                        }},
                        "query_weight": 1.0,
                        "rescore_query_weight": 1.0,
                    },
                }
            return b

        first_bodies = [
            body_of(i, rescore=False) for i in range(RR_QUERIES)
        ]
        rr_bodies = [
            body_of(i, rescore=True, source=True)
            for i in range(RR_QUERIES)
        ]
        log("[rag_rerank] warmup/compile (rerank column + maxsim)…")
        for b in rr_bodies[:4]:
            svc.search(dict(b))
        for b in first_bodies[:4]:
            svc.search(dict(b))
        # leg + rerank timing windows
        with svc._rrf_lock:
            rrf0 = dict(svc.rrf_stats)
        rs0 = rerank_model.stats_snapshot()
        first_qps, first_p50, first_p99, _ = run_load(svc, first_bodies)
        rr_qps, rr_p50, rr_p99, _ = run_load(svc, rr_bodies)
        rs1 = rerank_model.stats_snapshot()
        with svc._rrf_lock:
            rrf1 = dict(svc.rrf_stats)
        n_resc = max(rs1["device_rescores"] - rs0["device_rescores"], 1)
        rerank_ms = (rs1["kernel_ms"] - rs0["kernel_ms"]) / n_resc
        n_rrf = max(rrf1["searches"] - rrf0["searches"], 1)
        leg_ms = {
            "bm25_leg_ms": round(
                (rrf1["bm25_leg_ms"] - rrf0["bm25_leg_ms"]) / n_rrf, 2
            ),
            "knn_leg_ms": round(
                (rrf1["knn_leg_ms"] - rrf0["knn_leg_ms"]) / n_rrf, 2
            ),
            "fuse_ms": round(
                (rrf1["fuse_ms"] - rrf0["fuse_ms"]) / n_rrf, 3
            ),
        }
        # ---- NDCG@10 vs the TRUE maxsim ordering (host float, full
        # corpus, filter-respecting): grades 3/2/1 for true top
        # 10/50/200 within the query's filter slice ----
        ndcg_first = []
        ndcg_rerank = []
        parity_ok = True
        for i in range(min(RR_EVAL, RR_QUERIES)):
            q = qtoks[i]  # [3, d]
            sims = np.einsum("qd,ntd->qnt", q, doc_toks).max(
                axis=2
            ).sum(axis=0)  # true maxsim per doc
            sims = np.where(cat_ords == (i % 8), sims, -np.inf)
            order = np.argsort(-sims)
            grades = {}
            for r, doc in enumerate(order[:200]):
                grades[str(int(doc))] = (
                    3 if r < 10 else (2 if r < 50 else 1)
                )
            a = svc.search(body_of(i, rescore=True))
            f = svc.search(body_of(i, rescore=False))
            o = svc_np.search(body_of(i, rescore=True))
            ids_a = [h["_id"] for h in a["hits"]["hits"]]
            ids_o = [h["_id"] for h in o["hits"]["hits"]]
            if ids_a != ids_o:
                parity_ok = False
            ndcg_rerank.append(_ndcg_at_10(ids_a, grades))
            ndcg_first.append(
                _ndcg_at_10([h["_id"] for h in f["hits"]["hits"]], grades)
            )
        block = {
            "kind": "filtered_hybrid_rrf_plus_rescore",
            "n_docs": RR_DOCS,
            "qps": round(rr_qps, 1),
            "p50_ms": round(rr_p50, 2),
            "p99_ms": round(rr_p99, 2),
            "first_stage_qps": round(first_qps, 1),
            "first_stage_p50_ms": round(first_p50, 2),
            "rerank_ms": round(rerank_ms, 2),
            **leg_ms,
            "ndcg_at_10": round(float(np.mean(ndcg_rerank)), 4),
            "first_stage_ndcg_at_10": round(
                float(np.mean(ndcg_first)), 4
            ),
            "oracle_parity": parity_ok,
            "rescore_stats": {
                k: rs1[k]
                for k in ("device_rescores", "host_rescores",
                          "skipped", "fallbacks", "ledger_bytes")
            },
        }
        log(
            f"[rag_rerank] {rr_qps:.1f} QPS p50={rr_p50:.2f}ms "
            f"(first stage {first_qps:.1f} QPS) rerank={rerank_ms:.2f}ms "
            f"legs: bm25={leg_ms['bm25_leg_ms']}ms "
            f"knn={leg_ms['knn_leg_ms']}ms | "
            f"NDCG@10 {block['first_stage_ndcg_at_10']} → "
            f"{block['ndcg_at_10']} (oracle_parity={parity_ok})"
        )
        return block
    finally:
        svc.close()
        svc_np.close()


# ---------------------------------------------------------------------------
# indexing mode: sustained mixed write+query traffic with NRT refresh
# ---------------------------------------------------------------------------

# scales with BENCH_N_DOCS so the tiny-corpus smoke runs stay fast
INGEST_BASE = int(os.environ.get("BENCH_INGEST_BASE", 0)) or min(
    100_000, max(N_DOCS // 10, 4_000)
)
INGEST_SECONDS = float(
    os.environ.get(
        "BENCH_INGEST_SECONDS", 15.0 if N_DOCS > 100_000 else 6.0
    )
)
INGEST_WRITERS = int(os.environ.get("BENCH_INGEST_WRITERS", 4))
INGEST_REFRESH = os.environ.get("BENCH_INGEST_REFRESH", "200ms")
# offered write rate (docs/s across writers): the mixed-traffic scenario
# measures SLO compliance at a sustained rate, not the write ceiling —
# an unthrottled writer pool just measures the GIL
INGEST_RATE = float(os.environ.get("BENCH_INGEST_RATE", 1500.0))
INGEST_VOCAB = 4000


def run_indexing_config():
    """The `indexing` scenario (streaming ingest & NRT search): one
    service serving an open-loop query stream while writer threads
    index a sustained document stream and the background refresher
    swaps double-buffered generations at `refresh_interval`. Reports
    sustained docs/s, refresh-lag percentiles (ack → searchable,
    worst-doc per refresh), and the query p99 under concurrent ingest
    next to the read-only p99 from the same service moments earlier —
    the ≤1.5× gate lives in scripts/ingest_smoke.sh."""
    from elasticsearch_tpu.cluster.indices import IndexService
    from elasticsearch_tpu.index import segment_build
    from elasticsearch_tpu.search.admission import admission

    # raw serving measurement: overload protection is measured by the
    # open_loop section, not here — shedding would muddy the p99 ratio
    admission.configure(enabled=False)
    rng = np.random.default_rng(SEED + 7)
    # Zipf-ish vocabulary so posting lists skew like real text
    vocab = np.array([f"w{i}" for i in range(INGEST_VOCAB)])
    zipf = 1.0 / np.arange(1, INGEST_VOCAB + 1) ** 1.1
    zipf /= zipf.sum()

    def make_source(r):
        n = int(r.integers(8, 16))
        words = r.choice(vocab, size=n, p=zipf)
        return {
            "body": " ".join(words),
            "popularity": int(r.integers(0, 1000)),
        }

    log(f"[indexing] seeding {INGEST_BASE} base docs "
        f"(refresh_interval={INGEST_REFRESH})…")
    prev_bg = os.environ.get("ES_TPU_BG_REFRESH")
    os.environ["ES_TPU_BG_REFRESH"] = "auto"
    svc = IndexService(
        "ingest-bench",
        settings={
            "number_of_shards": 1,
            "search.backend": "jax",
            "refresh_interval": INGEST_REFRESH,
        },
        mappings_json={
            "properties": {
                "body": {"type": "text"},
                "popularity": {"type": "integer"},
            }
        },
    )
    try:
        t_seed = time.perf_counter()
        for i in range(INGEST_BASE):
            svc.index_doc(f"b{i}", make_source(rng))
        svc.refresh()
        seed_wall = time.perf_counter() - t_seed
        log(f"[indexing] seeded in {seed_wall:.1f}s "
            f"({INGEST_BASE / seed_wall:.0f} docs/s single-writer)")
        # build-kernel warmup: stream a few refresh intervals of writes
        # through the NRT loop so the pow2-bucketed build kernels (and
        # the swap/prewarm path) compile BEFORE the measured windows
        log("[indexing] build warmup (compile the refresh pipeline)…")
        r0 = np.random.default_rng(SEED + 3)
        per_writer_dt = INGEST_WRITERS / max(INGEST_RATE, 1.0)
        warm_n = max(int(INGEST_RATE * 1.0), 64)
        for i in range(warm_n):
            svc.index_doc(f"warm{i}", make_source(r0))
            if i % max(warm_n // 4, 1) == 0:
                svc.wait_for_refresh(timeout=30)
        svc.wait_for_refresh(timeout=30)
        # query stream: mid-frequency two-term matches
        mids = vocab[40:400]
        q_bodies = [
            {
                "query": {"match": {"body": " ".join(
                    rng.choice(mids, size=2)
                )}},
                "size": K,
            }
            for _ in range(512)
        ]
        for b in q_bodies[:6]:
            svc.search(b)
        # read-only baseline: closed-loop peak, then the open-loop rate
        ro_qps, ro_p50, _, _ = run_load(svc, q_bodies, threads=64)
        rate = max(0.4 * ro_qps, 4.0)
        slo_ms = max(8.0 * ro_p50, 250.0)
        log(f"[indexing] read-only: {ro_qps:.1f} QPS closed-loop; "
            f"open-loop at {rate:.0f}/s…")
        ro = run_open_loop(
            svc, q_bodies, rate_qps=rate, duration_s=INGEST_SECONDS,
            slo_ms=slo_ms,
        )
        # ---- mixed phase: writers + the SAME open-loop query rate ----
        segment_build.reset_stats()
        stop = threading.Event()
        written = [0] * INGEST_WRITERS

        def writer(tid):
            # paced open-loop writer: INGEST_RATE/INGEST_WRITERS docs/s
            r = np.random.default_rng(SEED + 100 + tid)
            n = 0
            t_start = time.perf_counter()
            next_t = 0.0
            while not stop.is_set():
                now = time.perf_counter() - t_start
                if now < next_t:
                    time.sleep(min(next_t - now, 0.02))
                    continue
                svc.index_doc(f"s{tid}-{n}", make_source(r))
                n += 1
                next_t += per_writer_dt
            written[tid] = n

        threads = [
            threading.Thread(target=writer, args=(t,), daemon=True)
            for t in range(INGEST_WRITERS)
        ]
        t_mix = time.perf_counter()
        for t in threads:
            t.start()
        mixed = run_open_loop(
            svc, q_bodies, rate_qps=rate, duration_s=INGEST_SECONDS,
            slo_ms=slo_ms, seed=SEED + 11,
        )
        stop.set()
        for t in threads:
            t.join(timeout=10)
        mix_wall = time.perf_counter() - t_mix
        docs_written = int(sum(written))
        ing = segment_build.stats_snapshot()
        # every streamed doc searchable after one final swap
        svc.refresh()
        total = svc.search({"size": 0, "track_total_hits": True})
        total_docs = total["hits"]["total"]["value"]
        ratio = (
            round(mixed["accepted_p99_ms"] / ro["accepted_p99_ms"], 3)
            if mixed["accepted_p99_ms"] and ro["accepted_p99_ms"]
            else None
        )
        block = {
            "kind": "mixed_write_query_nrt",
            "base_docs": INGEST_BASE,
            "refresh_interval": INGEST_REFRESH,
            "writers": INGEST_WRITERS,
            "offered_docs_per_s": INGEST_RATE,
            "docs_per_s": round(docs_written / mix_wall, 1),
            "docs_written": docs_written,
            "seed_docs_per_s": round(INGEST_BASE / seed_wall, 1),
            "refresh_lag": ing["refresh_lag"],
            "refreshes": ing["refreshes"],
            "concurrent_refreshes": ing["concurrent_refreshes"],
            "device_builds": ing["device_builds"],
            "host_builds": ing["host_builds"],
            "build_kernels": ing["build_kernels"],
            "overlap_ms": ing["overlap_ms"],
            "prewarm_ms": ing["prewarm_ms"],
            "generations_discarded": ing["generations_discarded"],
            "readonly_qps_closed_loop": round(ro_qps, 1),
            "readonly_p50_ms": ro["accepted_p50_ms"],
            "readonly_p99_ms": ro["accepted_p99_ms"],
            "mixed_p50_ms": mixed["accepted_p50_ms"],
            "mixed_p99_ms": mixed["accepted_p99_ms"],
            "mixed_goodput_qps": mixed["goodput_qps"],
            "p99_ratio_vs_readonly": ratio,
            "total_docs_after": total_docs,
            "all_streamed_docs_searchable": bool(
                total_docs == INGEST_BASE + warm_n + docs_written
            ),
        }
        log(
            f"[indexing] {block['docs_per_s']} docs/s sustained "
            f"({INGEST_WRITERS} writers) | refresh lag p50="
            f"{ing['refresh_lag']['p50_ms']}ms p95="
            f"{ing['refresh_lag']['p95_ms']}ms | query p99 "
            f"{ro['accepted_p99_ms']}ms read-only → "
            f"{mixed['accepted_p99_ms']}ms under ingest "
            f"({ratio}x) | builds: {ing['device_builds']} device / "
            f"{ing['host_builds']} host, "
            f"{ing['generations_discarded']} discarded"
        )
        return block
    finally:
        svc.close()
        if prev_bg is None:
            os.environ.pop("ES_TPU_BG_REFRESH", None)
        else:
            os.environ["ES_TPU_BG_REFRESH"] = prev_bg


def main():
    t0 = time.perf_counter()
    # Persistent XLA compilation cache, before anything compiles: the
    # serving path compiles a fixed handful of programs, so repeat runs
    # skip warm-up compilation. Where it lives is decided in one place:
    # JAX_COMPILATION_CACHE_DIR if set, else a fixed git-ignored
    # directory inside the checkout.
    from elasticsearch_tpu.common.compile_cache import configure_compile_cache

    log(f"compile cache: {configure_compile_cache()}")
    # closed-loop sections measure RAW serving capacity: the admission
    # gate stays off so the numbers remain comparable across rounds;
    # the open-loop section below re-arms it to measure protection
    from elasticsearch_tpu.search.admission import admission

    admission.configure(enabled=False)
    log(f"building {N_DOCS} doc corpus…")
    seg_jax, seg_np, body_df, title_df = build_corpus()
    log(f"index built ({time.perf_counter()-t0:.1f}s); starting services…")
    svc_jax = make_service(seg_jax, "jax")
    svc_np = make_service(seg_np, "numpy")
    bodies = build_bodies(body_df, title_df)

    from elasticsearch_tpu.search import sparse as sparse_mod

    configs = {}
    oracle_n = {
        "match": 96, "bool": 64, "multi_match": 64, "knn": 16,
        "sparse_retrieval": 32, "hybrid_rrf": 12,
    }
    gate_n = {"match": 12, "bool": 8, "multi_match": 8, "knn": 8,
              "sparse_retrieval": 8, "hybrid_rrf": 6}

    batcher = svc_jax._batcher
    depth_configured = batcher.pipeline_depth
    for name in (
        "match", "bool", "multi_match", "knn", "sparse_retrieval",
        "hybrid_rrf",
    ):
        blist = bodies[name]
        log(f"[{name}] warmup/compile…")
        tw = time.perf_counter()
        for b in blist[:6]:
            svc_jax.search(b)
        log(f"[{name}] warm ({time.perf_counter()-tw:.1f}s)")
        # per-window sparse counters (impact_bytes are upload-time
        # numbers and stay cumulative; see the block below)
        sparse0 = (
            sparse_mod.stats_snapshot()
            if name == "sparse_retrieval" else None
        )
        if name == "hybrid_rrf":
            # per-leg breakdown over the measured window only (warmup
            # included compile time)
            with svc_jax._rrf_lock:
                for key in svc_jax.rrf_stats:
                    svc_jax.rrf_stats[key] = 0
                for dq in svc_jax.rrf_leg_samples.values():
                    dq.clear()
        batch0 = batcher.batching_stats()
        qps, p50, p99, _ = run_load(svc_jax, blist)
        batch_block = batching_window(batch0, batcher.batching_stats())
        rrf_snapshot = dict(svc_jax.rrf_stats) if name == "hybrid_rrf" else None
        rrf_leg_block = leg_p50s(svc_jax) if name == "hybrid_rrf" else None
        log(f"[{name}] jax: {qps:.1f} QPS, p50={p50:.2f}ms p99={p99:.2f}ms")
        # single-inflight latency: throughput-mode batching must not
        # hide a latency regression
        p50_b1 = batch1_p50(svc_jax, blist)
        log(f"[{name}] single-inflight p50={p50_b1:.2f}ms")
        # pipelining A/B on the SAME run: depth=1 (the classic
        # dispatch→collect loop) vs the configured depth
        depth_block = {}
        if name in ("match", "knn") and depth_configured > 1:
            batcher.pipeline_depth = 1
            d1_qps, d1_p50, _, _ = run_load(svc_jax, blist)
            batcher.pipeline_depth = depth_configured
            depth_block = {
                "qps_depth1": round(d1_qps, 1),
                "p50_depth1_ms": round(d1_p50, 2),
                "depth_speedup": round(qps / d1_qps, 3) if d1_qps else None,
            }
            log(f"[{name}] depth1: {d1_qps:.1f} QPS p50={d1_p50:.2f}ms "
                f"→ depth{depth_configured} speedup "
                f"{depth_block['depth_speedup']}x")
        o_qps, o_p50, _, _ = run_load(
            svc_np, blist[: oracle_n[name]], threads=ORACLE_THREADS
        )
        log(f"[{name}] cpu oracle: {o_qps:.1f} QPS, p50={o_p50:.2f}ms")
        recall, max_rel = recall_gate(
            svc_jax, svc_np, blist, n=gate_n[name]
        )
        log(f"[{name}] recall gate: {recall:.4f} (max score delta "
            f"{max_rel:.2e})")
        configs[name] = {
            "qps": round(qps, 1),
            "p50_ms": round(p50, 2),
            "p99_ms": round(p99, 2),
            "p50_batch1_ms": round(p50_b1, 2),
            "cpu_oracle_qps": round(o_qps, 1),
            "vs_oracle": round(qps / o_qps, 2) if o_qps else None,
            "recall": round(recall, 4),
            "max_score_rel_delta": float(f"{max_rel:.3e}"),
            "batching": batch_block,
            **depth_block,
        }
        log(
            f"[{name}] batching: avg_width="
            f"{batch_block['avg_launch_width']} occupancy="
            f"{batch_block['avg_occupancy']} "
            f"buckets={batch_block['bucket_hit_rates']} "
            f"express={batch_block['express_lane_hits']}"
        )
        if name == "sparse_retrieval":
            # learned-sparse serving block: quantized-vs-oracle
            # recall@10 (the ≥0.95 gate lives in sparse_smoke.sh), the
            # int8 value-plane compression headline, and the block-max
            # pruning counters over the measured window
            st1 = sparse_mod.stats_snapshot()
            rec10 = []
            for b in blist[:24]:
                got = {
                    h["_id"] for h in svc_jax.search(dict(b))["hits"]["hits"]
                }
                want = [
                    h["_id"] for h in svc_np.search(dict(b))["hits"]["hits"]
                ]
                if want:
                    rec10.append(len(got & set(want)) / len(want))
            ib = st1["impact_bytes"]
            fb = st1["impact_fp32_equivalent_bytes"]
            configs[name].update(
                {
                    "kind": "impact_int8",
                    "recall_at_10_vs_fp32_oracle": round(
                        float(np.mean(rec10)), 4
                    ),
                    "quantized_searches": (
                        st1["quantized_searches"]
                        - sparse0["quantized_searches"]
                    ),
                    "tiles_pruned": (
                        st1["tiles_pruned"] - sparse0["tiles_pruned"]
                    ),
                    "tiles_scored": (
                        st1["tiles_scored"] - sparse0["tiles_scored"]
                    ),
                    "impact_bytes": ib,
                    "impact_fp32_equivalent_bytes": fb,
                    "impact_compression": (
                        round(fb / ib, 2) if ib else None
                    ),
                    "ledger_bytes": st1["ledger_bytes"],
                }
            )
            log(
                f"[sparse_retrieval] recall@10="
                f"{configs[name]['recall_at_10_vs_fp32_oracle']} "
                f"compression={configs[name]['impact_compression']}x "
                f"pruned={configs[name]['tiles_pruned']}/"
                f"{configs[name]['tiles_pruned'] + configs[name]['tiles_scored']}"
            )
        if name == "hybrid_rrf":
            # hybrid execution breakdown: per-leg wall time measured
            # from leg fan-out start (overlapped legs therefore SUM to
            # more than the request wall time — that overlap is the
            # point) + device-vs-host fusion counts
            st = rrf_snapshot
            n_rrf = max(1, st["searches"])
            configs[name].update(
                {
                    "bm25_leg_ms": round(st["bm25_leg_ms"] / n_rrf, 2),
                    "knn_leg_ms": round(st["knn_leg_ms"] / n_rrf, 2),
                    "sparse_leg_ms": round(st["sparse_leg_ms"] / n_rrf, 2),
                    "fuse_ms": round(st["fuse_ms"] / n_rrf, 2),
                    "device_fused": st["device_fused"],
                    "host_fused": st["host_fused"],
                    **(rrf_leg_block or {}),
                }
            )
            log(
                f"[hybrid_rrf] legs: bm25={configs[name]['bm25_leg_ms']}ms "
                f"knn={configs[name]['knn_leg_ms']}ms "
                f"sparse={configs[name]['sparse_leg_ms']}ms "
                f"fuse={configs[name]['fuse_ms']}ms "
                f"(device_fused={st['device_fused']}, "
                f"host_fused={st['host_fused']}, "
                f"per-leg p50 {rrf_leg_block})"
            )

    # WAND variant of the match config (track_total_hits: false)
    wand_bodies = [
        {**b, "track_total_hits": False} for b in bodies["match"]
    ]
    svc_jax.search(wand_bodies[0])
    qps_wand, p50_wand, _, _ = run_load(svc_jax, wand_bodies)
    log(f"[match+wand] jax: {qps_wand:.1f} QPS, p50={p50_wand:.2f}ms")

    # ---- cache configs: cold vs warm QPS + hit rates ----
    from elasticsearch_tpu.search.query_cache import (
        filter_cache,
        request_cache,
    )

    log("[filtered_bool] warmup/compile…")
    for b in bodies["filtered_bool"][:6]:
        svc_jax.search(b)
    # cold: every request carries a UNIQUE filter term — full filter
    # evaluation per request even though bitsets get cached
    filter_cache.clear()
    cold_qps, cold_p50, _, _ = run_load(svc_jax, bodies["filtered_bool_cold"])
    # warm: 8 rotating filters — bitsets resolve from the device cache
    filter_cache.clear()
    for b in bodies["filtered_bool"][:8]:
        svc_jax.search(b)  # populate the 8 rotating bitsets
    st0 = filter_cache.node_stats()
    warm_qps, warm_p50, warm_p99, _ = run_load(
        svc_jax, bodies["filtered_bool"]
    )
    st1 = filter_cache.node_stats()
    fb_p50_b1 = batch1_p50(svc_jax, bodies["filtered_bool"])
    hits = st1["hit_count"] - st0["hit_count"]
    misses = st1["miss_count"] - st0["miss_count"]
    fb_hit_rate = hits / max(1, hits + misses)
    fb_recall, fb_rel = recall_gate(
        svc_jax, svc_np, bodies["filtered_bool"], n=8
    )
    configs["filtered_bool"] = {
        "qps": round(warm_qps, 1),
        "cold_qps": round(cold_qps, 1),
        "warm_qps": round(warm_qps, 1),
        "p50_ms": round(warm_p50, 2),
        "p99_ms": round(warm_p99, 2),
        "p50_batch1_ms": round(fb_p50_b1, 2),
        "cold_p50_ms": round(cold_p50, 2),
        "query_cache_hit_rate": round(fb_hit_rate, 4),
        "recall": round(fb_recall, 4),
        "max_score_rel_delta": float(f"{fb_rel:.3e}"),
    }
    log(
        f"[filtered_bool] cold={cold_qps:.1f} QPS warm={warm_qps:.1f} QPS "
        f"(hit rate {fb_hit_rate:.3f}, recall {fb_recall:.4f}, "
        f"max delta {fb_rel:.2e})"
    )

    log("[repeated_agg] warmup/compile…")
    svc_jax.search(bodies["repeated_agg"][0])
    request_cache.clear()
    agg_cold_qps, agg_cold_p50, _, _ = run_load(
        svc_jax, bodies["repeated_agg"]
    )
    st0 = request_cache.node_stats()
    agg_warm_qps, agg_warm_p50, _, _ = run_load(
        svc_jax, bodies["repeated_agg"] * 8
    )
    st1 = request_cache.node_stats()
    agg_p50_b1 = batch1_p50(svc_jax, bodies["repeated_agg"])
    hits = st1["hit_count"] - st0["hit_count"]
    misses = st1["miss_count"] - st0["miss_count"]
    agg_hit_rate = hits / max(1, hits + misses)
    # agg parity vs the oracle (cache must be float-exact with the
    # uncached path; the oracle recomputes every time)
    agg_max_rel = 0.0
    for b in bodies["repeated_agg"][:4]:
        jv = svc_jax.search(b)["aggregations"]["pop_avg"]["value"]
        ov = svc_np.search(b)["aggregations"]["pop_avg"]["value"]
        if ov:
            agg_max_rel = max(agg_max_rel, abs(jv - ov) / abs(ov))
    configs["repeated_agg"] = {
        "qps": round(agg_warm_qps, 1),
        "cold_qps": round(agg_cold_qps, 1),
        "warm_qps": round(agg_warm_qps, 1),
        "p50_ms": round(agg_warm_p50, 2),
        "p50_batch1_ms": round(agg_p50_b1, 2),
        "cold_p50_ms": round(agg_cold_p50, 2),
        "request_cache_hit_rate": round(agg_hit_rate, 4),
        "agg_max_rel_delta": float(f"{agg_max_rel:.3e}"),
    }
    log(
        f"[repeated_agg] cold={agg_cold_qps:.1f} QPS "
        f"warm={agg_warm_qps:.1f} QPS (hit rate {agg_hit_rate:.3f}, "
        f"agg delta {agg_max_rel:.2e})"
    )

    # ---- cold_agg: unique-body (cache-miss) dashboard traffic, host
    # AggCollector vs the device segment-sum engine on the SAME bodies,
    # with an exact agg-parity gate between the two paths ----
    from elasticsearch_tpu.search import aggs_device

    log("[cold_agg] warmup/compile…")
    os.environ["ES_TPU_DEVICE_AGGS"] = "force"  # silent host routing
    # would invalidate the A/B — force makes it a hard error instead
    try:
        for b in bodies["cold_agg"][:4]:
            svc_jax.search(b)
        dev0 = aggs_device.stats_snapshot()["device_routed"]
        agg_dev_qps, agg_dev_p50, agg_dev_p99, _ = run_load(
            svc_jax, bodies["cold_agg"]
        )
        dev_routed = (
            aggs_device.stats_snapshot()["device_routed"] - dev0
        )
        os.environ["ES_TPU_DEVICE_AGGS"] = "off"
        for b in bodies["cold_agg"][:2]:
            svc_jax.search(b)
        agg_host_qps, agg_host_p50, _, _ = run_load(
            svc_jax, bodies["cold_agg"]
        )
        # parity gate: device partials reduce to EXACTLY the host
        # collector's response (the "never a silent wrong answer"
        # contract, measured); the numpy oracle service cross-checks
        # the backend too
        os.environ["ES_TPU_DEVICE_AGGS"] = "force"
        agg_parity_exact = True
        for b in bodies["cold_agg"][:6]:
            dev_aggs = svc_jax.search(b)["aggregations"]
            os.environ["ES_TPU_DEVICE_AGGS"] = "off"
            host_aggs = svc_jax.search(b)["aggregations"]
            oracle_aggs = svc_np.search(b)["aggregations"]
            os.environ["ES_TPU_DEVICE_AGGS"] = "force"
            if dev_aggs != host_aggs or dev_aggs != oracle_aggs:
                agg_parity_exact = False
    finally:
        os.environ["ES_TPU_DEVICE_AGGS"] = "auto"
    agg_speedup = agg_dev_qps / max(agg_host_qps, 1e-9)
    configs["cold_agg"] = {
        "qps": round(agg_dev_qps, 1),
        "host_qps": round(agg_host_qps, 1),
        "device_qps": round(agg_dev_qps, 1),
        "speedup_vs_host": round(agg_speedup, 2),
        "p50_ms": round(agg_dev_p50, 2),
        "p99_ms": round(agg_dev_p99, 2),
        "host_p50_ms": round(agg_host_p50, 2),
        "device_routed": int(dev_routed),
        "agg_parity_exact": bool(agg_parity_exact),
    }
    log(
        f"[cold_agg] host={agg_host_qps:.1f} QPS "
        f"device={agg_dev_qps:.1f} QPS ({agg_speedup:.2f}x, "
        f"parity_exact={agg_parity_exact})"
    )

    # ---- ann_knn: the IVF ANN tier vs the exact brute-force baseline
    # (the `knn` config above IS the exact baseline — kept forever as
    # the float oracle). Its OWN clustered-vector corpus: real embedding
    # spaces are clustered, which is both the regime where IVF's
    # locality assumption holds and the honest shape for a recall
    # number (uniform random vectors are ANN's degenerate worst case).
    # Sweeps nprobe and reports recall@10 vs the exact path NEXT TO the
    # QPS it buys; the hard gates live in scripts/ann_smoke.sh. ----
    configs["knn"]["kind"] = "exact_brute_force"
    ann_block = run_ann_config(configs)
    configs["ann_knn"] = ann_block

    # ---- rag_rerank: the end-to-end RAG scenario — filtered hybrid
    # retrieval (bm25 + kNN under a keyword filter, RRF-fused) feeding
    # the device late-interaction reranker over the fused top-k, then
    # fetch. rerank_ms sits next to the per-leg times; NDCG@10 against
    # the TRUE maxsim ordering shows what the second stage buys over
    # the first; hard gates live in scripts/rerank_smoke.sh. ----
    if os.environ.get("BENCH_RERANK", "1") != "0":
        configs["rag_rerank"] = run_rerank_config()

    # ---- indexing: streaming ingest & NRT search under mixed traffic —
    # sustained docs/s + refresh-lag percentiles + query p99 under
    # concurrent ingest vs the read-only number (double-buffered device
    # segment builds; gates live in scripts/ingest_smoke.sh) ----
    if os.environ.get("BENCH_INDEXING", "1") != "0":
        configs["indexing"] = run_indexing_config()

    # single-thread oracle (GIL-free per-core honesty number)
    o1_qps, _, _, _ = run_load(svc_np, bodies["match"][:24], threads=1)
    log(f"[match] cpu oracle single-thread: {o1_qps:.1f} QPS")

    # ---- open-loop overload mode: Poisson arrivals at 2× the measured
    # closed-loop peak, admission gate ARMED. The protection claim is a
    # goodput claim: the node sheds with 429+Retry-After and keeps
    # completed-within-SLO throughput near the closed-loop peak instead
    # of collapsing into unbounded queueing. ----
    open_block = None
    if os.environ.get("BENCH_OPEN_LOOP", "1") != "0":
        dur = float(os.environ.get("BENCH_OPEN_SECONDS", 20.0))

        def one_open(config_name, rate_factor, slo_ms, label):
            """One admission-armed open-loop (Poisson) window on one
            config; returns the run_open_loop block + admission
            snapshot."""
            closed = configs[config_name]["qps"]
            rate = max(rate_factor * closed, 1.0)
            log(
                f"[open_loop:{config_name}:{label}] Poisson arrivals at "
                f"{rate_factor}x closed-loop peak ({rate:.0f}/s) for "
                f"{dur:.0f}s, SLO {slo_ms:.0f}ms…"
            )
            admission.reset()
            admission.configure(enabled=True)
            try:
                blk = run_open_loop(
                    svc_jax, bodies[config_name], rate_qps=rate,
                    duration_s=dur, slo_ms=slo_ms,
                )
            finally:
                adm_stats = admission.stats()
                admission.reset()
                admission.configure(enabled=False)
            blk["rate_factor"] = rate_factor
            blk["closed_loop_qps"] = closed
            blk["goodput_vs_closed_loop"] = (
                round(blk["goodput_qps"] / closed, 3) if closed else None
            )
            blk["admission"] = {
                k: adm_stats[k]
                for k in (
                    "limit", "queue_delay_ewma_ms", "pressure_tier",
                    "admitted", "queued_total", "shed_queue_full",
                    "shed_deadline", "shed_rejected", "brownouts",
                    "limit_decreases", "limit_increases",
                )
            }
            log(
                f"[open_loop:{config_name}:{label}] "
                f"offered={blk['offered_qps']}/s "
                f"goodput={blk['goodput_qps']}/s "
                f"({blk['goodput_vs_closed_loop']}x closed-loop) "
                f"shed={blk['shed_429']} "
                f"accepted_p50={blk['accepted_p50_ms']}ms "
                f"accepted_p99={blk['accepted_p99_ms']}ms "
                f"limit={blk['admission']['limit']}"
            )
            return blk

        slo_ms = float(
            os.environ.get(
                "BENCH_SLO_MS",
                max(4.0 * configs["match"]["p50_ms"], 250.0),
            )
        )
        over_factor = float(os.environ.get("BENCH_OPEN_FACTOR", 2.0))
        mod_factor = float(os.environ.get("BENCH_OPEN_MODERATE_FACTOR", 0.4))
        # moderate load FIRST: its accepted p50 is the interactive-
        # latency headline the pad-bucket ladder exists for (a lone
        # arrival rides the express lane at bucket 1 instead of a padded
        # full-width launch); the 2x overload window after it is the
        # PR 6 protection claim
        open_block = {
            "match": {
                "moderate": one_open(
                    "match", mod_factor, slo_ms, "moderate"
                ),
                "overload": one_open(
                    "match", over_factor, slo_ms, "overload"
                ),
            }
        }
        # hybrid_rrf joins the open-loop mode: the worst closed-loop p50
        # offender — both legs now ride bucketed launches; per-leg p50
        # shows where the remaining time goes
        hy_slo = float(
            os.environ.get(
                "BENCH_HYBRID_SLO_MS",
                max(4.0 * configs["hybrid_rrf"]["p50_ms"], 1000.0),
            )
        )
        with svc_jax._rrf_lock:
            for dq in svc_jax.rrf_leg_samples.values():
                dq.clear()
        hy = one_open("hybrid_rrf", mod_factor, hy_slo, "moderate")
        hy.update(leg_p50s(svc_jax))
        open_block["hybrid_rrf"] = {"moderate": hy}
        log(
            f"[open_loop:hybrid_rrf] per-leg p50: "
            f"bm25={hy.get('bm25_leg_p50_ms')}ms "
            f"knn={hy.get('knn_leg_p50_ms')}ms"
        )

    # device time is not measured here: the per-cell harness reads it
    # from the profiler's device plane (benchmarks/, PERF.md §3)
    pipeline_block = {"depth": batcher.pipeline_depth}
    log(f"[pipeline] depth={pipeline_block['depth']}")

    # ---- mesh scaling sweep (its own multi-shard index) ----
    mesh_block = None
    if os.environ.get("BENCH_MESH", "1") != "0":
        log(f"[mesh] building {MESH_DOCS}-doc corpus over "
            f"{MESH_SHARDS} shards…")
        svc_mesh, svc_mesh_np, mesh_df = build_mesh_services()
        mesh_block = mesh_sweep(svc_mesh, svc_mesh_np, mesh_df)

    headline = max(configs["match"]["qps"], qps_wand)
    base = configs["match"]["cpu_oracle_qps"]
    recall_ok = all(
        c.get("recall", 1.0) >= 0.99
        for nm, c in configs.items()
        if nm != "sparse_retrieval"  # deliberately lossy int8 serving;
        # its own gate is recall_at_10_vs_fp32_oracle >= 0.95
    )
    vs = round(headline / base, 2) if base and recall_ok else None
    print(
        json.dumps(
            {
                "metric": "bm25_top10_qps_1m_docs_serving_path",
                "value": round(headline, 1),
                "unit": "queries/s",
                "vs_baseline": vs,
                "qps_exact_totals": configs["match"]["qps"],
                "qps_wand": round(qps_wand, 1),
                "p50_ms": configs["match"]["p50_ms"],
                "p99_ms": configs["match"]["p99_ms"],
                "p50_ms_wand": round(p50_wand, 2),
                "cpu_oracle_qps": base,
                "cpu_oracle_qps_single_thread": round(o1_qps, 1),
                "recall_at_1000": configs["match"]["recall"],
                "pipeline": pipeline_block,
                "mesh": mesh_block,
                "open_loop": open_block,
                "configs": configs,
                "baseline_kind": (
                    "measured NumPy oracle: dense vectorized scorer (no "
                    "WAND skipping), same serving path, "
                    f"{ORACLE_THREADS} GIL-bound threads; single-thread "
                    "number reported separately"
                ),
                "recall_residue": (
                    "device vs oracle divergence is fp32 re-association "
                    "at the top-k boundary; max relative score delta per "
                    "config is in configs.*.max_score_rel_delta"
                ),
                "n_docs": N_DOCS,
                "dims": DIMS,
                "threads": THREADS,
                "host_cores": len(os.sched_getaffinity(0)),
            }
        )
    )


if __name__ == "__main__":
    main()
