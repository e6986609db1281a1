"""Continuous batching over the pad-bucket launch ladder (round 7).

Contracts under test:
  * every ladder bucket is FLOAT-EXACT vs the fixed-BPAD launch shape
    (match / bool / multi_match / knn; chunked AND fused engines) and
    vs the NumPy oracle — bucketing is padding only, never semantics;
  * lone queries ride the express lane (depth-1, bucket-1) with
    identical results, and the hit is counted;
  * after a family's eager bucket warmup, randomized bucket load
    compiles NOTHING new (jit cache-size probe);
  * scheduling invariants survive the ladder: the 429 queue bound,
    close/drain during randomized bucket load, and deadline shedding
    at dequeue;
  * the wait-timeout bugfix: a timed-out waiter CANCELS its job (it
    never launches into a dead waiter) — batcher-level and through the
    shard timeout path;
  * the per-bucket launch histogram surfaces in `_nodes/stats`.
"""

import threading
import time

import numpy as np
import pytest

from elasticsearch_tpu.cluster.indices import IndexService
from elasticsearch_tpu.common.settings import (
    BATCH_BUCKETS_ENV,
    batch_buckets,
    bucket_for,
)
from elasticsearch_tpu.ops import scoring
from elasticsearch_tpu.search import dsl
from elasticsearch_tpu.search.batcher import (
    EsRejectedExecutionError,
    QueryBatcher,
    extract_knn_plan,
    extract_match_plan,
    extract_serve_plan,
)

WORDS = [
    "alpha", "beta", "gamma", "delta", "epsilon", "zeta", "eta", "theta",
    "iota", "kappa", "lam", "mu", "nu", "xi", "omicron", "pi",
]
DIMS = 8


def _zipf(n):
    w = 1.0 / np.arange(1, n + 1)
    return w / w.sum()


def make_service(n_docs=240, seed=0, waves=3, backend="jax", name="cb"):
    rng = np.random.default_rng(seed)
    svc = IndexService(
        name,
        settings={"number_of_shards": 1, "search.backend": backend},
        mappings_json={
            "properties": {
                "title": {"type": "text"},
                "body": {"type": "text"},
                "vec": {"type": "dense_vector", "dims": DIMS,
                        "similarity": "cosine"},
            }
        },
    )
    per_wave = max(1, n_docs // waves)
    for i in range(n_docs):
        kt = int(rng.integers(1, 4))
        kb = int(rng.integers(3, 12))
        svc.index_doc(
            str(i),
            {
                "title": " ".join(rng.choice(WORDS, kt, p=_zipf(len(WORDS)))),
                "body": " ".join(rng.choice(WORDS, kb, p=_zipf(len(WORDS)))),
                "vec": [float(x) for x in rng.normal(size=DIMS)],
            },
        )
        if (i + 1) % per_wave == 0:
            svc.refresh()
    svc.refresh()
    return svc


@pytest.fixture(scope="module")
def service():
    svc = make_service()
    yield svc
    svc.close()


@pytest.fixture(scope="module")
def oracle():
    svc = make_service(backend="numpy", name="cb-oracle")
    yield svc
    svc.close()


def workerless(monkeypatch, **kw):
    b = QueryBatcher(**kw)
    monkeypatch.setattr(b, "_ensure_thread", lambda: None)
    return b


def td_fingerprint(td):
    """Exact (unrounded) identity of a TopDocs."""
    return (
        [(h.doc_id, h.segment, h.local_doc, h.score) for h in td.hits],
        td.total,
        td.relation,
        td.max_score,
    )


# kNN scores are NOT bit-identical across launch shapes (ROADMAP D7): the
# row count of `queries @ vectors.T` (ops/scoring.knn_scores) picks the
# matmul's internal tiling, so the same fp32 dot product is summed in
# another order — a last-ulp difference, bounded by d * 2^-24. The bound
# is scoring.KNN_SCORE_RTOL, the one chip_smoke.py uses for the kNN
# family too; text families stay float-exact.


def assert_same_topdocs(got, ref, kind):
    if kind != "knn":
        assert td_fingerprint(got) == td_fingerprint(ref)
        return
    ids = lambda td: [(h.doc_id, h.segment, h.local_doc) for h in td.hits]
    assert ids(got) == ids(ref)
    assert (got.total, got.relation) == (ref.total, ref.relation)
    np.testing.assert_allclose(
        [h.score for h in got.hits] + [got.max_score],
        [h.score for h in ref.hits] + [ref.max_score],
        rtol=scoring.KNN_SCORE_RTOL, atol=0.0,
    )


# ---------------------------------------------------------------------
# ladder selection
# ---------------------------------------------------------------------


class TestLadder:
    def test_default_ladder(self):
        assert batch_buckets(32) == (1, 4, 8, 16, 32)
        assert batch_buckets(8) == (1, 4, 8)

    def test_env_override_and_validation(self, monkeypatch):
        monkeypatch.setenv(BATCH_BUCKETS_ENV, "2, 8 16")
        assert batch_buckets(32) == (2, 8, 16)
        monkeypatch.setenv(BATCH_BUCKETS_ENV, "0,64,7")
        assert batch_buckets(32) == (7,)  # out-of-range values dropped
        monkeypatch.setenv(BATCH_BUCKETS_ENV, "garbage")
        assert batch_buckets(32) == (1, 4, 8, 16, 32)  # fallback
        monkeypatch.setenv(BATCH_BUCKETS_ENV, "32")
        assert batch_buckets(32) == (32,)  # the fixed-shape baseline

    def test_bucket_for_smallest_cover(self):
        ladder = (1, 4, 8, 16, 32)
        assert bucket_for(1, ladder) == 1
        assert bucket_for(2, ladder) == 4
        assert bucket_for(4, ladder) == 4
        assert bucket_for(9, ladder) == 16
        assert bucket_for(32, ladder) == 32

    def test_bucket_for_data_axis_multiple(self):
        ladder = (1, 4, 8, 16, 32)
        # the mesh data axis shards the query batch: bucket must divide
        assert bucket_for(1, ladder, multiple_of=2) == 4
        assert bucket_for(5, ladder, multiple_of=4) == 8
        # no qualifying ladder entry → round up to the multiple
        assert bucket_for(3, (1, 3), multiple_of=2) == 4


# ---------------------------------------------------------------------
# float-exact parity: every bucket vs the fixed-BPAD shape + the oracle
# ---------------------------------------------------------------------


def match_plans(svc, n, tth=10_000):
    out = []
    for i in range(n):
        w1 = WORDS[i % len(WORDS)]
        w2 = WORDS[(i * 3 + 1) % len(WORDS)]
        q = dsl.parse_query({"match": {"body": f"{w1} {w2}"}})
        p = extract_match_plan(q, svc.mappings, svc.analysis, tth)
        assert p is not None
        out.append((p, q))
    return out


def serve_plans(svc, n):
    out = []
    for i in range(n):
        w1 = WORDS[i % len(WORDS)]
        w2 = WORDS[(i * 5 + 2) % len(WORDS)]
        body = {"bool": {"must": [{"term": {"body": w1}}],
                         "should": [{"match": {"title": w2}}]}}
        q = dsl.parse_query(body)
        p = extract_serve_plan(q, svc.mappings, svc.analysis)
        assert p is not None
        out.append((p, q))
    return out


def knn_plans(svc, n, seed=3, nc=50):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        sec = dsl.parse_knn({
            "field": "vec",
            "query_vector": [float(x) for x in rng.normal(size=DIMS)],
            "k": 8,
            "num_candidates": nc,
        })
        p = extract_knn_plan([sec], svc.mappings)
        assert p is not None
        out.append((p, None))
    return out


def run_bucket(b, ex, plans, kind, kb, rows):
    """Dispatch ONE group of len(plans) jobs at a padded launch width of
    `rows` through the real group path; returns the TopDocs list."""
    jobs = [
        b.submit_nowait(ex, p, 10 if kind != "knn" else 8, kind=kind,
                        query=q)
        for p, q in plans
    ]
    if kind == "match":
        pend = b._dispatch_match_group(jobs, plans[0][0].field, kb,
                                       rows=rows)
        b._collect_match_group(jobs, kb, pend)
    elif kind == "serve":
        pend = b._dispatch_serve_group(jobs, kb, rows=rows)
        b._collect_serve_group(jobs, kb, pend)
    else:
        pend = b._dispatch_knn_group(jobs, rows=rows)
        b._collect_knn_group(jobs, pend)
    return [QueryBatcher.wait(j, timeout=30) for j in jobs]


class TestBucketParity:
    @pytest.mark.parametrize("kind", ["match", "serve", "knn"])
    def test_every_bucket_matches_fixed_shape(
        self, service, monkeypatch, kind
    ):
        ex = service._executor(service.shards[0])
        tiny = workerless(monkeypatch, workers=1)
        maker = {"match": match_plans, "serve": serve_plans,
                 "knn": knn_plans}[kind]
        kb = 16
        for rows in batch_buckets(scoring.BPAD):
            plans = maker(service, rows)  # full occupancy at this bucket
            got = run_bucket(tiny, ex, plans, kind, kb, rows)
            ref = run_bucket(tiny, ex, plans, kind, kb, scoring.BPAD)
            for g, r in zip(got, ref):
                assert_same_topdocs(g, r, kind)
            # partial occupancy: fewer jobs than the bucket width
            if rows > 1:
                part = plans[: rows // 2 + 1]
                got_p = run_bucket(tiny, ex, part, kind, kb, rows)
                ref_p = run_bucket(tiny, ex, part, kind, kb, scoring.BPAD)
                for g, r in zip(got_p, ref_p):
                    assert_same_topdocs(g, r, kind)
        tiny.close()

    @pytest.mark.parametrize("counted", [
        None,
        {"operator": "and"},
        {"minimum_should_match": 2},
    ], ids=["or_jobs", "and_job_beside_or", "msm_job_beside_or"])
    def test_fused_engine_bucket_parity(self, monkeypatch, counted):
        """Force the fused single-round-trip scorer (normally gated to
        large segments) so the bucketed plan upload path is exercised
        too — not just the chunked engine. With `counted`, one job of
        every launch holds a count threshold, which makes the launch of
        the `or` jobs beside it a counted one: every job still answers
        with the unbatched executor's hits and totals."""
        from elasticsearch_tpu.search import executor_jax

        monkeypatch.setattr(executor_jax, "FUSED_MIN_DOCS", 10)
        svc = make_service(n_docs=300, seed=7, name="cb-fused")
        try:
            ex = svc._executor(svc.shards[0])
            assert ex.fused_scorer_mf(0, ("body",)) is not None
            tiny = workerless(monkeypatch, workers=1)
            before = dict(tiny.stats)
            for rows in (1, 4, 32):
                plans = match_plans(svc, rows)
                if counted:
                    q = dsl.parse_query({"match": {"body": {
                        "query": "alpha beta gamma", **counted}}})
                    p = extract_match_plan(
                        q, svc.mappings, svc.analysis, 10_000)
                    assert p.msm > 1
                    plans[-1] = (p, q)
                got = run_bucket(tiny, ex, plans, "match", 16, rows)
                ref = run_bucket(tiny, ex, plans, "match", 16, scoring.BPAD)
                for g, r, (_, q) in zip(got, ref, plans):
                    assert td_fingerprint(g) == td_fingerprint(r), rows
                    alone = ex.search(q, size=10)
                    assert [(h.doc_id, h.score) for h in g.hits] == [
                        (h.doc_id, pytest.approx(h.score, rel=1e-6))
                        for h in alone.hits]
                    assert (g.total, g.relation) == (
                        alone.total, alone.relation)
            # every job of every launch stayed in the fused kernel
            segs = len(ex.reader.segments)
            assert tiny.stats["launches"] - before["launches"] == 6 * segs
            assert tiny.stats["fused_jobs"] - before["fused_jobs"] == (
                2 * (1 + 4 + 32) * segs)
            tiny.close()
        finally:
            svc.close()

    def test_end_to_end_parity_with_oracle(self, service, oracle):
        """The bucketed serving path (express lane + whatever batches
        form under concurrency) stays hit-for-hit with the NumPy
        oracle for every plan family."""
        rng = np.random.default_rng(17)
        bodies = []
        for i in range(24):
            w = WORDS[int(rng.integers(0, 8))]
            w2 = WORDS[int(rng.integers(0, len(WORDS)))]
            kind = i % 4
            if kind == 0:
                bodies.append(
                    {"query": {"match": {"body": f"{w} {w2}"}}, "size": 7}
                )
            elif kind == 1:
                bodies.append({
                    "query": {"bool": {
                        "must": [{"term": {"body": w}}],
                        "should": [{"match": {"title": w2}}],
                    }},
                    "size": 7,
                })
            elif kind == 2:
                bodies.append({
                    "query": {"multi_match": {
                        "query": f"{w} {w2}",
                        "fields": ["title", "body"],
                        "tie_breaker": 0.3,
                    }},
                    "size": 7,
                })
            else:
                v = [float(x) for x in rng.normal(size=DIMS)]
                bodies.append({
                    "knn": {"field": "vec", "query_vector": v, "k": 5,
                            "num_candidates": 50},
                    "size": 5,
                })
        results = [None] * len(bodies)
        errs = []
        cursor = [0]
        lock = threading.Lock()

        def worker():
            while True:
                with lock:
                    i = cursor[0]
                    if i >= len(bodies):
                        return
                    cursor[0] += 1
                try:
                    results[i] = service.search(bodies[i])
                except Exception as e:  # pragma: no cover
                    errs.append(e)
                    return

        ts = [threading.Thread(target=worker) for _ in range(8)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert not errs, errs
        for body, got in zip(bodies, results):
            want = oracle.search(body)
            assert [
                (h["_id"], round(h["_score"], 4))
                for h in got["hits"]["hits"]
            ] == [
                (h["_id"], round(h["_score"], 4))
                for h in want["hits"]["hits"]
            ], body


# ---------------------------------------------------------------------
# a batch launches every group it holds before it collects any
# ---------------------------------------------------------------------

PAIRS = {
    "match": ("_dispatch_match_group", "_collect_match_group"),
    "serve": ("_dispatch_serve_group", "_collect_serve_group"),
    "knn": ("_dispatch_knn_group", "_collect_knn_group"),
}


class TestLaunchThenCollect:
    def test_two_families_launch_in_submission_order_then_collect(
        self, service, monkeypatch
    ):
        """One `match` and one `knn` job in a batch (a hybrid request's
        legs): both groups are launched before either is collected, in
        submission order, and each answer is the job's own run alone,
        bit for bit (the same one-row launches)."""
        ex = service._executor(service.shards[0])
        b = workerless(monkeypatch, workers=1)
        (mp, mq), = match_plans(service, 1)
        (kp, _), = knn_plans(service, 1)

        def submit():
            return [b.submit_nowait(ex, mp, 10, kind="match", query=mq),
                    b.submit_nowait(ex, kp, 8, kind="knn")]

        alone = []
        for j in submit():
            b._collect_batch(b._dispatch_batch([j], express=True))
            alone.append(QueryBatcher.wait(j, timeout=30))
        assert b.stats["groups_launched_together"] == 0  # one group each
        order = []
        for kind in ("match", "knn"):
            for name in PAIRS[kind]:
                def spy(*a, _real=getattr(b, name), _name=name, **kw):
                    order.append(_name)
                    return _real(*a, **kw)
                monkeypatch.setattr(b, name, spy)
        jobs = submit()
        ctx = b._dispatch_batch(jobs)
        assert not any(j.done() for j in jobs)
        assert b._inflight["text"] == b._inflight["knn"] == 1
        b._collect_batch(ctx)
        assert order == [PAIRS["match"][0], PAIRS["knn"][0],
                         PAIRS["match"][1], PAIRS["knn"][1]]
        for j, ref in zip(jobs, alone):
            assert (td_fingerprint(QueryBatcher.wait(j, timeout=30))
                    == td_fingerprint(ref))
        assert b.stats["groups_launched_together"] == 2
        assert all(n == 0 for n in b._inflight.values())
        # the text group was in flight while the kNN group was launched
        text, knn = (j.group for j in jobs)
        assert text.t_dispatched <= knn.t_start
        assert knn.t_dispatched <= text.t_collect <= knn.t_collect
        b.close()

    @pytest.mark.parametrize("kind", ["match", "serve", "knn"])
    def test_first_request_wakes_before_its_ladder_warms(
        self, service, monkeypatch, kind
    ):
        """Bucket warming is compile time, not the first query's time:
        nothing warms before the batch's collect, and the waiter is
        awake at every warm-up launch, which `wait_warm_idle` reads as
        running from before the waiter woke."""
        ex = service._executor(service.shards[0])
        b = workerless(monkeypatch, workers=1)
        b.warmup_enabled = True
        maker = {"match": match_plans, "serve": serve_plans,
                 "knn": knn_plans}[kind]
        (plan, q), = maker(service, 1)
        j = b.submit_nowait(ex, plan, 8, kind=kind, query=q)
        real = getattr(b, PAIRS[kind][0])
        seen = []

        def spy(jobs, *a, **kw):
            seen.append((kw["record"], j.done(), b._warm_inflight))
            return real(jobs, *a, **kw)

        monkeypatch.setattr(b, PAIRS[kind][0], spy)
        ctx = b._dispatch_batch([j], express=True)
        assert seen == [(True, False, 0)]
        assert b._warmed == set() and not j.done()
        b._collect_batch(ctx)
        assert QueryBatcher.wait(j, timeout=30).hits
        assert seen[1:] == [(False, True, 1)] * (len(b.buckets) - 1)
        assert b.wait_warm_idle(timeout=1.0)
        assert b.stats["warmup_failures"] == 0
        b.close()


# ---------------------------------------------------------------------
# express lane
# ---------------------------------------------------------------------


class TestExpressLane:
    def test_lone_query_rides_express_lane(self, service, oracle):
        b = service._batcher
        before = b.stats["express_lane_hits"]
        hist0 = dict(b.batching_stats()["launches_by_bucket"])
        body = {"query": {"match": {"body": "alpha gamma"}}, "size": 7}
        got = service.search(body)
        assert b.stats["express_lane_hits"] > before
        hist1 = b.batching_stats()["launches_by_bucket"]
        assert hist1.get("1", 0) > hist0.get("1", 0)  # bucket-1 launch
        want = oracle.search(body)
        assert [
            (h["_id"], round(h["_score"], 4)) for h in got["hits"]["hits"]
        ] == [
            (h["_id"], round(h["_score"], 4)) for h in want["hits"]["hits"]
        ]
        assert got["hits"]["total"] == want["hits"]["total"]


# ---------------------------------------------------------------------
# no recompile after warmup (the jit cache-size probe)
# ---------------------------------------------------------------------


def _cache_sizes():
    fns = {
        "_chunk_add": scoring._chunk_add,
        "_chunk_add_cnt": scoring._chunk_add_cnt,
        "_finalize": scoring._finalize,
        "_fused_query_mf": scoring._fused_query_mf,
        "_merge_segments": scoring._merge_segments,
        "_knn_merge_segments": scoring._knn_merge_segments,
        "knn_topk_batch": scoring.knn_topk_batch,
        "topk_hits": scoring.topk_hits,
    }
    return {name: fn._cache_size() for name, fn in fns.items()}


class TestNoRecompileAfterWarmup:
    def test_randomized_bucket_load_compiles_nothing_new(self):
        """One query per family (with eager warmup armed) must leave the
        jit caches complete: randomized concurrent load across every
        bucket afterwards compiles ZERO new programs."""
        svc = make_service(n_docs=200, seed=11, name="cb-warm")
        try:
            svc._batcher.warmup_enabled = True
            # one query per family signature → _warm_ladder compiles the
            # whole ladder for each (same k bucket, fixed nc)
            warm_bodies = [
                {"query": {"match": {"body": "alpha beta"}}, "size": 7},
                {"query": {"bool": {
                    "must": [{"term": {"body": "alpha"}}],
                    "should": [{"match": {"title": "beta"}}]}}, "size": 7},
                {"query": {"multi_match": {
                    "query": "gamma delta", "fields": ["title", "body"],
                    "tie_breaker": 0.3}}, "size": 7},
                {"knn": {"field": "vec",
                         "query_vector": [0.1] * DIMS, "k": 5,
                         "num_candidates": 50}, "size": 5},
            ]
            for body in warm_bodies:
                svc.search(body)
            # the warm loop runs on the worker AFTER each triggering
            # request completes — quiesce before snapshotting the jit
            # caches or the warm tail races the probe
            assert svc._batcher.wait_warm_idle()
            sizes0 = _cache_sizes()

            rng = np.random.default_rng(23)
            bodies = []
            for i in range(64):
                w = WORDS[int(rng.integers(0, 8))]
                w2 = WORDS[int(rng.integers(0, len(WORDS)))]
                kind = i % 4
                if kind == 0:
                    bodies.append({"query": {"match": {
                        "body": f"{w} {w2}"}}, "size": 7})
                elif kind == 1:
                    bodies.append({"query": {"bool": {
                        "must": [{"term": {"body": w}}],
                        "should": [{"match": {"title": w2}}]}},
                        "size": 7})
                elif kind == 2:
                    bodies.append({"query": {"multi_match": {
                        "query": f"{w} {w2}",
                        "fields": ["title", "body"],
                        "tie_breaker": 0.3}}, "size": 7})
                else:
                    v = [float(x) for x in rng.normal(size=DIMS)]
                    bodies.append({"knn": {
                        "field": "vec", "query_vector": v, "k": 5,
                        "num_candidates": 50}, "size": 5})
            errs = []
            cursor = [0]
            lock = threading.Lock()

            def worker():
                while True:
                    with lock:
                        i = cursor[0]
                        if i >= len(bodies):
                            return
                        cursor[0] += 1
                    try:
                        svc.search(bodies[i])
                    except Exception as e:  # pragma: no cover
                        errs.append(e)
                        return

            # vary concurrency so many bucket sizes actually occur
            for threads in (1, 5, 12):
                cursor[0] = 0
                ts = [threading.Thread(target=worker)
                      for _ in range(threads)]
                for t in ts:
                    t.start()
                for t in ts:
                    t.join()
            assert not errs, errs
            assert svc._batcher.wait_warm_idle()
            sizes1 = _cache_sizes()
            assert sizes1 == sizes0, (
                "bucketed load recompiled after warmup: "
                f"{ {k: (sizes0[k], sizes1[k]) for k in sizes0 if sizes0[k] != sizes1[k]} }"
            )
        finally:
            svc.close()


# ---------------------------------------------------------------------
# scheduling invariants under the ladder
# ---------------------------------------------------------------------


class TestWarmupFailureCounted:
    def test_failed_warm_launch_is_counted_and_live_query_answers(
        self, caplog
    ):
        """A warm launch that raises (a launch shape the device refuses)
        is counted in `warmup_failures` and logged once; warm-up stays
        opportunistic — the live query that triggered it still answers."""
        svc = make_service(n_docs=120, seed=5, name="cb-warmfail")
        try:
            b = svc._batcher
            b.warmup_enabled = True
            real = b._dispatch_knn_group

            def refuse_warm(jobs, rows=None, record=True):
                if not record:  # only warm launches carry record=False
                    raise RuntimeError("launch shape refused")
                return real(jobs, rows=rows, record=record)

            b._dispatch_knn_group = refuse_warm
            body = {"knn": {"field": "vec", "query_vector": [0.1] * DIMS,
                            "k": 5, "num_candidates": 50}, "size": 5}
            with caplog.at_level("WARNING"):
                resp = svc.search(body)
                assert b.wait_warm_idle()
            assert len(resp["hits"]["hits"]) == 5  # the live query answered
            # one failure per ladder bucket other than the live one
            assert b.stats["warmup_failures"] == len(b.buckets) - 1
            assert b.batching_stats()["warmup_failures"] == len(b.buckets) - 1
            logged = [r for r in caplog.records
                      if "warm-up launch failed" in r.getMessage()]
            assert len(logged) == 1  # the first failure, not every one
            # and the family still serves afterwards
            assert len(svc.search(body)["hits"]["hits"]) == 5
        finally:
            svc.close()


class TestColdClock:
    """The congestion signal the admission layer steers on is the
    enqueue→dispatch wait LESS the seconds the dispatcher workers spent
    compiling meanwhile (batcher module comment, "cold clock"): a cold
    node must not read its own compiles as load and shed the next
    request, while a wait behind real work is still reported whole."""

    @pytest.mark.parametrize("compiling", [True, False],
                             ids=["behind_a_compile", "behind_real_work"])
    def test_queue_delay_signal(self, service, monkeypatch, compiling):
        import jax.monitoring

        from elasticsearch_tpu.search.admission import admission

        ex = service._executor(service.shards[0])
        mp = [p for p, _ in match_plans(service, 3)]
        b = QueryBatcher(workers=1)
        b.warmup_enabled = False
        try:
            # everything these plans launch is compiled before the probe
            for p in mp:
                assert QueryBatcher.wait(b.submit_nowait(ex, p, 10), 60)
            samples = []
            monkeypatch.setattr(admission, "observe_queue_delay",
                                samples.append)
            held = 0.4
            real = b._dispatch_match_group
            real_collect = b._collect_match_group
            first = threading.Event()
            # what the held worker really spent, on the batcher's clock:
            # asleep (reported as compile time) and running the first
            # group afterwards, its dispatch and its collect (real work,
            # which the queued jobs wait behind too). On a loaded host
            # (the driver's six workers) the sleep overshoots and the
            # run takes tens of ms; pinned to the nominal 0.4 s, their
            # sum read over the 75 ms target
            spent = {}

            def timed_collect(jobs, *a, **kw):
                t0 = time.perf_counter()
                try:
                    return real_collect(jobs, *a, **kw)
                finally:
                    spent.setdefault(
                        "collecting", time.perf_counter() - t0)

            def slow_first(jobs, *a, **kw):
                if first.is_set():
                    return real(jobs, *a, **kw)
                first.set()
                t0 = time.perf_counter()
                time.sleep(held)  # the one worker is held this long
                spent["asleep"] = time.perf_counter() - t0
                if compiling:  # ...by the compiler, as JAX reports it
                    jax.monitoring.record_event_duration_secs(
                        "/jax/core/compile/backend_compile_duration",
                        spent["asleep"],
                    )
                t0 = time.perf_counter()
                try:
                    return real(jobs, *a, **kw)
                finally:
                    spent["running"] = time.perf_counter() - t0

            monkeypatch.setattr(b, "_dispatch_match_group", slow_first)
            monkeypatch.setattr(b, "_collect_match_group", timed_collect)
            j1 = b.submit_nowait(ex, mp[0], 10)
            assert first.wait(10)
            queued = [b.submit_nowait(ex, p, 10) for p in mp[1:]]
            for j in [j1] + queued:
                assert QueryBatcher.wait(j, 60) is not None
            worst = max(samples)
            if compiling:
                # the compile is taken out whole; what is left is the
                # wait behind the first group's own run
                assert (worst - spent["running"] - spent["collecting"]
                        < admission.target_delay_s), (samples, spent)
            else:
                assert worst >= 0.75 * held, samples
        finally:
            b.close()


class TestSchedulingInvariants:
    def test_429_bound_unchanged(self, service, monkeypatch):
        ex = service._executor(service.shards[0])
        plan = extract_match_plan(
            dsl.parse_query({"match": {"body": "alpha"}}),
            service.mappings, service.analysis, False,
        )
        tiny = workerless(monkeypatch, workers=1, queue_capacity=4)
        rejected = 0
        for _ in range(10):
            try:
                tiny.submit_nowait(ex, plan, 5)
            except EsRejectedExecutionError:
                rejected += 1
        assert rejected == 6
        assert tiny.stats["rejected"] == 6
        tiny.close()  # queued waiters must fail, not hang

    def test_flood_and_close_under_randomized_buckets(self, service):
        """A flood of mixed-family jobs (bucket sizes land wherever the
        race puts them) all complete; close() mid-traffic fails the
        rest instead of hanging, and the workers exit."""
        ex = service._executor(service.shards[0])
        mp = [p for p, _ in match_plans(service, 8)]
        kp = [p for p, _ in knn_plans(service, 4, seed=5)]
        tiny = QueryBatcher(workers=3, queue_capacity=64)
        jobs = []
        for i in range(48):
            try:
                if i % 3 == 2:
                    jobs.append(tiny.submit_nowait(
                        ex, kp[i % len(kp)], 8, kind="knn"))
                else:
                    jobs.append(tiny.submit_nowait(
                        ex, mp[i % len(mp)], 10))
            except EsRejectedExecutionError:
                pass
        done = 0
        for j in jobs:
            td = QueryBatcher.wait(j, timeout=30)
            assert td is not None
            done += 1
        assert done == len(jobs)
        # close with fresh jobs racing in: nobody may hang
        tail = []
        for i in range(8):
            try:
                tail.append(tiny.submit_nowait(ex, mp[i % len(mp)], 10))
            except EsRejectedExecutionError:
                pass
        tiny.close()
        for j in tail:
            assert j.event.wait(20)
        for t in tiny._threads:
            t.join(timeout=10)
            assert not t.is_alive()

    def test_deadline_shed_at_dequeue_preserved(self, service, monkeypatch):
        """_admit_job still drops dead jobs before any bucket is chosen:
        a mixed queue of dead and live jobs sheds exactly the dead ones
        and the live ones complete normally."""
        from elasticsearch_tpu.search.failures import SearchTimeoutError

        ex = service._executor(service.shards[0])
        mp = [p for p, _ in match_plans(service, 4)]
        b = QueryBatcher()
        b.workers = 0  # keep everything queued
        dead = [
            b.submit_nowait(ex, mp[i], 10,
                            deadline=time.monotonic() - 0.01)
            for i in range(3)
        ]
        live = [b.submit_nowait(ex, mp[i], 10) for i in range(4)]
        b.workers = 2
        b._ensure_thread()
        for j in dead:
            with pytest.raises(SearchTimeoutError):
                QueryBatcher.wait(j, timeout=10)
        for j in live:
            assert QueryBatcher.wait(j, timeout=30) is not None
        assert b.stats["shed_dead_jobs"] == 3
        b.close()


# ---------------------------------------------------------------------
# wait-timeout cancels the job (the satellite bugfix)
# ---------------------------------------------------------------------


class TestWaitTimeoutCancelsJob:
    def test_wait_or_cancel_drops_queued_job(self, service):
        """Regression: wait(job, timeout) used to abandon a timed-out
        job in the queue, where it could later dispatch into the dead
        waiter. wait_or_cancel cancels it — it never launches."""
        ex = service._executor(service.shards[0])
        plan = extract_match_plan(
            dsl.parse_query({"match": {"body": "alpha"}}),
            service.mappings, service.analysis, False,
        )
        b = QueryBatcher()
        b.workers = 0  # no dispatcher: the job stays queued
        job = b.submit_nowait(ex, plan, 5)
        with pytest.raises(TimeoutError):
            b.wait_or_cancel(job, timeout=0.05)
        assert job.event.is_set()
        assert job.error is not None
        assert b.stats["cancelled_jobs"] == 1
        # a worker starting later must drop the job at dequeue
        b.workers = 1
        b._ensure_thread()
        deadline = time.monotonic() + 5.0
        while b._queue.qsize() and time.monotonic() < deadline:
            time.sleep(0.01)
        assert b.stats["jobs"] == 0, "timed-out job entered a batch"
        assert b.stats["launches"] == 0
        b.close()

    def test_shard_timeout_cancels_queued_job_end_to_end(self):
        """Through the real shard path: a request whose timeout budget
        expires while its batched job is still queued returns a
        timed-out partial AND cancels the job — a worker arriving later
        never dispatches it."""
        svc = make_service(n_docs=40, seed=3, name="cb-timeout")
        try:
            b = svc._batcher
            b.workers = 0  # nothing drains: the job must sit queued
            resp = svc.search({
                "query": {"match": {"body": "alpha"}},
                "timeout": "120ms",
            })
            assert resp["timed_out"] is True
            # the coordinator may return its timed-out partial before
            # the abandoned shard thread finishes cancelling: poll
            deadline = time.monotonic() + 5.0
            while (
                b.stats["cancelled_jobs"] == 0
                and time.monotonic() < deadline
            ):
                time.sleep(0.01)
            assert b.stats["cancelled_jobs"] == 1
            b.workers = 1
            b._ensure_thread()
            deadline = time.monotonic() + 5.0
            while b._queue.qsize() and time.monotonic() < deadline:
                time.sleep(0.01)
            assert b.stats["jobs"] == 0, "dead job entered a batch"
            assert b.stats["launches"] == 0
        finally:
            svc.close()


# ---------------------------------------------------------------------
# observability
# ---------------------------------------------------------------------


class TestBatchingStats:
    def test_batching_stats_shape(self, service):
        service.search({"query": {"match": {"body": "alpha"}}, "size": 5})
        bs = service._batcher.batching_stats()
        assert set(bs) == {
            "buckets", "launches_by_bucket", "occupancy_jobs",
            "occupancy_slots", "avg_occupancy", "express_lane_hits",
            "warmup_failures", "worker_compile_ms", "worker_compiles",
            "fused_hot_slots", "serve_hot_slots", "direct_collect_groups",
            "rare_slots_scattered", "rare_slots_budget",
            "unplanned_queries",
            "groups_launched_together",
        }
        assert bs["warmup_failures"] == 0
        assert bs["worker_compile_ms"] > 0.0  # this batcher compiled
        assert bs["buckets"] == list(batch_buckets(scoring.BPAD))
        assert sum(bs["launches_by_bucket"].values()) > 0
        assert 0.0 < bs["avg_occupancy"] <= 1.0
        assert bs["occupancy_slots"] >= bs["occupancy_jobs"] > 0

    def test_nodes_stats_batching_block(self):
        from elasticsearch_tpu.cluster.service import ClusterService
        from elasticsearch_tpu.rest.actions import RestActions

        c = ClusterService()
        try:
            c.create_index("cbs", {
                "settings": {"search.backend": "jax"},
                "mappings": {"properties": {"body": {"type": "text"}}},
            })
            idx = c.indices["cbs"]
            for i in range(20):
                idx.index_doc(str(i), {"body": f"alpha beta {i}"})
            idx.refresh()
            idx.search({"query": {"match": {"body": "alpha"}}})
            actions = RestActions(c)
            _, resp = actions.nodes_stats(None, {}, {})
            blk = resp["nodes"]["node-0"]["pipeline"]["batching"]
            assert blk["buckets"] == list(batch_buckets(scoring.BPAD))
            assert sum(blk["launches_by_bucket"].values()) > 0
            assert blk["express_lane_hits"] >= 1
            assert 0.0 < blk["avg_occupancy"] <= 1.0
        finally:
            c.close()
