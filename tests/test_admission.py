"""Overload protection end to end (search/admission.py): weighted fair
queueing, AIMD limit convergence, deadline shedding, brownout tiers,
retry budgets, and the 429 + Retry-After rejection contract.

Reference analogs: ES bounded thread-pool queues rejecting with
EsRejectedExecutionException, HierarchyCircuitBreakerService, the 8.x
SearchBackpressure machinery, and SRE-style retry budgets. The tier-1
suite pins ES_TPU_ADMISSION=off (conftest); every test here arms an
explicit controller (or the process-global one, restored by the
_reset_admission fixture)."""

import threading
import time

import numpy as np
import pytest

from elasticsearch_tpu.cluster.indices import (
    ACTION_SHARD_SEARCH,
    IndexService,
)
from elasticsearch_tpu.common.faults import faults
from elasticsearch_tpu.search.admission import (
    AdmissionController,
    EsOverloadedError,
    admission,
    apply_brownout,
    overload_body,
)


def _controller(**kw):
    kw.setdefault("enabled", True)
    return AdmissionController(**kw)


# ---------------------------------------------------------------------
# weighted fair queueing (stride scheduling)
# ---------------------------------------------------------------------


class TestFairQueueing:
    def test_weighted_fair_share_under_contention(self):
        """With one slot and queued tenants at weight 2 vs 1, grants
        interleave ~2:1 (stride scheduling), FIFO within a tenant."""
        ctrl = _controller(min_limit=1, max_limit=1, initial_limit=1)
        t0 = ctrl.acquire("warm")  # holds the only slot
        grant_order = []
        order_lock = threading.Lock()

        def contender(tenant, weight):
            ticket = ctrl.acquire(tenant, weight=weight)
            with order_lock:
                grant_order.append(tenant)
            ctrl.release(ticket)

        threads = []
        # queue heavy (weight 2) and light (weight 1) alternately so
        # arrival order can't explain the outcome (daemon: a failing
        # assert must not hang the interpreter on a blocked waiter)
        for i in range(6):
            threads.append(
                threading.Thread(
                    target=contender, args=("heavy", 2.0), daemon=True
                )
            )
            threads.append(
                threading.Thread(
                    target=contender, args=("light", 1.0), daemon=True
                )
            )
        for i, t in enumerate(threads):
            t.start()
            # deterministic queue order: wait until this contender is in
            while ctrl.stats()["queued"] <= i:
                time.sleep(0.001)
        ctrl.release(t0)  # opens the floodgate; each release chains on
        for t in threads:
            t.join(timeout=10.0)
        assert len(grant_order) == 12
        # stride scheduling: in any prefix, heavy drains ~2x light
        first8 = grant_order[:8]
        assert first8.count("heavy") >= 5, grant_order
        st = ctrl.stats()
        assert st["tenants"]["heavy"]["admitted"] == 6
        assert st["tenants"]["light"]["admitted"] == 6
        assert st["inflight"] == 0 and st["queued"] == 0

    def test_equal_weights_round_robin(self):
        ctrl = _controller(min_limit=1, max_limit=1, initial_limit=1)
        t0 = ctrl.acquire("warm")
        grant_order = []
        lock = threading.Lock()

        def contender(tenant):
            ticket = ctrl.acquire(tenant)
            with lock:
                grant_order.append(tenant)
            ctrl.release(ticket)

        threads = [
            threading.Thread(target=contender, args=(t,), daemon=True)
            for t in ("a", "a", "a", "b", "b", "b")
        ]
        for i, t in enumerate(threads):
            t.start()
            while ctrl.stats()["queued"] <= i:
                time.sleep(0.001)
        ctrl.release(t0)
        for t in threads:
            t.join(timeout=10.0)
        # equal stride → strict alternation regardless of arrival order
        assert grant_order[:4] in (["a", "b", "a", "b"],
                                   ["b", "a", "b", "a"]), grant_order


# ---------------------------------------------------------------------
# AIMD limit convergence (batcher queue-delay signal + `load` faults)
# ---------------------------------------------------------------------


class TestAimdLimit:
    def test_decrease_and_recover(self):
        ctrl = _controller(
            target_delay_ms=50, min_limit=4, max_limit=64, initial_limit=32
        )
        # sustained over-target waits: multiplicative decrease, at most
        # once per limit-many observations
        for _ in range(400):
            ctrl.observe_queue_delay(0.2)
        st = ctrl.stats()
        assert st["limit"] == 4, st
        assert st["limit_decreases"] >= 3
        # calm signal: additive recovery (+1 per window)
        for _ in range(200):
            ctrl.observe_queue_delay(0.001)
        st2 = ctrl.stats()
        assert st2["limit"] > 4
        assert st2["limit_increases"] >= 1

    def test_synthetic_load_fault_drives_limit_down(self):
        """The `load` fault kind injects delay_ms as a synthetic
        congestion sample at the admission.acquire site — no sleeping,
        no real queue needed."""
        ctrl = _controller(
            target_delay_ms=50, min_limit=2, max_limit=16, initial_limit=16
        )
        faults.configure({
            "seed": 5,
            "rules": [
                {"site": "admission.acquire", "kind": "load",
                 "delay_ms": 400},
            ],
        })
        for _ in range(200):
            try:
                ctrl.release(ctrl.acquire("load-test"))
            except EsOverloadedError:
                pass  # sustained synthetic load reaches tier 4
        st = ctrl.stats()
        assert st["limit"] == 2, st
        assert st["limit_decreases"] >= 2
        assert st["queue_delay_ewma_ms"] > 300


# ---------------------------------------------------------------------
# deadline-aware shedding
# ---------------------------------------------------------------------


class TestDeadlineShedding:
    def test_queued_request_past_deadline_is_shed(self):
        ctrl = _controller(min_limit=1, max_limit=1, initial_limit=1)
        t0 = ctrl.acquire("hold")
        with pytest.raises(EsOverloadedError) as ei:
            ctrl.acquire("late", deadline=time.monotonic() + 0.1)
        assert ei.value.status == 429
        assert ei.value.shed == "deadline"
        assert ei.value.retry_after >= 1
        assert ctrl.stats()["shed_deadline"] == 1
        ctrl.release(t0)
        # the slot is intact: a fresh acquire succeeds immediately
        t1 = ctrl.acquire("next")
        ctrl.release(t1)

    def test_batcher_sheds_dead_job_at_dequeue(self):
        """A job whose deadline is already spent when a worker dequeues
        it fails its waiter with a timeout and never launches."""
        from elasticsearch_tpu.search.batcher import QueryBatcher
        from elasticsearch_tpu.search.failures import SearchTimeoutError

        b = QueryBatcher()
        b.workers = 0  # no dispatcher: the job stays queued
        job = b.submit_nowait(
            object(), None, 5, kind="match",
            deadline=time.monotonic() - 0.01,
        )
        assert not job.done()
        b.workers = 1  # now let a worker drain the queue
        b._ensure_thread()
        with pytest.raises(SearchTimeoutError):
            QueryBatcher.wait(job, timeout=10.0)
        assert b.stats["shed_dead_jobs"] == 1
        assert b.stats["jobs"] == 0  # never entered a dispatch batch
        assert b.stats["launches"] == 0
        b.close()

    def test_fan_out_skips_replica_retry_when_budget_spent(self):
        """Satellite: a slow-then-failed primary must not overshoot
        `timeout=` by a whole second attempt. The coordinator abandons
        the shard at the deadline; WITHOUT the in-thread budget check
        the abandoned worker would still fire the replica retry (a
        second 250ms call) into the void."""
        calls = []

        def fake_remote(node, action, payload):
            calls.append(node)
            time.sleep(0.25)  # slower than the whole request budget
            raise RuntimeError(f"simulated copy failure on [{node}]")

        svc = IndexService(
            "rep",
            settings={"number_of_shards": 1, "search.backend": "numpy"},
            mappings_json={"properties": {"body": {"type": "text"}}},
            routing={0: {"primary": "nB", "replicas": ["nC"],
                         "in_sync": ["nB", "nC"]}},
            local_node="coord",
            remote_call=fake_remote,
        )
        resp = svc.search(
            {"query": {"match_all": {}}, "timeout": "200ms"}
        )
        assert resp["timed_out"] is True
        assert resp["_shards"]["failed"] == 1
        reason = resp["_shards"]["failures"][0]["reason"]
        assert reason["type"] == "timeout_exception"
        # let the abandoned worker thread run to completion: it must
        # NOT have attempted the second copy (budget already spent)
        time.sleep(0.5)
        assert len(calls) == 1, calls
        svc.close()


# ---------------------------------------------------------------------
# brownout degraded modes
# ---------------------------------------------------------------------


class TestBrownoutTiers:
    def test_tier_transitions_track_pressure_ratio(self):
        ctrl = _controller(target_delay_ms=100)
        assert ctrl.pressure_tier() == 0
        seen = []
        # ewma rises monotonically under a constant over-target signal:
        # the tier walks 0 → 4 without skipping downward
        for _ in range(120):
            ctrl.observe_queue_delay(0.5)
            seen.append(ctrl.pressure_tier())
        assert seen[-1] == 4
        for a, b in zip(seen, seen[1:]):
            assert b >= a  # monotone under monotone pressure
        assert {1, 2, 3} & set(seen), seen  # intermediate tiers visible

    def test_apply_brownout_transforms(self):
        body = {
            "query": {"match": {"body": "x"}},
            "search_type": "dfs_query_then_fetch",
            "track_total_hits": True,
            "profile": True,
            "knn": {"field": "v", "query_vector": [0.1], "k": 10,
                    "num_candidates": 100},
            "retriever": {"rrf": {"retrievers": [], "rank_window_size": 200}},
            "aggs": {"t": {"terms": {"field": "f", "size": 500}}},
        }
        b1, a1 = apply_brownout(body, 1)
        assert "search_type" not in b1
        assert b1["track_total_hits"] == 10_000
        assert "profile" not in b1
        assert b1["knn"]["num_candidates"] == 100  # tier 1 keeps knn
        assert "dfs_skipped" in a1 and "total_hits_capped" in a1
        b2, a2 = apply_brownout(body, 2)
        assert b2["knn"]["num_candidates"] == 50
        assert b2["retriever"]["rrf"]["rank_window_size"] == 100
        assert b2["aggs"]["t"]["terms"]["size"] == 16
        assert "num_candidates_halved" in a2
        agg_body = {"size": 0, "aggs": {"t": {"terms": {"field": "f"}}}}
        b3, a3 = apply_brownout(agg_body, 3)
        assert b3["_cache_only"] is True
        assert "request_cache_only" in a3
        # the original bodies are never mutated
        assert body["track_total_hits"] is True
        assert "_cache_only" not in agg_body

    def test_allow_degraded_false_opts_out(self):
        body = {"query": {"match_all": {}}, "profile": True,
                "allow_degraded": False}
        out, actions = apply_brownout(body, 3)
        assert out is body and actions == []

    def test_degraded_search_carries_overload_metadata(self):
        svc = IndexService(
            "brown",
            settings={"number_of_shards": 1, "search.backend": "numpy"},
            mappings_json={"properties": {"body": {"type": "text"}}},
        )
        svc.index_doc("d1", {"body": "alpha beta"})
        svc.refresh()
        admission.configure(enabled=True, target_delay_ms=10)
        for _ in range(40):
            admission.observe_queue_delay(0.025)  # ratio → 2.5, tier 3
        resp = svc.search({"query": {"match": {"body": "alpha"}}})
        assert resp["hits"]["hits"]
        assert resp["_overload"]["pressure_tier"] >= 2
        assert resp["_overload"]["pressure_mode"] in (
            "shrink_window", "cache_only",
        )
        svc.close()

    def test_cache_only_tier_serves_hits_and_sheds_misses(self):
        """Tier 3: an agg-only body answers from the shard request
        cache; a miss is shed with 429 instead of computed."""
        svc = IndexService(
            "cacheonly",
            settings={"number_of_shards": 1, "search.backend": "numpy"},
            mappings_json={"properties": {
                "body": {"type": "text"}, "n": {"type": "integer"},
            }},
        )
        for i in range(8):
            svc.index_doc(f"d{i}", {"body": "alpha", "n": i})
        svc.refresh()
        agg_body = {
            "size": 0,
            "query": {"match": {"body": "alpha"}},
            "aggs": {"s": {"avg": {"field": "n"}}},
        }
        warm = svc.search(dict(agg_body))  # populates the request cache
        admission.configure(enabled=True, target_delay_ms=10)
        for _ in range(40):
            admission.observe_queue_delay(0.025)  # tier 3, below reject
        assert admission.pressure_tier() == 3
        hit = svc.search(dict(agg_body))
        assert hit["aggregations"] == warm["aggregations"]
        assert hit["_overload"]["pressure_tier"] == 3
        assert "request_cache_only" in hit["_overload"]["actions"]
        cold = {
            "size": 0,
            "query": {"match": {"body": "alpha"}},
            "aggs": {"s2": {"sum": {"field": "n"}}},  # never cached
        }
        with pytest.raises(EsOverloadedError) as ei:
            svc.search(cold)
        assert ei.value.shed == "cache_only_miss"
        svc.close()

    def test_tier4_rejects_outright(self):
        ctrl = _controller(target_delay_ms=10)
        for _ in range(60):
            ctrl.observe_queue_delay(0.5)
        with pytest.raises(EsOverloadedError) as ei:
            ctrl.acquire("any")
        assert ei.value.shed == "pressure_reject"
        assert ei.value.status == 429
        body = overload_body(ei.value, ei.value.retry_after)
        assert body["status"] == 429
        assert body["error"]["type"] == "es_rejected_execution_exception"
        assert body["es.overloaded"]["pressure_mode"] == "reject"


# ---------------------------------------------------------------------
# retry budget
# ---------------------------------------------------------------------


class TestColdNode:
    def test_cold_node_does_not_shed_its_first_requests(self, monkeypatch):
        """A node's defaults (admission on, bucket warm-up on) on a cold
        process: requests that fan out to more jobs than the batcher has
        workers queue behind first-use compiles and warm-up ladders for
        whole seconds. That wait is the compiler's, not load — it must
        not reach the congestion EWMA: a plain sequence of requests is
        served with nothing shed."""
        monkeypatch.setenv("ES_TPU_MESH", "off")  # per-shard fan-out
        admission.configure(enabled=True)
        n_shards, dims = 8, 16
        rng = np.random.default_rng(3)
        svc = IndexService(
            "cold-node",
            settings={"number_of_shards": n_shards, "search.backend": "jax"},
            mappings_json={"properties": {
                "body": {"type": "text"},
                "vec": {"type": "dense_vector", "dims": dims,
                        "similarity": "cosine"},
            }},
        )
        try:
            svc._batcher.warmup_enabled = True
            assert svc._batcher.workers < n_shards
            for i in range(32 * n_shards):
                words = rng.integers(0, 12, size=6)
                svc.index_doc(str(i), {
                    "body": " ".join(f"term{w}" for w in words),
                    "vec": [float(x) for x in rng.normal(size=dims)],
                })
            svc.refresh()
            bodies = [
                {"query": {"match": {"body": "term0 term1"}}, "size": 5},
                {"knn": {"field": "vec", "k": 5, "num_candidates": 20,
                         "query_vector": [0.1] * dims}, "size": 5},
                {"query": {"match": {"body": "term2 term3 term4"}},
                 "size": 5},
                {"query": {"bool": {
                    "must": [{"term": {"body": "term0"}}],
                    "should": [{"match": {"body": "term5"}}]}}, "size": 5},
            ]
            for body in bodies:  # back to back, no waiting for warm idle
                assert svc.search(body)["hits"]["hits"]
            st = admission.stats()
            assert st["shed_rejected"] == 0 and st["brownouts"] == 0, st
            assert st["queue_delay_ewma_ms"] < st["target_delay_ms"], st
            # compiles were seen, and kept out of the signal
            assert svc._batcher.batching_stats()["worker_compile_ms"] > 0.0
        finally:
            svc.close()


class TestRetryBudget:
    def test_token_bucket_caps_retry_ratio(self):
        ctrl = _controller(retry_budget_ratio=0.1, retry_budget_cap=2.0)
        # drain the initial allowance
        while ctrl.retry_allowed():
            pass
        denied0 = ctrl.stats()["retries_denied"]
        assert denied0 == 1
        # 10 admitted requests accrue exactly one retry token
        for _ in range(10):
            ctrl.release(ctrl.acquire("t"))
        assert ctrl.retry_allowed() is True
        assert ctrl.retry_allowed() is False
        st = ctrl.stats()
        assert st["retries_denied"] == 2

    def test_fan_out_retry_denied_when_budget_exhausted(self):
        from elasticsearch_tpu.cluster.service import ClusterError

        calls = []
        fail_next = [True]

        def fake_remote(node, action, payload):
            calls.append((node, action))
            if fail_next[0]:
                fail_next[0] = False
                raise RuntimeError(f"simulated copy failure on [{node}]")
            return {
                "total": 1, "relation": "eq", "max_score": 1.0,
                "hits": [{"_id": "x1", "_score": 1.0, "_source": {}}],
            }

        svc = IndexService(
            "rb",
            settings={"number_of_shards": 1, "search.backend": "numpy"},
            mappings_json={"properties": {"body": {"type": "text"}}},
            routing={0: {"primary": "nB", "replicas": ["nC"],
                         "in_sync": ["nB", "nC"]}},
            local_node="coord",
            remote_call=fake_remote,
        )
        admission.configure(enabled=True)
        while admission.retry_allowed():
            pass  # exhaust the node's retry tokens
        # budget empty: the single copy failure is NOT retried — with
        # one shard that means "all shards failed"
        with pytest.raises(ClusterError) as ei:
            svc.search({"query": {"match_all": {}}})
        assert ei.value.status == 503
        assert len(calls) == 1
        assert admission.stats()["retries_denied"] >= 2
        # live traffic refills the bucket (ratio 0.1/request): the same
        # failure now retries on the other copy and succeeds
        for _ in range(10):
            admission.release(admission.acquire("filler"))
        fail_next[0] = True
        resp = svc.search({"query": {"match_all": {}}})
        assert resp["_shards"]["failed"] == 0
        assert [h["_id"] for h in resp["hits"]["hits"]] == ["x1"]
        assert len(calls) == 3  # failed attempt + granted retry
        svc.close()


# ---------------------------------------------------------------------
# deterministic overload replay (fault harness)
# ---------------------------------------------------------------------


class TestDeterministicReplay:
    SCHEDULE = {
        "seed": 11,
        "rules": [
            {"site": "admission.acquire", "kind": "load",
             "delay_ms": 260, "prob": 0.4},
        ],
    }

    def _run_schedule(self):
        ctrl = _controller(
            target_delay_ms=60, min_limit=2, max_limit=16, initial_limit=16
        )
        faults.configure(dict(self.SCHEDULE))
        decisions = []
        for i in range(120):
            try:
                t = ctrl.acquire("replay")
                decisions.append(("grant", t.tier))
                ctrl.release(t)
            except EsOverloadedError as e:
                decisions.append(("shed", e.shed))
        faults.clear()
        return decisions, ctrl.stats()

    def test_same_schedule_same_decisions(self):
        """The acceptance gate: replaying the same seeded overload
        schedule yields the SAME shed/brownout decision sequence."""
        d1, s1 = self._run_schedule()
        d2, s2 = self._run_schedule()
        assert d1 == d2
        assert s1["limit"] == s2["limit"]
        assert s1["shed_rejected"] == s2["shed_rejected"]
        # the schedule actually exercised the machinery: brownouts AND
        # tier-4 sheds both appear
        kinds = {d[0] for d in d1}
        assert kinds == {"grant", "shed"}, d1[:20]
        tiers = {t for k, t in d1 if k == "grant"}
        assert tiers - {0}, "schedule never brought out a brownout tier"


# ---------------------------------------------------------------------
# queued-job cancellation (satellite)
# ---------------------------------------------------------------------


class TestQueuedJobCancel:
    def test_cancel_before_dispatch_never_launches(self):
        from elasticsearch_tpu.search.batcher import QueryBatcher
        from elasticsearch_tpu.tasks import TaskCancelledException

        b = QueryBatcher()
        b.workers = 0  # keep the job queued: no dispatcher yet
        job = b.submit_nowait(object(), None, 5, kind="match")
        assert b.cancel(job) is True
        with pytest.raises(TaskCancelledException):
            QueryBatcher.wait(job, timeout=1.0)
        # a worker starting later must drop the job at dequeue
        b.workers = 1
        b._ensure_thread()
        deadline = time.monotonic() + 5.0
        while b._queue.qsize() and time.monotonic() < deadline:
            time.sleep(0.01)
        assert b.stats["jobs"] == 0, "cancelled job entered a batch"
        assert b.stats["launches"] == 0
        assert b.stats["cancelled_jobs"] == 1
        assert b.cancel(job) is False  # already completed
        b.close()

    def test_task_cancel_mid_wait_cancels_queued_job(self):
        """Integration: a cancellable task cancelled while its batched
        job is still queued fails the request with
        task_cancelled_exception and the job never launches."""
        from elasticsearch_tpu.tasks import (
            TaskCancelledException,
            TaskManager,
        )

        svc = IndexService(
            "cancelq",
            settings={"number_of_shards": 1, "search.backend": "jax"},
            mappings_json={"properties": {"body": {"type": "text"}}},
        )
        for i in range(32):
            svc.index_doc(f"d{i}", {"body": "alpha beta gamma"})
        svc.refresh()
        svc.search({"query": {"match": {"body": "alpha"}}})  # warm/compile
        launches0 = svc._batcher.stats["launches"]
        # stall every dispatch so the second job stays queued long
        # enough for the cancel to land first
        faults.configure({
            "seed": 1,
            "rules": [{"site": "batcher.dispatch", "kind": "stall",
                       "delay_ms": 600}],
        })
        tm = TaskManager("n")
        task = tm.register("indices:data/read/search", "t", cancellable=True)
        timer = threading.Timer(0.15, task.cancel)
        timer.start()
        t0 = time.monotonic()
        try:
            with pytest.raises(TaskCancelledException):
                svc.search(
                    {"query": {"match": {"body": "alpha"}}}, task=task
                )
        finally:
            timer.cancel()
        elapsed = time.monotonic() - t0
        # the request aborted promptly (poll granularity), well inside
        # the 600ms dispatch stall
        assert elapsed < 0.5, elapsed
        # the shard thread's poll cancelled the queued job in place
        deadline = time.monotonic() + 5.0
        while (
            svc._batcher.stats["cancelled_jobs"] < 1
            and time.monotonic() < deadline
        ):
            time.sleep(0.01)
        assert svc._batcher.stats["cancelled_jobs"] == 1
        # one shard, a planned job: both searches stayed on the calling
        # thread, and the poll that cancelled the job was the shard's
        # own wait (`_wait_batched`), not the fan-out pool's gather loop
        assert svc.fan_out_stats == {"inline": 2, "pooled": 0}
        faults.clear()
        assert launches0 >= 1  # the warm query did launch
        svc.close()


# ---------------------------------------------------------------------
# observability: the `admission` block in `_nodes/stats` + REST 429s
# ---------------------------------------------------------------------


class TestObservability:
    def test_nodes_stats_admission_block(self):
        from elasticsearch_tpu.cluster.service import ClusterService
        from elasticsearch_tpu.rest.actions import RestActions

        cluster = ClusterService()
        actions = RestActions(cluster)
        cluster.create_index("obs", {"settings": {"number_of_shards": 1}})
        admission.configure(enabled=True)
        t = admission.acquire("obs", weight=3.0)
        status, payload = actions.nodes_stats(None, {}, {})
        admission.release(t)
        assert status == 200
        block = payload["nodes"]["node-0"]["admission"]
        assert block["enabled"] is True
        assert block["inflight"] == 1
        assert block["limit"] >= 1
        assert block["pressure_mode"] == "normal"
        assert block["tenants"]["obs"] == {
            "queued": 0, "active": 1, "admitted": 1, "weight": 3.0,
        }
        for key in ("admitted", "shed_deadline", "shed_queue_full",
                    "shed_rejected", "brownouts", "retries_denied",
                    "retry_tokens", "tier_grants", "queue_delay_ewma_ms"):
            assert key in block, key
        cluster.close()

    def test_cluster_settings_update_reconfigures_admission(self):
        from elasticsearch_tpu.cluster.service import ClusterService

        cluster = ClusterService()
        cluster.update_cluster_settings({
            "persistent": {
                "search": {"admission": {
                    "enabled": True,
                    "target_delay_ms": 250,
                    "max_queue": 7,
                }},
            }
        })
        st = admission.stats()
        assert st["enabled"] is True
        assert st["target_delay_ms"] == 250.0
        assert st["max_queue"] == 7
        cluster.close()

    def test_http_429_carries_retry_after_and_overload_body(self):
        """Satellite: every 429 path emits a Retry-After header and the
        structured rejection body over real HTTP."""
        import json as _json
        import urllib.error
        import urllib.request

        from elasticsearch_tpu.rest.server import ElasticsearchTpuServer

        server = ElasticsearchTpuServer(port=0)
        server.start_background()
        try:
            req = urllib.request.Request(
                f"http://127.0.0.1:{server.port}/t429",
                data=b'{"settings": {"number_of_shards": 1}}',
                headers={"Content-Type": "application/json"},
                method="PUT",
            )
            urllib.request.urlopen(req).read()
            admission.configure(enabled=True, target_delay_ms=10)
            for _ in range(60):
                admission.observe_queue_delay(0.5)  # tier 4: reject
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(
                    f"http://127.0.0.1:{server.port}/t429/_search"
                )
            err = ei.value
            assert err.code == 429
            retry_after = err.headers.get("Retry-After")
            assert retry_after is not None and int(retry_after) >= 1
            payload = _json.loads(err.read())
            assert payload["error"]["type"] == (
                "es_rejected_execution_exception"
            )
            assert payload["es.overloaded"]["pressure_mode"] == "reject"
            assert payload["es.overloaded"]["retry_after_s"] == int(
                retry_after
            )
        finally:
            admission.reset()
            server.close()

    def test_batcher_queue_full_429_is_shaped(self):
        """The pre-existing batcher queue-full 429 now renders with the
        overload body + Retry-After (handler-level check)."""
        from elasticsearch_tpu.search.batcher import (
            EsRejectedExecutionError,
        )

        e = EsRejectedExecutionError(
            "rejected execution: search queue capacity [8] reached"
        )
        body = overload_body(e, 3)
        assert body["status"] == 429
        assert body["error"]["root_cause"][0]["type"] == (
            "es_rejected_execution_exception"
        )
        assert body["es.overloaded"]["retry_after_s"] == 3

    def test_queue_full_sheds_with_429(self):
        ctrl = _controller(
            min_limit=1, max_limit=1, initial_limit=1, max_queue=1
        )
        t0 = ctrl.acquire("full")
        blocked = threading.Thread(
            target=lambda: ctrl.release(ctrl.acquire("full")),
            daemon=True,
        )
        blocked.start()
        while ctrl.stats()["queued"] < 1:
            time.sleep(0.001)
        with pytest.raises(EsOverloadedError) as ei:
            ctrl.acquire("full")
        assert ei.value.shed == "queue_full"
        assert ctrl.stats()["shed_queue_full"] == 1
        ctrl.release(t0)
        blocked.join(timeout=5.0)
        assert ctrl.stats()["inflight"] == 0
