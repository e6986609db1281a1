"""Mesh-parallel serving: the whole-index SPMD path (parallel/
mesh_executor.MeshExecutor) vs the sequential per-shard fan-out.

Runs on the forced 8-virtual-device CPU platform (tests/conftest.py sets
XLA_FLAGS=--xla_force_host_platform_device_count=8), so the full
shard_map program — per-entry scoring, local top-k, all_gather + k-way
merge, psum totals — executes with real cross-device collectives and no
TPU. The headline contract: every routed config is FLOAT-EXACT vs the
sequential path (same scores bit-for-bit, same (score desc, shard asc,
segment asc, doc asc) order, same totals).
"""

import os

import numpy as np
import pytest

from elasticsearch_tpu.cluster.indices import IndexService

pytestmark = pytest.mark.mesh

DIMS = 8
VOCAB = ["alpha", "beta", "gamma", "delta", "epsilon", "zeta", "eta",
         "theta"]


@pytest.fixture(autouse=True)
def _mesh_env():
    """No test may leak a forced mesh mode into the rest of tier-1."""
    old = os.environ.get("ES_TPU_MESH")
    yield
    if old is None:
        os.environ.pop("ES_TPU_MESH", None)
    else:
        os.environ["ES_TPU_MESH"] = old


def make_service(name, n_shards=4, batches=2, per_batch=60, seed=0):
    svc = IndexService(
        name,
        settings={"number_of_shards": n_shards, "search.backend": "jax"},
        mappings_json={
            "properties": {
                "title": {"type": "text"},
                "body": {"type": "text"},
                "vec": {
                    "type": "dense_vector",
                    "dims": DIMS,
                    "similarity": "cosine",
                },
            }
        },
    )
    rng = np.random.default_rng(seed)
    doc = 0
    for _ in range(batches):
        for _ in range(per_batch):
            words = rng.choice(VOCAB, size=int(rng.integers(3, 8)))
            v = rng.normal(size=DIMS)
            svc.index_doc(
                str(doc),
                {
                    "title": " ".join(rng.choice(VOCAB, size=2)),
                    "body": " ".join(words),
                    "vec": [float(x) for x in v],
                },
            )
            doc += 1
        svc.refresh()
    return svc


@pytest.fixture(scope="module")
def service():
    svc = make_service("mesh-parity")
    yield svc
    svc.close()


def hits_of(resp):
    return [(h["_id"], h["_score"]) for h in resp["hits"]["hits"]]


def mesh_vs_seq(svc, body):
    """(mesh response, sequential response); asserts the mesh actually
    served the first one."""
    mex = svc.mesh_executor()
    os.environ["ES_TPU_MESH"] = "force"
    try:
        routed0 = mex.stats["routed"]
        rm = svc.search(body)
        assert mex.stats["routed"] == routed0 + 1, "request not mesh-routed"
    finally:
        os.environ["ES_TPU_MESH"] = "off"
    rs = svc.search(body)
    return rm, rs


def assert_parity(rm, rs, totals=True):
    assert hits_of(rm) == hits_of(rs)  # ids, order, scores bit-for-bit
    assert rm["hits"]["max_score"] == rs["hits"]["max_score"]
    if totals:
        assert rm["hits"]["total"] == rs["hits"]["total"]
    assert rm["_shards"]["failed"] == 0
    assert rm["timed_out"] is False


TEXT_BODIES = [
    {"query": {"match": {"body": "alpha gamma"}}, "size": 10},
    {"query": {"match": {"body": {"query": "alpha beta",
                                  "operator": "and"}}}, "size": 10},
    {"query": {"match": {"body": {"query": "alpha beta gamma",
                                  "minimum_should_match": 2}}}, "size": 10},
    {"query": {"bool": {"must": [{"term": {"body": "alpha"}}],
                        "should": [{"match": {"title": "beta"}}]}},
     "size": 10},
    {"query": {"bool": {"should": [{"match": {"body": "gamma"}},
                                   {"match": {"title": "delta"}}]}},
     "size": 10},
    {"query": {"multi_match": {"query": "gamma delta",
                               "fields": ["title^2", "body"]}}, "size": 10},
    {"query": {"multi_match": {"query": "alpha epsilon",
                               "fields": ["title", "body"],
                               "type": "most_fields"}}, "size": 10},
]


class TestFloatExactParity:
    def test_match_bool_multimatch(self, service):
        for body in TEXT_BODIES:
            rm, rs = mesh_vs_seq(service, body)
            assert_parity(rm, rs)

    def test_bool_same_field_multi_clause(self, service):
        # must + multi-term should on ONE field: the tiny segments here
        # send the sequential bool through the generic per-clause
        # executor, whose f32 association order ((w0)+(w1+w2)) differs
        # from the flat-plan kernels' tile order (((w0+w1)+w2)) in the
        # last ulp — the same divergence the sequential path already
        # has between its fused (>=100k docs) and fallback segments.
        # Contract: identical ranking, scores within fp32 association.
        body = {
            "query": {
                "bool": {
                    "must": [{"term": {"body": "alpha"}}],
                    "should": [{"match": {"body": "beta gamma"}}],
                }
            },
            "size": 10,
        }
        rm, rs = mesh_vs_seq(service, body)
        assert [h[0] for h in hits_of(rm)] == [h[0] for h in hits_of(rs)]
        assert np.allclose(
            [h[1] for h in hits_of(rm)],
            [h[1] for h in hits_of(rs)],
            rtol=1e-5, atol=0.0,
        )
        assert rm["hits"]["total"] == rs["hits"]["total"]

    def test_knn(self, service):
        rng = np.random.default_rng(3)
        for k, nc in ((8, 20), (5, 7), (10, 200)):
            body = {
                "knn": {
                    "field": "vec",
                    "query_vector": [float(x) for x in rng.normal(size=DIMS)],
                    "k": k,
                    "num_candidates": nc,
                },
                "size": k,
            }
            rm, rs = mesh_vs_seq(service, body)
            assert_parity(rm, rs)

    def test_knn_size_beyond_k(self, service):
        # size > knn.k: the sequential path serves up to k hits PER
        # SHARD (k cut per shard, THEN the global size page), so the
        # page can hold up to k x n_shards hits — the mesh collect must
        # apply the same per-shard rank caps, not a global k cut
        rng = np.random.default_rng(4)
        body = {
            "knn": {
                "field": "vec",
                "query_vector": [float(x) for x in rng.normal(size=DIMS)],
                "k": 3,
                "num_candidates": 10,
            },
            "size": 20,
        }
        rm, rs = mesh_vs_seq(service, body)
        assert_parity(rm, rs)
        assert len(rm["hits"]["hits"]) > 3  # several shards contribute

    def test_pagination_and_source(self, service):
        body = {"query": {"match": {"body": "alpha gamma"}},
                "from": 5, "size": 7, "_source": False}
        rm, rs = mesh_vs_seq(service, body)
        assert_parity(rm, rs)
        assert all("_source" not in h for h in rm["hits"]["hits"])
        body2 = {"query": {"match": {"body": "alpha"}}, "size": 3,
                 "_source": ["title"]}
        rm, rs = mesh_vs_seq(service, body2)
        assert_parity(rm, rs)
        assert [h.get("_source") for h in rm["hits"]["hits"]] == [
            h.get("_source") for h in rs["hits"]["hits"]
        ]

    def test_track_total_hits_variants(self, service):
        for tth in (True, False, 5):
            body = {"query": {"match": {"body": "alpha"}},
                    "size": 5, "track_total_hits": tth}
            rm, rs = mesh_vs_seq(service, body)
            if tth is False:
                assert "total" not in rm["hits"]
                assert "total" not in rs["hits"]
                assert_parity(rm, rs, totals=False)
            elif tth == 5:
                # pruning may engage sequentially; both must agree on
                # the capped value and the hit page stays identical
                assert rm["hits"]["total"]["value"] == \
                    rs["hits"]["total"]["value"]
                assert hits_of(rm) == hits_of(rs)
            else:
                assert_parity(rm, rs)


class TestLayouts:
    def test_fold_more_entries_than_devices(self):
        # 4 shards x 3 refresh generations = 12 entries on 8 devices
        # → fold factor 2 with padded rows
        svc = make_service("mesh-fold", n_shards=4, batches=3,
                           per_batch=40, seed=5)
        try:
            os.environ["ES_TPU_MESH"] = "force"
            snap = svc.mesh_executor().ensure_snapshot()
            assert len(snap.entries) == 12
            assert snap.fold >= 2
            assert snap.e_pad >= len(snap.entries)
            for body in (TEXT_BODIES[0], TEXT_BODIES[3]):
                rm, rs = mesh_vs_seq(svc, body)
                assert_parity(rm, rs)
        finally:
            svc.close()

    def test_non_power_of_two_shards(self):
        svc = make_service("mesh-npot", n_shards=5, batches=1,
                           per_batch=75, seed=6)
        try:
            for body in (TEXT_BODIES[0], TEXT_BODIES[5]):
                rm, rs = mesh_vs_seq(svc, body)
                assert_parity(rm, rs)
        finally:
            svc.close()

    def test_data_axis_parity(self):
        # ES_TPU_MESH_DATA=2: the query batch shards over a 2-wide
        # ``data`` axis while shards take the remaining devices
        svc = make_service("mesh-data-axis", n_shards=3, batches=1,
                           per_batch=60, seed=11)
        old = os.environ.get("ES_TPU_MESH_DATA")
        os.environ["ES_TPU_MESH_DATA"] = "2"
        try:
            for body in (TEXT_BODIES[0], TEXT_BODIES[3]):
                rm, rs = mesh_vs_seq(svc, body)
                assert_parity(rm, rs)
        finally:
            if old is None:
                os.environ.pop("ES_TPU_MESH_DATA", None)
            else:
                os.environ["ES_TPU_MESH_DATA"] = old
            svc.close()

    def test_make_mesh_folding_api(self):
        import jax

        from elasticsearch_tpu.parallel import fold_factor, make_mesh

        devs = jax.devices()
        m5 = make_mesh(5, devices=devs)  # non-power-of-two axis
        assert m5.shape["shards"] == 5
        assert fold_factor(m5, 5) == 1
        m12 = make_mesh(12, devices=devs)  # fewer devices than shards
        assert m12.shape["shards"] == len(devs)
        assert fold_factor(m12, 12) == -(-12 // len(devs))
        m1 = make_mesh(12, devices=devs[:1])  # all folded on one device
        assert m1.shape["shards"] == 1
        assert fold_factor(m1, 12) == 12


class TestRoutingPredicate:
    def test_auto_mode_engages_multi_shard(self, service):
        os.environ.pop("ES_TPU_MESH", None)  # auto
        mex = service.mesh_executor()
        assert mex.available()
        routed0 = mex.stats["routed"]
        service.search({"query": {"match": {"body": "alpha"}}, "size": 3})
        assert mex.stats["routed"] == routed0 + 1

    def test_single_shard_stays_sequential(self):
        svc = make_service("mesh-1shard", n_shards=1, batches=1,
                           per_batch=30, seed=7)
        try:
            os.environ.pop("ES_TPU_MESH", None)  # auto
            assert not svc.mesh_executor().available()
            r = svc.search({"query": {"match": {"body": "alpha"}},
                            "size": 3})
            assert r["hits"]["hits"]
        finally:
            svc.close()

    def test_ineligible_bodies_fall_through(self, service):
        os.environ["ES_TPU_MESH"] = "force"
        mex = service.mesh_executor()
        routed0 = mex.stats["routed"]
        # aggs, sort, timeout, hybrid: all must take the shard path
        service.search({
            "query": {"match": {"body": "alpha"}}, "size": 0,
            "aggs": {"n": {"value_count": {"field": "title"}}},
        })
        service.search({"query": {"match": {"body": "alpha"}},
                        "sort": [{"_id": "asc"}], "size": 3})
        service.search({"query": {"match": {"body": "alpha"}},
                        "timeout": "10s", "size": 3})
        assert mex.stats["routed"] == routed0


class TestLifecycle:
    def test_generation_bump_rebuilds_snapshot(self):
        svc = make_service("mesh-gen", n_shards=3, batches=1,
                           per_batch=45, seed=8)
        try:
            os.environ["ES_TPU_MESH"] = "force"
            mex = svc.mesh_executor()
            r = svc.search({"query": {"match": {"body": "theta"}},
                            "size": 50})
            before_ids = {h["_id"] for h in r["hits"]["hits"]}
            rebuilds0 = mex.stats["rebuilds"]
            svc.index_doc("fresh-doc", {
                "title": "theta", "body": "theta theta theta",
                "vec": [0.0] * DIMS,
            })
            svc.refresh()
            r2 = svc.search({"query": {"match": {"body": "theta"}},
                             "size": 50})
            ids2 = {h["_id"] for h in r2["hits"]["hits"]}
            assert "fresh-doc" in ids2
            assert "fresh-doc" not in before_ids
            assert mex.stats["rebuilds"] == rebuilds0 + 1
        finally:
            svc.close()

    def test_hbm_budget_degrades_to_sequential(self, monkeypatch):
        svc = make_service("mesh-hbm", n_shards=3, batches=1,
                           per_batch=45, seed=9)
        try:
            from elasticsearch_tpu.common.memory import hbm_ledger

            os.environ["ES_TPU_MESH"] = "force"
            mex = svc.mesh_executor()
            monkeypatch.setattr(hbm_ledger, "budget", hbm_ledger.used + 1)
            degraded0 = hbm_ledger.stats_counters["degraded"]
            rm = svc.search({"query": {"match": {"body": "alpha"}},
                             "size": 10})
            assert mex.stats["fallbacks"] >= 1
            assert mex.stats["degraded"] >= 1
            assert mex.stats["routed"] == 0
            assert hbm_ledger.stats_counters["degraded"] > degraded0
            os.environ["ES_TPU_MESH"] = "off"
            rs = svc.search({"query": {"match": {"body": "alpha"}},
                             "size": 10})
            assert hits_of(rm) == hits_of(rs)
        finally:
            svc.close()

    def test_snapshot_release_returns_ledger_bytes(self):
        svc = make_service("mesh-ledger", n_shards=2, batches=1,
                           per_batch=30, seed=10)
        try:
            from elasticsearch_tpu.common.memory import hbm_ledger

            os.environ["ES_TPU_MESH"] = "force"
            base = hbm_ledger.stats()["by_category"].get("mesh", 0)
            svc.search({"query": {"match": {"body": "alpha"}}, "size": 5})
            charged = hbm_ledger.stats()["by_category"].get("mesh", 0)
            assert charged > base
            svc.mesh_executor().close()
            # every byte this index's snapshot charged comes back
            assert hbm_ledger.stats()["by_category"].get("mesh", 0) == base
        finally:
            svc.close()


class TestFaultInjection:
    def test_dispatch_fault_falls_back(self, service):
        from elasticsearch_tpu.common.faults import faults

        os.environ["ES_TPU_MESH"] = "off"
        body = {"query": {"match": {"body": "alpha gamma"}}, "size": 10}
        rs = service.search(body)
        os.environ["ES_TPU_MESH"] = "force"
        mex = service.mesh_executor()
        fb0 = mex.stats["fallbacks"]
        faults.configure({
            "seed": 0,
            "rules": [{"site": "batcher.dispatch", "match": {"mesh": 1},
                       "kind": "error", "prob": 1.0, "times": 1}],
        })
        rm = service.search(body)
        faults.clear()
        assert mex.stats["fallbacks"] == fb0 + 1
        assert hits_of(rm) == hits_of(rs)
        assert rm["_shards"]["failed"] == 0

    def test_collect_fault_falls_back(self, service):
        from elasticsearch_tpu.common.faults import faults

        os.environ["ES_TPU_MESH"] = "off"
        body = {
            "knn": {"field": "vec", "query_vector": [0.5] * DIMS,
                    "k": 6, "num_candidates": 20},
            "size": 6,
        }
        rs = service.search(body)
        os.environ["ES_TPU_MESH"] = "force"
        mex = service.mesh_executor()
        fb0 = mex.stats["fallbacks"]
        faults.configure({
            "seed": 0,
            "rules": [{"site": "batcher.collect", "match": {"mesh": 1},
                       "kind": "error", "prob": 1.0, "times": 1}],
        })
        rm = service.search(body)
        faults.clear()
        assert mex.stats["fallbacks"] == fb0 + 1
        assert hits_of(rm) == hits_of(rs)


class TestObservability:
    def test_nodes_stats_devices_and_mesh_block(self):
        from elasticsearch_tpu.cluster.service import ClusterService
        from elasticsearch_tpu.rest.actions import RestActions

        c = ClusterService()
        try:
            os.environ["ES_TPU_MESH"] = "force"
            c.create_index("meshstats", {
                "settings": {"number_of_shards": 2,
                             "search.backend": "jax"},
                "mappings": {"properties": {"body": {"type": "text"}}},
            })
            idx = c.indices["meshstats"]
            for i in range(24):
                idx.index_doc(str(i), {"body": f"alpha beta w{i % 5}"})
            idx.refresh()
            idx.search({"query": {"match": {"body": "alpha"}}, "size": 5})
            actions = RestActions(c)
            _, resp = actions.nodes_stats(None, {}, {})
            pipe = resp["nodes"]["node-0"]["pipeline"]
            assert pipe["mesh"]["routed"] >= 1
        finally:
            for svc in list(c.indices.values()):
                svc.close()

    def test_stats_snapshot_shape(self, service):
        snap = service.mesh_executor().stats_snapshot()
        assert {"routed", "launches", "jobs", "rebuilds", "degraded",
                "fallbacks", "entries", "devices"} <= set(snap)
