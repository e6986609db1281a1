"""Settings registry, cluster settings API, stats counters, profile."""

import pytest

from elasticsearch_tpu.cluster import ClusterError, ClusterService, IndexService
from elasticsearch_tpu.common.settings import (
    SettingsError,
    validate_index_settings,
)


class TestIndexSettingsRegistry:
    def test_unknown_setting_rejected(self):
        cs = ClusterService()
        with pytest.raises(ClusterError) as ei:
            cs.create_index("x", {"settings": {"index": {"bogus_setting": 1}}})
        assert "unknown setting" in ei.value.reason

    def test_typed_parsing_and_validation(self):
        with pytest.raises(SettingsError):
            validate_index_settings({"number_of_shards": 0}, creating=True)
        with pytest.raises(SettingsError):
            validate_index_settings({"number_of_shards": "abc"}, creating=True)
        with pytest.raises(SettingsError):
            validate_index_settings({"refresh_interval": "xyz"}, creating=True)
        out = validate_index_settings(
            {"number_of_shards": "3", "refresh_interval": "5s"}, creating=True
        )
        assert out == {"number_of_shards": 3, "refresh_interval": "5s"}

    def test_static_settings_not_updateable(self):
        cs = ClusterService()
        cs.create_index("idx")
        for key in ("number_of_shards", "search.backend"):
            with pytest.raises(ClusterError):
                cs.update_settings("idx", {"index": {key: "2"}})
        cs.update_settings("idx", {"index": {"number_of_replicas": 2}})
        assert cs.get_index("idx").settings["number_of_replicas"] == 2


class TestClusterSettings:
    def test_update_and_get(self):
        cs = ClusterService()
        out = cs.update_cluster_settings(
            {"persistent": {"search.max_buckets": 1000}}
        )
        assert out["persistent"]["search"]["max_buckets"] == 1000
        assert cs.cluster_settings.get("search.max_buckets") == 1000
        # transient overrides persistent
        cs.update_cluster_settings({"transient": {"search.max_buckets": 500}})
        assert cs.cluster_settings.get("search.max_buckets") == 500
        # null removes
        cs.update_cluster_settings({"transient": {"search.max_buckets": None}})
        assert cs.cluster_settings.get("search.max_buckets") == 1000

    def test_unknown_cluster_setting(self):
        cs = ClusterService()
        with pytest.raises(ClusterError):
            cs.update_cluster_settings({"persistent": {"nope.nope": 1}})

    def test_auto_create_index_disabled(self):
        cs = ClusterService()
        cs.update_cluster_settings(
            {"persistent": {"action.auto_create_index": False}}
        )
        with pytest.raises(ClusterError):
            cs.get_or_autocreate("newidx")
        cs.update_cluster_settings(
            {"persistent": {"action.auto_create_index": True}}
        )
        assert cs.get_or_autocreate("newidx") is not None


class TestStatsAndProfile:
    def test_stats_counters(self):
        idx = IndexService("st", settings={"number_of_shards": 2})
        for i in range(10):
            idx.index_doc(str(i), {"a": i})
        idx.delete_doc("3")
        idx.refresh()
        idx.search({"query": {"match_all": {}}})
        idx.search({"query": {"match_all": {}}})
        st = idx.stats()["primaries"]
        assert st["indexing"]["index_total"] == 10
        assert st["indexing"]["delete_total"] == 1
        assert st["search"]["query_total"] == 2
        assert st["refresh"]["total"] >= 1
        assert st["docs"]["count"] == 9

    def test_profile_response_shape(self):
        # numpy pins the per-shard coordinator path: profiled requests
        # ride the SAME route as unprofiled ones, so on the forced
        # 8-device platform a 2-shard jax search would take the SPMD
        # mesh and report the fused launch instead of per-shard trees
        # (that branch is covered in tests/test_profile.py)
        idx = IndexService("pf", settings={
            "number_of_shards": 2, "search.backend": "numpy",
        })
        idx.index_doc("1", {"body": "hello profile"})
        idx.refresh()
        r = idx.search(
            {"query": {"match": {"body": "hello"}}, "profile": True}
        )
        shards = r["profile"]["shards"]
        assert len(shards) == 2
        q = shards[0]["searches"][0]["query"][0]
        assert q["type"] == "MatchQuery"
        assert q["time_in_nanos"] >= 0
        assert "collector" in shards[0]["searches"][0]
