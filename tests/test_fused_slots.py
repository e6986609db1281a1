"""The fused text kernel's dense hot-term slot budget (scoring.FUSED_H).

Contract under test: a `match` whose hot terms fit the budget scores in
the fused kernel (counted in `fused_jobs`, its `dispatch` span tagged
`overflow: False`); one hot term more sends it to the chunked block-max
path (counted in `fused_overflow_jobs`); either way ids, order, scores
and totals are those of the chunked path and of the NumPy oracle; and
`pipeline.batching.fused_hot_slots` counts the slots fused jobs used.
"""

import json

import numpy as np
import pytest

from elasticsearch_tpu.cluster.indices import IndexService
from elasticsearch_tpu.common import tracing
from elasticsearch_tpu.ops import scoring
from elasticsearch_tpu.search import executor_jax

H = scoring.FUSED_H
N_DOCS = 1600  # terms are hot from max(1024, n // 128) postings up
HOT = [f"hot{i:02d}" for i in range(H + 1)]  # one more than the budget
RARE = [f"rare{i:02d}" for i in range(40)]


def make_service(name: str, backend: str) -> IndexService:
    """Every HOT word is in ~85% of the documents (a dense row each on
    the jax backend), every RARE word in ~6% (sparse tiles)."""
    rng = np.random.default_rng(11)
    svc = IndexService(
        name,
        settings={"number_of_shards": 1, "search.backend": backend},
        mappings_json={"properties": {"body": {"type": "text"}}},
    )
    for i in range(N_DOCS):
        words = []
        for w in HOT:
            if rng.random() < 0.85:
                words += [w] * int(rng.integers(1, 4))
        words += list(rng.choice(RARE, int(rng.integers(1, 5))))
        rng.shuffle(words)
        svc.index_doc(str(i), {"body": " ".join(words)})
    svc.refresh()
    return svc


@pytest.fixture(scope="module")
def service():
    """One segment on the jax backend, the fused kernel forced on (it
    is normally gated to large segments)."""
    orig = executor_jax.FUSED_MIN_DOCS
    executor_jax.FUSED_MIN_DOCS = 10
    svc = make_service("fused-slots", "jax")
    yield svc
    svc.close()
    executor_jax.FUSED_MIN_DOCS = orig


@pytest.fixture(scope="module")
def oracle():
    svc = make_service("fused-slots-oracle", "numpy")
    yield svc
    svc.close()


def body_with(n_hot: int) -> dict:
    """A match of `n_hot` hot terms and two rare ones; exact totals, so
    the chunked path runs with pruning off."""
    return {
        "query": {"match": {"body": " ".join(HOT[:n_hot] + RARE[3:5])}},
        "size": 10, "track_total_hits": True,
    }


def search(svc, body: dict) -> dict:
    return svc.search(json.loads(json.dumps(body)))


def page(resp: dict):
    hits = resp["hits"]["hits"]
    return ([h["_id"] for h in hits], [h["_score"] for h in hits],
            resp["hits"]["total"])


def dispatch_tags(svc, body: dict) -> dict:
    tracing.clear()
    handle = tracing.begin("search", index=svc.name)
    search(svc, body)
    tracing.end(handle)
    spans = tracing.recent(1)[0]["spans"]
    tracing.clear()
    return next(s["tags"] for s in spans if s["name"] == "dispatch")


def test_the_index_really_has_hot_rows(service):
    ex = service._executor(service.shards[0])
    fs = ex.fused_scorer_mf(0, ("body",))
    assert fs is not None and fs.n_hot_slots == H
    assert fs.parts[0]["dense"].shape == (len(HOT), N_DOCS)
    assert fs.plan_shape_rows(1) == (1, 2 * scoring.FUSED_T_RARE + 2 * H + 1)


@pytest.mark.parametrize("n_hot", [0, 4, 5, H, H + 1])
def test_hot_terms_up_to_the_budget_score_in_the_fused_kernel(
    service, oracle, monkeypatch, n_hot
):
    body = body_with(n_hot)
    stats = service._batcher.stats
    before = dict(stats)
    served = search(service, body)
    fused = stats["fused_jobs"] - before["fused_jobs"]
    overflowed = stats["fused_overflow_jobs"] - before["fused_overflow_jobs"]
    tags = dispatch_tags(service, body)
    if n_hot <= H:
        assert (fused, overflowed) == (1, 0)
        assert tags["overflow"] is False
        assert tags["launches"] == 1
    else:
        assert (fused, overflowed) == (0, 1)
        assert tags["overflow"] is True
    assert_same_as_chunked_and_oracle(service, oracle, monkeypatch, body,
                                      served)


def assert_same_as_chunked_and_oracle(service, oracle, monkeypatch, body,
                                      served):
    """ids, order, scores (rtol 1e-6) and totals of `served` against the
    chunked path alone (no fused scorer for the segment) and the oracle."""
    ex = service._executor(service.shards[0])
    monkeypatch.setattr(ex, "fused_scorer_mf", lambda si, fields: None)
    stats = service._batcher.stats
    before = stats["fused_jobs"]
    chunked = search(service, body)
    assert stats["fused_jobs"] == before
    ids, scores, total = page(served)
    assert len(ids) == 10
    for name, other in (("chunked", chunked), ("oracle", search(oracle, body))):
        o_ids, o_scores, o_total = page(other)
        assert ids == o_ids, name
        np.testing.assert_allclose(scores, o_scores, rtol=1e-6, atol=0.0,
                                   err_msg=name)
        assert total == o_total, name
    assert total["relation"] == "eq"


@pytest.mark.parametrize("match", [
    {"query": " ".join(HOT[:6] + RARE[3:4]), "operator": "and"},
    {"query": " ".join(HOT[2:H] + RARE[3:6]),
     "minimum_should_match": H - 1},
], ids=["and_of_six_hot_and_one_rare", "most_of_ten_hot_and_three_rare"])
def test_the_count_plane_takes_hot_rows_too(service, oracle, monkeypatch,
                                            match):
    """`operator: and` / `minimum_should_match` mask by a per-document
    count of matching terms, which the hot rows add to as the tiles do."""
    body = {"query": {"match": {"body": match}}, "size": 10,
            "track_total_hits": True}
    before = service._batcher.stats["fused_jobs"]
    served = search(service, body)
    assert service._batcher.stats["fused_jobs"] == before + 1
    assert 10 <= served["hits"]["total"]["value"] < N_DOCS
    assert_same_as_chunked_and_oracle(service, oracle, monkeypatch, body,
                                      served)


def test_fused_hot_slots_counts_the_slots_fused_jobs_used(service):
    b = service._batcher
    before = b.batching_stats()["fused_hot_slots"]
    assert sorted(before, key=int) == [str(h) for h in range(H + 1)]
    jobs_before = b.stats["fused_jobs"]
    sent = [0, 1, 4, 5, 5, H, H + 1]  # the last one overflows: not counted
    for n_hot in sent:
        search(service, body_with(n_hot))
    after = b.batching_stats()["fused_hot_slots"]
    delta = {int(h): after[h] - before[h] for h in after}
    expected = {h: sent.count(h) for h in range(H + 1)}
    assert delta == expected
    assert sum(delta.values()) == b.stats["fused_jobs"] - jobs_before == 6


def test_nodes_stats_reports_the_histogram(service):
    from elasticsearch_tpu.cluster.service import ClusterService
    from elasticsearch_tpu.rest.actions import RestActions

    c = ClusterService()
    try:
        c.indices[service.name] = service
        search(service, body_with(3))
        _, resp = RestActions(c).nodes_stats(None, {}, {})
        node = next(iter(resp["nodes"].values()))
        hist = node["pipeline"]["batching"]["fused_hot_slots"]
        assert hist == service._batcher.batching_stats()["fused_hot_slots"]
        assert hist["3"] >= 1
    finally:
        c.indices.pop(service.name, None)  # the fixture closes it
        c.close()


def unrolled_hot_rows(acc, cnt, dense, inv_norm, hot_ids, hot_w, signed):
    """The reference: every slot of the budget in turn, used or not (the
    kernel's form before the loop over the slots in use)."""
    import jax.numpy as jnp

    n = acc.shape[1]
    for h in range(hot_ids.shape[1]):
        hid, w = hot_ids[:, h], hot_w[:, h]
        ok = hid >= 0
        row_tf = dense[jnp.clip(hid, 0, dense.shape[0] - 1)].astype(
            jnp.float32)
        wa = jnp.where(ok, jnp.abs(w) if signed else w, 0.0)[:, None]
        contrib = wa - wa / (jnp.float32(1.0) + row_tf * inv_norm[None, :])
        match = (row_tf > 0) & ok[:, None]
        acc = acc + jnp.where(match, contrib, 0.0)
        if cnt is not None:
            counted = match & (w > 0)[:, None] if signed else match
            cnt = cnt.at[:, :n].add(counted.astype(jnp.int32))
    return acc, cnt


@pytest.mark.parametrize("signed,count_width", [
    (False, None), (False, 0), (True, 1),
], ids=["match_no_count", "match_count", "serve_signed_count"])
def test_the_loop_over_used_slots_adds_what_the_unrolled_pass_adds(
    signed, count_width
):
    """Rows of one launch using 0, 3, all and 7 slots: bit-equal sums and
    counts (a padded slot added 0.0)."""
    import jax.numpy as jnp

    rng = np.random.default_rng(3)
    n, n_hot, used = 300, 20, [0, 3, H, 7]
    dense = jnp.asarray(rng.integers(0, 4, (n_hot, n)).astype(np.uint8))
    inv_norm = jnp.asarray(rng.uniform(0.5, 2.0, n).astype(np.float32))
    hot_ids = np.full((len(used), H), -1, np.int32)
    hot_w = np.zeros((len(used), H), np.float32)
    for b, u in enumerate(used):
        hot_ids[b, :u] = rng.choice(n_hot, u, replace=False)
        hot_w[b, :u] = rng.uniform(0.1, 3.0, u)
        if signed:
            hot_w[b, :u] *= rng.choice([-1.0, 1.0], u)
    acc = jnp.asarray(rng.uniform(0, 1, (len(used), n)).astype(np.float32))
    cnt = None if count_width is None else jnp.asarray(
        rng.integers(0, 3, (len(used), n + count_width)).astype(np.int32))
    args = (dense, inv_norm, jnp.asarray(hot_ids), jnp.asarray(hot_w), signed)
    got_acc, got_cnt = scoring._add_hot_rows(acc, cnt, *args)
    ref_acc, ref_cnt = unrolled_hot_rows(acc, cnt, *args)
    np.testing.assert_array_equal(np.asarray(got_acc), np.asarray(ref_acc))
    assert (got_cnt is None) == (ref_cnt is None)
    if cnt is not None:
        np.testing.assert_array_equal(np.asarray(got_cnt),
                                      np.asarray(ref_cnt))
    assert not np.array_equal(np.asarray(got_acc)[1:], np.asarray(acc)[1:])
    np.testing.assert_array_equal(np.asarray(got_acc)[0], np.asarray(acc)[0])
