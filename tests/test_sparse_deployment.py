"""The learned-sparse deployment (big-ann-benchmarks' sparse track;
`benchmarks/configs/msmarco-splade-sparse.json`) at a small size:
SPLADE-shaped weighted-token queries over a few tens of thousands of
seeded passages of the benchmark's own corpus builder
(`corpora/splade_impacts.py`), served over HTTP through the batcher's
`sparse` family and held to the benchmark's own plain reference
(`references/impact_sum.py`) by the benchmark's own rule
(`benchmarks/compare.py`, `exact`: ids tie group by tie group, scores
within 1e-5, `hits.total` equal), under both storages of the impacts
(the shipped int8 twin, and `index.sparse.quantization: none`).

48,000 passages: the most frequent term is in 25% of them, 12,000, so a
total past `track_total_hits`'s 10,000 can be proved by one term and a
job may drop tiles; the full 49-token queries match ~33,000 passages.
"""

import http.client
import json
import os
import sys

import numpy as np
import pytest

from elasticsearch_tpu.common import tracing

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from compare import compare_all, compare_one, reference_body  # noqa: E402
from plugins import load_json, load_plugin  # noqa: E402
from run import place_segment  # noqa: E402

DOCS, SEED, N_BODIES = 48_000, 5, 24
STORAGES = ("int8", "float32")


def post(port: int, path: str, body: dict, method: str = "POST") -> dict:
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=300)
    try:
        conn.request(method, path, body=json.dumps(body).encode(),
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        payload = resp.read()
        assert resp.status == 200, (resp.status, payload[:400])
        return json.loads(payload)
    finally:
        conn.close()


def get(port: int, path: str) -> dict:
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=300)
    try:
        conn.request("GET", path)
        return json.loads(conn.getresponse().read())
    finally:
        conn.close()


def vector_body(field: str, vector: dict, **extra) -> dict:
    return {"query": {"sparse_vector": {"field": field,
                                        "query_vector": vector}},
            "size": 10, "_source": False, **extra}


class Deployment:
    """One server holding the corpus under both storages, the plain
    reference of each, and the bodies the cases pick from."""

    def __init__(self):
        from elasticsearch_tpu.rest.server import ElasticsearchTpuServer

        base = load_json("configs", "msmarco-splade-sparse.json")
        corpus = load_plugin("corpora", base["corpus"]["builder"]).build(
            base, SEED, DOCS)
        self.corpus = corpus
        self.field = corpus["body_context"]["field"]
        self.configs, self.refs, self.index = {}, {}, {}
        self.server = ElasticsearchTpuServer(port=0)
        self.server.start_background()
        self.port = self.server.port
        for storage in STORAGES:
            config = json.loads(json.dumps(base))
            settings = dict(config["settings"])
            if storage == "float32":
                settings["index.sparse.quantization"] = "none"
                config["guarantees"]["stored"] = "float32"
            else:
                # the deployment's own file: the node's shipped storage
                assert "index.sparse.quantization" not in settings
                assert config["guarantees"]["stored"] == "int8"
            index = f"{config['index']}-{storage}"
            post(self.port, f"/{index}", {"settings": settings,
                                          "mappings": corpus["mappings"]},
                 "PUT")
            place_segment(self.server.cluster.indices[index],
                          corpus["segment"])
            self.configs[storage], self.index[storage] = config, index
            self.refs[storage] = load_plugin(
                "references", config["reference"]).Reference(
                    corpus["reference"], config)
        gen = load_plugin("bodies", base["body"]["generator"])
        ctx = corpus["body_context"]
        self.bodies = [json.loads(b) for b in gen.make(
            ctx, base["body"]["args"], np.random.default_rng([37, 9]),
            N_BODIES)]
        df = np.asarray(ctx["term_df"])
        width = ctx["term_width"]
        self.df_of = {f"t{int(t):0{width}d}": int(d)
                      for t, d in zip(ctx["terms"], df)}
        by_df = sorted(self.df_of, key=lambda t: -self.df_of[t])
        self.frequent = by_df  # most frequent first
        corpus_mod = load_plugin("corpora", base["corpus"]["builder"])
        rng = np.random.default_rng(11)
        # a query of the 128 most frequent tokens: several trips of
        # TILE_STEP tiles at one row
        w = corpus_mod.draw_weights(
            rng, np.full(128, 0.6), base["body"]["args"]["weights"])
        self.long_body = vector_body(self.field, {
            t: round(float(x), 4) for t, x in zip(by_df[:128], w)})
        rare = [t for t in by_df if 40 <= self.df_of[t] <= 400][:3]
        # the most frequent token beside a light, rare one: the first's
        # tail tiles cannot reach the top ten, and the tiles that are
        # kept hold far fewer than 10,000 passages
        self.pruning_vector = {by_df[0]: 2.0, rare[0]: 0.05}
        # the int8 column serves `by_df[0]` from its dense row, which
        # never drops: there the tiles that drop are a heavy token's
        # just under the row threshold (eight tiles), beside the most
        # frequent one at a weight that only proves the total
        self.cold = [t for t in by_df if self.df_of[t] < 1024]
        self.pruning_vectors = {
            "float32": self.pruning_vector,
            "int8": {by_df[0]: 0.01, self.cold[0]: 2.0},
        }
        # tokens that all hold a row in the int8 column: no tile launch
        self.hot_body = vector_body(
            self.field, {t: 0.5 + 0.125 * i for i, t in enumerate(by_df[:5])})
        # a full-shape query that holds the most frequent token, as
        # nearly every query of the deployment's size holds one whose
        # postings alone pass 10,000: phase A and theta run
        full = dict(self.bodies[7]["query"]["sparse_vector"]["query_vector"])
        full.setdefault(by_df[0], 0.5)
        self.proved_body = vector_body(self.field, full)
        # three rare tokens: a total far below 10,000
        self.rare_body = vector_body(
            self.field, {t: 1.0 + 0.25 * i for i, t in enumerate(rare)})

    def search(self, storage: str, body: dict) -> dict:
        return post(self.port, f"/{self.index[storage]}/_search", body)

    def held(self, storage: str, body: dict, served: dict) -> dict:
        g = self.configs[storage]["guarantees"]
        (expected,) = self.refs[storage].answer_many(
            [reference_body(g["rule"], body)])
        got = compare_one(g["rule"], g["score_rtol"], body, served, expected)
        assert got["page_ok"], got["why"]
        assert got["total_ok"], (served["hits"]["total"],
                                 expected["hits"]["total"])
        assert got["score_rel"] <= g["score_rtol"], got["score_rel"]
        return expected

    def sparse_stats(self) -> dict:
        node = next(iter(get(self.port, "/_nodes/stats")["nodes"].values()))
        return node["sparse"]

    def last_trace(self) -> dict:
        return get(self.port, "/_internal/traces?n=1")["traces"][-1]


@pytest.fixture(scope="module")
def dep():
    d = Deployment()
    yield d
    d.server.close()


@pytest.mark.parametrize("storage", STORAGES)
@pytest.mark.parametrize("i", range(6))
def test_full_shape_query_over_http_is_the_plain_references(dep, storage, i):
    body = dep.bodies[i]
    assert 8 <= len(body["query"]["sparse_vector"]["query_vector"]) <= 128
    expected = dep.held(storage, body, dep.search(storage, body))
    # ~49 tokens drawn by posting mass match most of the shard
    assert expected["hits"]["total"] == {"value": 10_000, "relation": "gte"}


@pytest.mark.parametrize("storage", STORAGES)
def test_total_below_the_threshold_is_exact(dep, storage):
    before = dep.sparse_stats()
    served = dep.search(storage, dep.rare_body)
    expected = dep.held(storage, dep.rare_body, served)
    total = served["hits"]["total"]
    assert total["relation"] == "eq" and 10 < total["value"] < 10_000
    assert total == expected["hits"]["total"]
    # nothing proves a total past the cap: no tile may be dropped
    after = dep.sparse_stats()
    assert after["tiles_pruned"] == before["tiles_pruned"]
    assert after["theta_host"] == before["theta_host"]


@pytest.mark.parametrize("storage", STORAGES)
def test_pruned_job_answers_elasticsearchs_total(dep, storage):
    """One token alone is in more passages than `track_total_hits`
    counts to, so tiles drop; the page and the total stay the
    reference's: 10,000 and `gte`, not the count over the kept tiles."""
    body = vector_body(dep.field, dep.pruning_vectors[storage])
    assert dep.df_of[dep.frequent[0]] > 10_000
    before = dep.sparse_stats()
    served = dep.search(storage, body)
    after = dep.sparse_stats()
    assert after["tiles_pruned"] > before["tiles_pruned"]
    assert after["pruned_searches"] == before["pruned_searches"] + 1
    assert after["theta_host"] == before["theta_host"] + 1
    kept = 128 * (after["tiles_scored"] - before["tiles_scored"])
    assert kept < 10_000  # a count over the kept tiles would fall short
    # the int8 column's most frequent token is counted whole by its row
    assert (after["dense_rows_scored"] - before["dense_rows_scored"]
            == (storage == "int8"))
    assert served["hits"]["total"] == {"value": 10_000, "relation": "gte"}
    dep.held(storage, body, served)


@pytest.mark.parametrize("storage", STORAGES)
def test_exact_total_is_counted_over_every_tile(dep, storage):
    """`track_total_hits: true`: no tile may be dropped, the same page,
    the count of passages that hold either token."""
    body = vector_body(dep.field, dep.pruning_vector, track_total_hits=True)
    before = dep.sparse_stats()
    served = dep.search(storage, body)
    after = dep.sparse_stats()
    assert after["tiles_pruned"] == before["tiles_pruned"]
    ref, data = dep.refs[storage], dep.corpus["reference"]
    holders = set()
    for token in dep.pruning_vector:
        t = ref.term_of[int(token[1:])]
        lo, hi = data["post_start"][t], data["post_start"][t + 1]
        holders.update(data["post_doc"][lo:hi].tolist())
    assert served["hits"]["total"] == {"value": len(holders),
                                       "relation": "eq"}
    default = dict(body)
    del default["track_total_hits"]
    pruned = dep.search(storage, default)
    assert ([h["_id"] for h in served["hits"]["hits"]]
            == [h["_id"] for h in pruned["hits"]["hits"]])


@pytest.mark.parametrize("storage", STORAGES)
def test_query_of_several_trips_stays_right(dep, storage):
    """128 frequent tokens: more tiles than one trip of the looped
    program carries at one row. The int8 column (its hot terms on rows)
    keeps under TILE_CAP tiles and scores them in ONE launch; the
    float32 column's row is longer than TILE_CAP and takes a second."""
    from elasticsearch_tpu.ops.impact import TILE_CAP, TILE_STEP, tile_trips

    before = dep.sparse_stats()
    served = dep.search(storage, dep.long_body)
    after = dep.sparse_stats()
    tiles = after["tiles_scored"] - before["tiles_scored"]
    launches = after["chunk_launches"] - before["chunk_launches"]
    trips = after["tile_trips"] - before["tile_trips"]
    assert tiles > 3 * TILE_STEP
    assert (tiles > TILE_CAP) == (storage == "float32")
    assert launches == -(-tiles // TILE_CAP) == 1 + (storage == "float32")
    assert trips == tile_trips([range(tiles)]) >= 4
    dep.held(storage, dep.long_body, served)


def test_no_launch_is_handed_a_plan_that_is_written_again(dep, monkeypatch):
    """A jitted call may read a host operand after it returns (the CPU
    backend aliases an aligned NumPy buffer), so `score_into` gives
    every launch a plan of its own: when the last launch is enqueued,
    each launch's plan still holds that launch's own tiles."""
    from elasticsearch_tpu.ops import impact as impact_ops

    cap = impact_ops.TILE_CAP
    sf = dep.corpus["segment"].sparse[dep.field]
    sc = impact_ops.ImpactScorer(sf.doc_ids, sf.qweights, DOCS)
    vector = dep.long_body["query"]["sparse_vector"]["query_vector"]
    _tids, tws, _bws, starts, counts = impact_ops.impact_tile_lists(
        sf, list(vector), list(vector.values()), True)
    tiles = np.concatenate([np.arange(s, s + c) for s, c in
                            zip(starts, counts)])
    weights = np.repeat(tws, counts)
    # the query four times over: a row past two launches' capacity
    reps = -(-(2 * cap + 1) // len(tiles))
    tiles, weights = np.tile(tiles, reps), np.tile(weights, reps)
    handed = []
    launch = impact_ops._impact_chunk_add

    def recording(doc_ids, values, acc, cnt, plan):
        handed.append(plan)
        return launch(doc_ids, values, acc, cnt, plan)

    monkeypatch.setattr(impact_ops, "_impact_chunk_add", recording)
    sc.score_into(*sc.new_acc(1), [tiles], [weights])
    assert len(handed) == impact_ops.chunk_launches([tiles]) >= 3
    assert not any(np.shares_memory(a, b) for i, a in enumerate(handed)
                   for b in handed[i + 1:])
    for c, plan in enumerate(handed):
        want = tiles[c * cap:(c + 1) * cap]
        m = len(want)
        assert plan.shape == (2, 1, cap) and plan.dtype == np.int32
        assert (plan[0, 0, :m] == want).all()
        assert (plan[0, 0, m:] == -1).all()
        assert (plan[1, 0, :m].view(np.float32)
                == weights[c * cap:c * cap + m]).all()


@pytest.mark.parametrize("storage", STORAGES)
def test_bf16_control_fails(dep, storage):
    """The reference one precision down, put in the program's place,
    is caught by pages or scores; the reference itself passes."""
    g = dep.configs[storage]["guarantees"]
    ref, bodies = dep.refs[storage], dep.bodies
    refs = ref.answer_many([reference_body(g["rule"], b) for b in bodies])
    same = compare_all(g, bodies, ref.answer_many(bodies), refs)
    assert same["correct"], same
    low = compare_all(g, bodies, ref.answer_many(bodies, precision="lower"),
                      refs)
    assert not low["correct"]
    assert (low["numbers"]["page_mismatches"][0] > 0
            or low["numbers"]["score_rel_max"][0] > g["score_rtol"])
    assert low["numbers"]["score_rel_max"][0] > 10 * g["score_rtol"]


def test_stored_precision_differs_between_the_storages(dep):
    """The int8 answers are the int8 reference's, not the float32
    one's: the check is tight on the stated storage."""
    g = dep.configs["int8"]["guarantees"]
    bodies = dep.bodies
    served = [dep.search("int8", b) for b in bodies]
    refs = dep.refs["float32"].answer_many(
        [reference_body(g["rule"], b) for b in bodies])
    cross = compare_all(g, bodies, served, refs)
    assert not cross["correct"]
    assert cross["numbers"]["score_rel_max"][0] > g["score_rtol"]


KB = 16  # the top-k bucket of a ten-hit page


def theta_case(dep, storage, vector, live):
    """(host theta, what the old device program `_threshold` returned
    for the same first tiles, the `KB`-th best score of the device's
    pass over EVERY tile, the plan) of one query under one live mask."""
    import jax.numpy as jnp

    from elasticsearch_tpu.ops import impact as impact_ops
    from elasticsearch_tpu.ops.scoring import _threshold

    sf = dep.corpus["segment"].sparse[dep.field]
    quantized = storage == "int8"
    values = sf.qweights if quantized else sf.weights
    tids, tws, bws, starts, counts = impact_ops.impact_tile_lists(
        sf, list(vector), list(vector.values()), quantized)
    bm = impact_ops.SparseBlockMax(
        sf.term_tile_start, sf.term_tile_count,
        sf.tile_qmax if quantized else sf.tile_max, tids, tws, bws)
    host = bm.host_theta(sf.doc_ids, values, live, KB)
    sc = impact_ops.ImpactScorer(sf.doc_ids, values, DOCS, live)
    acc, _cnt = sc.score_into(*sc.new_acc(1), [bm.starts], [bm.tws])
    twin = float(_threshold(
        acc, None if live is None else jnp.asarray(live), k=KB,
        block_size=4096)[0][0])
    tiles = np.concatenate([np.arange(s, s + c) for s, c in
                            zip(starts, counts)])
    scores, _docs, _tot = sc.finalize(
        *sc.score_into(*sc.new_acc(1), [tiles], [np.repeat(tws, counts)]),
        KB)
    return host, twin, float(scores[0][KB - 1]), bm


def first_tile_sums(dep, storage, bm):
    """doc -> float64 sum of the first tiles' products, all docs live."""
    sf = dep.corpus["segment"].sparse[dep.field]
    values = sf.qweights if storage == "int8" else sf.weights
    sums = np.zeros(DOCS)
    d = sf.doc_ids[bm.starts]
    p = bm.tws[:, None] * values[bm.starts].astype(np.float32)
    np.add.at(sums, d[d >= 0], p[d >= 0].astype(np.float64))
    return sums


@pytest.mark.parametrize("storage", STORAGES)
@pytest.mark.parametrize("n_tokens", [2, 3, 5, 8, 16, 33, 49, 80, 128])
def test_host_theta_is_a_sound_bound_within_its_margin(dep, storage,
                                                       n_tokens):
    """Seeded queries drawn by posting mass, all docs live and with the
    first tiles' best docs deleted: the host's theta never passes the
    device's final `KB`-th best score (nothing of the page can drop),
    never passes the old device threshold of the same first tiles, and
    stays within its stated margin of it; deleted docs lower it."""
    rng = np.random.default_rng([38, n_tokens])
    tokens = np.asarray(dep.frequent)
    mass = np.asarray([dep.df_of[t] for t in tokens], np.float64)
    for _ in range(3):
        picked = rng.choice(tokens, n_tokens, replace=False,
                            p=mass / mass.sum())
        vector = {str(t): float(w) for t, w in
                  zip(picked, rng.uniform(0.02, 3.0, n_tokens))}
        host, twin, final, bm = theta_case(dep, storage, vector, None)
        assert np.isfinite(twin) and host <= twin <= final
        margin = (n_tokens + 2) * 2.0 ** -22
        assert host >= twin * (1.0 - margin)
        best = np.argsort(-first_tile_sums(dep, storage, bm))[:2 * KB]
        live = np.ones(DOCS, bool)
        live[best] = False
        host_d, twin_d, final_d, _ = theta_case(dep, storage, vector, live)
        assert host_d <= twin_d <= final_d and host_d < host
        assert host_d == -np.inf or host_d >= twin_d * (1.0 - margin)


@pytest.mark.parametrize("storage", STORAGES)
def test_host_theta_needs_a_full_page_of_live_matches(dep, storage):
    """Fewer than `KB` live docs in the first tiles: `-inf`, as the
    masked top-k of the old program read; exactly `KB`: finite."""
    token = next(t for t in dep.frequent if 2 * KB <= dep.df_of[t] <= 128)
    vector = {token: 1.5}
    _host, _twin, _final, bm = theta_case(dep, storage, vector, None)
    holders = np.flatnonzero(first_tile_sums(dep, storage, bm) > 0)
    assert len(holders) == dep.df_of[token]
    for left, finite in ((KB - 1, False), (KB, True)):
        live = np.ones(DOCS, bool)
        live[holders[left:]] = False
        host, twin, final, _ = theta_case(dep, storage, vector, live)
        assert np.isfinite(host) == np.isfinite(twin) == finite
        assert host <= twin <= final or not finite


@pytest.mark.parametrize("which", ["full_shape", "long", "pruning", "rare",
                                   "hot"])
def test_every_transfer_of_a_sparse_job_is_counted(dep, which):
    """`transfer.scoring.*` moves by exactly what the job moved: the hot
    list's two planes where the query holds a term with a dense row,
    the one staged plan of the tile pass's launch, and the packed
    collect, the job's one download (theta is the host's)."""
    from elasticsearch_tpu.ops.impact import DENSE_SLOTS, TILE_CAP, TILE_STEP

    body = {"full_shape": dep.proved_body, "long": dep.long_body,
            "pruning": vector_body(dep.field, dep.pruning_vectors["int8"]),
            "rare": dep.rare_body, "hot": dep.hot_body}[which]
    dep.search("int8", body)  # every program built
    s0, x0 = dep.sparse_stats(), tracing.transfer_stats()
    dep.search("int8", body)
    s1, x1 = dep.sparse_stats(), tracing.transfer_stats()
    spans = {s["name"]: s for s in dep.last_trace()["spans"]}
    rows = spans["dispatch"]["tags"]["rows"]
    tiles = s1["tiles_scored"] - s0["tiles_scored"]
    launches = s1["chunk_launches"] - s0["chunk_launches"]
    dense = s1["dense_launches"] - s0["dense_launches"]
    assert launches == -(-tiles // TILE_CAP) == (which != "hot")
    assert (s1["tile_trips"] - s0["tile_trips"] == -(-tiles // TILE_STEP)
            == spans["dispatch"]["tags"]["trips"])
    assert dense == (which != "rare")
    # theta is computed where a total is proved AND some tile could drop
    assert s1["theta_host"] - s0["theta_host"] == (which not in ("rare",
                                                                 "hot"))
    assert x1["h2d_count"] - x0["h2d_count"] == launches + 2 * dense
    assert (x1["h2d_bytes"] - x0["h2d_bytes"]
            == launches * rows * TILE_CAP * (4 + 4)
            + dense * rows * DENSE_SLOTS * (4 + 4))
    assert x1["d2h_count"] - x0["d2h_count"] == 1
    assert (x1["d2h_bytes"] - x0["d2h_bytes"]
            == spans["collect"]["tags"]["d2h_bytes"])


@pytest.mark.parametrize("rows", [1, 4])
@pytest.mark.parametrize("tiles", [0, 1, 512, 513, 1300])
def test_staged_plans_are_noted_as_the_launches_upload_them(dep, rows,
                                                            tiles):
    """The plans of ALL a scoring's chunk launches are staged at once;
    what is noted stays what each launch uploads: one transfer of
    TILE_CAP x (4 + 4) B a query row a launch (tile ids, weight bits),
    and the row launch's two planes, 512 B a query row."""
    from elasticsearch_tpu.ops import impact as impact_ops

    cap = impact_ops.TILE_CAP
    sf = dep.corpus["segment"].sparse[dep.field]
    sc = impact_ops.ImpactScorer(sf.doc_ids, sf.qweights, DOCS)
    tl = np.arange(tiles, dtype=np.int64) % sf.n_tiles
    lists = [tl[: tiles // (j + 1)] for j in range(rows)]
    weights = [np.full(len(t), 0.5, np.float32) for t in lists]
    launches = -(-tiles // cap)
    x0 = tracing.transfer_stats()
    staged = sc.stage_chunks(rows, lists, weights)
    assert tracing.transfer_stats() == x0  # staging uploads nothing
    assert staged.shape == (launches, 2, rows, cap)
    assert staged.dtype == np.int32
    for j, t in enumerate(lists):  # each row's tiles, launch after launch
        flat = staged[:, 0, j].ravel()
        assert np.array_equal(flat[: len(t)], t)
        assert (flat[len(t):] == -1).all()
        assert (staged[:, 1, j].ravel()[: len(t)].view(np.float32)
                == 0.5).all()
    acc, cnt = sc.add_chunks(*sc.new_acc(rows), staged)
    x1 = tracing.transfer_stats()
    assert x1["h2d_count"] - x0["h2d_count"] == launches
    assert x1["h2d_bytes"] - x0["h2d_bytes"] == 8 * cap * rows * launches
    assert x1["d2h_count"] == x0["d2h_count"]
    assert acc.shape == cnt.shape == (rows, DOCS + 1)
    held = dep.server.cluster.indices[dep.index["int8"]]
    dep.search("int8", dep.hot_body)
    rows_sc = held._executor(held.shards[0]).impact_scorer(
        0, dep.field, True)
    x0 = tracing.transfer_stats()
    rows_sc.add_rows(rows, [np.asarray([0, 1], np.int32)],
                     [np.asarray([1.0, 2.0], np.float32)])
    x1 = tracing.transfer_stats()
    assert x1["h2d_count"] - x0["h2d_count"] == 2
    assert x1["h2d_bytes"] - x0["h2d_bytes"] == 512 * rows


def test_the_request_goes_the_normal_path(dep):
    """A planned sparse job on the request thread's inline fan-out: the
    seven job spans under `shard_search`, `sparse_theta` under
    `dispatch` with no launch of its own, the group's tags, nothing
    unplanned, no fallback."""
    from elasticsearch_tpu.rest.actions import RestActions

    def numbers():
        _s, out = RestActions(dep.server.cluster).nodes_stats(None, {}, {})
        node = out["nodes"]["node-0"]
        return {"inline": node["thread_pool"]["search"]["fan_out"]["inline"],
                "pooled": node["thread_pool"]["search"]["fan_out"]["pooled"],
                "unplanned": node["pipeline"]["batching"]["unplanned_queries"],
                **node["sparse"]}

    body = dep.proved_body
    n_terms = len(body["query"]["sparse_vector"]["query_vector"])
    before = numbers()
    dep.search("int8", body)
    after = numbers()
    assert after["inline"] == before["inline"] + 1
    assert after["pooled"] == before["pooled"]
    assert after["unplanned"] == before["unplanned"]
    assert after["fallbacks"] == before["fallbacks"]
    assert after["searches"] == before["searches"] + 1
    assert after["quantized_searches"] == before["quantized_searches"] + 1
    trace = dep.last_trace()
    by_id = {s["id"]: s for s in trace["spans"]}
    spans = {s["name"]: s for s in trace["spans"]}
    shard = spans["shard_search"]
    for name in ("plan", "queue_wait", "dispatch", "inflight", "collect",
                 "wake", "fetch"):
        assert spans[name]["parent_id"] == shard["id"], name
    assert spans["plan"]["tags"] == {"family": "sparse", "planned": True}
    assert spans["fan_out"]["tags"]["inline"] is True
    tags = spans["dispatch"]["tags"]
    assert tags["family"] == "sparse" and tags["quantized"] is True
    assert tags["terms"] == n_terms
    assert tags["tiles_scored"] == (after["tiles_scored"]
                                    - before["tiles_scored"])
    assert tags["tiles_pruned"] == (after["tiles_pruned"]
                                    - before["tiles_pruned"])
    assert tags["chunk_launches"] == (after["chunk_launches"]
                                      - before["chunk_launches"]) == 1
    assert tags["trips"] == (after["tile_trips"]
                             - before["tile_trips"]) >= 1
    assert tags["dense_rows"] == (after["dense_rows_scored"]
                                  - before["dense_rows_scored"]) >= 1
    assert tags["tiles_dense"] == (after["tiles_dense"]
                                   - before["tiles_dense"]) >= 1
    assert after["dense_launches"] == before["dense_launches"] + 1
    assert after["theta_host"] == before["theta_host"] + 1
    theta = spans["sparse_theta"]
    assert by_id[theta["parent_id"]]["name"] == "dispatch"
    assert theta["tags"]["launches"] == 0
    assert theta["tags"]["postings"] == 128 * n_terms
    assert (spans["dispatch"]["start_ns"] <= theta["start_ns"]
            and theta["start_ns"] + theta["duration_ns"]
            <= spans["dispatch"]["start_ns"]
            + spans["dispatch"]["duration_ns"])
    plan = spans["sparse_plan"]
    assert by_id[plan["parent_id"]]["name"] == "dispatch"
    assert plan["start_ns"] <= theta["start_ns"]
    assert (theta["start_ns"] + theta["duration_ns"]
            <= plan["start_ns"] + plan["duration_ns"]
            < spans["dispatch"]["start_ns"] + spans["dispatch"]["duration_ns"])
    assert plan["tags"] == {
        "segment": 0, "terms": n_terms,
        "cold_terms": n_terms - tags["dense_rows"],
        "tiles_kept": tags["tiles_scored"]}
    assert spans["collect"]["tags"]["merged"] is True


def test_rows_take_the_hot_terms_off_the_tile_pass(dep):
    """The same request under both storages: the int8 column scores its
    hot terms from rows (`tiles_dense`, `dense_rows_scored`,
    `dense_launches` move) and `tiles_scored` falls by what they held;
    the float32 column moves none of the three."""
    body = dep.proved_body
    vector = body["query"]["sparse_vector"]["query_vector"]
    hot = [t for t in vector if dep.df_of[t] >= 1024]
    assert 0 < len(hot) < len(vector)
    moved = {}
    for storage in STORAGES:
        before = dep.sparse_stats()
        dep.held(storage, body, dep.search(storage, body))
        after = dep.sparse_stats()
        moved[storage] = {k: after[k] - before[k] for k in (
            "tiles_scored", "tiles_pruned", "tiles_dense",
            "dense_rows_scored", "dense_launches", "chunk_launches")}
    f32, i8 = moved["float32"], moved["int8"]
    assert (f32["tiles_dense"], f32["dense_rows_scored"],
            f32["dense_launches"]) == (0, 0, 0)
    assert i8["dense_rows_scored"] == len(hot) and i8["dense_launches"] == 1
    assert i8["tiles_dense"] == sum(-(-dep.df_of[t] // 128) for t in hot)
    assert i8["tiles_scored"] < f32["tiles_scored"]
    assert (i8["tiles_scored"] + i8["tiles_pruned"] + i8["tiles_dense"]
            == f32["tiles_scored"] + f32["tiles_pruned"])
    assert i8["chunk_launches"] <= f32["chunk_launches"]
    gauges = dep.sparse_stats()
    assert 0 < gauges["dense_rows_held"] == gauges["dense_rows_wanted"] == len(
        [t for t in dep.df_of if dep.df_of[t] >= 1024])
    assert gauges["dense_rows_bytes"] == gauges["dense_rows_held"] * (
        -(-(DOCS + 1) // 4096) * 4096)


@pytest.mark.parametrize("first", ["rare", "hot"])
def test_the_warm_up_leaves_no_program_to_build(dep, first):
    """With the bucket ladder armed, the first request of a (field,
    storage) compiles `_impact_dense_add` and `_impact_chunk_add` at
    every bucket of the ladder but its own, whatever the dummy holds
    (rare tokens alone: no row; hot tokens alone: no tile); once its
    own bucket has seen both kinds of term, a launch of any width
    builds nothing."""
    from elasticsearch_tpu.ops import impact as impact_ops
    from elasticsearch_tpu.search import batcher as batcher_mod

    config = dep.configs["int8"]
    index = f"{config['index']}-warm-{first}"
    post(dep.port, f"/{index}", {"settings": dict(config["settings"]),
                                 "mappings": dep.corpus["mappings"]}, "PUT")
    svc = dep.server.cluster.indices[index]
    place_segment(svc, dep.corpus["segment"])
    svc._batcher.warmup_enabled = True
    programs = (impact_ops._impact_dense_add, impact_ops._impact_chunk_add)
    for p in programs:  # what earlier cases built at these shapes
        p.clear_cache()
    try:
        post(dep.port, f"/{index}/_search",
             {"rare": dep.rare_body, "hot": dep.hot_body}[first])
        assert svc._batcher.wait_warm_idle()
        # the ladder's own buckets hold both programs; the live
        # request's bucket (one row) what that request used
        n = len(svc._batcher.buckets)
        assert n > 1
        assert [p._cache_size() for p in programs] == (
            [n - 1, n] if first == "rare" else [n, n - 1])
        for body in (dep.proved_body, dep.hot_body, dep.long_body):
            served = post(dep.port, f"/{index}/_search", body)
            dep.held("int8", body, served)
        built = [p._cache_size() for p in programs]
        assert built == [n, n]
        ex = svc._executor(svc.shards[0])
        fam = batcher_mod.FAMILIES["sparse"]
        for b in svc._batcher.buckets:
            jobs = []
            for body in (dep.proved_body, dep.hot_body)[:b]:
                from elasticsearch_tpu.search import dsl
                from elasticsearch_tpu.search import sparse as sparse_mod

                q = dsl.parse_query(body["query"])
                q.sparse = sparse_mod.resolve(svc.settings, False)
                plan = batcher_mod.extract_sparse_plan(q, svc.mappings)
                jobs.append(batcher_mod._Job(ex, plan, 10, kind="sparse",
                                             query=q))
            key = fam.share(jobs[0].plan)
            fam.collect(svc._batcher, jobs, key, 16,
                        fam.dispatch(svc._batcher, jobs, key, 16, b, False),
                        False)
        assert [p._cache_size() for p in programs] == built
        assert svc._batcher.stats["warmup_failures"] == 0
    finally:
        svc._batcher.warmup_enabled = False


def test_the_new_layer_metrics_read_a_rehearsals_observations(dep):
    """The three metric files this family's rows add load, name readers
    that exist, and read the node's own counters as the harness hands
    them over (`run.py` `node_numbers` deltas; the traced window's
    modules by the jitted program's name); from a program without the
    counters or the kernel they read nothing and do not raise."""
    from elasticsearch_tpu.ops import impact as impact_ops
    from run import Http, node_numbers

    http = Http(dep.port)
    before = node_numbers(http)
    for body in (dep.proved_body, dep.hot_body, dep.rare_body):
        dep.search("int8", body)
    after = node_numbers(http)
    counts = {k: v - before[k] for k, v in after.items() if k in before}
    module = "jit_" + impact_ops._impact_dense_add.__wrapped__.__name__
    obs = {"counts": counts, "gauges": after,
           "profile": {"modules": {module: (2, 0.003)}}}
    bench = load_json("..", "BENCHMARK.json")
    listed = {m["name"]: m for m in bench["per_layer"]}
    read = {}
    for name in ("impact_dense_ms", "impact_dense_rows_per_req",
                 "impact_dense_tiles_share"):
        spec = load_json("layer_metrics", f"{name}.json")
        entry = listed[name]
        assert entry["workloads"] == ["msmarco-splade-sparse.solo"]
        assert (spec["unit"], spec["layer"], spec["moves"]) == (
            entry["unit"], entry["layer"], entry["moves"])
        reader = load_plugin("readers", spec["reader"])
        read[name] = reader.read(obs, spec["args"])
        bare = {"counts": {"sparse.searches": 3, "sparse.tiles_scored": 9},
                "gauges": {}, "profile": {"modules": {}}}
        assert reader.read(bare, spec["args"]) is None
    assert read["impact_dense_ms"] == pytest.approx(1.5)
    vectors = [b["query"]["sparse_vector"]["query_vector"]
               for b in (dep.proved_body, dep.hot_body, dep.rare_body)]
    hot = sum(dep.df_of[t] >= 1024 for v in vectors for t in v)
    assert read["impact_dense_rows_per_req"] == pytest.approx(hot / 3)
    assert 50 < read["impact_dense_tiles_share"] < 100
    assert read["impact_dense_tiles_share"] == pytest.approx(
        100 * counts["sparse.tiles_dense"]
        / (counts["sparse.tiles_dense"] + counts["sparse.tiles_scored"]))


def test_the_builders_plan_is_the_planners(dep):
    """`sparse_from_plan` on the builder's array plan equals
    `sparse_from_plan(sparse_plan(dict of dicts))` on the same small
    postings: the builder's layout is the planner's."""
    from elasticsearch_tpu.index.segment import sparse_from_plan, sparse_plan

    config = dep.configs["int8"]
    docs = 2_000
    corpus = load_plugin("corpora", config["corpus"]["builder"]).build(
        config, 3, docs)
    built = corpus["segment"].sparse[dep.field]
    data = corpus["reference"]
    width = corpus["body_context"]["term_width"]
    inv = {}
    for i, t in enumerate(data["terms"]):
        lo, hi = int(data["post_start"][i]), int(data["post_start"][i + 1])
        inv[f"t{int(t):0{width}d}"] = dict(zip(
            data["post_doc"][lo:hi].tolist(),
            data["post_w"][lo:hi].tolist()))
    planned = sparse_from_plan(sparse_plan(inv, 0.0), docs, built.exists)
    assert planned.terms == built.terms and planned.pruned == built.pruned == 0
    for name in ("term_df", "term_tile_start", "term_tile_count", "doc_ids",
                 "weights", "qweights", "scales", "tile_max", "tile_qmax",
                 "exists"):
        a, b = getattr(planned, name), getattr(built, name)
        assert a.dtype == b.dtype and a.shape == b.shape, name
        np.testing.assert_array_equal(a, b, err_msg=name)


def test_the_corpus_keeps_the_sources_shapes(dep):
    """What the configuration's file states of the source, read off the
    built data: the whole vocabulary's ids, ~127 non-zeros a passage,
    ~49 a query, positive weights inside SPLADE's range, no static
    pruning, one seed moving ids and weights but not the structure."""
    config = dep.configs["int8"]
    args, data = config["corpus"]["args"], dep.corpus["reference"]
    assert len(data["terms"]) == args["vocab_in_use"] == 30_100
    assert data["terms"].max() < args["vocab"] == 30_522
    df = np.diff(data["post_start"])
    assert (df >= 1).all()
    assert abs(len(data["post_doc"]) / DOCS - 127) < 3
    assert abs(df.max() / DOCS - args["df_law"]["max_share"]) < 0.02
    w = data["post_w"]
    assert w.dtype == np.float32 and 0 < w.min() and w.max() <= 3.5
    sf = dep.corpus["segment"].sparse[dep.field]
    assert sf.pruned == 0 and int(sf.term_df.sum()) == len(w)
    sizes = [len(b["query"]["sparse_vector"]["query_vector"])
             for b in dep.bodies]
    assert 40 < np.mean(sizes) < 58
    other = load_plugin("corpora", config["corpus"]["builder"]).build(
        config, SEED + 1, DOCS)["reference"]
    assert (np.diff(other["post_start"]) == df).all()
    assert not (other["post_doc"][:1000] == data["post_doc"][:1000]).all()
    assert not (other["post_w"][:1000] == w[:1000]).all()
