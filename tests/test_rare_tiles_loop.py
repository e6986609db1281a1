"""The fused text program's rare-term pass (`scoring._add_rare_tiles`).

Contract under test: the pass walks the tile slots a launch CARRIES, in
chunks of `scoring.RARE_CHUNK`, and a launch's packed result (scores,
doc order, totals) is bit-equal on the CPU to the one-pass scatter over
all `FUSED_T_RARE` slots that it replaced (kept here as the plain form),
and agrees with a float64 NumPy scoring of the same plans; for `match`
(one field, launched uncounted and counted) and for the serve family's
`sum` and `max_tie` combines with signed weights. The trip count depends on the
plan alone, so one program serves every tile count; and the batcher's
counters say how many slots its launches scattered of how many budgeted.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticsearch_tpu.ops import scoring

import test_fused_slots as slots_mod

T = scoring.FUSED_T_RARE
H = scoring.FUSED_H
C = scoring.RARE_CHUNK
TILE = 128
N_DOCS = 3000
N_TILES = 400
N_HOT = 3
K = 10
USED = [0, 1, C - 1, C, C + 1, T - 1, T]
PROGRAMS = ["match", "match_cnt", "mf_sum", "mf_max_tie"]
TIE = np.float32(0.3)


def one_pass_rare_tiles(acc, cnt, doc_ids, tfs, inv_norm, rare_ti, rare_tw,
                        signed, clauses=False):
    """The plain form: gather, score and scatter-add every slot of the
    budget at once, used or not, a scatter a row (the rare pass before
    the loop), on `_add_rare_tiles`' flat planes."""
    n = inv_norm.shape[0]
    B = rare_ti.shape[0]
    if clauses:  # ids carry their clause counter: its unit counts
        rare_ti, units = scoring.clause_units(rare_ti)
    tile_ok = rare_ti >= 0
    safe = jnp.clip(rare_ti, 0, doc_ids.shape[0] - 1)
    rows_d = doc_ids[safe]  # [B, T, 128]
    rows_t = tfs[safe]
    valid = (rows_d >= 0) & tile_ok[:, :, None]
    tgt = jnp.where(valid, rows_d, n)
    inv = inv_norm[jnp.clip(rows_d, 0, n - 1)]
    w = (jnp.abs(rare_tw) if signed else rare_tw)[:, :, None]
    s = w - w / (jnp.float32(1.0) + rows_t.astype(jnp.float32) * inv)
    s = jnp.where(valid, s, 0.0)
    acc = jax.vmap(lambda a, d, v: a.at[d.ravel()].add(v.ravel()))(
        acc.reshape(B, n + 1), tgt, s).ravel()
    if cnt is not None:
        counted = valid & (rare_tw > 0)[:, :, None] if signed else valid
        if clauses:
            counted = jnp.where(counted, units[:, :, None], 0)
        cnt = jax.vmap(
            lambda c, d, v: c.at[d.ravel()].add(v.ravel().astype(jnp.int32))
        )(cnt.reshape(B, n + 1), tgt, counted).ravel()
    return acc, cnt


def make_field(seed: int) -> dict:
    """One field's postings tiles, norms and dense rows, as
    `JaxExecutor.fused_parts` holds them (NumPy twins beside them)."""
    rng = np.random.default_rng(seed)
    doc_ids = np.full((N_TILES, TILE), -1, np.int32)
    tfs = np.zeros((N_TILES, TILE), np.int32)
    for t in range(N_TILES):
        m = int(rng.integers(1, TILE + 1))
        doc_ids[t, :m] = np.sort(rng.choice(N_DOCS, m, replace=False))
        tfs[t, :m] = rng.integers(1, 6, m)
    inv_norm = rng.uniform(0.2, 2.0, N_DOCS).astype(np.float32)
    dense = (rng.random((N_HOT, N_DOCS)) < 0.4) * rng.integers(
        1, 9, (N_HOT, N_DOCS))
    dense = dense.astype(np.uint8)
    return {
        "np": (doc_ids, tfs, inv_norm, dense),
        "doc_ids": jnp.asarray(doc_ids), "tfs": jnp.asarray(tfs),
        "inv_norm": jnp.asarray(inv_norm), "dense": jnp.asarray(dense),
        "wide": None,
    }


@pytest.fixture(scope="module")
def fields():
    return [make_field(5), make_field(6)]


def section(rng, n_tiles: int, n_hot: int, signed: bool):
    """(rare_tiles, rare_w, hot_rows, hot_w) of one job and field."""
    tiles = rng.choice(N_TILES, n_tiles, replace=n_tiles > N_TILES)
    rw = rng.uniform(0.5, 3.0, n_tiles).astype(np.float32)
    hw = rng.uniform(0.5, 3.0, n_hot).astype(np.float32)
    if signed:  # a should-term scores and does not count
        rw = np.where(rng.random(n_tiles) < 0.3, -rw, rw).astype(np.float32)
        hw = np.where(rng.random(n_hot) < 0.3, -hw, hw).astype(np.float32)
    return tiles.astype(np.int64), rw, np.arange(n_hot, dtype=np.int64), hw


def numpy_field_scores(field, sec):
    """float64 (score, count) planes of one job's section on one field."""
    doc_ids, tfs, inv_norm, dense = field["np"]
    tiles, rw, hot, hw = sec
    acc = np.zeros(N_DOCS)
    cnt = np.zeros(N_DOCS, np.int64)
    for t, w in zip(tiles, rw):
        ok = doc_ids[t] >= 0
        d = doc_ids[t][ok]
        x = tfs[t][ok].astype(np.float64) * inv_norm[d]
        np.add.at(acc, d, abs(w) - abs(w) / (1.0 + x))
        np.add.at(cnt, d, int(w > 0))
    for r, w in zip(hot, hw):
        tf = dense[r].astype(np.float64)
        hit = tf > 0
        acc += np.where(hit, abs(w) - abs(w) / (1.0 + tf * inv_norm), 0.0)
        cnt += hit & (w > 0)
    return acc, cnt


def numpy_topk(score, mask):
    order = np.lexsort((np.arange(N_DOCS), -np.where(mask, score, -np.inf)))
    top = order[:K]
    return score[top], top, int(mask.sum())


def launch(program: str, fields, plans, rows: int, rare_pass=None):
    """The packed result of one launch of `program` over `plans`
    [(sections, msm)]; with `rare_pass`, of the same program traced
    afresh with that function in `_add_rare_tiles`' place."""
    # `match`: one field, no tie_breaker, counted only where a job holds
    # a count threshold
    one_field = program.startswith("match")
    if one_field:
        fields = fields[:1]
    fs = scoring.MultiFusedScorer(
        ("title", "body")[:len(fields)], fields, None)
    fn, statics = scoring._fused_query_mf, {
        "t_rare": T, "n_hot": H, "k": K,
        "combine": "sum" if one_field else program[3:],
        "counted": program != "match"}
    packed = fs.pack_plans(
        [(secs[:len(fields)], msm) for secs, msm in plans], rows=rows)
    args = (
        tuple(f["doc_ids"] for f in fields),
        tuple(f["tfs"] for f in fields),
        tuple(f["inv_norm"] for f in fields),
        tuple(f["dense"] for f in fields),
        None, jnp.asarray(packed), None if one_field else TIE,
    )
    if rare_pass is None:
        return np.asarray(fn(*args, **statics))
    orig = scoring._add_rare_tiles
    scoring._add_rare_tiles = rare_pass
    try:  # a trace of its own, so the replaced pass is the one it holds
        key = (program, rare_pass)
        if key not in _REPLACED:
            _REPLACED[key] = jax.jit(
                functools.partial(fn.__wrapped__, **statics))
        return np.asarray(_REPLACED[key](*args))
    finally:
        scoring._add_rare_tiles = orig


_REPLACED: dict = {}  # (program, rare pass) -> its jitted program


def make_plans(program: str, tile_counts, seed: int):
    """One job a row: `tile_counts[row]` tiles in the first field, a
    third of that in the second (serve programs), one to three hot
    rows, a count threshold of 2 where the program counts."""
    rng = np.random.default_rng(seed)
    signed = program.startswith("mf")
    msm = 1 if program == "match" else 2
    plans = []
    for row, nt in enumerate(tile_counts):
        secs = [section(rng, nt, 1 + row % N_HOT, signed)]
        if signed:
            secs.append(section(rng, nt // 3, row % N_HOT, signed))
        plans.append((secs, msm))
    return plans


def assert_launch_agrees(program: str, fields, plans, rows: int):
    """Bit-equal to the one-pass form; ids, order and totals those of
    the float64 NumPy scoring, scores within float32's reach of it."""
    got = launch(program, fields, plans, rows)
    plain = launch(program, fields, plans, rows,
                   rare_pass=one_pass_rare_tiles)
    assert np.array_equal(got, plain)
    scores = got[:, :K].copy().view(np.float32)
    for row, (secs, msm) in enumerate(plans):
        planes = [numpy_field_scores(f, s) for f, s in zip(fields, secs)]
        accs = np.stack([a for a, _ in planes])
        cnt = sum(c for _, c in planes)
        if program == "mf_max_tie":
            score = accs.max(0) + float(TIE) * (accs.sum(0) - accs.max(0))
        else:
            score = accs.sum(0)
        mask = cnt >= msm if program != "match" else score > 0
        want_s, want_d, want_total = numpy_topk(score, mask)
        found = min(K, want_total)
        assert got[row, 2 * K] == want_total
        assert got[row, K:K + found].tolist() == want_d[:found].tolist()
        np.testing.assert_allclose(
            scores[row, :found], want_s[:found], rtol=2e-6, atol=0.0)
        assert np.all(np.isneginf(scores[row, found:]))
    for row in range(len(plans), rows):  # pad rows match nothing
        assert got[row, 2 * K] == 0


@pytest.mark.parametrize("program", PROGRAMS)
@pytest.mark.parametrize("rows", [1, 4])
@pytest.mark.parametrize("used", USED)
def test_loop_equals_the_one_pass_scatter(fields, used, rows, program):
    counts = [used, max(used - 1, 0), used // 2, 0][:rows]
    plans = make_plans(program, counts, seed=1000 * rows + used)
    assert_launch_agrees(program, fields, plans, rows)


@pytest.mark.parametrize("program", PROGRAMS)
@pytest.mark.parametrize("counts", [[1, 200], [5, 40], [0, 0, C + 3]],
                         ids=["1_and_200", "later_row_past_the_first",
                              "only_the_last_row"])
def test_rows_of_unequal_use_share_one_launch(fields, counts, program):
    """The trip count is the launch's largest row: a row that ends
    early adds nothing in the later trips, and a later row's tiles past
    the first row's last are not dropped. A four-row bucket, so one
    pad row rides along."""
    plans = make_plans(program, counts, seed=77 + len(counts))
    assert_launch_agrees(program, fields, plans, rows=4)


def test_a_budget_that_is_no_multiple_of_the_chunk(fields):
    """`t_rare` is a constructor argument: a budget of 20 slots takes
    two trips of 16, the second half padding."""
    f = fields[0]
    fs = scoring.MultiFusedScorer(("body",), [f], None, t_rare=20)
    rng = np.random.default_rng(3)
    secs = [section(rng, n, 1, False) for n in (20, 17)]
    s, d, tot = fs.search(
        [([sec], 1) for sec in secs], K, "sum", None, rows=2, counted=False)
    for row, sec in enumerate(secs):
        score, cnt = numpy_field_scores(f, sec)
        want_s, want_d, want_total = numpy_topk(score, score > 0)
        assert tot[row] == want_total
        assert d[row].tolist() == want_d.tolist()
        np.testing.assert_allclose(s[row], want_s, rtol=2e-6, atol=0.0)


def test_one_program_serves_every_tile_count(fields):
    """The trip count is data: launches of 0, 1 and 256 tiles at one
    row bucket compile `_fused_query_mf` once."""
    launch("match", fields, make_plans("match", [3], seed=1), rows=2)
    before = scoring._fused_query_mf._cache_size()
    for used in (0, 1, T):
        launch("match", fields, make_plans("match", [used], seed=2), rows=2)
    assert scoring._fused_query_mf._cache_size() == before


@pytest.mark.parametrize("rows, counts, want", [
    (1, [0], 0), (1, [1], C), (1, [C], C), (1, [C + 1], 2 * C),
    (4, [3, 40], 4 * 3 * C), (4, [T], 4 * T),
])
def test_rare_slots_scattered(rows, counts, want):
    assert scoring.rare_slots_scattered(rows, counts) == want


# ---- through the service: the counters, and the NumPy oracle ----------

service = slots_mod.service
oracle = slots_mod.oracle


@pytest.mark.parametrize("n_rare", [1, C + 1, 40])
def test_match_of_many_rare_terms_against_chunked_and_oracle(
    service, oracle, monkeypatch, n_rare
):
    """A `match` whose rare terms take one tile each: one, two and
    three trips of the loop; ids, order, scores and totals those of the
    chunked path and of the NumPy backend (`test_fused_slots`'
    references)."""
    body = {"query": {"match": {"body": " ".join(
        slots_mod.HOT[:2] + slots_mod.RARE[:n_rare])}},
        "size": 10, "track_total_hits": True}
    b = service._batcher
    before = dict(b.stats)
    served = slots_mod.search(service, body)
    assert b.stats["fused_jobs"] == before["fused_jobs"] + 1
    tiles = b.stats["fused_rare_tiles"] - before["fused_rare_tiles"]
    assert tiles >= n_rare
    trips = -(-tiles // C)
    assert (b.stats["rare_slots_scattered"]
            - before["rare_slots_scattered"]) == trips * C
    assert b.stats["rare_slots_budget"] - before["rare_slots_budget"] == T
    assert slots_mod.dispatch_tags(service, body)["rare_tiles"] == tiles
    slots_mod.assert_same_as_chunked_and_oracle(
        service, oracle, monkeypatch, body, served)


def test_serve_launch_counts_its_slots_a_field(service):
    """A `bool` launch of the serve family: the budget is T a field and
    row, the scattered slots follow the field's largest section."""
    b = service._batcher
    before = dict(b.stats)
    body = {"query": {"bool": {
        "must": [{"term": {"body": slots_mod.RARE[0]}}],
        "should": [{"match": {"body": " ".join(slots_mod.RARE[1:C + 2])}}],
    }}}
    slots_mod.search(service, body)
    delta = {k: b.stats[k] - before[k] for k in (
        "serve_launches", "serve_rare_tiles", "fused_rare_tiles",
        "rare_slots_scattered", "rare_slots_budget")}
    assert delta["serve_launches"] == 1 and delta["fused_rare_tiles"] == 0
    assert delta["serve_rare_tiles"] >= C + 2
    assert delta["rare_slots_budget"] == T
    assert delta["rare_slots_scattered"] == C * -(
        -delta["serve_rare_tiles"] // C)


def test_nodes_stats_carry_the_counters(service):
    from elasticsearch_tpu.cluster.service import ClusterService
    from elasticsearch_tpu.rest.actions import RestActions

    c = ClusterService()
    try:
        c.indices[service.name] = service
        slots_mod.search(service, slots_mod.body_with(1))
        _, resp = RestActions(c).nodes_stats(None, {}, {})
        node = next(iter(resp["nodes"].values()))
        stats = service._batcher.stats
        assert (node["thread_pool"]["search"]["fused_rare_tiles"]
                == stats["fused_rare_tiles"] >= 2)
        for k in ("rare_slots_scattered", "rare_slots_budget"):
            assert node["pipeline"]["batching"][k] == stats[k] > 0
    finally:
        c.indices.pop(service.name, None)  # the fixture closes it
        c.close()
