"""The filtered kNN family's lead route (`scoring.pack_lead_plans`,
`scoring.knn_topk_lead`, ops/pallas_lead.py; routed in
`QueryBatcher._dispatch_knn_filtered`): a launch whose jobs all LEAD BY
POSTINGS scores the lead clause's documents and nothing else, and
answers what the mask + scan path answers: ids, order, float32 scores,
rows passed, totals.

Two levels. The programs over a made-up segment (10,000 rows: not a
multiple of 128, so the block path's tail is exercised; a tenth of the
rows deleted or without a vector), both ways the candidates' rows are
fetched: gathered by row (what a CPU array gets) and block by block
through the Pallas kernel (what the chip's layout gets; here in
interpret mode, asked for explicitly). And the served path over the
filtered deployment's 30,000 rows (`test_filtered_knn_deployment`'s),
where `KNN_LEAD_SCAN_ROWS` is set to 1 for the test (at its measured
value a segment under 32,768 rows has no lead but an empty one).
"""

import types

import jax.numpy as jnp
import numpy as np
import pytest

import test_filtered_bool_deployment as programs_mod
import test_filtered_knn_deployment as dep_mod
from elasticsearch_tpu.common.faults import faults
from elasticsearch_tpu.ops import pallas_lead, scoring
from elasticsearch_tpu.search import batcher as batcher_mod
from test_filtered_knn_deployment import DOCS

N, DIMS, KC = 10_000, 16, 16
# term -> df. "common" and "common2" hold bit rows; "few" passes fewer
# than KC rows; "long" is six tiles (three trips at a chunk of two);
# "mid" is over the verified range's limit
DFS = {"rare": 300, "rarer": 50, "few": 5, "long": 700, "common": 3000,
       "common2": 2000, "mid": 128 * (scoring.KNN_LEAD_VERIFY_TILES_MAX + 1)}
ON_ROWS = ("common", "common2")


class MadeUp:
    """A segment's arrays as the device holds them: the tag field's
    tiled postings, its bit rows, `cand`, and three vector fields."""

    def __init__(self):
        rng = np.random.default_rng(50)
        self.cand = rng.random(N) > 0.1  # deleted, or no vector
        self.postings, tiles, start, count = {}, [], [], []
        for df in DFS.values():
            ids = np.sort(rng.choice(N, df, replace=False)).astype(np.int32)
            self.postings[len(start)] = ids
            t = -(-df // 128)
            padded = np.full(t * 128, -1, np.int32)
            padded[:df] = ids
            start.append(sum(count))
            count.append(t)
            tiles.append(padded.reshape(t, 128))
        self.doc_ids = np.concatenate(tiles)
        names = list(DFS)
        self.pf = types.SimpleNamespace(
            term_id=lambda t: names.index(t) if t in names else -1,
            term_tile_start=np.asarray(start), term_tile_count=np.asarray(count))
        self.bits = scoring.build_filter_bit_rows(
            jnp.asarray(self.doc_ids), self.pf.term_tile_start,
            self.pf.term_tile_count, [names.index(t) for t in ON_ROWS], N)
        ints = rng.integers(-128, 128, (N, DIMS)).astype(np.int8)
        floats = rng.standard_normal((N, DIMS)).astype(np.float32)
        unit = floats / np.linalg.norm(floats, axis=1, keepdims=True)
        self.fields = {
            "byte_l2": (ints, np.asarray(scoring.knn_row_norms(ints)),
                        "l2_norm"),
            "float_cosine": (unit, None, "cosine"),
            "float_l2": (floats, None, "l2_norm"),
        }
        self.rng = rng

    def passing(self, clauses) -> np.ndarray:
        """Rows a filter passes, by plain set arithmetic."""
        names = list(DFS)
        rows = np.flatnonzero(self.cand)
        for clause in clauses:
            held = [self.postings[names.index(t)] for t in clause
                    if t in names]
            rows = np.intersect1d(
                rows, np.unique(np.concatenate(held)) if held
                else np.empty(0, np.int32))
        return rows


@pytest.fixture(scope="module")
def seg():
    return MadeUp()


@pytest.fixture
def every_lead(monkeypatch):
    """A lead slot need stand for one row only: leads of any length."""
    monkeypatch.setattr(scoring, "KNN_LEAD_SCAN_ROWS", 1)


def reference_scores(q, rows, similarity) -> np.ndarray:
    q, rows = q.astype(np.float64), rows.astype(np.float64)
    if similarity == "l2_norm":
        return 1.0 / (1.0 + ((rows - q) ** 2).sum(axis=1))
    qn = np.linalg.norm(q)
    return (1.0 + rows @ (q / (qn or 1.0))) / 2.0


FILTERS = {
    "one_rare_tag": [(("rare",),)],
    "rare_and_bit_row": [(("rare",), ("common",))],
    "rare_and_rare": [(("rare",), ("rarer",))],
    "rare_and_any_of_two_bit_rows": [(("rare",), ("common", "common2"))],
    "three_clauses": [(("rare",), ("long",), ("common",))],
    "fewer_than_k_pass": [(("few",),)],
    "nothing_passes": [(("few",), ("rarer",), ("common2",))],
    "tag_the_segment_lacks": [(("nowhere",),)],
    "lacking_tag_beside_a_bit_row": [(("common",), ("nowhere",))],
    "lead_longer_than_one_trip": [(("long",),)],
    "four_rows_leads_of_different_length": [
        (("rare",), ("rarer",)), (("long",),), (("few",), ("common",))],
}


@pytest.mark.parametrize("field", ["byte_l2", "float_cosine", "float_l2"])
@pytest.mark.parametrize("gather", ["rows", "blocks"])
@pytest.mark.parametrize("case", sorted(FILTERS))
def test_lead_program_is_the_mask_and_scan_and_the_plain_answer(
        seg, every_lead, monkeypatch, case, gather, field):
    monkeypatch.setattr(scoring, "KNN_LEAD_CHUNK", 2)  # 256 slots a trip
    filters = FILTERS[case]
    rows = 4 if len(filters) > 1 else 1  # the fourth row is a pad row
    vectors, norms, similarity = seg.fields[field]
    q = np.zeros((rows, DIMS), np.float32)
    q[:len(filters)] = seg.rng.integers(-128, 128, (len(filters), DIMS))
    fp = scoring.pack_filter_plans(seg.pf, filters, rows, seg.bits)
    mask, scan_passed = scoring.knn_filter_mask(
        seg.doc_ids, seg.cand, fp.plan, seg.bits.plane)
    scan_s, scan_d = scoring.knn_topk_filtered(
        q, vectors, mask, similarity, KC, norms)
    lp = scoring.pack_lead_plans(seg.pf, filters, rows, N, seg.bits)
    assert lp is not None and lp.terms == fp.terms
    s, d, passed = scoring.knn_topk_lead(
        q, vectors, norms, seg.cand, seg.doc_ids, seg.bits.plane, lp.plan,
        similarity=similarity, k=KC, blocks=gather == "blocks",
        interpret=gather == "blocks")
    s, d, scan_s, scan_d = map(np.asarray, (s, d, scan_s, scan_d))
    assert np.array_equal(np.asarray(passed), np.asarray(scan_passed))
    finite = np.isfinite(scan_s)
    assert np.array_equal(np.isfinite(s), finite)
    assert np.array_equal(d[finite], scan_d[finite])
    if field == "byte_l2":
        assert np.array_equal(s, scan_s)  # whole numbers: bit for bit
    else:
        np.testing.assert_allclose(
            s[finite], scan_s[finite], rtol=scoring.KNN_SCORE_RTOL)
    for ji, clauses in enumerate(filters):
        want = seg.passing(clauses)
        assert int(passed[ji]) == len(want)
        ref = reference_scores(q[ji], np.asarray(vectors)[want], similarity)
        order = np.lexsort((want, -ref))[:KC]
        assert d[ji][:len(order)].tolist() == want[order].tolist()
        np.testing.assert_allclose(
            s[ji][:len(order)], ref[order], rtol=scoring.KNN_SCORE_RTOL)
        assert not np.isfinite(s[ji][len(order):]).any()
    assert not np.isfinite(s[len(filters):]).any()
    assert not np.asarray(passed)[len(filters):].any()


def test_block_kernel_skips_the_slots_it_is_not_handed(seg):
    """`block_dots` over three query rows: whole, cut short by `count`,
    and empty; slots named -1 fetch nothing."""
    vectors = seg.fields["byte_l2"][0][:N // 128 * 128]
    rng = np.random.default_rng(3)
    q = rng.integers(-128, 128, (3, DIMS)).astype(np.float32)
    docs = np.sort(rng.choice(len(vectors), (3, 256)), axis=1).astype(np.int32)
    blk, lane = docs // 128, docs % 128
    blk[1, ::3] = -1
    count = np.asarray([256, 100, 0], np.int32)
    out = np.asarray(pallas_lead.block_dots(
        q, vectors, blk, count, interpret=True))
    for b in range(3):
        named = (np.arange(256) < count[b]) & (blk[b] >= 0)
        want = vectors[docs[b]].astype(np.float32) @ q[b]
        assert np.array_equal(
            out[b, np.arange(256), lane[b]][named], want[named])


# ---- the rule ----------------------------------------------------------------

ROUTES = {
    # filters of one launch -> whether the launch leads
    "rare_tag": ([(("rare",),)], True),
    "rare_and_bit_row": ([(("common",), ("rare",))], True),
    "two_small_tags": ([(("rare",), ("rarer",))], True),
    "tag_the_segment_lacks": ([(("nowhere",), ("mid",))], True),
    "terms_lead": ([(("rare", "rarer"),)], False),
    "terms_lead_beside_a_rare_tag": ([(("rare",), ("rarer", "few"))], False),
    "mid_sized_second_tag": ([(("rare",), ("mid",))], False),
    "bit_rows_alone": ([(("common",), ("common2",))], False),
    "mixed_launch": ([(("rare",),), (("common",),)], False),
}


@pytest.mark.parametrize("route", sorted(ROUTES))
def test_routing_table(seg, every_lead, route):
    filters, leads = ROUTES[route]
    lp = scoring.pack_lead_plans(seg.pf, filters, 4, N, seg.bits)
    assert (lp is not None) == leads
    if leads:
        S = scoring.FILTER_SLOT_BUCKETS[0]
        assert lp.plan.shape == (4, 3 * S + 1)
        assert (lp.plan[len(filters):] == 0).all()  # pad rows
        assert lp.lead_rows == 128 * int(lp.plan[:, S].sum())


def test_mid_sized_tag_leads_alone_and_the_lead_is_the_shorter(
        seg, every_lead):
    S = scoring.FILTER_SLOT_BUCKETS[0]
    names = list(DFS)
    lp = scoring.pack_lead_plans(seg.pf, [(("mid",),)], 1, N, seg.bits)
    assert lp.lead_rows == DFS["mid"]
    lp = scoring.pack_lead_plans(
        seg.pf, [(("rare",), ("rarer",), ("common",))], 1, N, seg.bits)
    start = seg.pf.term_tile_start
    assert lp.plan[0, :3].tolist() == [
        start[names.index("rarer")], start[names.index("rare")],
        seg.bits.row_of_term[names.index("common")]]
    assert lp.plan[0, 2 * S:2 * S + 3].tolist() == [
        1, 1, scoring.FILTER_BIT_OPENS]
    assert lp.plan[0, 3 * S] == 3 and lp.tiles == 1 + 3 and lp.bit_terms == 1


@pytest.mark.parametrize("n_docs, tiles", [
    (10_000_000, 305), (1_000_000, 30), (32_768, 1), (30_000, 0)])
def test_a_lead_slot_stands_for_the_rows_a_scan_would_read(n_docs, tiles):
    """The measured rule (PERF.md section 6, PR 50): a candidate costs
    what ~175 scanned rows cost, so a lead may hold a 256th of the
    segment's rows at most."""
    assert scoring.KNN_LEAD_SCAN_ROWS == 256
    assert scoring.knn_lead_tiles_max(n_docs) == tiles


def test_lead_over_the_segments_limit_scans(seg):
    assert scoring.pack_lead_plans(
        seg.pf, [(("rare",),)], 1, N, seg.bits) is None  # 3 tiles > 0
    empty = scoring.pack_lead_plans(
        seg.pf, [(("nowhere",),)], 1, N, seg.bits)
    assert empty is not None and empty.lead_rows == 0


def test_host_arrays_are_gathered_by_row(seg):
    vectors = seg.fields["byte_l2"][0]
    assert not scoring.rows_on_lanes(vectors)
    assert not scoring.rows_on_lanes(jnp.asarray(vectors))  # row-major here


# ---- the served path -------------------------------------------------------------

@pytest.fixture(scope="module")
def dep():
    d = dep_mod.Deployment()
    yield d
    d.server.close()


def tags_by_route(dep):
    rare = [dep.tag(t) for t in dep.by_df if 100 <= dep.df[t] < 1024][:2]
    common = dep.tag(dep.by_df[0])
    return {"rare": [rare[0]], "rare_and_common": [rare[0], common],
            "two_rare": rare}


@pytest.mark.parametrize("which", ["rare", "rare_and_common", "two_rare"])
def test_served_lead_answer_is_the_scans_and_the_references(
        dep, monkeypatch, which):
    """Over HTTP: the same request scanned (the rule as measured: no
    lead at 30,000 rows) and led; both held to the plain reference."""
    body = dep.body(tags_by_route(dep)[which])
    before = dep.node()["knn_filtered"]
    scanned = dep.search(body)
    middle = dep.node()["knn_filtered"]
    assert middle["lead_searches"] == before["lead_searches"]
    assert middle["mask_launches"] == before["mask_launches"] + 1
    monkeypatch.setattr(scoring, "KNN_LEAD_SCAN_ROWS", 1)
    led = dep.search(body)
    after = dep.node()["knn_filtered"]
    assert after["lead_searches"] == middle["lead_searches"] + 1
    assert led["hits"] == scanned["hits"]
    dep.held(body, led)


def test_lead_search_counts_and_spans(dep, monkeypatch):
    """One led request: `searches`, the terms, the lead's and verified
    range's tiles, the candidate slots as `rows_scanned` and `lead_rows`,
    the rows passed; no mask launch, no block select; the `knn_lead`
    span where `filter_mask` would be and the group's `lead` tag."""
    monkeypatch.setattr(scoring, "KNN_LEAD_SCAN_ROWS", 1)
    tags = tags_by_route(dep)["rare_and_common"]
    lead_tiles = dep.scattered_tiles(tags)
    body = dep.body(tags)
    before = dep.node()["knn_filtered"]
    served = dep.search(body)
    after = dep.node()["knn_filtered"]
    dep.held(body, served)
    grew = {k: after[k] - before[k] for k in after}
    assert grew == {
        "searches": 1, "lead_searches": 1, "lead_rows": 128 * lead_tiles,
        "rows_scanned": 128 * lead_tiles,
        "rows_passed": dep_mod.rows_passing(dep, tags),
        "filter_tiles": lead_tiles, "filter_terms": 2, "bitset_terms": 1,
        "mask_launches": 0, "block_select_launches": 0, "fallbacks": 0}
    spans = {s["name"]: s for s in dep.last_trace()["spans"]}
    disp = spans["dispatch"]
    assert disp["tags"]["lead"] is True and disp["tags"]["filtered"] is True
    assert disp["tags"]["filter_tiles"] == lead_tiles
    assert "filter_mask" not in spans
    lead = spans["knn_lead"]
    assert lead["parent_id"] == disp["id"]
    assert lead["tags"] == {
        "segment": 0, "launches": 1, "tiles": lead_tiles,
        "lead_rows": 128 * lead_tiles, "bitset_terms": 1,
        "bitset_rows_held": len(dep.on_rows)}


def test_scanned_request_keeps_its_span_and_carries_no_lead_tag(dep):
    body = dep.body(tags_by_route(dep)["rare"])
    dep.search(body)
    spans = {s["name"]: s for s in dep.last_trace()["spans"]}
    assert "lead" not in spans["dispatch"]["tags"]
    assert "filter_mask" in spans and "knn_lead" not in spans


def jobs_of(dep, bodies):
    from elasticsearch_tpu.search import dsl

    svc = dep.server.cluster.indices[dep.index]
    ex = svc._executor(svc.shards[0])
    jobs = []
    for body in bodies:
        plan = batcher_mod.extract_knn_plan(
            [dsl.parse_knn(body["knn"])], svc.mappings)
        jobs.append(batcher_mod._Job(ex, plan, 10, kind="knn"))
    return svc._batcher, jobs


def held_job(dep, body, job):
    td = job.result
    dep.held(body, {"hits": {
        "total": {"value": td.total, "relation": td.relation},
        "hits": [{"_id": h.doc_id, "_score": h.score} for h in td.hits]}})


@pytest.mark.parametrize("second, leads", [("two_rare", True),
                                           ("common", False)])
def test_launch_leads_only_where_every_job_does(
        dep, monkeypatch, second, leads):
    """Two jobs at a four-row bucket: both lead -> one lead launch, each
    row by its own lead, pad rows empty; one of them names a bit row
    alone -> the launch scans all its rows as it did."""
    monkeypatch.setattr(scoring, "KNN_LEAD_SCAN_ROWS", 1)
    routes = tags_by_route(dep)
    routes["common"] = [dep.tag(dep.by_df[0])]
    bodies = [dep.body(routes["rare"]),
              dep.body(routes[second],
                       vector=dep.bodies[2]["knn"]["query_vector"])]
    b, jobs = jobs_of(dep, bodies)
    before = dict(b.knn_filtered)
    b._collect_knn_group(jobs, b._dispatch_knn_group(jobs, rows=4))
    grew = {k: b.knn_filtered[k] - before[k] for k in before}
    assert grew["searches"] == 2
    assert grew["lead_searches"] == (2 if leads else 0)
    assert grew["mask_launches"] == (0 if leads else 1)
    slots = 128 * sum(dep.scattered_tiles(routes[r][:1])
                      for r in ("rare", second))
    assert grew["rows_scanned"] == (slots if leads else 2 * DOCS)
    for body, job in zip(bodies, jobs):
        held_job(dep, body, job)


def test_filter_fault_still_falls_back_a_job(dep, monkeypatch):
    """The `knn.filter` fault site fires before the route is chosen: a
    request that would lead is served by the unbatched executor."""
    monkeypatch.setattr(scoring, "KNN_LEAD_SCAN_ROWS", 1)
    body = dep.body(tags_by_route(dep)["two_rare"])
    want = dep.search(body)
    before = dep.node()["knn_filtered"]
    faults.configure({"rules": [{"site": "knn.filter", "kind": "error"}]})
    try:
        served = dep.search(body)
    finally:
        faults.clear()
    after = dep.node()["knn_filtered"]
    assert after["fallbacks"] == before["fallbacks"] + 1
    assert after["lead_searches"] == before["lead_searches"]
    assert served["hits"] == want["hits"]
    dep.held(body, served)


def test_deleted_rows_do_not_lead(dep, monkeypatch):
    monkeypatch.setattr(scoring, "KNN_LEAD_SCAN_ROWS", 1)
    index = "yfcc-deletes"
    body = dep.body(tags_by_route(dep)["rare"])
    first = dep.search(body, index)
    dep.held(body, first)
    gone = [int(h["_id"]) for h in first["hits"]["hits"][:3]]
    eng = dep.server.cluster.indices[index].shards[0]
    live = np.ones(DOCS, bool)
    live[gone] = False
    eng.live_docs = [live]
    eng.change_generation += 1
    before = dep.node()["knn_filtered"]
    served = dep.search(body, index)
    assert dep.node()["knn_filtered"]["lead_searches"] == (
        before["lead_searches"] + 1)
    wide = dep.body(tags_by_route(dep)["rare"], k=20, num_candidates=100)
    wide["size"] = 20
    (expected,) = dep.ref.answer_many([wide])
    want = [h for h in expected["hits"]["hits"]
            if int(h["_id"]) not in gone][:10]
    assert [h["_id"] for h in served["hits"]["hits"]] == [
        h["_id"] for h in want]


# ---- the programs the route leaves alone ---------------------------------------

def _scan_text(byte_rows: bool) -> str:
    n = 1000
    rows = np.zeros((n, 16), np.int8 if byte_rows else np.float32)
    return scoring.knn_topk_filtered.lower(
        np.zeros((2, 16), np.float32), rows, np.ones((2, n), bool),
        "l2_norm" if byte_rows else "cosine", 16,
        *((np.zeros(n, np.float32),) if byte_rows else ())).as_text()


def _bare_text() -> str:
    n = 1000
    return scoring.knn_topk_batch.lower(
        np.zeros((2, 16), np.float32), np.ones(2, bool),
        np.zeros((n, 16), np.float32), np.ones(n, bool), "cosine", 16,
    ).as_text()


# computed at this PR's parent, 370536c, with these very functions
SCAN_PROGRAMS = {
    "knn_bare_float_rows": (
        _bare_text,
        "1dc47fc4f5f0dbbb9cf9f737d3e408ac3e402b477aa89cd9b9f191cb177e44ed"),
    "knn_scan_byte_rows": (
        lambda: _scan_text(True),
        "c0445165665ffec01e1eb2da09b19d36974678014dd923b68c4e9c72b8f71c82"),
    "knn_scan_float_rows": (
        lambda: _scan_text(False),
        "48e9b97ec809898cc4c3048af80b29a36aa0a764b5a73cf503567b0c7301cc75"),
}


@pytest.mark.parametrize("program", sorted(
    {**programs_mod.PARENT_PROGRAMS, **SCAN_PROGRAMS}))
def test_scan_path_programs_lower_to_the_parents_text(program):
    """The lead route is a program of its own: the mask program (both
    forms), the fused text launches, the scan and the bare kNN launch
    (which shares `knn_scores` with it) keep the parent's program text,
    so their metrics read what they read."""
    import hashlib

    lower, digest = {**programs_mod.PARENT_PROGRAMS, **SCAN_PROGRAMS}[program]
    assert hashlib.sha256(lower().encode()).hexdigest() == digest
