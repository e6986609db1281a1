"""The text programs' top-k at a depth the compiler sorts for (PR 54):
`scoring.select_topk` = `scoring.topk_block_rows`' rule over (n, k) +
`scoring._block_topk` at the block rows it names.

What is held here, on the CPU: the selection's VALUES are
`lax.top_k`'s bit for bit and in order, every column strictly above the
k-th value comes back once and no column twice (so only which members
of a tie group the cut splits is the selection's choice); a rescore
window cut from it (`window_tie_refill` + `window_cut`) is the first
1,000 by (score desc, doc asc), exactly; the rule says no at every
shape an older cell launches; and those cells' programs lower to the
parent's text at their REAL widths."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticsearch_tpu.ops import scoring

INT_LOWEST = np.iinfo(np.int32).min

# (n, k, rows): widths a CPU sorts quickly, none a multiple of G x 128,
# each wide enough for the rule to engage at its k
SHAPES = {
    "n300007_k1024_b1": (300_007, 1024, 1),
    "n151111_k256_b2": (151_111, 256, 2),
    "n262221_k1024_b4": (262_221, 1024, 4),
}
CONTENTS = ("all_distinct", "tie_straddles_k", "tie_of_5000_on_top",
            "fewer_than_k_finite", "exactly_k_finite", "none_finite")


def make_plane(content: str, n: int, k: int, rows: int, dtype) -> np.ndarray:
    """A [rows, n] plane of whole-number values (so the float and the
    int32 planes hold the same order), every row a permutation of its
    own; -inf / the int32 minimum where nothing matched."""
    rng = np.random.default_rng([54, n, k, rows, CONTENTS.index(content)])
    out = np.empty((rows, n), np.float64)
    for b in range(rows):
        if content == "all_distinct":
            vals = np.arange(1, n + 1)
        elif content == "tie_straddles_k":
            # k - 300 distinct above a group of 2,000 equal values that
            # the cut at k splits, distinct lower values below it
            top = np.arange(5_000_000, 5_000_000 + k - 300)
            vals = np.concatenate([
                top, np.full(2_000, 4_000_000),
                np.arange(1, n - len(top) - 2_000 + 1)])
        elif content == "tie_of_5000_on_top":
            vals = np.concatenate([
                np.full(5_000, 7_000_000), rng.integers(1, 400, n - 5_000)])
        else:
            finite = {"fewer_than_k_finite": k - 167,
                      "exactly_k_finite": k, "none_finite": 0}[content]
            vals = np.concatenate([
                rng.integers(1, 300, finite), np.full(n - finite, -np.inf)])
        out[b] = rng.permutation(vals)
    if np.issubdtype(dtype, np.floating):
        return out.astype(dtype)
    return np.where(np.isfinite(out), out, INT_LOWEST).astype(dtype)


def check_selection(plane: np.ndarray, k: int, s, cols) -> None:
    s, cols = np.asarray(s), np.asarray(cols)
    want, _ = jax.lax.top_k(jnp.asarray(plane), k)
    # the values: `lax.top_k`'s multiset, in its (descending) order
    assert s.dtype == plane.dtype and np.array_equal(s, np.asarray(want))
    for b in range(len(plane)):
        assert (s[b][1:] <= s[b][:-1]).all()
        assert len(set(cols[b].tolist())) == k  # no column twice
        assert cols[b].min() >= 0 and cols[b].max() < plane.shape[1]
        assert np.array_equal(plane[b, cols[b]], s[b])
        above = np.flatnonzero(plane[b] > s[b, -1])
        got = cols[b][s[b] > s[b, -1]]
        assert sorted(got.tolist()) == above.tolist()


@pytest.mark.parametrize("dtype", [np.float32, np.int32], ids=["f32", "i32"])
@pytest.mark.parametrize("content", CONTENTS)
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_selection_returns_top_ks_values_and_every_column_above_the_cut(
        shape, content, dtype):
    n, k, rows = SHAPES[shape]
    plane = make_plane(content, n, k, rows, dtype)
    # the block form is what runs: by the rule where it engages, else
    # at the rows the rule would name past its threshold
    G = scoring.topk_block_rows(n, k)
    if G:
        s, cols = jax.jit(scoring.select_topk, static_argnums=1)(plane, k)
    else:
        s, cols = jax.jit(scoring._block_topk, static_argnums=(1, 2))(
            plane, k, 16)
    check_selection(plane, k, s, cols)


@pytest.mark.parametrize("block_rows", [8, 16, 32, 64, 128])
def test_every_block_size_selects_the_same_values(block_rows):
    """Exactness does not depend on G: the rule's choice is one of
    speed. A width with a tail past the last whole group."""
    n, k = 16 * 128 * 128 + 4_321, 1024
    plane = make_plane("tie_straddles_k", n, k, 2, np.float32)
    s, cols = jax.jit(scoring._block_topk, static_argnums=(1, 2))(
        plane, k, block_rows)
    check_selection(plane, k, s, cols)


@pytest.mark.parametrize("n, k", [(3_000, 1024), (40_000, 1024),
                                  (1_000_000, 16), (1_000_000, 128),
                                  (8_841_823, 128), (401_729, 16),
                                  (30_000, 512)])
def test_rule_keeps_the_plain_selection(n, k):
    """No whole group, a plane too narrow for k blocks to be a small
    part of it, or a k the compiler selects for without a sort: the
    program is `lax.top_k`'s, as it was."""
    assert scoring.topk_block_rows(n, k) == 0
    text = jax.jit(scoring.select_topk, static_argnums=1).lower(
        jax.ShapeDtypeStruct((1, n), jnp.float32), k).as_text()
    plain = jax.jit(jax.lax.top_k, static_argnums=1).lower(
        jax.ShapeDtypeStruct((1, n), jnp.float32), k).as_text()
    assert text.replace("select_topk", "top_k") == plain


@pytest.mark.parametrize("n, k, block_rows", [
    (1_000_000, 1024, 32), (1_000_000, 2048, 16), (1_000_000, 512, 32),
    (1_000_000, 256, 64),
    (8_841_823, 1024, 128), (4_000_000, 1024, 64), (262_144, 1024, 16)])
def test_rule_names_blocks_near_the_square_root(n, k, block_rows):
    assert scoring.topk_block_rows(n, k) == block_rows
    groups = n // (block_rows * scoring.KNN_BLOCK)
    assert groups * scoring.KNN_BLOCK >= 8 * k


# ---- a rescore window cut from the selection ---------------------------------

WINDOW, BUCKET = 1000, 1024


def window_rows(kind: str, n: int, rng) -> np.ndarray:
    """One row of first-stage scores (float32, -inf = no match)."""
    row = np.full(n, -np.inf, np.float32)
    cols = rng.permutation(n)
    if kind == "tie_runs_past_the_bucket":
        # 940 above, then 3,000 at one score: ranks 941..3,940
        row[cols[:940]] = 50.0 + rng.permutation(940).astype(np.float32)
        row[cols[940:3_940]] = 7.5
        row[cols[3_940:9_000]] = rng.integers(1, 7, 5_060)
    elif kind == "tie_ends_inside_the_bucket":
        row[cols[:990]] = 50.0 + rng.permutation(990).astype(np.float32)
        row[cols[990:1_015]] = 7.5  # ranks 991..1,015
        row[cols[1_015:30_000]] = rng.integers(1, 7, 28_985) / 2.0
    elif kind == "one_score_everywhere":
        row[cols[:40_000]] = 3.25
    elif kind == "short":
        row[cols[:857]] = rng.integers(1, 50, 857) / 4.0  # heavy ties
    elif kind == "exactly_the_bucket":
        row[cols[:BUCKET]] = rng.integers(1, 9, BUCKET) / 4.0
    elif kind == "nothing_matched":
        pass
    else:
        raise ValueError(kind)
    return row


def first_stage_window(masked: np.ndarray):
    """What a windowed launch computes, then the batcher's cut a row
    (`QueryBatcher._window_topk`'s steps over one segment)."""
    @jax.jit
    def launch(plane):
        top_s, top_d = scoring.select_topk(plane, BUCKET)
        return top_s, top_d, scoring.window_tie_refill(plane, top_s, WINDOW)

    s, d, fill = (np.asarray(x) for x in launch(masked))
    s, _seg, d = scoring.rank_order(s, np.zeros_like(d), d)
    return [scoring.window_cut(s[b], d[b], fill[b], WINDOW)
            for b in range(len(s))]


WINDOW_KINDS = ("tie_runs_past_the_bucket", "tie_ends_inside_the_bucket",
                "one_score_everywhere", "short", "exactly_the_bucket",
                "nothing_matched")


@pytest.mark.parametrize("rows", [1, 4])
@pytest.mark.parametrize("kind", WINDOW_KINDS)
def test_window_is_the_first_thousand_by_score_then_doc(kind, rows):
    n = 300_007
    assert scoring.topk_block_rows(n, BUCKET)
    rng = np.random.default_rng([54, WINDOW_KINDS.index(kind), rows])
    # a launch of several rows mixes the kinds: row b is kind b past
    kinds = [WINDOW_KINDS[(WINDOW_KINDS.index(kind) + b) % len(WINDOW_KINDS)]
             for b in range(rows)]
    masked = np.stack([window_rows(kd, n, rng) for kd in kinds])
    for b, (scores, docs, refilled) in enumerate(first_stage_window(masked)):
        row = masked[b]
        matched = np.flatnonzero(np.isfinite(row))
        order = matched[np.lexsort((matched, -row[matched]))][:WINDOW]
        assert docs.tolist() == order.tolist(), kinds[b]
        assert np.array_equal(scores, row[order])
        # refilled where the tie group at the window's edge reaches the
        # bucket's last slot (it may run past what was fetched)
        ranked = row[matched[np.lexsort((matched, -row[matched]))]]
        assert refilled == (len(ranked) >= BUCKET
                            and ranked[BUCKET - 1] == ranked[WINDOW - 1])
        assert refilled == (kinds[b] in (
            "tie_runs_past_the_bucket", "one_score_everywhere",
            "exactly_the_bucket")), kinds[b]


def _top_ks_over(text: str, n: int) -> list:
    """The `lax.top_k`s (chlo.top_k) of a lowered text whose operand is
    `n` columns wide."""
    takes = [ln for ln in text.splitlines() if "chlo.top_k" in ln]
    assert takes
    return [ln for ln in takes if f"x{n}x" in ln]


def test_refill_stays_a_branch_and_needs_no_wide_sort():
    """The refill's selection is the same function; a launch none of
    whose rows needs it still pays a compare (`lax.cond`)."""
    n = 300_007
    text = jax.jit(
        lambda p: scoring.window_tie_refill(
            p, scoring.select_topk(p, BUCKET)[0], WINDOW)).lower(
        jax.ShapeDtypeStruct((1, n), jnp.float32)).as_text()
    assert "stablehlo.case" in text or "stablehlo.if" in text
    assert not _top_ks_over(text, n)


# ---- the older cells' launches at their real widths -------------------------

CELL_DOCS, CELL_TILES, CELL_HOT = 1_000_000, 1_160_811, 500
FILTERED_DOCS = 10_000_000


def _fused_text_at_the_cells_width(k: int, counted: bool, **kw) -> str:
    s = jax.ShapeDtypeStruct
    width = 2 * scoring.FUSED_T_RARE + 2 * scoring.FUSED_H + 1
    return scoring._fused_query_mf.lower(
        (s((CELL_TILES, 128), jnp.int32),),
        (s((CELL_TILES, 128), jnp.int32),),
        (s((CELL_DOCS,), jnp.float32),),
        (s((CELL_HOT, CELL_DOCS), jnp.uint8),),
        None, s((1, width), jnp.int32), None,
        t_rare=scoring.FUSED_T_RARE, n_hot=scoring.FUSED_H, k=k,
        combine="sum", counted=counted, **kw,
    ).as_text()


def _filtered_scan_text_at_the_cells_width() -> str:
    s = jax.ShapeDtypeStruct
    return scoring.knn_topk_filtered.lower(
        s((1, 192), jnp.float32), s((FILTERED_DOCS, 192), jnp.int8),
        s((1, FILTERED_DOCS), jnp.bool_), "l2_norm", 128,
        s((FILTERED_DOCS,), jnp.float32)).as_text()


# program -> (its lowering from shapes alone, the digest that text has
# at this PR's parent, 30933b9, computed there with these very functions)
REAL_WIDTH_PROGRAMS = {
    "match_launch_k16": (
        lambda: _fused_text_at_the_cells_width(16, False),
        "55c22975165eae2fc29fefad02b727730d82d5974370a586bcefe59901128c9c"),
    "serve_launch_k16": (
        lambda: _fused_text_at_the_cells_width(16, True),
        "de56245ee2d59752ef6cd964483b34837b82f848479d60c934dba0dc15c77cf5"),
    "hybrid_text_leg_k128": (
        lambda: _fused_text_at_the_cells_width(128, False),
        "bc9fe05acf094232e5227d72ecfbb20272d7fefd25519b108b67879b218e4121"),
    "filtered_scan_10m_k128": (
        _filtered_scan_text_at_the_cells_width,
        "5134930607962791e06e43fcb39d0787558554dd118e6c793662f36c200bd1d0"),
}


@pytest.mark.parametrize("program", sorted(REAL_WIDTH_PROGRAMS))
def test_older_cells_launches_lower_to_the_parents_text(program):
    """A `match` launch (k 16, uncounted), a serve launch (k 16,
    counted), the hybrid's text leg (bucket 128), each over the passage
    cell's 1,000,000 documents, and the filtered scan over 10,000,000 x
    192 int8 rows at its k: byte for byte the parent's programs, at the
    widths where a rule over (n, k) could engage."""
    lower, digest = REAL_WIDTH_PROGRAMS[program]
    assert hashlib.sha256(lower().encode()).hexdigest() == digest


def test_window_launch_at_the_cells_width_sorts_nothing_a_million_wide():
    text = _fused_text_at_the_cells_width(BUCKET, False, tie_window=WINDOW)
    assert scoring.topk_block_rows(CELL_DOCS, BUCKET) == 32
    assert not _top_ks_over(text, CELL_DOCS)


# ---- `rank_order`: the tied runs sorted where they lie (PR 58) ---------------

def whole_row_rank_order(scores, segs, docs):
    """`scoring.rank_order` as it was: a row with a tie sorted whole,
    as Python tuples (the reference the run-wise form is held to)."""
    scores, segs, docs = scores.copy(), segs.copy(), docs.copy()
    for b in range(len(scores)):
        ranked = sorted(zip((-scores[b]).tolist(), segs[b].tolist(),
                            docs[b].tolist()))
        negated, segs[b], docs[b] = zip(*ranked)
        scores[b] = [-x for x in negated]
    return scores, segs, docs


def downloaded_rows(rng, rows: int, k: int):
    """[rows, k] as a top-k download holds them: scores descending with
    planted runs of exact ties (some at rank 0, some at the last real
    rank), -inf padding last, candidates of two segments in the order a
    device might have left them (any)."""
    scores = np.full((rows, k), -np.inf, np.float32)
    segs = rng.integers(0, 2, (rows, k)).astype(np.int32)
    docs = np.stack([rng.permutation(4 * k)[:k] for _ in range(rows)]
                    ).astype(np.int32)
    for b in range(rows):
        n = int(rng.integers(2, k + 1)) if b % 3 else k  # real candidates
        distinct = int(rng.integers(1, n + 1)) if b % 4 else n  # b 0: no tie
        vals = np.sort(rng.choice(np.arange(1, 8 * k), distinct,
                                  replace=False))[::-1] / np.float32(4)
        # every value once, the rest of the row repeats of some of them
        pick = np.r_[np.arange(distinct),
                     rng.integers(0, distinct, n - distinct)]
        scores[b, :n] = vals[np.sort(pick)]
    return scores, segs, docs


@pytest.mark.parametrize("keys", ["packed", "tuples"])
@pytest.mark.parametrize("seed", range(4))
@pytest.mark.parametrize("k, rows", [(16, 24), (1024, 8)])
def test_rank_order_sorts_the_tied_runs_as_the_whole_row_sort_did(
        k, rows, seed, keys, monkeypatch):
    if keys == "tuples":  # a row whose (run, segment, doc) pass 63 bits
        monkeypatch.setattr(scoring, "RANK_KEY_ROOM", 0)
    rng = np.random.default_rng([58, k, seed])
    scores, segs, docs = downloaded_rows(rng, rows, k)
    kept = (scores.copy(), segs.copy(), docs.copy())
    got = scoring.rank_order(scores, segs, docs)
    want = whole_row_rank_order(scores, segs, docs)
    real = np.isfinite(scores)
    tied_rows = 0
    for b in range(rows):
        n = int(real[b].sum())
        for g, w, given in zip(got, want, kept):
            assert g.dtype == given.dtype
            assert g[b, :n].tolist() == w[b, :n].tolist()
            # the padding stays where the download left it
            assert g[b, n:].tolist() == given[b, n:].tolist()
        tied_rows += len(set(scores[b, :n].tolist())) < n
    assert tied_rows >= rows // 2  # the rows do hold what is tested
    # the arguments are the caller's: never written to
    assert all(np.array_equal(a, b) for a, b in
               zip((scores, segs, docs), kept))


@pytest.mark.parametrize("k", [16, 1024])
def test_rank_order_returns_rows_without_a_tie_as_they_are(k):
    rng = np.random.default_rng([58, k])
    scores = np.sort(rng.permutation(8 * k)[:k].astype(np.float32))[::-1]
    scores = np.tile(scores, (3, 1))
    scores[1, k // 2:] = -np.inf  # padding ties with itself: not a tie
    segs = rng.integers(0, 2, (3, k)).astype(np.int32)
    docs = rng.integers(0, 9, (3, k)).astype(np.int32)
    got = scoring.rank_order(scores, segs, docs)
    assert got[0] is scores and got[1] is segs and got[2] is docs


# ---- the served path: the rule engages, the window stays Lucene's ------------

@pytest.fixture(scope="module")
def dep():
    import test_colbert_rescore_deployment as deployment

    d = deployment.Deployment()
    yield d
    d.close()


def test_served_window_from_block_maxima_is_the_references_and_counted(
        dep, monkeypatch):
    """The late-interaction deployment at 6,000 passages, where the rule
    says no; with its depth threshold lifted (read when a program is
    traced: these window sizes are no other test's) the first stage's
    bucket of 64 comes from block maxima (G 8: 5 groups, 640 maxima),
    the answers are the plain reference's, and the batcher counts the
    windows by the same rule."""
    from elasticsearch_tpu.models import rerank as rerank_model

    def served(window: int) -> int:
        before = rerank_model.stats_snapshot()["window_block_selected"]
        for body in dep.bodies[:6]:
            body = {**body, "rescore": {**body["rescore"],
                                        "window_size": window}}
            dep.held(body, dep.search(body))
        return rerank_model.stats_snapshot()["window_block_selected"] - before

    assert not scoring.topk_block_rows(6000, 64)
    assert served(38) == 0
    monkeypatch.setattr(scoring, "TOPK_PLAIN_MAX", 0)
    assert scoring.topk_block_rows(6000, 64) == 8
    traced, block_topk = [], scoring._block_topk
    monkeypatch.setattr(
        scoring, "_block_topk",
        lambda plane, k, rows: (traced.append((plane.dtype.name, k, rows)),
                                block_topk(plane, k, rows))[1])
    assert served(37) == 6
    # the launch's program holds the form twice: the bucket, the refill
    assert set(traced) == {("float32", 64, 8), ("int32", 64, 8)}
