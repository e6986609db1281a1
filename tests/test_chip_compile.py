"""The main path's programs, compiled for a DESCRIBED TPU v5e (2x2) at
the real shapes — no chip attached, nothing runs.

What this guards: a kernel or program the chip's compiler refuses (a
block that disagrees with XLA's tiling, a program that does not fit the
device's memory, a step that cannot be partitioned over the mesh) fails
here, on the CPU, in tier-1 — not on the first chip call. Interpret-mode
Pallas tests and XLA-CPU parity tests cannot see any of that.

What it is not: a chip run. A compile that passes says nothing about
results or times; chip_smoke.py is the run.

The topology is described inside a module-scoped fixture — never at
import time, never in conftest.py, never autouse — and every compile
happens in the test's own process: only one process may load the TPU
library, and under xdist only the worker that is handed this file does.
All such tests live in this ONE file for the same reason.

Shapes are the one-chip share of MS MARCO passage the benchmark builds
(bench.build_corpus: 1,000,000 docs, 50k-term body / 20k-term title
vocabulary, 768-d fp16 vectors) as the batcher launches them (dtypes and
plan widths read off a live CPU run of the same corpus at small scale).
"""

import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no compiler logs in /tmp

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from elasticsearch_tpu.ops import scoring
from elasticsearch_tpu.parallel.mesh import DATA_AXIS, SHARD_AXIS

N_DOCS = 1_000_000
DIMS = 768
TILE = 128
BODY_TILES = 250_000  # ~25M postings / 128 + per-term tail padding
TITLE_TILES = 80_000
BODY_HOT = 512  # dense uint8 hot-term rows (512 MiB cap / 1M docs = 536)
TITLE_HOT = 256
HBM_BYTES = 16 * 1024**3  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler here: nothing to ask
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    """(data=1, shards=4) over the four described devices — the layout
    parallel/mesh.make_mesh builds for a 4-shard index on a 2x2 host."""
    assert len(topo.devices) == 4
    return Mesh(
        np.asarray(topo.devices).reshape(1, 4), (DATA_AXIS, SHARD_AXIS)
    )


def _on(sharding):
    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    return spec


def _fits(compiled) -> int:
    """Bytes ONE device needs for ONE program — arguments + outputs +
    temporaries (not what else the process keeps resident) — and they
    must fit the chip."""
    m = compiled.memory_analysis()
    total = (
        m.argument_size_in_bytes
        + m.output_size_in_bytes
        + m.temp_size_in_bytes
    )
    assert total < HBM_BYTES, f"program needs {total} bytes of HBM"
    return total


# ---------------------------------------------------------------------------
# exact kNN — the batched matmul + top-k the batcher launches per segment
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("rows", [1, 32])
def test_exact_knn_topk_batch(one_chip, rows):
    s = _on(one_chip)
    compiled = scoring.knn_topk_batch.lower(
        s((rows, DIMS), jnp.float32),
        s((rows,), jnp.bool_),
        s((N_DOCS, DIMS), jnp.float16),
        s((N_DOCS,), jnp.bool_),
        similarity="cosine",
        k=100,
    ).compile()
    _fits(compiled)


# the filtered kNN deployment (benchmarks/configs/yfcc10m-filtered-knn.json):
# 10M x 192 int8 rows under a mask a query row, built from the tag
# field's 932,453 postings tiles; one row (the cell) and the ladder's top
FILTERED_DOCS = 10_000_000
FILTERED_TILES = 932_453


def _elements(shape: str) -> int:
    """Elements of the first array of an HLO shape string."""
    dims = re.search(r"\[([\d,]*)\]", shape).group(1)
    return int(np.prod([int(d) for d in dims.split(",") if d] or [1]))


def _sorted_widths(hlo: str) -> list:
    """Elements every sort or top-k of a compiled program orders (a
    `sort`'s own shape; a `TopK` custom call's operand), and a `while`
    counted as the whole plane: the chip's compiler lowers a wide
    `lax.top_k` to a loop of sorts."""
    shape_of = dict(re.findall(r"%?([\w.\-]+) = (\(?\w+\[[\d,]*\])", hlo))
    widths = []
    for line in hlo.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = (\(?\w+\[[\d,]*\]).*? "
                     r"(sort|while|custom-call)\(%?([\w.\-]+)", line)
        if m is None:
            continue
        shape, op, operand = m.groups()
        if op == "sort":
            widths.append(_elements(shape))
        elif op == "while":
            widths.append(FILTERED_DOCS)
        elif 'custom_call_target="TopK"' in line:
            widths.append(_elements(shape_of[operand]))
    return widths


@pytest.mark.parametrize("rows", [1, 32])
def test_filtered_byte_knn_programs(one_chip, rows):
    s = _on(one_chip)
    width = 3 * scoring.FILTER_SLOT_BUCKETS[0] + 1
    mask = scoring.knn_filter_mask.lower(
        s((FILTERED_TILES, TILE), jnp.int32),
        s((FILTERED_DOCS,), jnp.bool_),
        s((rows, width), jnp.int32),
    ).compile()
    assert "while" in mask.as_text()  # the trips are a loop of the program
    scan = scoring.knn_topk_filtered.lower(
        s((rows, 192), jnp.float32),
        s((FILTERED_DOCS, 192), jnp.int8),
        s((rows, FILTERED_DOCS), jnp.bool_),
        similarity="l2_norm",
        k=128,
        norms=s((FILTERED_DOCS,), jnp.float32),
    ).compile()
    norms = scoring.knn_row_norms.lower(
        s((FILTERED_DOCS, 192), jnp.int8)).compile()
    # each beside what the deployment keeps resident: rows, their norms
    # and two planes
    resident = FILTERED_DOCS * 196 + 2 * FILTERED_TILES * TILE * 4
    assert _fits(mask) + resident < HBM_BYTES
    assert _fits(scan) + resident < HBM_BYTES
    assert _fits(norms) + resident < HBM_BYTES
    # the 10M-wide sort cannot come back unnoticed: nothing the scan
    # orders is wider than the plane of block maxima (padded to whole
    # tiles: twice over is room enough, the score plane is 128 times)
    hlo = scan.as_text()
    assert scoring.knn_block_select(FILTERED_DOCS, 128)
    widest = 2 * rows * (FILTERED_DOCS // scoring.KNN_BLOCK)
    widths = _sorted_widths(hlo)
    assert widths and max(widths) <= widest, widths
    # nor the second pass over the rows: ONE fusion reads the int8 operand
    entry = hlo[hlo.index("ENTRY"):]
    stored = re.search(
        rf"%?([\w.\-]+) = s8\[{FILTERED_DOCS},192\]\S* parameter",
        entry).group(1)
    readers = [ln for ln in entry.splitlines() if "parameter(" not in ln
               and re.search(rf"[(, ]%?{re.escape(stored)}[,)]", ln)]
    assert len(readers) == 1 and " fusion(" in readers[0], readers


# the bit rows the deployment's tag field holds: its 122 tags in 78,125
# rows or more (dense_row_min_df(10M)), 1.25 MB a row
FILTERED_BIT_ROWS = 122


@pytest.mark.parametrize("slots", scoring.FILTER_SLOT_BUCKETS)
def test_filtered_mask_program_with_bit_rows(one_chip, slots):
    """One row (the cell) at both slot buckets: the program compiles, fits
    beside the deployment's resident set, and its branch for a launch that
    scatters nothing holds no operation over the 40 MB count plane."""
    s = _on(one_chip)
    words = scoring.filter_bit_words(FILTERED_DOCS)
    assert words * 32 >= FILTERED_DOCS and words * 4 < 1_300_000
    mask = scoring.knn_filter_mask.lower(
        s((FILTERED_TILES, TILE), jnp.int32),
        s((FILTERED_DOCS,), jnp.bool_),
        s((1, 3 * slots + 1), jnp.int32),
        s((FILTERED_BIT_ROWS, words), jnp.uint32),
    ).compile()
    resident = (FILTERED_DOCS * 196 + FILTERED_TILES * TILE * 4
                + FILTERED_BIT_ROWS * words * 4)
    assert _fits(mask) + resident < HBM_BYTES
    hlo = mask.as_text()
    (cond,) = re.findall(
        r"conditional\(.*branch_computations=\{%?([\w.\-]+), %?([\w.\-]+)\}",
        hlo)
    plane = f"s32[{FILTERED_DOCS + 1}]"
    bodies = [_computation(hlo, name) for name in cond]
    with_plane = [plane in _reachable(hlo, body) for body in bodies]
    # false branch first: the launch that scatters nothing
    assert with_plane == [False, True], with_plane
    assert "while" not in _reachable(hlo, bodies[0])


@pytest.mark.parametrize("rows", [1, 32])
def test_filtered_knn_lead_program(one_chip, rows):
    """The lead route's one program at the deployment's shapes, its rows
    fetched block by block as the chip's layout asks: Mosaic takes the
    kernel, the transposed view of the rows is no copy of them (the
    program's temporaries stay under a tenth of the 1.92 GB a
    relayout would make), and no program text gathers stored rows."""
    s = _on(one_chip)
    words = scoring.filter_bit_words(FILTERED_DOCS)
    lead = scoring.knn_topk_lead.lower(
        s((rows, 192), jnp.float32),
        s((FILTERED_DOCS, 192), jnp.int8),
        s((FILTERED_DOCS,), jnp.float32),
        s((FILTERED_DOCS,), jnp.bool_),
        s((FILTERED_TILES, TILE), jnp.int32),
        s((FILTERED_BIT_ROWS, words), jnp.uint32),
        s((rows, 3 * scoring.FILTER_SLOT_BUCKETS[0] + 1), jnp.int32),
        similarity="l2_norm", k=128, blocks=True,
    ).compile()
    hlo = lead.as_text()
    assert "tpu_custom_call" in hlo and "while" in hlo
    assert lead.memory_analysis().temp_size_in_bytes < FILTERED_DOCS * 192 // 10
    resident = (FILTERED_DOCS * 196 + FILTERED_TILES * TILE * 4
                + FILTERED_BIT_ROWS * words * 4)
    assert _fits(lead) + resident < HBM_BYTES
    assert f"s8[{FILTERED_DOCS},192]" in hlo  # the resident rows, as held
    assert not re.search(r"= s8\[\d+,\d+,192\]", hlo)  # no gathered rows


def _computation(hlo: str, name: str) -> str:
    """The text of one named computation of an HLO module."""
    m = re.search(rf"^%?{re.escape(name)} .*?^}}", hlo, re.M | re.S)
    assert m is not None, name
    return m.group(0)


def _reachable(hlo: str, body: str) -> str:
    """`body` and the text of every computation it calls, transitively."""
    seen, todo, text = set(), [body], []
    while todo:
        cur = todo.pop()
        text.append(cur)
        for name in re.findall(
                r"(?:calls|to_apply|body|condition)=%?([\w.\-]+)", cur):
            if name not in seen:
                seen.add(name)
                todo.append(_computation(hlo, name))
    return "\n".join(text)


# the phrase deployment (benchmarks/configs/msmarco-phrase.json): the
# positions plane of 1,000,000 passages as corpora/zipf_text_ordered.py
# builds it (class width -> passages of the class; 271 MB of int32), a
# phrase of three words; one row (the cell) and the ladder's top
PHRASE_CLASSES = {8: 53, 16: 6255, 24: 46875, 32: 108819, 48: 299740,
                  64: 243111, 96: 219359, 128: 56474, 192: 17794, 256: 1520}


@pytest.mark.parametrize("rows", [1, 32])
def test_phrase_program(one_chip, rows):
    from elasticsearch_tpu.ops import phrase

    s = _on(one_chip)
    assert sum(PHRASE_CLASSES.values()) == N_DOCS
    mats = tuple(s((w, n), jnp.int32) for w, n in PHRASE_CLASSES.items())
    width = 3
    compiled = phrase.phrase_topk.lower(
        mats,
        s((N_DOCS,), jnp.int32),
        s((N_DOCS,), jnp.float32),
        None,  # live: no deletes in a freshly built segment
        s((rows, 2 * width + 1), jnp.int32),
        k=16,
    ).compile()
    plane = sum(4 * w * n for w, n in PHRASE_CLASSES.items())
    # beside what the deployment keeps resident: the text layout's
    # tiles, norms and dense rows (~2 GB)
    assert _fits(compiled) + 2 * 1024**3 < HBM_BYTES
    # the plane is an operand, never copied: the program's temporaries
    # stay under a few planes of scores a row, far under the plane's
    # size times the rows
    m = compiled.memory_analysis()
    assert m.argument_size_in_bytes >= plane
    assert m.temp_size_in_bytes < plane + rows * 64 * N_DOCS, (
        m.temp_size_in_bytes)
    # no sort wider than the score plane, no gather of the plane
    hlo = compiled.as_text()
    assert "scatter" not in hlo


# ---------------------------------------------------------------------------
# the fuzzy family: the expansion program over the passage shard's
# dictionary plane, and the fused program at the family's slot budgets
# ---------------------------------------------------------------------------

FUZZY_TERMS = 894_836  # msmarco-fuzzy-match's spelled dictionary
PASSAGE_TILES = 1_160_811  # the passage shard's tiles, 500 dense rows


def test_fuzzy_expansion_and_its_wide_fused_program(one_chip):
    from elasticsearch_tpu.models.fuzzy import (
        PLANE_FRONT, PLANE_LEN, PLANE_PAD)
    from elasticsearch_tpu.ops import fuzzy as fuzzy_ops

    s = _on(one_chip)
    width = -(-FUZZY_TERMS // PLANE_PAD) * PLANE_PAD
    sub = width // fuzzy_ops.LANES
    # a lone request's launch and a full one: Mosaic compiles the
    # blocked kernel inside both
    for rows in (1, 32):
        slots = fuzzy_ops.word_slots(rows)
        compiled = fuzzy_ops.fuzzy_expand.lower(
            s((PLANE_FRONT + PLANE_LEN, sub, fuzzy_ops.LANES), jnp.uint8),
            s((width,), jnp.int32),
            s((slots + 1, fuzzy_ops._SLOT), jnp.int32),
            keep=50, transpositions=True, blocked=True,
        ).compile()
        m = compiled.memory_analysis()
        assert (slots * 100 * 4 <= m.output_size_in_bytes
                <= 2 * slots * 100 * 4)
        hlo = compiled.as_text()
        assert hlo.count("tpu_custom_call") == 1
        # the band never leaves the kernel: no loop of the program
        # carries rows as wide as the plane beside the lengths (the plain
        # row loop carries ten, the program before it two of
        # `[5, width]`), and what the program keeps beside its operands
        # is a word's distances and keys, not a table
        wide_row = "s32[%d,%d]" % (sub, fuzzy_ops.LANES)
        loops = [ln for ln in hlo.splitlines() if " while(" in ln]
        assert loops and max(ln.count(wide_row) for ln in loops) <= 1, loops
        assert "[5,%d]" % width not in hlo
        assert m.temp_size_in_bytes < 64 * width, m.temp_size_in_bytes
        assert _fits(compiled) + 3 * 1024**3 < HBM_BYTES
    T, H = scoring.FUZZY_T_RARE, scoring.FUZZY_H
    assert T > scoring.FUSED_T_RARE and H > scoring.FUSED_H
    wide = scoring._fused_query_mf.lower(
        (s((PASSAGE_TILES, TILE), jnp.int32),),
        (s((PASSAGE_TILES, TILE), jnp.int32),),
        (s((N_DOCS,), jnp.float32),),
        (s((500, N_DOCS), jnp.uint8),),
        None,
        s((32, 2 * T + 2 * H + 1), jnp.int32),
        None,
        t_rare=T, n_hot=H, k=16, combine="sum", counted=False,
    ).compile()
    _fits(wide)


# ---------------------------------------------------------------------------
# fused text programs at the plan shape the batcher sends
# ---------------------------------------------------------------------------


def _plan_width(n_fields: int) -> int:
    # MultiFusedScorer.plan_shape_rows
    return n_fields * (2 * scoring.FUSED_T_RARE + 2 * scoring.FUSED_H) + 1


def _lower_match(s, rows: int, counted: bool = False, hot: int = BODY_HOT,
                 tiles: int = BODY_TILES):
    """The fused program as `match` launches it: one field, no
    tie_breaker operand, uncounted unless a job of the launch holds a
    count threshold; lowered."""
    return scoring._fused_query_mf.lower(
        (s((tiles, TILE), jnp.int32),),
        (s((tiles, TILE), jnp.int32),),
        (s((N_DOCS,), jnp.float32),),
        (s((hot, N_DOCS), jnp.uint8),),
        None,  # live: no deletes in a freshly built segment
        s((rows, _plan_width(1)), jnp.int32),
        None,  # tie: nothing reads it at one field, nothing is uploaded
        t_rare=scoring.FUSED_T_RARE,
        n_hot=scoring.FUSED_H,
        k=16,
        combine="sum",
        counted=counted,
    )


def _lower_multi_field(s, rows: int):
    """MultiFusedScorer's program as `multi_match` best_fields sends it
    (title+body, "max_tie"), lowered; `bool` rides the same program over
    one field with "sum", a strict subset of this one."""
    tiles = (TITLE_TILES, BODY_TILES)
    hot = (TITLE_HOT, BODY_HOT)
    return scoring._fused_query_mf.lower(
        tuple(s((t, TILE), jnp.int32) for t in tiles),
        tuple(s((t, TILE), jnp.int32) for t in tiles),
        tuple(s((N_DOCS,), jnp.float32) for _ in tiles),
        tuple(s((h, N_DOCS), jnp.uint8) for h in hot),
        None,
        s((rows, _plan_width(2)), jnp.int32),
        s((), jnp.float32),
        t_rare=scoring.FUSED_T_RARE,
        n_hot=scoring.FUSED_H,
        k=16,
        combine="max_tie",
    )


@pytest.mark.parametrize("rows", [1, 32])
def test_fused_match_program(one_chip, rows):
    _fits(_lower_match(_on(one_chip), rows).compile())


def test_fused_multi_field_program(one_chip):
    _fits(_lower_multi_field(_on(one_chip), 32).compile())


@pytest.mark.parametrize("rows", [1, 32])
def test_fused_multi_field_program_at_document_length(one_chip, rows):
    """The same program over the one-chip share of MS MARCO document the
    benchmark builds (401,729 docs; `zipf_title_body`'s tile counts; a
    1 GiB row budget a field), where nine body terms' tf passes 255 and
    hold uint16 rows beside the uint8 plane."""
    n, tiles, hot = 401_729, (636_122, 4_406_437), (66, 2_654)
    s = _on(one_chip)
    compiled = scoring._fused_query_mf.lower(
        tuple(s((t, TILE), jnp.int32) for t in tiles),
        tuple(s((t, TILE), jnp.int32) for t in tiles),
        tuple(s((n,), jnp.float32) for _ in tiles),
        tuple(s((h, n), jnp.uint8) for h in hot),
        None,
        s((rows, _plan_width(2)), jnp.int32),
        s((), jnp.float32),
        (None, s((9, n), jnp.uint16)),
        t_rare=scoring.FUSED_T_RARE,
        n_hot=scoring.FUSED_H,
        k=16,
        combine="max_tie",
    ).compile()
    _fits(compiled)


def test_fused_bool_program_at_passage_shapes(one_chip):
    """`bool` as the Boolean deployment sends it (the benchmark's
    `msmarco-bool-wand.solo`): the same program over ONE field with the
    "sum" combine, one row a launch, whose count plane holds the clause
    counters (`scoring.clauses_hit`: shifts and a population count)."""
    s = _on(one_chip)
    compiled = scoring._fused_query_mf.lower(
        (s((BODY_TILES, TILE), jnp.int32),),
        (s((BODY_TILES, TILE), jnp.int32),),
        (s((N_DOCS,), jnp.float32),),
        (s((BODY_HOT, N_DOCS), jnp.uint8),),
        None,
        s((1, _plan_width(1)), jnp.int32),
        s((), jnp.float32),
        t_rare=scoring.FUSED_T_RARE,
        n_hot=scoring.FUSED_H,
        k=16,
        combine="sum",
    ).compile()
    _fits(compiled)
    assert "popcnt" in compiled.as_text() or "population" in compiled.as_text()


@pytest.mark.parametrize("rows", [1, 8])
@pytest.mark.parametrize("filtered, negated", [
    (True, False), (False, True), (True, True)],
    ids=["filtered", "negated", "both"])
def test_fused_bool_program_under_masks_and_a_veto(
        one_chip, rows, filtered, negated):
    """The filtered and negated Boolean deployment's launches (the
    benchmark's `msmarco-filtered-bool.solo`: 1,000,000 passages,
    1,160,811 text tiles, 500 dense rows; the tag field's 28,819
    doc-id tiles, 39 bit rows of 31,744 words, a mask plan of 8 slots):
    the rows' masks are built INSIDE the one fused program (its loop
    over the plan's tile ranges and the bit rows' unpack are there, no
    second module), the veto is one more shift of the count plane it
    already holds, and an unfiltered launch carries neither the tag
    field's operands nor the mask's second count plane."""
    s = _on(one_chip)
    words = scoring.filter_bit_words(N_DOCS)
    fmask = (s((28_819, TILE), jnp.int32), s((rows, 3 * 8 + 1), jnp.int32),
             s((39, words), jnp.uint32)) if filtered else None
    text = {}
    for key, (fm, neg) in {"bare": (None, False),
                           "this": (fmask, negated)}.items():
        compiled = scoring._fused_query_mf.lower(
            (s((1_160_811, TILE), jnp.int32),),
            (s((1_160_811, TILE), jnp.int32),),
            (s((N_DOCS,), jnp.float32),),
            (s((500, N_DOCS), jnp.uint8),),
            None,
            s((rows, _plan_width(1)), jnp.int32),
            s((), jnp.float32),
            None,
            fm,
            t_rare=scoring.FUSED_T_RARE, n_hot=scoring.FUSED_H, k=16,
            combine="sum", negated=neg,
        ).compile()
        _fits(compiled)
        text[key] = compiled.as_text()
    row = f"u32[39,{words}]"
    assert row not in text["bare"] and "s32[28819,128]" not in text["bare"]
    assert (row in text["this"]) == filtered
    assert ("s32[28819,128]" in text["this"]) == filtered
    # a filtered launch's output row ends in the documents its filter passed
    assert (f"s32[{rows},34]" in text["this"]) == filtered
    assert f"s32[{rows},33]" in text["bare"]


@pytest.mark.parametrize("rows", [1, 8])
def test_uncounted_match_program_holds_no_count_plane(one_chip, rows):
    """At the passage cell's shapes (`msmarco-passage-bm25`: 1,000,000
    docs, 1,160,811 tiles, 500 dense rows) the program a `match` launch
    of `or` jobs runs allocates no int32 count plane of rows x (n + 1)
    and scatters once a trip of its rare loop (the scores); the counted
    program of the same shapes holds the plane and the second scatter,
    and the clause counters' population count."""
    plane = f"s32[{rows * (N_DOCS + 1)}]"
    texts = {
        counted: _lower_match(
            _on(one_chip), rows, counted, hot=500, tiles=1_160_811,
        ).compile().as_text()
        for counted in (False, True)
    }
    scatters = {c: len(re.findall(r" scatter\(", t)) for c, t in texts.items()}
    assert plane not in texts[False] and scatters[False] == 1
    assert plane in texts[True] and scatters[True] == 2
    assert "popcnt" not in texts[False] and "popcnt" in texts[True]
    assert all(" while(" in t for t in texts.values())


@pytest.mark.parametrize("family", ["match", "serve"])
def test_rare_pass_is_a_loop_inside_the_one_program(one_chip, family):
    """The rare-term pass compiles as a `while` over chunks of
    RARE_CHUNK tiles inside the one program a row bucket, as `match`
    (one field, uncounted) and as the serve family launch it: no operand
    as wide as the slot budget (rows x 256 tiles x 128 postings) is
    left, and nothing but the plan's shape and whether the launch counts
    decides the program, so the programs `_warm_ladder` compiles a
    family are its row buckets, as before the loop (`negated` and
    `tie_window` ride the group key: a negated group, and a first stage
    that feeds a rescore window, warm ladders of their own)."""
    import inspect

    rows = 1
    lower = {"match": _lower_match, "serve": _lower_multi_field}[family]
    lowered = lower(_on(one_chip), rows)
    params = inspect.signature(
        scoring._fused_query_mf.__wrapped__).parameters.values()
    assert {p.name for p in params if p.kind == p.KEYWORD_ONLY} == {
        "t_rare", "n_hot", "k", "combine", "counted", "negated",
        "tie_window"}
    text = lowered.compile().as_text()
    budget = rows * scoring.FUSED_T_RARE * TILE
    chunk = rows * scoring.RARE_CHUNK * TILE
    assert f"[{budget}]" not in text and f",{budget}]" not in text
    assert " while(" in text and f"[{chunk}]" in text


def test_cross_segment_merges(one_chip):
    """merge_segment_topk / knn_merge_segment_topk's kernels over four
    segments' device-resident candidate buffers: for text two fused
    launches' packed rows, unpacked inside the merge's own trace, beside
    two chunked triples."""
    s = _on(one_chip)
    rows, segs, kt, kk = 32, 4, 16, 128
    packed = s((rows, 2 * kt + 1), jnp.int32)
    triple = (s((rows, kt), jnp.float32), s((rows, kt), jnp.int32),
              s((rows,), jnp.int32))
    text = scoring._merge_segments.lower(
        (packed, triple, packed, triple), segs=tuple(range(segs)), k=kt,
    ).compile()
    _fits(text)
    knn = scoring._knn_merge_segments.lower(
        tuple(s((rows, kk), jnp.float32) for _ in range(segs)),
        tuple(s((rows, kk), jnp.int32) for _ in range(segs)),
        s((segs * kk,), jnp.int32),
        s((rows, segs * kk), jnp.bool_),
        k=kk,
    ).compile()
    _fits(knn)


# ---------------------------------------------------------------------------
# the mesh steps on a Mesh over the four described devices
# ---------------------------------------------------------------------------

MESH_SHARDS = 4
SHARD_DOCS = N_DOCS // MESH_SHARDS
SHARD_TILES = 96_000  # 250k docs x ~25 tokens / 128 + per-term padding


def test_mesh_knn_step(mesh4):
    """build_mesh_knn_step: the builder closes over its stacked arrays,
    so it is traced inside an outer jit whose arguments stand for them —
    the program compiled is the one the mesh executor launches."""
    from elasticsearch_tpu.parallel.sharded import build_mesh_knn_step

    rows, kc = 32, 128

    def on(spec):
        return _on(NamedSharding(mesh4, spec))

    def launch(vectors, cand, queries, nc):
        return build_mesh_knn_step(mesh4, vectors, cand, "cosine", kc)(
            queries, nc
        )

    compiled = jax.jit(launch).lower(
        on(P(SHARD_AXIS, None, None))(
            (MESH_SHARDS, SHARD_DOCS, DIMS), jnp.float16
        ),
        on(P(SHARD_AXIS, None))((MESH_SHARDS, SHARD_DOCS), jnp.bool_),
        on(P(DATA_AXIS, None))((rows, DIMS), jnp.float32),
        on(P(SHARD_AXIS, DATA_AXIS))((MESH_SHARDS, rows), jnp.int32),
    ).compile()
    # each device holds ONE shard's vectors (250k x 768 fp16 = 366 MiB),
    # not the whole stack
    assert _fits(compiled) < MESH_SHARDS * SHARD_DOCS * DIMS * 2
    assert "all-gather" in compiled.as_text()


def test_mesh_text_step(mesh4):
    from elasticsearch_tpu.parallel.sharded import build_mesh_text_step

    rows, t_slots, kb = 32, 256, 16

    def on(spec):
        return _on(NamedSharding(mesh4, spec))

    p3, p2 = P(SHARD_AXIS, None, None), P(SHARD_AXIS, None)
    p_plan = P(SHARD_AXIS, DATA_AXIS, None)

    def launch(doc_ids, tfs, inv_norm, live, ti, tw, tv, msm):
        step = build_mesh_text_step(
            mesh4, [doc_ids], [tfs], [inv_norm], live, kb,
            with_cnt=False, count_signed=False,
        )
        return step([ti], [tw], [tv], msm)

    compiled = jax.jit(launch).lower(
        on(p3)((MESH_SHARDS, SHARD_TILES, TILE), jnp.int32),
        on(p3)((MESH_SHARDS, SHARD_TILES, TILE), jnp.int32),
        on(p2)((MESH_SHARDS, SHARD_DOCS), jnp.float32),
        on(p2)((MESH_SHARDS, SHARD_DOCS), jnp.bool_),
        on(p_plan)((MESH_SHARDS, rows, t_slots), jnp.int32),
        on(p_plan)((MESH_SHARDS, rows, t_slots), jnp.float32),
        on(p_plan)((MESH_SHARDS, rows, t_slots), jnp.bool_),
        on(P(DATA_AXIS))((rows,), jnp.int32),
    ).compile()
    _fits(compiled)
    text = compiled.as_text()
    assert "all-gather" in text  # the ICI candidate merge
    assert "all-reduce" in text  # psum of the totals


# ---------------------------------------------------------------------------
# one program each from the families that compile only on first use
# (the agg segment-sum, ops/agg_kernels.sorted_bucket_counts, compiles
# too but takes ~20 s here at 1M docs — too slow to keep in tier-1)
# ---------------------------------------------------------------------------


def test_ivf_probe(one_chip):
    from elasticsearch_tpu.ops import ivf
    from elasticsearch_tpu.search.ann import DEFAULT_NPROBE

    nlist = ivf.auto_nlist(N_DOCS)  # 2000 clusters of ~500
    cmax = 768  # the build splits clusters past ~1.5x the mean
    n_flat = N_DOCS + cmax
    s = _on(one_chip)
    compiled = ivf._ivf_probe_topk.lower(
        s((32, DIMS), jnp.float32),
        s((32,), jnp.bool_),
        s((nlist, DIMS), jnp.float32),
        s((nlist,), jnp.int32),
        s((nlist,), jnp.int32),
        s((n_flat,), jnp.int32),
        s((n_flat, DIMS), jnp.float16),
        None,
        None,
        None,
        similarity="cosine",
        nprobe=DEFAULT_NPROBE,
        k=16,  # the candidate page of a k<=16 request; 128 takes ~9 s here
        cmax=cmax,
        qchunk=ivf.QCHUNK,
    ).compile()
    _fits(compiled)


def test_impact_scorer_chunk(one_chip):
    """ImpactScorer's looped tile pass over an int8 impact column at the
    learned-sparse deployment's size (`msmarco-splade-sparse`: ~127
    non-zeros a passage over 1M passages), at the one-row (express lane)
    bucket: one plan of TILE_CAP tiles a row, the flat planes the loop's
    carry; the 32-row bucket takes ~10 s here."""
    from elasticsearch_tpu.ops import impact

    n_tiles = 1_005_620  # the builder's count at 1,000,000 passages
    rows = 1
    s = _on(one_chip)
    compiled = impact._impact_chunk_add.lower(
        s((n_tiles, TILE), jnp.int32),
        s((n_tiles, TILE), jnp.int8),
        s((rows, N_DOCS + 1), jnp.float32),
        s((rows, N_DOCS + 1), jnp.int32),
        s((2, rows, impact.TILE_CAP), jnp.int32),
    ).compile()
    _fits(compiled)
    assert " while(" in compiled.as_text()  # the trips stayed a loop


@pytest.mark.parametrize("rows", [1, 32])
def test_impact_dense_rows(one_chip, rows):
    """The row pass of the same deployment (`_impact_dense_add`) at the
    one-row bucket and the ladder's top, over the rows the 1 GiB budget
    holds at 1M passages, and the launch that builds them from the
    resident tile planes (`_impact_rows_fill`): temporaries of a few
    tens of MB, not a second plane."""
    from elasticsearch_tpu.ops import impact

    n_tiles = 1_005_620
    stride = impact.impact_row_stride(N_DOCS)
    held = (1 << 30) // stride
    assert stride % impact.ROW_ALIGN == 0 and held == 1069
    s = _on(one_chip)
    compiled = impact._impact_dense_add.lower(
        s((held * stride,), jnp.int8),
        s((rows, impact.DENSE_SLOTS), jnp.int32),
        s((rows, impact.DENSE_SLOTS), jnp.float32),
        width=N_DOCS + 1,
    ).compile()
    _fits(compiled)
    if rows > 1:
        return
    fill = impact._impact_rows_fill.lower(
        s((held * stride,), jnp.int8),
        s((n_tiles, TILE), jnp.int32),
        s((n_tiles, TILE), jnp.int8),
        s((impact.ROWS_FILL_TILES,), jnp.int32),
        s((impact.ROWS_FILL_TILES,), jnp.int32),
        stride=stride,
    ).compile()
    _fits(fill)
    assert fill.memory_analysis().temp_size_in_bytes < 256 * 1024 * 1024


def test_maxsim_rescore(one_chip):
    from elasticsearch_tpu.ops import rerank

    rows, qt, d, window, tmax = 8, 32, 128, 128, 64
    n_tok = N_DOCS * 24  # ColBERT-shaped: tens of tokens per passage
    s = _on(one_chip)
    compiled = rerank._maxsim_rescore.lower(
        s((rows, qt, d), jnp.float32),
        s((rows, qt), jnp.bool_),
        s((N_DOCS,), jnp.int32),
        s((N_DOCS,), jnp.int32),
        s((n_tok + tmax, d), jnp.float16),
        None,
        s((rows, window), jnp.int32),
        s((rows, window), jnp.float32),
        s((rows, window), jnp.bool_),
        s((2,), jnp.float32),
        tmax=tmax,
        window=window,
    ).compile()
    _fits(compiled)


def test_maxsim_rescore_at_the_late_interaction_deployments_shapes(one_chip):
    """`msmarco-colbert-rescore`: one query row of 32 vectors against a
    window bucket of 1,024 candidates x 180 token slots x 128 bytes,
    gathered from a ~69M-row byte column (described, not allocated; 67
    upload blocks of 1,048,576 rows), at the stated precision. Its
    temporaries at the widest launch the cell warms (one row: the
    rerank family warms no ladder) must fit beside the 10.9 GB the
    deployment keeps resident, and a launch of several rows walks them
    (`lax.map`), so its temporaries do not grow with the rows."""
    from elasticsearch_tpu.ops import rerank
    from elasticsearch_tpu.search import executor_jax

    s = _on(one_chip)
    col_rows = 67 * executor_jax.RERANK_BLOCK_ROWS
    resident = col_rows * 128 + 8 * N_DOCS + 2_030_000_000

    def lower(rows):
        return rerank._maxsim_rescore.lower(
            s((rows, 32, 128), jnp.float32),
            s((rows, 32), jnp.bool_),
            s((N_DOCS,), jnp.int32),
            s((N_DOCS,), jnp.int32),
            s((col_rows, 128), jnp.int8),
            None,  # a byte field: no scales plane
            s((rows, 1024), jnp.int32),
            s((rows, 1024), jnp.float32),
            s((rows, 1024), jnp.bool_),
            s((2,), jnp.float32),
            tmax=180,
            window=1000,
        ).compile()

    one = lower(1)
    m = one.memory_analysis()
    assert m.argument_size_in_bytes >= col_rows * 128
    assert resident + m.temp_size_in_bytes + m.output_size_in_bytes < HBM_BYTES
    # one row's gathered bytes, their bfloat16 twin and the three
    # parts' products would be 23.6 + 47 + 71 MB if each were a plane
    assert m.temp_size_in_bytes < 160 * 1024 * 1024, m.temp_size_in_bytes
    hlo = one.as_text()
    assert "bf16" in hlo  # the split query parts, not a float32 pass
    four = lower(4).memory_analysis()
    assert four.temp_size_in_bytes < 160 * 1024 * 1024, (
        four.temp_size_in_bytes)
    # the column's assembly: a block written in place into the donated
    # buffer (no second copy of 8.9 GB)
    place = executor_jax._place_block.lower(
        s((col_rows, 128), jnp.int8),
        s((executor_jax.RERANK_BLOCK_ROWS, 128), jnp.int8),
        s((), jnp.int32),
    ).compile().memory_analysis()
    assert place.alias_size_in_bytes >= col_rows * 128
    assert place.temp_size_in_bytes < 1024 * 1024


def _lower_window(s, rows: int, k: int, tie_window: int, program=None):
    """The fused program as a rescore's first stage launches it over
    the late-interaction deployment's segment; lowered."""
    return (program or scoring._fused_query_mf).lower(
        (s((1_160_811, TILE), jnp.int32),),
        (s((1_160_811, TILE), jnp.int32),),
        (s((N_DOCS,), jnp.float32),),
        (s((500, N_DOCS), jnp.uint8),),
        None,
        s((rows, _plan_width(1)), jnp.int32),
        None,
        t_rare=scoring.FUSED_T_RARE,
        n_hot=scoring.FUSED_H,
        k=k,
        combine="sum",
        counted=False,
        tie_window=tie_window,
    )


@pytest.mark.parametrize("rows", [1, 4])
def test_fused_match_program_selects_a_rescore_window(one_chip, rows):
    """The first stage of the late-interaction deployment: the fused
    program at the window's bucket (k 1,024) with the window's tie
    refill (`tie_window` 1,000), beside the resident set. Neither
    selection sorts the million-wide plane (`scoring.select_topk`: at
    the parent `lax.top_k` lowered, in this program, to a sort of
    f32[rows, 1000000] and, in the refill's branch, of s32[rows,
    1000000]): what is sorted is the plane of block maxima and the
    chosen blocks' candidates."""
    compiled = _lower_window(_on(one_chip), rows, 1024, 1000).compile()
    m = compiled.memory_analysis()
    assert m.output_size_in_bytes >= 4 * rows * (3 * 1024 + 1)  # the refill
    assert _fits(compiled) + 67 * (1 << 20) * 128 < HBM_BYTES
    hlo = compiled.as_text()  # every computation, the branches' too
    assert "conditional" in hlo  # the refill is a branch
    G = scoring.topk_block_rows(N_DOCS, 1024)
    blocks = N_DOCS // (G * scoring.KNN_BLOCK) * scoring.KNN_BLOCK
    sorts = re.findall(r"= \(?(\w+)\[([\d,]*)\][^=]*? sort\(", hlo)
    kinds = {dtype for dtype, _dims in sorts}
    assert {"f32", "s32"} <= kinds, sorts  # the bucket's and the refill's
    for _dtype, dims in sorts:
        columns = int(dims.split(",")[-1])
        assert columns <= blocks + 1024 * G < N_DOCS // 8, (dims, sorts)


@pytest.mark.parametrize("k, tie_window", [(16, 0), (128, 0), (128, 100)])
def test_fused_match_program_at_a_shallow_k_is_the_plain_selections(
        k, tie_window, monkeypatch):
    """The rule says no at a page's bucket and at the hybrid's window
    of 100: the program's text is that of a build whose every selection
    is `lax.top_k`, the program it was."""
    assert not scoring.topk_block_rows(N_DOCS, k)
    ruled = _lower_window(jax.ShapeDtypeStruct, 1, k, tie_window).as_text()
    monkeypatch.setattr(scoring, "select_topk", jax.lax.top_k)
    plain = jax.jit(
        scoring._fused_query_mf.__wrapped__,
        static_argnames=("t_rare", "n_hot", "k", "combine", "counted",
                         "negated", "tie_window"))
    assert ruled == _lower_window(
        jax.ShapeDtypeStruct, 1, k, tie_window, plain).as_text()


def test_segment_build_postings(one_chip):
    """The device segment build's postings kernel at one refresh-sized
    launch (8,192 docs x ~32 tokens, pow2-bucketed)."""
    from elasticsearch_tpu.ops import index_build

    n_slots, n_docs_pad, p_pad = 16_384 * TILE, 8_192, 262_144
    s = _on(one_chip)
    compiled = index_build._postings_kernel(n_slots, n_docs_pad).lower(
        s((p_pad,), jnp.int32),
        s((p_pad,), jnp.int32),
        s((p_pad,), jnp.int32),
        s((n_docs_pad,), jnp.int32),
    ).compile()
    _fits(compiled)
