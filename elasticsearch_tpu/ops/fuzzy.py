"""The fuzzy expansion on the device: a launch's words against a text
field's term dictionary, each word's kept ordinals and distances back
(models/fuzzy.py states the semantics, equations 1-4, and holds the
recurrence this program runs: `band_row`).

The dictionary is a plane the segment keeps beside its postings
(`DeviceTermPlane`: code points transposed, a row a position, and the
lengths; 36 B a term, 32 MB at the passage shard's 894,836 terms),
uploaded at the field's first fuzzy search. One program, `fuzzy_expand`,
serves a launch: for each word, in a loop that runs as many trips as the
launch holds words, it computes the word's banded
optimal-string-alignment table against every term (integer vector work
with no matrix product in it), reads each term's distance off row m,
keys the candidates by (boost class, ordinal) and selects the best
`keep` with one top-k (from block maxima on a wide plane:
`scoring._block_topk`). The
key is exact: a word of m code points at most MAX_EDITS edits away has
at most 1 + MAX_EDITS * (MAX_EDITS + 1) distinct boosts (d / min(m,
len(t)), len(t) >= m - d), the host ranks them as fractions
(`class_ranks`), and rank and ordinal share one positive float32's bits,
which are distinct, so the top-k has no tie to break. Every term within
a word's edits is a candidate; none is skipped because a bound says it
is unlikely.

The table is walked a BLOCK of dictionary columns at a time
(`_block_kernel`, a Pallas kernel with a grid over the plane's blocks of
PLANE_PAD columns): the block's 36 plane rows are read once and widened
to int32 in VMEM, then the word's m rows are looped INSIDE the block,
the ten band rows of rows i - 1 and i - 2 the loop's values, so the band
never reaches HBM, and only the distances are written. The columns lie
over sublanes AND lanes (the plane is `[rows, width / 128, 128]`), so
every vector register the recurrence touches is full (a band stacked
`[5, width]` lies along the sublanes: an operation on one diagonal
fills an eighth of each register it touches, and the stack is rebuilt
every row: 0.34 ms a row at 894,976 columns against 0.0175 here,
PERF.md section 6, PR 55 and PR 56). Mosaic compiles
the kernel, so it serves where the plane lives on a TPU
(`DeviceTermPlane.blocked`); elsewhere the same `_word_table` runs as a
plain loop over the rows with every column at once
(`_distances_plain`), which is also the kernel's reference in the tests.
Both call the one `band_row`.

What it reads that it need not: every term, whatever its length (a word
of m code points can only reach lengths m - k .. m + k: a plane ordered
by length would read a fraction), and every row of the band for a word
of one edit. `least_work` states the least, for the benchmark's roofline.
"""

from __future__ import annotations

import functools
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..common.tracing import launch, note_transfer
from ..models import fuzzy as fuzzy_model
from ..models.fuzzy import BAND, MAX_EDITS, PLANE_LEN, PLANE_PAD
from . import scoring

# words a query row may bring to a launch: the launch's word slots are
# rows x this, a shape of the row bucket alone (a question of 2-12 words
# fits; a longer one is the unbatched executor's)
WORDS_PER_ROW = 16
# the longest word the plane answers: a longer one could reach a term
# that is kept apart (`TermPlane.long_ids`)
MAX_WORD_LEN = PLANE_LEN - MAX_EDITS
KEEP_MAX = 128  # `max_expansions` the program's one top-k selects
ORDINAL_BITS = 27  # a term's ordinal in its key: scoring.SLOT_ID_BITS
_ORDINAL_MASK = (1 << ORDINAL_BITS) - 1
_CLASSES = (MAX_EDITS + 1) * (MAX_EDITS + 1)
# a word's slot in the packed upload: its code points behind one zero,
# then m, k and the class ranks; the launch's word count rides slot 0
_SLOT = 1 + PLANE_LEN + 2 + _CLASSES

LANES = 128
# sublane rows of a block of the plane: PLANE_PAD columns, whole native
# tiles of a plane of one, two or four bytes a code point (32 / 16 / 8
# rows). 128 and 32 read fastest of the sizes probed on the chip (a
# smaller block pays more grid steps a word, a smaller chunk leaves
# vector slots empty, a larger one spills: PERF.md section 6, PR 56)
BLOCK_ROWS = PLANE_PAD // LANES
# sublane rows whose band the kernel walks at once
CHUNK_ROWS = 32


def word_slots(rows: int) -> int:
    """The word slots of a launch of `rows` query rows: two shapes, a
    lone request's and a full launch's (the expansion program compiles
    in ~10 s: a shape a row bucket would be six of them; an unused slot
    costs its share of the upload and the download, no trip)."""
    return WORDS_PER_ROW if rows == 1 else WORDS_PER_ROW * 32


class DeviceTermPlane:
    """A `TermPlane` on the device, in dictionary order, as the
    expansion reads it: `chars` [rows, width / 128, 128] (column c at
    [c // 128, c % 128]: the columns fill sublanes and lanes), `lens`
    int32[width], the width whole blocks of PLANE_PAD columns.
    `blocked`: the plane lives on a TPU, where the blocked kernel
    serves (`fuzzy_expand`)."""

    def __init__(self, plane: fuzzy_model.TermPlane, n_terms: int,
                 device=None):
        rows, width = plane.chars.shape
        if width % PLANE_PAD or width > _ORDINAL_MASK:
            raise ValueError(
                "a dictionary plane of %d columns: not whole blocks of %d,"
                " or past the key's ordinal bits" % (width, PLANE_PAD))
        self.chars = jax.device_put(
            plane.chars.reshape(rows, width // LANES, LANES), device)
        self.lens = jax.device_put(plane.lens, device)
        self.n_terms = n_terms
        self.nbytes = plane.nbytes
        self.blocked = next(iter(self.chars.devices())).platform == "tpu"


def pack_words(words: Sequence[Tuple[np.ndarray, int]], slots: int):
    """int32[slots + 1, _SLOT]: row 0 holds the word count, row 1 + i
    word i as (code points, edits)."""
    out = np.zeros((slots + 1, _SLOT), np.int32)
    out[0, 0] = len(words)
    for i, (cp, k) in enumerate(words):
        m = len(cp)
        row = out[1 + i]
        row[1: 1 + m] = cp
        row[1 + PLANE_LEN] = m
        row[2 + PLANE_LEN] = k
        row[3 + PLANE_LEN:] = fuzzy_model.class_ranks(m, k)
    return out


def _word_table(slab_at, code_point, m, lens, transpositions: bool):
    """D[m][len(t)] of one word against the columns `lens` is shaped as
    (min(distance, CAP), int32): rows 1 .. m of the banded table, the
    ten band rows of rows i - 1 and i - 2 carried as separate arrays.
    `slab_at(i)`: the plane's rows i .. i + BAND over those columns,
    int32; `code_point(i)`: the word's code point i - 1 (0 at i = 0)."""
    # (tied to `lens`, which is never negative: a loop's carry cannot
    # start as a constant in the kernel, Mosaic lays a constant out
    # replicated and the rows it becomes not)
    row0, none = ([jnp.maximum(cell, lens - (1 << 20)) for cell in r]
                  for r in fuzzy_model.first_rows(jnp, lens, jnp.int32))

    def table_row(i, carry):
        prev, prev2 = carry
        cur = fuzzy_model.band_row(
            jnp, i, list(prev), list(prev2), slab_at(i),
            code_point(i), code_point(i - 1), transpositions)
        return tuple(cur), prev

    last, _ = jax.lax.fori_loop(
        1, m + 1, table_row, (tuple(row0), tuple(none)))
    return fuzzy_model.last_cell(jnp, m, lens, list(last))


def _block_kernel(row_ref, chars_ref, lens_ref, dist_ref, wide_ref, *,
                  transpositions: bool):
    """One block of columns of one word's table: the block's plane rows
    widened once, then the word's rows walked a chunk of sublane rows at
    a time, the band never out of the chip's registers / VMEM."""
    from jax.experimental import pallas as pl

    wide_ref[...] = chars_ref[...].astype(jnp.int32)
    m = row_ref[1 + PLANE_LEN]

    def chunk(c, carry):
        at = pl.ds(pl.multiple_of(c * CHUNK_ROWS, CHUNK_ROWS), CHUNK_ROWS)
        dist_ref[at, :] = _word_table(
            lambda i: [wide_ref[i + e, at, :] for e in range(BAND + 1)],
            lambda i: row_ref[i], m, lens_ref[at, :], transpositions)
        return carry

    jax.lax.fori_loop(0, BLOCK_ROWS // CHUNK_ROWS, chunk, 0)


def _distances_blocked(chars, lens, row, transpositions: bool,
                       interpret: bool):
    """The word of slot `row` against every column, a block at a time
    (grid: the plane's blocks; the slot rides in SMEM)."""
    # (here, as scoring imports its kernel: Pallas costs a second to
    # import, which a process that never expands a word need not pay)
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows, sub, _ = chars.shape
    block = pl.BlockSpec((BLOCK_ROWS, LANES), lambda b, *_: (b, 0))
    return pl.pallas_call(
        functools.partial(_block_kernel, transpositions=transpositions),
        out_shape=jax.ShapeDtypeStruct((sub, LANES), jnp.int32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(sub // BLOCK_ROWS,),
            in_specs=[
                pl.BlockSpec((rows, BLOCK_ROWS, LANES),
                             lambda b, *_: (0, b, 0)),
                block,
            ],
            out_specs=block,
            scratch_shapes=[pltpu.VMEM((rows, BLOCK_ROWS, LANES), jnp.int32)],
        ),
        interpret=interpret,
    )(row, chars, lens)


def _distances_plain(chars, lens, row, transpositions: bool):
    """The same by a plain loop over the table's rows, every column at
    once (the band through memory a row): the form off the chip, and the
    kernel's reference."""
    return _word_table(
        lambda i: list(jax.lax.dynamic_slice_in_dim(
            chars, i, BAND + 1, axis=0).astype(jnp.int32)),
        lambda i: row[i], row[1 + PLANE_LEN], lens, transpositions)


@functools.partial(jax.jit, static_argnames=(
    "keep", "transpositions", "blocked", "interpret"))
def fuzzy_expand(chars, lens, packed, *, keep: int, transpositions: bool,
                 blocked: bool = False, interpret: bool = False):
    """-> int32[slots, 2 * keep]: each word's kept ordinals (best boost
    first, ties by ordinal; -1 past the last) and their distances.
    `blocked` (`DeviceTermPlane.blocked`): the table by the blocked
    kernel, which Mosaic compiles (`interpret`: off the chip, tests)."""
    slots = packed.shape[0] - 1
    n_words = packed[0, 0]
    width = lens.shape[0]
    pos = jnp.arange(width, dtype=jnp.int32)
    lens_sl = lens.reshape(chars.shape[1:])

    def one_word(wi, out):
        row = jax.lax.dynamic_index_in_dim(packed, wi + 1, keepdims=False)
        m, k = row[1 + PLANE_LEN], row[2 + PLANE_LEN]
        ranks = row[3 + PLANE_LEN:]
        dist = (_distances_blocked(chars, lens_sl, row, transpositions,
                                   interpret) if blocked
                else _distances_plain(chars, lens_sl, row, transpositions)
                ).reshape(width)
        # the candidate's boost class: (distance, how much shorter than
        # the word the shorter of the two is)
        shorter = m - jnp.minimum(m, lens)
        cls = jnp.clip(dist, 0, MAX_EDITS) * (MAX_EDITS + 1) + jnp.clip(
            shorter, 0, MAX_EDITS)
        rank = jnp.full_like(dist, -1)
        for c in range(_CLASSES):
            rank = jnp.where(cls == c, ranks[c], rank)
        ok = (dist <= k) & (rank >= 0) & (shorter <= MAX_EDITS)
        # a positive NORMAL float32's bits: the class above the ordinal
        bits = (((_CLASSES - rank) << ORDINAL_BITS)
                + (_ORDINAL_MASK - pos) + (1 << 23))
        key = jnp.where(
            ok, jax.lax.bitcast_convert_type(bits, jnp.float32), -jnp.inf)
        # from block maxima where the plane holds 8 x keep blocks (PR 54:
        # a quarter of `lax.top_k`'s time at a million columns and k 128);
        # the keys are distinct, so either form returns the same columns
        G = scoring.KNN_BLOCK
        wide = width // (G * G) * G >= 8 * keep
        top, at = (scoring._block_topk(key[None, :], keep, G) if wide
                   else jax.lax.top_k(key[None, :], keep))
        top, at = top[0], at[0]
        kept = top > -jnp.inf
        line = jnp.concatenate([
            jnp.where(kept, at, -1), jnp.where(kept, dist[at], 0)])
        return jax.lax.dynamic_update_slice(out, line[None, :], (wi, 0))

    return jax.lax.fori_loop(
        0, n_words, one_word, jnp.full((slots, 2 * keep), -1, jnp.int32))


def expand_async(plane: DeviceTermPlane, words: List[Tuple[np.ndarray, int]],
                 slots: int, keep: int, transpositions: bool):
    """Launches `fuzzy_expand` for `words` ((code points, edits), each
    of 1..MAX_WORD_LEN code points and 1..MAX_EDITS edits) WITHOUT
    waiting: the device array `decode` reads."""
    packed = pack_words(words, slots)
    note_transfer("h2d", packed.nbytes)
    cells = sum(len(cp) * BAND for cp, _k in words) * plane.n_terms
    with launch("fuzzy_expand", 1, packed.nbytes, cells):
        return fuzzy_expand(plane.chars, plane.lens, packed, keep=keep,
                            transpositions=transpositions,
                            blocked=plane.blocked)


def decode(out: np.ndarray, n_words: int, keep: int):
    """[(ordinals int64[], distances int32[])] a word from the downloaded
    result, kept terms only."""
    got = []
    for i in range(n_words):
        ords = out[i, :keep]
        n = int((ords >= 0).sum())
        got.append((ords[:n].astype(np.int64), out[i, keep: keep + n]))
    return got


def least_work(word_lens: Sequence[int], edits: Sequence[int],
               terms_by_len: np.ndarray, bytes_per_code_point: int = 1):
    """(bytes, band cells) the semantics ask of ANY exact expansion of
    these words over a dictionary with `terms_by_len[l]` terms of l code
    points: a word of m code points and k edits can only be within k of
    a term of m - k .. m + k code points, whose code points and length
    it must read, and telling a distance <= k takes the 2k + 1 diagonals
    of an m-row table a term."""
    # (a term kept apart from the plane counts under NO_TERM_LEN: out of
    # every word's reach here, as the host's expansion of it is uncounted)
    terms_by_len = np.asarray(terms_by_len)[: fuzzy_model.NO_TERM_LEN]
    csum = np.concatenate([[0], np.cumsum(terms_by_len)])
    weighted = np.concatenate([[0], np.cumsum(
        terms_by_len * np.arange(len(terms_by_len)))])
    top = len(terms_by_len) - 1
    nbytes = cells = 0
    for m, k in zip(word_lens, edits):
        lo, hi = max(0, m - k), min(top, m + k)
        if hi < lo:
            continue
        n = int(csum[hi + 1] - csum[lo])
        nbytes += int(weighted[hi + 1] - weighted[lo]) * bytes_per_code_point
        nbytes += n  # a length a term
        cells += n * m * (2 * k + 1)
    return nbytes, cells
