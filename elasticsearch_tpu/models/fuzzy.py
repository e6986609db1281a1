"""Lucene-parity fuzzy term expansion, the host's side: what `match` with
`fuzziness` and the `fuzzy` query mean, in plain NumPy.

Parity target (from memory of Lucene 8/9: `FuzzyQuery`, `FuzzyTermsEnum`,
`MultiTermQuery.TopTermsBlendedFreqScoringRewrite`; Elasticsearch's
`Fuzziness`). For one analyzed word `w` of `m` code points on a field:

  1. edits   k(w) = 0 if m < lo, 1 if m < hi, else 2 (`AUTO` = `AUTO:3,6`);
             a number 0..2 is taken as it is. k = 0: a plain term.
  2. candidates: every dictionary term t with d(w, t) <= k, d the
             optimal-string-alignment distance (insert, delete,
             substitute, transpose two adjacent code points, each 1;
             `fuzzy_transpositions: false` leaves the last out), sharing
             w's first `prefix_length` code points.
  3. boost(t) = 1 if d = 0, else 1 - d / min(m, len(t)), float32; a
             candidate whose boost is not positive is dropped
             (FuzzyTermsEnum accepts `similarity > 0` only).
  4. the `max_expansions` candidates of highest boost are kept, ties by
             term ascending.
  5. blend:  df* = the largest df among the kept terms; every kept term
             is scored with idf(df*).
  6. score:  sum over words and their kept terms present in a document of
             boost(t) * idf(df*) * tf / (tf + k1 * (1 - b + b * dl / avgdl)).

The distance is ONE recurrence, `band_row`, written over an array
namespace: the oracle drives it with NumPy row by row (`osa_within`), the
device program with `jax.numpy` inside a loop (ops/fuzzy.py). It is the
banded form: only the 2 * MAX_EDITS + 1 diagonals around the main one can
hold a distance <= MAX_EDITS, and every cell is capped at MAX_EDITS + 1.
"""

from __future__ import annotations

from dataclasses import dataclass
from fractions import Fraction
from typing import Sequence, Tuple

import numpy as np

MAX_EDITS = 2  # Lucene's LevenshteinAutomata.MAXIMUM_SUPPORTED_DISTANCE
BAND = 2 * MAX_EDITS + 1
CAP = MAX_EDITS + 1  # "more than any k": what a cell is capped at
# code points a plane row holds; a longer term is kept apart (`long_ids`)
# and a word that could reach one is expanded over an ad-hoc plane
PLANE_LEN = 32
# rows in front of / behind a term's code points in the transposed plane,
# so that row i's slab (code points i - MAX_EDITS - 2 .. i + MAX_EDITS - 1)
# starts at row i and never leaves the array
PLANE_FRONT = MAX_EDITS + 2
NO_TERM_LEN = 255  # the length of a padding column: no word reaches it
# columns a plane that goes to the device is padded to: a block of the
# expansion kernel (ops/fuzzy.py), 128 sublane rows of 128 lanes
PLANE_PAD = 128 * 128


class FuzzinessError(ValueError):
    pass


def parse_fuzziness(value) -> Tuple[int, int]:
    """`fuzziness` as (lo, hi): a word of fewer than `lo` code points
    takes 0 edits, fewer than `hi` 1, else 2. `AUTO` = (3, 6); a number
    0, 1 or 2 is the pair that gives a word of any length that many."""
    text = str(value).strip().upper()
    if text == "AUTO":
        return 3, 6
    if text.startswith("AUTO:"):
        try:
            lo, hi = (int(x) for x in text[5:].split(","))
        except ValueError:
            raise FuzzinessError(f"invalid fuzziness [{value}]")
        if lo < 0 or hi < lo:
            raise FuzzinessError(f"invalid fuzziness [{value}]")
        return lo, hi
    try:
        n = float(text)
    except ValueError:
        raise FuzzinessError(f"invalid fuzziness [{value}]")
    if n != int(n) or not 0 <= n <= MAX_EDITS:
        raise FuzzinessError(
            f"invalid fuzziness [{value}]: 0, 1, 2 or AUTO[:lo,hi]")
    big = 1 << 30
    return ((big, big), (0, big), (0, 0))[int(n)]


def edits_for(fuzziness, m: int) -> int:
    lo, hi = parse_fuzziness(fuzziness)
    return 0 if m < lo else (1 if m < hi else 2)


def code_points(word: str) -> np.ndarray:
    return np.frombuffer(word.encode("utf-32-le"), np.uint32).astype(np.int32)


@dataclass
class TermPlane:
    """A term dictionary as the distance recurrence reads it: `chars`
    [PLANE_FRONT + length, n] code points TRANSPOSED (row PLANE_FRONT + c
    holds every term's code point c, 0 past its end), `lens` int32[n]
    (NO_TERM_LEN for a term kept apart), in dictionary order. `long_ids`:
    the terms longer than `length`, absent from `chars`."""

    chars: np.ndarray
    lens: np.ndarray
    length: int
    long_ids: np.ndarray

    @property
    def nbytes(self) -> int:
        return int(self.chars.nbytes + self.lens.nbytes)


def build_term_plane(terms: Sequence[str], length: int = PLANE_LEN,
                     pad_to: int = 1) -> TermPlane:
    """The plane of `terms` (kept in their order), columns padded to a
    multiple of `pad_to` with terms no word reaches."""
    n = len(terms)
    width = -(-max(n, 1) // pad_to) * pad_to
    lens = np.fromiter(map(len, terms), np.int64, count=n)
    flat = np.frombuffer("".join(terms).encode("utf-32-le"), np.uint32)
    if len(flat) != int(lens.sum()):  # astral code points: len() counted
        lens = np.array([len(code_points(t)) for t in terms], np.int64)
    starts = np.cumsum(lens) - lens
    col = np.repeat(np.arange(n, dtype=np.int64), lens)
    pos = np.arange(len(flat), dtype=np.int64) - np.repeat(starts, lens)
    keep = (lens <= length)[col]
    dtype = np.min_scalar_type(int(flat.max()) if len(flat) else 0)
    chars = np.zeros((PLANE_FRONT + length, width), dtype)
    chars[PLANE_FRONT + pos[keep], col[keep]] = flat[keep]
    plane_lens = np.full(width, NO_TERM_LEN, np.int32)
    plane_lens[:n] = np.where(lens <= length, lens, NO_TERM_LEN)
    return TermPlane(chars, plane_lens, length,
                     np.flatnonzero(lens > length).astype(np.int64))


def band_row(xp, i, prev, prev2, slab, w_cur, w_prev, transpositions: bool):
    """Row i (1-based, a scalar or a traced scalar) of the banded
    distance table of one word against every column of a plane: `prev`,
    `prev2` are rows i - 1 and i - 2 as BAND arrays (cell e of row r is
    D[r][r + e - MAX_EDITS]), `slab` the plane's rows i .. i + BAND
    (slab[e + 1] is each term's code point j - 1 for cell e's column
    j = i + e - MAX_EDITS, slab[e] its code point j - 2), `w_cur` /
    `w_prev` the word's code points i - 1 and i - 2. -> row i's cells."""
    cur = []
    for e in range(BAND):
        j = i + (e - MAX_EDITS)
        sub = prev[e] + (slab[e + 1] != w_cur)
        val = xp.minimum(sub, CAP)
        if e + 1 < BAND:
            val = xp.minimum(val, prev[e + 1] + 1)
        if e > 0:
            val = xp.minimum(val, cur[e - 1] + 1)
        if transpositions:
            swap = ((slab[e] == w_cur) & (slab[e + 1] == w_prev)
                    & (i >= 2) & (j >= 2))
            val = xp.where(swap, xp.minimum(val, prev2[e] + 1), val)
        # column 0 is the word's own prefix deleted; left of it, nothing
        val = xp.where(j == 0, xp.minimum(i, CAP), val)
        val = xp.where(j < 0, CAP, val)
        cur.append(val.astype(prev[e].dtype))
    return cur


def first_rows(xp, like, dtype):
    """Rows 0 and -1 of the band over columns shaped as `like`."""
    row0 = [xp.full_like(like, e - MAX_EDITS if e >= MAX_EDITS else CAP,
                         dtype=dtype) for e in range(BAND)]
    none = [xp.full_like(like, CAP, dtype=dtype) for _ in range(BAND)]
    return row0, none


def last_cell(xp, m, lens, row):
    """D[m][len(t)] of each column from row m's cells: CAP where the
    lengths differ by more than the band."""
    dist = xp.full_like(row[0], CAP)
    for e in range(BAND):
        dist = xp.where(lens - m == e - MAX_EDITS, row[e], dist)
    return dist


def osa_within(word: np.ndarray, chars: np.ndarray, lens: np.ndarray,
               transpositions: bool = True) -> np.ndarray:
    """min(distance, CAP) of `word` (code points) to every column of a
    transposed plane (`TermPlane.chars` layout), int8[n]."""
    m = len(word)
    prev, prev2 = first_rows(np, lens, np.int8)
    w = np.concatenate([[0], word]).astype(np.int64)
    for i in range(1, m + 1):
        cur = band_row(np, i, prev, prev2, chars[i: i + BAND + 1],
                       w[i], w[i - 1], transpositions)
        prev, prev2 = cur, prev
    return last_cell(np, m, lens, prev)


def boosts_of(dist: np.ndarray, m: int, lens: np.ndarray) -> np.ndarray:
    """float32 boosts as Lucene computes them (equation 3)."""
    minlen = np.minimum(m, lens).astype(np.float32)
    b = np.float32(1.0) - dist.astype(np.float32) / np.maximum(minlen, 1)
    return np.where(dist == 0, np.float32(1.0), b).astype(np.float32)


def class_ranks(m: int, k: int) -> np.ndarray:
    """int32[(MAX_EDITS + 1) * (MAX_EDITS + 1)]: the rank (0 = best) of
    the boost of a candidate at distance d whose shorter length is
    m - s, at index d * (MAX_EDITS + 1) + s; -1 where (d, s) is no
    candidate (d > k, or a boost that is not positive). The device keys
    its selection on it, so the order is the exact fractions', which the
    float32 boosts keep (distinct fractions of denominators <= 32 lie
    further apart than a rounding)."""
    side = MAX_EDITS + 1
    frac = {}
    for d in range(side):
        for s in range(side):
            minlen = m - s
            if d > k or (d > 0 and minlen <= d):
                continue
            frac[(d, s)] = Fraction(0) if d == 0 else Fraction(d, minlen)
    order = sorted(set(frac.values()))
    out = np.full(side * side, -1, np.int32)
    for (d, s), f in frac.items():
        out[d * side + s] = order.index(f)
    return out


def select_kept(dist: np.ndarray, lens: np.ndarray, ids: np.ndarray, m: int,
                k: int, max_expansions: int):
    """Equations 3-4 over candidate columns: (`ids` kept, their float32
    boosts, their distances), best boost first, ties by id ascending
    (ids are dictionary ordinals: the dictionary is sorted)."""
    ok = dist <= k
    ids, dist, lens = ids[ok], dist[ok], lens[ok]
    boost = boosts_of(dist, m, lens)
    pos = boost > 0
    ids, dist, boost = ids[pos], dist[pos], boost[pos]
    order = np.lexsort((ids, -boost))[:max_expansions]
    return ids[order], boost[order], dist[order].astype(np.int32)


def expand_word(plane: TermPlane, terms: Sequence[str], word: str, k: int,
                prefix_length: int = 0, max_expansions: int = 50,
                transpositions: bool = True):
    """One word against one dictionary on the host: (ordinals, boosts,
    distances) of its kept terms. A word whose neighbours may be longer
    than the plane's rows is expanded over a plane of its own."""
    cp = code_points(word)
    m = len(cp)
    if m + k > plane.length and len(plane.long_ids):
        near = [i for i in plane.long_ids.tolist()
                if abs(len(code_points(terms[i])) - m) <= k]
        own = build_term_plane([terms[i] for i in near], length=m + k)
        ids_l, b_l, d_l = expand_word(
            own, [], word, k, prefix_length, max_expansions, transpositions)
        ids_l = np.asarray(near, np.int64)[ids_l]
    else:
        ids_l = np.empty(0, np.int64)
        b_l = np.empty(0, np.float32)
        d_l = np.empty(0, np.int32)
    lens = plane.lens
    cand = np.flatnonzero(np.abs(lens - m) <= k)
    if m > plane.length:
        cand = cand[:0]
    for c in range(min(prefix_length, m)):
        cand = cand[plane.chars[PLANE_FRONT + c, cand] == cp[c]]
    if prefix_length > m:
        cand = cand[:0]
    dist = osa_within(cp[: plane.length], plane.chars[:, cand], lens[cand],
                      transpositions) if len(cand) else np.empty(0, np.int8)
    ids, boost, d = select_kept(dist, lens[cand], cand, m, k, max_expansions)
    if len(ids_l):
        ids = np.concatenate([ids, ids_l])
        boost = np.concatenate([boost, b_l])
        d = np.concatenate([d, d_l])
        order = np.lexsort((ids, -boost))[:max_expansions]
        ids, boost, d = ids[order], boost[order], d[order]
    return ids, boost, d


def blended_idf(doc_count: int, dfs: np.ndarray) -> np.float32:
    """Equation 5: idf of the largest df among a word's kept terms
    (float64 math, float32 result, as `bm25.idf`)."""
    df = float(np.max(dfs))
    return np.float32(np.log(1.0 + (doc_count - df + 0.5) / (df + 0.5)))


def term_weights(boost: float, idf: np.float32,
                 boosts: np.ndarray) -> np.ndarray:
    """float32 weights of one word's kept terms: query boost x the
    word's blended idf x each term's boost, in that order (the oracle
    and the batcher's plan share the one product)."""
    return (np.float32(boost) * np.float32(idf)) * boosts.astype(np.float32)


@dataclass(frozen=True)
class FuzzyParams:
    """What a `match` with `fuzziness` or a `fuzzy` query asks of the
    expansion, hashable (a batcher group's key holds it)."""

    fuzziness: str = "AUTO"
    prefix_length: int = 0
    max_expansions: int = 50
    transpositions: bool = True

    def edits(self, word: str) -> int:
        return edits_for(self.fuzziness, len(code_points(word)))
