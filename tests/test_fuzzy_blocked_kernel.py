"""The blocked expansion kernel (ops/fuzzy.py `_distances_blocked`: the
form the chip runs, a block of dictionary columns at a time), interpreted
on the CPU, against the oracle that drives the same `band_row` with NumPy
(`osa_within`, `expand_word`) and against the plain row loop the CPU
serves with: planes of one, two and four bytes a code point, with and
without transpositions, words of 1, 3, 8 and MAX_WORD_LEN code points at
1 and 2 edits, dictionaries of less than a block, exactly one, and
several with the last padded.
"""

import functools
import random

import jax
import numpy as np
import pytest

from elasticsearch_tpu.models import fuzzy as fuzzy_model
from elasticsearch_tpu.ops import fuzzy as fuzzy_ops

ALPHABETS = {"ab": np.uint8, "abc": np.uint8, "abcdefgh": np.uint8,
             "aé中": np.uint16, "aé中𝒳": np.uint32}
# less than one block, exactly one, three with the last padded
SIZES = (1000, fuzzy_model.PLANE_PAD, 2 * fuzzy_model.PLANE_PAD + 900)
KEEP = 50


@functools.lru_cache(maxsize=None)
def dictionary(alphabet: str, n: int):
    """n distinct spellings, most of 1-14 code points (two letters spell
    32,766 of them), a tenth around MAX_WORD_LEN; the plane the device
    holds and its device copy."""
    rng = random.Random(f"{alphabet}/{n}")
    terms = set()
    while len(terms) < n:
        length = (rng.randint(fuzzy_ops.MAX_WORD_LEN - 3, fuzzy_model.PLANE_LEN)
                  if rng.random() < 0.1 else rng.randint(1, 14))
        terms.add("".join(rng.choice(alphabet) for _ in range(length)))
    terms = sorted(terms)
    plane = fuzzy_model.build_term_plane(terms, pad_to=fuzzy_model.PLANE_PAD)
    return terms, plane, fuzzy_ops.DeviceTermPlane(plane, n)


def a_word(terms, alphabet: str, m: int, rng) -> str:
    """A spelling of m code points one substitution off a term's, where
    the dictionary holds a term that long."""
    same = [t for t in terms if len(fuzzy_model.code_points(t)) == m]
    if not same:
        return "".join(rng.choice(alphabet) for _ in range(m))
    word = list(rng.choice(same))
    word[rng.randrange(m)] = rng.choice(alphabet)
    return "".join(word)


@functools.partial(jax.jit, static_argnames=("transpositions",))
def blocked_distances(chars, lens, row, transpositions):
    return fuzzy_ops._distances_blocked(
        chars, lens.reshape(chars.shape[1:]), row, transpositions,
        interpret=True).reshape(-1)


@pytest.mark.parametrize("m", [1, 3, 8, fuzzy_ops.MAX_WORD_LEN])
@pytest.mark.parametrize("n_terms", SIZES)
@pytest.mark.parametrize("transpositions", [True, False])
@pytest.mark.parametrize("alphabet", list(ALPHABETS))
def test_the_blocked_kernel_is_the_oracles(alphabet, transpositions,
                                           n_terms, m):
    terms, plane, dev = dictionary(alphabet, n_terms)
    assert plane.chars.dtype == ALPHABETS[alphabet]
    assert dev.chars.shape[1] * fuzzy_ops.LANES == -(
        -n_terms // fuzzy_model.PLANE_PAD) * fuzzy_model.PLANE_PAD
    word = a_word(terms, alphabet, m, random.Random(m * 7 + n_terms))
    cp = fuzzy_model.code_points(word)
    sent = [(cp, 1), (cp, 2)]
    packed = fuzzy_ops.pack_words(sent, fuzzy_ops.WORDS_PER_ROW)
    # every column's distance, the padding's too
    np.testing.assert_array_equal(
        np.asarray(blocked_distances(
            dev.chars, dev.lens, packed[1], transpositions)),
        fuzzy_model.osa_within(cp, plane.chars, plane.lens, transpositions))
    # and what the program keeps of them: the oracle's, and the plain
    # row loop's
    blocked, plain = (np.asarray(fuzzy_ops.fuzzy_expand(
        dev.chars, dev.lens, packed, keep=KEEP,
        transpositions=transpositions, **form))
        for form in ({"blocked": True, "interpret": True}, {}))
    np.testing.assert_array_equal(blocked, plain)
    for (_cp, k), (ords, dist) in zip(
            sent, fuzzy_ops.decode(blocked, len(sent), KEEP)):
        ids, _boosts, d = fuzzy_model.expand_word(
            plane, terms, word, k, max_expansions=KEEP,
            transpositions=transpositions)
        np.testing.assert_array_equal(ords, ids)
        np.testing.assert_array_equal(dist, d)


def test_a_plane_that_is_not_whole_blocks_is_refused():
    plane = fuzzy_model.build_term_plane(["a", "b"], pad_to=1024)
    with pytest.raises(ValueError, match="whole blocks"):
        fuzzy_ops.DeviceTermPlane(plane, 2)


def test_the_cpu_serves_with_the_plain_row_loop():
    _terms, _plane, dev = dictionary("abc", SIZES[0])
    assert dev.blocked is False
