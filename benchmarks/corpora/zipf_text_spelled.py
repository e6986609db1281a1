"""`corpora/zipf_text.py`'s corpus with SPELLINGS: the same postings law
(`stats_seed`, tiles, dense rows: the passage cell's planes), its terms
named as a language names them instead of `w000123`, whose fixed-width
digits give every term 54 neighbours at one edit and thousands at two,
which no vocabulary has. The builder is loaded by name and run on the
configuration as it stands; this file renames the terms, re-sorts the
dictionary by spelling (`PostingsField.terms` is sorted: the per-term
arrays move, the tiles stay where they are) and hands the spellings, by
frequency rank, to the body generator and the plain reference.

The spelling law (`corpus.args.spelling`, every number `assumed`), drawn
from `stats_seed` alone, so a dictionary is the same on every `--seed`:

  (a) length by rank: round(len_base + len_slope * ln(rank + 1) + N(0,
      len_sigma)), clipped to 1..len_max: frequent terms are short;
  (b) consonants and vowels in turn, as a pronounceable word has them
      (it starts on a vowel with probability `vowel_start`), each drawn
      from English's letter frequencies within its class: short words
      then crowd a small space, as English's do (`the`: `then`, `them`,
      `they`, `she`, `he`, ...);
  (c) `variant_share` of the terms past the first `first_variant_rank`
      are VARIANTS of a more frequent term (its rank drawn uniformly
      below their own): that term under a
      suffix (`s`, `ed`, `ing`) or under one random edit (substitute,
      insert, delete, transpose), because a real vocabulary's rare terms
      are inflections and misspellings of its common ones, and those are
      what fill a word's 50 places.

Imports a symbol only this deployment's program defines (the dictionary
plane's builder), at module level: a program without the fuzzy family
fails HERE, at import, before any corpus is built, instead of answering
the cell's bodies as plain `match` requests.
"""

from __future__ import annotations

import numpy as np

from elasticsearch_tpu.models.fuzzy import build_term_plane  # noqa: F401
from plugins import load_plugin

# English letter frequencies (per cent; Lewand, Cryptological Mathematics)
LETTER_FREQ = {
    "e": 12.702, "t": 9.056, "a": 8.167, "o": 7.507, "i": 6.966,
    "n": 6.749, "s": 6.327, "h": 6.094, "r": 5.987, "d": 4.253,
    "l": 4.025, "c": 2.782, "u": 2.758, "m": 2.406, "w": 2.360,
    "f": 2.228, "g": 2.015, "y": 1.974, "p": 1.929, "b": 1.492,
    "v": 0.978, "k": 0.772, "j": 0.153, "x": 0.150, "q": 0.095,
    "z": 0.074,
}
LETTERS = "".join(LETTER_FREQ)
VOWELS = "aeiou"
SUFFIXES = ("s", "ed", "ing")


def one_edit(word: str, kind: int, at: float, letter: str) -> str:
    """`word` under one edit: 0 substitute, 1 insert, 2 delete, 3
    transpose, at the place `at` (a share of the word) picks."""
    n = len(word)
    if kind == 1:
        i = int(at * (n + 1))
        return word[:i] + letter + word[i:]
    if kind == 2 and n > 1:
        i = int(at * n)
        return word[:i] + word[i + 1:]
    if kind == 3 and n > 1:
        i = int(at * (n - 1))
        return word[:i] + word[i + 1] + word[i] + word[i + 2:]
    i = int(at * n)
    return word[:i] + letter + word[i + 1:]


def spell(vocab: int, stats_seed: int, law: dict) -> list:
    """`vocab` distinct lower-case spellings, by frequency rank."""
    rng = np.random.default_rng([int(stats_seed), 77])
    p = np.array([LETTER_FREQ[c] for c in LETTERS])
    is_vowel = np.array([c in VOWELS for c in LETTERS])
    p_v, p_c = np.where(is_vowel, p, 0.0), np.where(is_vowel, 0.0, p)
    rank = np.arange(vocab)
    length = np.clip(np.rint(
        law["len_base"] + law["len_slope"] * np.log(rank + 1.0)
        + rng.normal(0.0, law["len_sigma"], vocab)), 1, law["len_max"]
    ).astype(np.int64)
    variant = rng.random(vocab) < law["variant_share"]
    variant[: int(law["first_variant_rank"])] = False
    base = np.floor(rng.random(vocab) * rank).astype(np.int64)
    suffixed = rng.random(vocab) < law["suffix_share"]
    suffix = rng.choice(len(SUFFIXES), size=vocab, p=law["suffix_mix"])
    kind = rng.integers(0, 4, size=vocab)
    at = rng.random(vocab)
    spare = 6  # fresh draws a term may use up before it gets a tail
    letters = rng.choice(len(LETTERS), size=(vocab, spare), p=p / p.sum())
    start = np.cumsum(length) - length
    # a word's letters: vowel and consonant in turn from its first
    n = int(length.sum())
    at_vowel = (np.repeat(rng.random(vocab) < law["vowel_start"], length)
                ^ ((np.arange(n) - np.repeat(start, length)) % 2 == 1))
    fresh = np.where(at_vowel,
                     rng.choice(len(LETTERS), size=n, p=p_v / p_v.sum()),
                     rng.choice(len(LETTERS), size=n, p=p_c / p_c.sum()))
    out: list = []
    seen: set = set()
    for r in range(vocab):
        word = None
        if variant[r]:
            stem = out[base[r]]
            for t in range(spare):
                if suffixed[r] and t == 0:
                    cand = stem + SUFFIXES[suffix[r]]
                else:
                    cand = one_edit(stem, int(kind[r] + t) % 4,
                                    (at[r] + 0.37 * t) % 1.0,
                                    LETTERS[letters[r, t]])
                if cand and cand not in seen:
                    word = cand
                    break
        if word is None:
            s = start[r]
            word = "".join(LETTERS[c] for c in fresh[s: s + length[r]])
            t = 0
            while word in seen:  # a taken spelling grows a tail
                word += LETTERS[letters[r, t % spare]]
                t += 1
        seen.add(word)
        out.append(word)
    return out


def build(config: dict, seed: int, docs: int) -> dict:
    out = load_plugin("corpora", "zipf_text").build(config, seed, docs)
    p = config["corpus"]["args"]
    field = p["field"]
    pf = out["segment"].postings[field]
    spelled = spell(len(pf.terms), p["stats_seed"], p["spelling"])
    order = np.argsort(np.array(spelled, dtype=object), kind="stable")
    pf.terms = [spelled[i] for i in order]
    for name in ("term_df", "term_total_tf", "term_tile_start",
                 "term_tile_count"):
        setattr(pf, name, getattr(pf, name)[order])
    pf._term_index = None
    out["reference"]["spellings"] = spelled
    out["body_context"] = {
        "field": field,
        "spellings": spelled,
        "term_total_tf": out["body_context"]["term_total_tf"],
    }
    return out
