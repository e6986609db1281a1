"""`match_phrase` bodies in luceneutil's phrase task classes
(`tasks/wikimedium.10M.tasks`: HighPhrase, MedPhrase, LowPhrase): exact
phrases (slop 0) that are RUNS OF CONSECUTIVE TOKENS OF STORED PASSAGES,
as luceneutil takes its phrases from the indexed text, binned by the
number of passages of THIS shard that hold the phrase
(`args["df_share"]`, as shares of the shard's passages: High, Med, Low
as [from, below)). Five classes in equal shares: `HighPhrase`,
`MedPhrase`, `LowPhrase` of two words, `MedPhrase3`, `LowPhrase3` of
three. No word stands twice in a phrase; no word is left out for being
frequent.

The classes come from a census of the token stream the corpus builder
hands over (`context["tokens"]`, `context["doc_start"]`): every
distinct run of two tokens with the number of passages holding it (one
sort of (word, word, passage) keys), then every distinct run of three
whose first two words are held by at least the lowest class's floor of
passages (a run of three is held by no more passages than its first
two). A phrase is drawn uniformly among the distinct phrases of its
class. The classes are cut once a corpus (kept by the stream's
identity; `make` is called from several threads).
"""

from __future__ import annotations

import json
import threading
from concurrent.futures import ThreadPoolExecutor

import numpy as np

# name -> (class of the phrase's passage count, its words)
CLASSES = {
    "HighPhrase": ("High", 2),
    "MedPhrase": ("Med", 2),
    "LowPhrase": ("Low", 2),
    "MedPhrase3": ("Med", 3),
    "LowPhrase3": ("Low", 3),
}
BITS = 20  # of a term id and of a passage in the census's sort keys
MASK = np.uint64((1 << BITS) - 1)

_census_lock = threading.Lock()
_census: dict = {}


def _runs(keys: np.ndarray):
    """(distinct values of keys >> BITS, how many distinct keys each
    has) of a SORTED key array whose low BITS hold the passage."""
    new = np.empty(len(keys), bool)
    new[:1] = True
    np.not_equal(keys[1:], keys[:-1], out=new[1:])
    run = keys[new] >> np.uint64(BITS)  # one entry a (run, passage)
    first = np.empty(len(run), bool)
    first[:1] = True
    np.not_equal(run[1:], run[:-1], out=first[1:])
    starts = np.flatnonzero(first)
    return run[starts], np.diff(starts, append=len(run))


def census(tokens: np.ndarray, doc_start: np.ndarray, floor: int):
    """{2: (int32[n, 2] runs, their passage counts), 3: (int32[n, 3],
    counts)}: every distinct run of two tokens, and the runs of three
    held by >= `floor` passages whose first two words are too."""
    docs = len(doc_start) - 1
    if docs > 1 << BITS or int(tokens.max()) >= 1 << BITS:
        raise ValueError("the census's sort keys hold 20 bits of term "
                         "and 20 of passage")
    lengths = np.diff(doc_start)
    passage = np.repeat(np.arange(docs, dtype=np.uint64), lengths)
    # slots left in its passage from each token on, itself counted
    room = np.repeat(doc_start[1:], lengths)
    room -= np.arange(len(tokens), dtype=np.int64)
    t = tokens.astype(np.uint64)
    two = room[:-1] >= 2  # a run of two starts here
    pair = ((t[:-1] << np.uint64(BITS)) | t[1:])[two]
    keys = (pair << np.uint64(BITS)) | passage[:-1][two]
    keys.sort()
    pairs, pair_df = _runs(keys)
    del keys
    out = {2: (np.stack([(pairs >> np.uint64(BITS)).astype(np.int32),
                         (pairs & MASK).astype(np.int32)], axis=1),
               pair_df)}
    # runs of three over the frequent pairs: a pair's rank in the sorted
    # table of frequent pairs stands for its two words in the key
    frequent = pairs[pair_df >= floor]
    if len(frequent) >= 1 << (64 - 2 * BITS):
        raise ValueError("too many frequent pairs for the triples' keys")
    longer = room[:-1][two] >= 3
    three, lead = np.flatnonzero(two)[longer], pair[longer]

    def find(lo_hi):
        lo, hi = lo_hi
        i = np.minimum(np.searchsorted(frequent, lead[lo:hi]),
                       max(len(frequent) - 1, 0))
        return i, frequent[i] == lead[lo:hi]

    cuts = np.linspace(0, len(three), 17).astype(np.int64)
    with ThreadPoolExecutor(max_workers=8) as pool:
        parts = list(pool.map(find, zip(cuts[:-1], cuts[1:])))
    rank = np.concatenate([i for i, _ok in parts]).astype(np.uint64)
    held = np.concatenate([ok for _i, ok in parts])
    three, rank = three[held], rank[held]
    keys = (((rank << np.uint64(BITS)) | t[three + 2]) << np.uint64(BITS)
            | passage[three])
    keys.sort()
    triples, triple_df = _runs(keys)
    keep = triple_df >= floor
    triples, triple_df = triples[keep], triple_df[keep]
    first_two = frequent[(triples >> np.uint64(BITS)).astype(np.int64)]
    out[3] = (np.stack([(first_two >> np.uint64(BITS)).astype(np.int32),
                        (first_two & MASK).astype(np.int32),
                        (triples & MASK).astype(np.int32)], axis=1),
              triple_df)
    return out


def census_floor(args: dict, docs: int) -> int:
    """The fewest passages a phrase of any class is held by."""
    return max(1, int(np.ceil(
        min(lo for lo, _hi in args["df_share"].values()) * docs)))


def class_phrases(context: dict, args: dict) -> dict:
    """{class name: int32[n, words] the distinct phrases of the class},
    from the context's census (the corpus builder makes it beside its
    own sorts) or one made here."""
    tokens = context["tokens"]
    with _census_lock:
        got = _census.get(id(tokens))
        if got is not None and got[0] is tokens:
            return got[1]
        docs = int(context["docs"])
        runs = context.get("census") or census(
            tokens, context["doc_start"], census_floor(args, docs))
        out = {}
        for name, (cls, words) in CLASSES.items():
            lo, hi = args["df_share"][cls]
            phrases, df = runs[words]
            inside = df >= lo * docs
            if hi is not None:
                inside &= df < hi * docs
            distinct = np.ones(len(phrases), bool)  # no word twice
            for a in range(words):
                for b in range(a + 1, words):
                    distinct &= phrases[:, a] != phrases[:, b]
            out[name] = phrases[inside & distinct]
        _census.clear()  # one corpus a process
        _census[id(tokens)] = (tokens, out)
        return out


def class_of(body: dict, field: str, phrases: dict) -> str:
    """The task class of one body this generator made, `phrases` being
    `class_phrases` of the same context and arguments."""
    words = [int(w[1:]) for w in body["query"]["match_phrase"][field].split()]
    for name, (_cls, n) in CLASSES.items():
        if n == len(words) and (phrases[name] == words).all(axis=1).any():
            return name
    raise ValueError(f"phrase {words} is of no class")


def make(context: dict, args: dict, rng: np.random.Generator, n: int) -> list:
    phrases = class_phrases(context, args)
    field, width = context["field"], context["term_width"]
    names = sorted(CLASSES)
    out = []
    for name in rng.choice(names, size=n):  # equal shares
        pool = phrases[name]
        words = pool[int(rng.integers(len(pool)))]
        text = " ".join(f"w{t:0{width}d}" for t in words.tolist())
        body = {"query": {"match_phrase": {field: text}},
                "size": args["size"], "_source": False}
        out.append(json.dumps(body, separators=(",", ":")).encode())
    return out
