"""Plain reference for the phrase cell: Elasticsearch's `match_phrase`
(slop 0) as Lucene's PhraseWeight scores it over the default
BM25Similarity, from the RAW TOKEN STREAM alone (passage -> term ids in
order): no postings, no positions plane, no batching, no import of the
program.

  a passage matches iff the words stand at consecutive positions in the
  query's order at least once;
  f        = the number of positions at which the phrase starts in it
  idf(t)   = ln(1 + (N - df(t) + 0.5) / (df(t) + 0.5))
  avgdl    = sumTotalTermFreq / N
  score(d) = (sum over the words of idf(word))
             * f / (f + k1 * (1 - b + b * dl(d) / avgdl))

with dl the passage's length as one SmallFloat byte holds it (decoded as
`references/bm25_match.py` decodes it) and df(t) the number of passages
holding t, counted here from the stream. float64 throughout, rounded to
float32 at the end as the program reports it. `hits.total` follows
`track_total_hits`'s default: exact up to 10,000, then a `gte` bound.
Order: score descending, ties by passage id.

The phrase is found by comparing shifted copies of the token array
(`tok[i] == a & tok[i + 1] == b ...`), a start kept where the whole run
lies inside one passage.

`precision="lower"` is the control: the summed idf, the tf factor and
their product each rounded to bfloat16 (the step below the float32 the
configuration states).
"""

from __future__ import annotations

import numpy as np

from lowprec import to_bf16
from plugins import load_plugin

TRACK_TOTAL_HITS = 10_000


class Reference:
    def __init__(self, data: dict, config: dict):
        g = config["guarantees"]
        self.k1, self.b = float(g["bm25_k1"]), float(g["bm25_b"])
        self.n = int(data["docs"])
        self.field = data["field"]
        self.tok = np.asarray(data["tokens"])
        self.doc_start = np.asarray(data["doc_start"], np.int64)
        self.passage_id = np.asarray(data["passage_id"], np.int64)
        lengths = np.diff(self.doc_start)
        # the passage of every token slot, and how many slots follow it
        # inside its passage
        self.slot_passage = np.repeat(
            np.arange(self.n, dtype=np.int32), lengths)
        self.room = (np.repeat(self.doc_start[1:], lengths)
                     - np.arange(len(self.tok), dtype=np.int64))
        quantized = load_plugin(
            "references", "bm25_match").quantized_lengths
        dl = quantized(lengths).astype(np.float64)
        avgdl = float(len(self.tok)) / self.n
        self.denom = self.k1 * (1.0 - self.b + self.b * dl / avgdl)
        # document frequencies from the stream: distinct (term, passage)
        pair = np.unique(self.tok.astype(np.int64) * self.n
                         + self.slot_passage)
        self.df = np.bincount(pair // self.n, minlength=int(data["vocab"]))

    def answer_many(self, bodies: list, precision: str = "full") -> list:
        return [self.answer(b, precision) for b in bodies]

    def phrase_freq(self, words: list):
        """(passages holding the phrase, its frequency in each)."""
        w = len(words)
        tok = self.tok
        last = len(tok) - w + 1
        hit = tok[:last] == words[0]
        for r in range(1, w):
            hit &= tok[r: last + r] == words[r]
        hit &= self.room[:last] >= w  # the run stays inside the passage
        passages, freq = np.unique(
            self.slot_passage[:last][hit], return_counts=True)
        return passages, freq

    def answer(self, body: dict, precision: str = "full") -> dict:
        spec = body["query"]["match_phrase"][self.field]
        text = spec["query"] if isinstance(spec, dict) else spec
        size = int(body.get("size", 10))
        words = [int(tok[1:]) for tok in text.split()]
        known = max(words) < len(self.df)  # else: a word no passage holds
        passages, freq = self.phrase_freq(words) if known else ((), ())
        n = len(passages)
        if not n:
            return {"hits": {"total": {"value": 0, "relation": "eq"},
                             "hits": []}}
        df = self.df[words].astype(np.float64)
        idf = np.log(1.0 + (self.n - df + 0.5) / (df + 0.5))
        f = freq.astype(np.float64)
        tf = f / (f + self.denom[passages])
        if precision == "lower":
            weight = np.float32(0.0)
            for x in idf:
                weight = to_bf16(weight + to_bf16(np.float32(x)))
            score = to_bf16(weight * to_bf16(tf.astype(np.float32))).astype(
                np.float64)
        else:
            score = (idf.sum() * tf).astype(np.float32).astype(np.float64)
        ids = self.passage_id[passages]
        take = min(size, n)
        order = np.lexsort((ids, -score))[:take]
        total = ({"value": n, "relation": "eq"} if n <= TRACK_TOTAL_HITS
                 else {"value": TRACK_TOTAL_HITS, "relation": "gte"})
        return {"hits": {"total": total, "hits": [
            {"_id": str(int(ids[i])), "_score": float(score[i])}
            for i in order
        ]}}
