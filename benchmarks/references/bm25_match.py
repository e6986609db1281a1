"""Plain reference for the text cell: Elasticsearch's default
BM25Similarity on a `match` query (an OR of its terms), straight from the
published formulas (Lucene 8+ BM25Similarity, SmallFloat.intToByte4):

  idf(t)   = ln(1 + (N - df + 0.5) / (df + 0.5))
  avgdl    = sumTotalTermFreq / N
  score(d) = sum_t idf(t) * tf / (tf + k1 * (1 - b + b * dl(d) / avgdl))

with dl the field length as one SmallFloat byte holds it. float64
throughout; no import of the program; the data are the raw seeded
posting stream, not the program's tiles. `hits.total` follows
`track_total_hits`'s default: exact up to 10,000, then a `gte` bound.

`precision="lower"` is the control: each term's contribution and the
running sum rounded to bfloat16 (the step below the float32 the
configuration states).
"""

from __future__ import annotations

import numpy as np

from lowprec import to_bf16

TRACK_TOTAL_HITS = 10_000


def _long_to_int4(i: int) -> int:
    bits = int(i).bit_length()
    if bits < 4:
        return int(i)
    shift = bits - 4
    return ((int(i) >> shift) & 0x07) | ((shift + 1) << 3)


def _int4_to_long(i: int) -> int:
    bits, shift = i & 0x07, (i >> 3) - 1
    return bits if shift == -1 else (bits | 0x08) << shift


_FREE = 255 - _long_to_int4(2**31 - 1)  # 24 lengths encode as themselves
LENGTH_TABLE = np.array(
    [b if b < _FREE else _FREE + _int4_to_long(b - _FREE) for b in range(256)],
    dtype=np.int64,
)


def quantized_lengths(lengths: np.ndarray) -> np.ndarray:
    """decode(encode(length)): the largest table value <= length."""
    idx = np.searchsorted(LENGTH_TABLE, lengths, side="right") - 1
    return LENGTH_TABLE[idx]


class Reference:
    def __init__(self, data: dict, config: dict):
        g = config["guarantees"]
        self.k1, self.b = float(g["bm25_k1"]), float(g["bm25_b"])
        self.n = int(data["docs"])
        self.field = data["field"]
        self.post_start = data["post_start"]
        self.post_doc, self.post_tf = data["post_doc"], data["post_tf"]
        self.df = np.diff(self.post_start)
        dl = quantized_lengths(data["lengths"]).astype(np.float64)
        avgdl = float(self.post_tf.sum(dtype=np.int64)) / self.n
        self.denom = self.k1 * (1.0 - self.b + self.b * dl / avgdl)

    def answer_many(self, bodies: list, precision: str = "full") -> list:
        return [self.answer(b, precision) for b in bodies]

    def answer(self, body: dict, precision: str = "full") -> dict:
        text = body["query"]["match"][self.field]
        size = int(body.get("size", 10))
        terms = sorted({int(tok[1:]) for tok in text.split()})
        # a dense float64 plane over the shard: a question's stop words
        # match nearly every passage
        score = np.zeros(self.n, np.float64)
        low = np.zeros(self.n, np.float32)
        hit = np.zeros(self.n, bool)
        for t in terms:
            lo, hi = int(self.post_start[t]), int(self.post_start[t + 1])
            d = self.post_doc[lo:hi]  # a term's docs are distinct
            tf = self.post_tf[lo:hi].astype(np.float64)
            idf = np.log(1.0 + (self.n - self.df[t] + 0.5) / (self.df[t] + 0.5))
            s = idf * tf / (tf + self.denom[d])
            if precision == "lower":
                # term by term, the product and the sum rounded at every step
                low[d] = to_bf16(low[d] + to_bf16(s.astype(np.float32)))
            else:
                score[d] += s
            hit[d] = True
        uniq = np.flatnonzero(hit)
        if not len(uniq):
            return {"hits": {"total": {"value": 0, "relation": "eq"},
                             "hits": []}}
        score = (low if precision == "lower" else score)[uniq].astype(
            np.float64)
        # Lucene's order: score descending, then doc id ascending
        take = min(size, len(uniq))
        if take < len(uniq):
            cut = np.argpartition(-score, take - 1)[:take]
            kth = score[cut].min()
            cand = np.flatnonzero(score >= kth)
        else:
            cand = np.arange(len(uniq))
        order = cand[np.lexsort((uniq[cand], -score[cand]))][:take]
        n = len(uniq)
        total = ({"value": n, "relation": "eq"} if n <= TRACK_TOTAL_HITS
                 else {"value": TRACK_TOTAL_HITS, "relation": "gte"})
        return {"hits": {"total": total, "hits": [
            {"_id": str(int(uniq[i])), "_score": float(score[i])}
            for i in order
        ]}}
