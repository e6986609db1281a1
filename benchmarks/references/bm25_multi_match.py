"""Plain reference for the document cell: Elasticsearch's `multi_match`
at its default type, `best_fields`, straight from the published
definitions. The query is a DisjunctionMaxQuery over one `match` query a
field (an OR of its terms under the field's default BM25Similarity,
Lucene 8+; `bm25_match.py`'s formulas, field by field, each field with its
own docCount, avgdl and SmallFloat length byte):

  field(d) = sum_t idf_f(t) * tf / (tf + k1 * (1 - b + b * dl_f(d) / avgdl_f))
  score(d) = max_f field(d) + tie_breaker * (sum_f field(d) - max_f field(d))

over the fields that match d; d matches if any field holds any word.
docCount_f counts the documents that have the field, and avgdl_f =
sumTotalTermFreq_f / docCount_f. float64 throughout; no import of the
program; the data are the raw seeded posting streams of both fields, not
the program's tiles. `hits.total` follows `track_total_hits`'s default:
exact up to 10,000, then a `gte` bound.

`precision="lower"` is the control: each term's contribution, each
field's running sum and the combination rounded to bfloat16 (the step
below the float32 the configuration states).
"""

from __future__ import annotations

import numpy as np

from lowprec import to_bf16
from plugins import load_plugin

TRACK_TOTAL_HITS = 10_000
# SmallFloat's length byte, as the one-field reference decodes it
quantized_lengths = load_plugin("references", "bm25_match").quantized_lengths


class _Field:
    def __init__(self, raw: dict, k1: float, b: float):
        self.post_start = raw["post_start"]
        self.post_doc, self.post_tf = raw["post_doc"], raw["post_tf"]
        self.df = np.diff(self.post_start)
        self.doc_count = int(np.count_nonzero(raw["lengths"]))
        dl = quantized_lengths(raw["lengths"]).astype(np.float64)
        avgdl = float(self.post_tf.sum(dtype=np.int64)) / self.doc_count
        self.denom = k1 * (1.0 - b + b * dl / avgdl)

    def add(self, t: int, score: np.ndarray, hit: np.ndarray, low: bool):
        lo, hi = int(self.post_start[t]), int(self.post_start[t + 1])
        if lo == hi:
            return
        d = self.post_doc[lo:hi]  # a term's docs are distinct
        tf = self.post_tf[lo:hi].astype(np.float64)
        n, df = self.doc_count, self.df[t]
        s = np.log(1.0 + (n - df + 0.5) / (df + 0.5)) * tf / (tf + self.denom[d])
        if low:
            # term by term, the product and the sum rounded at every step
            score[d] = to_bf16(
                score[d].astype(np.float32) + to_bf16(s.astype(np.float32)))
        else:
            score[d] += s
        hit[d] = True


class Reference:
    def __init__(self, data: dict, config: dict):
        g = config["guarantees"]
        self.n = int(data["docs"])
        self.fields = {
            name: _Field(raw, float(g["bm25_k1"]), float(g["bm25_b"]))
            for name, raw in data["fields"].items()}

    def answer_many(self, bodies: list, precision: str = "full") -> list:
        return [self.answer(b, precision) for b in bodies]

    def answer(self, body: dict, precision: str = "full") -> dict:
        q = body["query"]["multi_match"]
        size = int(body.get("size", 10))
        tie = float(q.get("tie_breaker", 0.0))
        low = precision == "lower"
        terms = sorted({int(tok[1:]) for tok in q["query"].split()})
        hit = np.zeros(self.n, bool)
        per_field = []
        for name in q["fields"]:
            # a dense float64 plane a field: a question's stop words match
            # nearly every document
            score = np.zeros(self.n, np.float64)
            for t in terms:
                self.fields[name].add(t, score, hit, low)
            per_field.append(score)
        uniq = np.flatnonzero(hit)
        if not len(uniq):
            return {"hits": {"total": {"value": 0, "relation": "eq"},
                             "hits": []}}
        stack = np.stack([s[uniq] for s in per_field])
        best = stack.max(axis=0)
        if low:
            rest = to_bf16((stack.sum(axis=0) - best).astype(np.float32))
            score = to_bf16(best.astype(np.float32)
                            + to_bf16(np.float32(tie) * rest)).astype(np.float64)
        else:
            score = best + tie * (stack.sum(axis=0) - best)
        # Lucene's order: score descending, then doc id ascending
        take = min(size, len(uniq))
        if take < len(uniq):
            cut = np.argpartition(-score, take - 1)[:take]
            cand = np.flatnonzero(score >= score[cut].min())
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
