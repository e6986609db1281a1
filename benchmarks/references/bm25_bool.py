"""Plain reference for the Boolean cell: Elasticsearch's `bool` query of
text clauses under the default BM25Similarity, straight from the
published definitions (Lucene's BooleanQuery; `bm25_match.py`'s BM25 and
SmallFloat length byte, whose `Reference` holds the data here too):

  a clause is a `term`, a `match` (operator or: it matches a passage that
  holds ANY of its words) or, one level deep under `must` / `should`, a
  `bool` of such clauses; its score on a passage is the sum of
  idf(t) * tf / (tf + k1 * (1 - b + b * dl / avgdl)) over its words the
  passage holds;
  a passage matches the `bool` if EVERY `must` clause matches it or, with
  no `must`, if ANY `should` clause does (the default
  `minimum_should_match`: 1 without `must`, 0 beside it);
  score(d) = the sum of the scores of every clause, `must` or `should`,
  that matches d.

Per clause a hit mask and a score plane over the shard, `must` masks
ANDed, `should` masks ORed, scores summed; float64 throughout; order by
score descending then passage ascending; `hits.total` by
`track_total_hits`'s default: exact up to 10,000, then a `gte` bound. No
import of the program; the data are the raw seeded posting stream, not
the program's tiles. `must_not`, `filter`, `minimum_should_match`,
`operator`, boosts and deeper nesting are not parsed: they raise.

`precision="lower"` is the control: each word's contribution and every
running sum rounded to bfloat16 (the step below the float32 the
configuration states).
"""

from __future__ import annotations

import numpy as np

from lowprec import to_bf16
from plugins import load_plugin

_match = load_plugin("references", "bm25_match")
TRACK_TOTAL_HITS = _match.TRACK_TOTAL_HITS


class Reference(_match.Reference):
    """The one-field reference's data (postings, df, the BM25 length
    term of every passage) and `answer_many`; `answer` is this file's."""

    def _words(self, hit, score, words: list, low: bool) -> None:
        """ORs the passages that hold any of `words` into `hit` and adds
        each word's BM25 contribution to `score`."""
        for word in words:
            t = int(word[1:])
            lo, hi = int(self.post_start[t]), int(self.post_start[t + 1])
            d = self.post_doc[lo:hi]  # a term's passages are distinct
            tf = self.post_tf[lo:hi].astype(np.float64)
            idf = np.log(1.0 + (self.n - self.df[t] + 0.5) / (self.df[t] + 0.5))
            s = idf * tf / (tf + self.denom[d])
            if low:
                score[d] = to_bf16(
                    score[d].astype(np.float32) + to_bf16(s.astype(np.float32)))
            else:
                score[d] += s
            hit[d] = True

    def _clause(self, clause: dict, low: bool, nested: bool):
        """(hit mask, score plane) of one clause."""
        (kind, inner), = clause.items()
        hit, score = np.zeros(self.n, bool), np.zeros(self.n, np.float64)
        if kind in ("term", "match"):
            (field, text), = inner.items()
            if field != self.field or not isinstance(text, str):
                raise ValueError(f"clause outside the reference: {clause}")
            self._words(hit, score, text.split() if kind == "match" else [text],
                        low)
            return hit, score
        if kind == "bool" and nested:
            return self._bool(inner, low, nested=False)
        raise ValueError(f"clause outside the reference: {clause}")

    def _bool(self, q: dict, low: bool, nested: bool = True):
        if set(q) - {"must", "should"} or not q:
            raise ValueError(f"bool outside the reference: {sorted(q)}")
        must = [self._clause(c, low, nested) for c in q.get("must", [])]
        should = [self._clause(c, low, nested) for c in q.get("should", [])]
        if must:
            hit = np.logical_and.reduce([h for h, _s in must])
        else:
            hit = np.logical_or.reduce([h for h, _s in should])
        score = np.zeros(self.n, np.float64)
        for _h, s in must + should:
            # a clause that does not match d scored nothing on d
            score = to_bf16((score + s).astype(np.float32)).astype(
                np.float64) if low else score + s
        return hit, np.where(hit, score, 0.0)

    def answer(self, body: dict, precision: str = "full") -> dict:
        (kind, q), = body["query"].items()
        if kind != "bool":
            raise ValueError(f"query outside the reference: {kind}")
        size = int(body.get("size", 10))
        hit, plane = self._bool(q, precision == "lower")
        uniq = np.flatnonzero(hit)
        if not len(uniq):
            return {"hits": {"total": {"value": 0, "relation": "eq"},
                             "hits": []}}
        score = plane[uniq]
        # Lucene's order: score descending, then doc id ascending
        take = min(size, len(uniq))
        if take < len(uniq):
            kth = np.partition(score, len(uniq) - take)[len(uniq) - take]
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
