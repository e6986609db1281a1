"""Plain reference for the filtered and negated Boolean cell:
Elasticsearch's `bool` with `filter` and `must_not` clauses under the
default BM25Similarity, straight from the published definitions
(Lucene's BooleanQuery; `bm25_bool.py`'s clauses and scores, whose
`Reference` holds the text's data here too):

  a passage matches the `bool` if EVERY `must` clause matches it, EVERY
  `filter` clause matches it and NO `must_not` clause does; of the
  `should` clauses at least `minimum_should_match` must match, by
  default 1 when the `bool` holds neither `must` nor `filter`, else 0;
  score(d) = the sum of the scores of every `must` and `should` clause
  that matches d: a `filter` clause adds nothing to the score, and a
  `must_not` clause's passages never match, whatever they would score;
  a `filter` clause here is a `term` or `terms` on the keyword field of
  tags (a passage matches if its bag holds the tag, any of the tags).

Per clause a hit mask and a score plane over the shard; float64
throughout; order by score descending then passage ascending;
`hits.total` by `track_total_hits`'s default: exact up to 10,000, then a
`gte` bound. No import of the program; the data are the raw seeded
streams (the text's postings; the bags of tags row-major, put in tag
order by a sort of this file's own), not the program's tiles.

`precision="lower"` is the control: each word's contribution and every
running sum rounded to bfloat16 (the step below the float32 the
configuration states); masks and totals are whole numbers and do not
move.
"""

from __future__ import annotations

import numpy as np

from lowprec import to_bf16
from plugins import load_plugin

_bool = load_plugin("references", "bm25_bool")
TRACK_TOTAL_HITS = _bool.TRACK_TOTAL_HITS


class Reference(_bool.Reference):
    def __init__(self, data: dict, config: dict):
        super().__init__(data, config)
        self.tag_field = data["tag_field"]
        # the passages of each tag: the row-major bags in tag order
        sizes = np.diff(data["bag_start"])
        row = np.repeat(np.asarray(data["bag_row"], np.int64), sizes)
        order = np.argsort(data["bag_tags"], kind="stable")
        self.tag_doc = row[order]
        n_tags = int(data["bag_tags"].max()) + 1 if len(order) else 0
        self.tag_start = np.zeros(n_tags + 1, np.int64)
        np.cumsum(np.bincount(data["bag_tags"], minlength=n_tags),
                  out=self.tag_start[1:])

    def _tagged(self, clause: dict) -> np.ndarray:
        """The passages a `filter` clause matches."""
        (kind, inner), = clause.items()
        (field, value), = inner.items()
        if kind not in ("term", "terms") or field != self.tag_field:
            raise ValueError(f"filter outside the reference: {clause}")
        hit = np.zeros(self.n, bool)
        for name in (value if kind == "terms" else [value]):
            t = int(name[1:])
            if 0 <= t < len(self.tag_start) - 1:
                hit[self.tag_doc[self.tag_start[t]:self.tag_start[t + 1]]] = True
        return hit

    def _bool(self, q: dict, low: bool, nested: bool = True):
        extra = {"filter", "must_not", "minimum_should_match"}
        if not nested or not set(q) & extra:
            return super()._bool(q, low, nested)
        if set(q) - extra - {"must", "should"}:
            raise ValueError(f"bool outside the reference: {sorted(q)}")
        must = [self._clause(c, low, False) for c in q.get("must", [])]
        should = [self._clause(c, low, False) for c in q.get("should", [])]
        filters = [self._tagged(c) for c in q.get("filter", [])]
        msm = q.get("minimum_should_match")
        if msm is None:
            msm = 0 if (must or filters) else 1
        if not isinstance(msm, int) or not (must or should):
            raise ValueError(f"bool outside the reference: {q}")
        hit = np.ones(self.n, bool)
        for h in [h for h, _s in must] + filters:
            hit &= h
        if msm > 0:
            hit &= np.sum([h for h, _s in should], axis=0) >= msm
        for c in q.get("must_not", []):
            hit &= ~self._clause(c, low, False)[0]
        score = np.zeros(self.n, np.float64)
        for _h, s in must + should:
            # a clause that does not match d scored nothing on d
            score = to_bf16((score + s).astype(np.float32)).astype(
                np.float64) if low else score + s
        return hit, np.where(hit, score, 0.0)
