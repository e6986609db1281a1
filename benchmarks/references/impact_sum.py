"""Plain reference for the learned-sparse cell: Elasticsearch's
`sparse_vector` query over a `sparse_vector` field, straight from the
definitions (ES 8.15 `SparseVectorQueryBuilder`: a disjunction of one
`FeatureField` clause a query token, each scoring query weight x stored
feature value):

  a passage matches if it holds ANY token of the query vector;
  score(d) = sum over the tokens d shares with the query of
             query_weight(t) x stored_impact(t, d), in float32;
  order by score descending then passage ascending; `hits.total` by
  `track_total_hits`'s default: exact up to 10,000, then a `gte` bound.

The stored impact is what the configuration's index format keeps
(`guarantees.stored`), computed HERE from the raw float32 weights by the
stated formula, not read from the program's planes:

  int8:    scale(t) = max_d |w(t, d)| / 127 (float32),
           q = clip(rint(w / scale), -127, 127), stored = q x scale;
  float32: stored = w.

No import of the program; the data are the raw seeded posting stream in
(term, passage) order, not the program's tiles.

`precision="lower"` is the control: every product and every running sum
rounded to bfloat16 (the step below the float32 the configuration
states). Beside it the reference prints once what big-ann-benchmarks'
sparse track asks of an index, the recall@10 of the answers over the
stored impacts against the answers over the float32 weights.
"""

from __future__ import annotations

import numpy as np

from lowprec import to_bf16

TRACK_TOTAL_HITS = 10_000


def quantize_int8(post_w: np.ndarray, post_start: np.ndarray) -> np.ndarray:
    """stored = q x scale a posting, per-term symmetric int8."""
    df = np.diff(post_start)
    top = np.maximum.reduceat(
        np.abs(post_w), np.minimum(post_start[:-1], len(post_w) - 1))
    top = np.where(df > 0, top, 0.0).astype(np.float32)
    scale = (top / np.float32(127.0)).astype(np.float32)
    safe = np.where(scale == 0.0, np.float32(1.0), scale).astype(np.float32)
    q = np.clip(np.rint(post_w / np.repeat(safe, df)), -127, 127).astype(
        np.float32)
    return (q * np.repeat(scale, df)).astype(np.float32)


class Reference:
    def __init__(self, data: dict, config: dict):
        self.n = int(data["docs"])
        self.field = data["field"]
        self.post_start = data["post_start"]
        self.post_doc, self.post_w = data["post_doc"], data["post_w"]
        self.term_of = {int(t): i for i, t in enumerate(data["terms"])}
        stored = config["guarantees"]["stored"]
        if stored == "int8":
            self.stored = quantize_int8(self.post_w, self.post_start)
        elif stored == "float32":
            self.stored = self.post_w
        else:
            raise ValueError(f"stored impacts outside the reference: {stored}")

    def answer_many(self, bodies: list, precision: str = "full") -> list:
        if precision == "lower" and self.stored is not self.post_w:
            self.say_recall(bodies)
        return [self.answer(b, precision) for b in bodies]

    def say_recall(self, bodies: list) -> None:
        recalls = []
        for body in bodies:
            want = {h["_id"] for h in
                    self.answer(body, impacts=self.post_w)["hits"]["hits"]}
            got = {h["_id"] for h in self.answer(body)["hits"]["hits"]}
            if want:
                recalls.append(len(want & got) / len(want))
        print(f"[reference] recall of the stored impacts' top pages against "
              f"float32 weights' over {len(recalls)} requests: mean "
              f"{np.mean(recalls):.4f}, min {np.min(recalls):.2f}",
              flush=True)

    def answer(self, body: dict, precision: str = "full",
               impacts: np.ndarray = None) -> dict:
        (kind, q), = body["query"].items()
        if kind != "sparse_vector" or q["field"] != self.field or set(q) - {
                "field", "query_vector"}:
            raise ValueError(f"query outside the reference: {body['query']}")
        impacts = self.stored if impacts is None else impacts
        size = int(body.get("size", 10))
        low = precision == "lower"
        score = np.zeros(self.n, np.float32)
        hit = np.zeros(self.n, bool)
        for token, weight in sorted(q["query_vector"].items()):
            t = self.term_of.get(int(token[1:]))
            if t is None:
                continue  # a token no passage holds
            lo, hi = int(self.post_start[t]), int(self.post_start[t + 1])
            d = self.post_doc[lo:hi]  # a term's passages are distinct
            s = np.float32(weight) * impacts[lo:hi]
            if low:
                score[d] = to_bf16(score[d] + to_bf16(s))
            else:
                score[d] += s
            hit[d] = True
        uniq = np.flatnonzero(hit)
        if not len(uniq):
            return {"hits": {"total": {"value": 0, "relation": "eq"},
                             "hits": []}}
        score = score[uniq].astype(np.float64)
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
