"""Plain reference for the late-interaction cell: ColBERT's re-ranking of
a BM25 window, as Elasticsearch's `rescore` phase applies it, from the
published rules and nothing of the program:

  the window is the first `window_size` passages of the `match` by score
  descending, then document ascending (Lucene's order; fewer where fewer
  match) - the existing plain reference `bm25_match`, loaded by name and
  asked for that many (float64 BM25);
  S(q, d) = sum over the query's token vectors q_i of the max over the
  passage's OWN token vectors d_j of q_i . d_j (0 for a passage without
  vectors), float64, one candidate at a time over exactly its own rows:
  no padding, no kernel;
  inside the window a passage's score becomes query_weight x BM25 +
  rescore_query_weight x S (QueryRescorer, score_mode total) and the
  window is ordered by that score descending, then first-stage rank
  ascending; passages past the window keep their BM25 score and order
  below it; the page is the first `size`;
  `hits.total` is the first stage's.

The token vectors are the raw seeded bytes (`tok_rows`, `tok_offsets`),
the query vectors the body's own decimals.

`precision="lower"` is the control: the query rows and every product
rounded to bfloat16 (the step below the float32 the configuration
states; what an MXU contraction at the default precision computes), the
sums in float32. The first stage stays in full precision: the control
lowers the kernel this configuration states a precision for.
"""

from __future__ import annotations

import numpy as np

from lowprec import to_bf16
from plugins import load_plugin


class Reference:
    def __init__(self, data: dict, config: dict):
        self.text_field = data["text"]["field"]
        self.text = load_plugin("references", "bm25_match").Reference(
            data["text"], config)
        self.tok_field = data["tok_field"]
        self.rows, self.offsets = data["tok_rows"], data["tok_offsets"]

    def maxsim(self, q: np.ndarray, doc: int, precision: str) -> float:
        rows = self.rows[int(self.offsets[doc]):int(self.offsets[doc + 1])]
        if not len(rows):
            return 0.0
        if precision == "lower":
            prod = to_bf16(to_bf16(q.astype(np.float32))[:, None, :]
                           * rows.astype(np.float32)[None, :, :])
            dots = prod.sum(axis=2, dtype=np.float32)
        else:
            dots = q @ rows.astype(np.float64).T
        return float(dots.max(axis=1).sum(dtype=dots.dtype))

    def answer_many(self, bodies: list, precision: str = "full") -> list:
        return [self.answer(b, precision) for b in bodies]

    def answer(self, body: dict, precision: str = "full") -> dict:
        size = int(body.get("size", 10))
        rescore = body["rescore"]
        window = int(rescore.get("window_size", 10))
        block = rescore["query"]
        params = block["rescore_query"]["rank_vectors"]
        assert params["field"] == self.tok_field
        q = np.asarray(params["query_vectors"], np.float64)
        qw = float(block.get("query_weight", 1.0))
        rw = float(block.get("rescore_query_weight", 1.0))
        first = self.text.answer(
            {"query": body["query"], "size": max(size, window)})
        hits = first["hits"]["hits"]
        blended = [qw * h["_score"]
                   + rw * self.maxsim(q, int(h["_id"]), precision)
                   for h in hits[:window]]
        order = sorted(range(len(blended)), key=lambda r: (-blended[r], r))
        ranked = ([(hits[r]["_id"], blended[r]) for r in order]
                  + [(h["_id"], h["_score"]) for h in hits[window:]])
        return {"hits": {"total": first["hits"]["total"], "hits": [
            {"_id": d, "_score": float(s)} for d, s in ranked[:size]]}}
