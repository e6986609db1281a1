"""Plain reference for the hybrid cell: upstream's `rrf` retriever
(x-pack rank-rrf, RRFRetrieverBuilder) over a `match` leg and an exact
kNN leg, from the published rule and nothing of the program:

  each leg ranks its top `rank_window_size` by score descending, then
  document ascending (ranks 1, 2, ...);
  score(d) = sum over the legs that rank d of 1 / (rank_constant + rank);
  the fused list is ordered by score descending, then document ascending,
  cut to `rank_window_size`, and the page is its first `size`.

The legs are the two existing plain references, loaded by name and asked
for a leg's window: `bm25_match` (float64 BM25) and `cosine_knn` (float32
candidates rescored in float64; this module's own loaded copy is told to
rescore enough candidates for a window of 100: its constant stands at 64,
for pages of 10). The sum is float64.

`hits.total` is what upstream reports for a ranked search, as the
configuration's `assumed` states it: the total of the combined query, a
passage counting once if the text query matches it or the kNN leg holds
it among its k, tracked to 10,000 (`eq` up to it, then `gte`). It is
counted here from the raw posting stream: the text query's passages as a
plane over the shard, plus the kNN leg's passages outside it.

`precision="lower"` is the control: both legs score in bfloat16 (their
own controls), which reorders ranks inside a leg; the RRF scores stay
exact rationals, so it shows as pages, not as score error.
"""

from __future__ import annotations

import numpy as np

from plugins import load_plugin

TRACK_TOTAL_HITS = 10_000


class Reference:
    def __init__(self, data: dict, config: dict):
        self.n = int(data["docs"])
        self.text_data = data["text"]
        self.text_field = data["text"]["field"]
        self.text = load_plugin("references", "bm25_match").Reference(
            data["text"], config)
        self.knn_mod = load_plugin("references", "cosine_knn")
        self.knn = self.knn_mod.Reference(data["vector"], config)

    def text_plane(self, query: dict) -> np.ndarray:
        """bool[n]: the passages a `match` (an OR of its words) matches."""
        start, doc = self.text_data["post_start"], self.text_data["post_doc"]
        hit = np.zeros(self.n, bool)
        for t in {int(tok[1:]) for tok in query["match"][self.text_field].split()}:
            hit[doc[int(start[t]):int(start[t + 1])]] = True
        return hit

    def answer_many(self, bodies: list, precision: str = "full") -> list:
        rrfs = [b["retriever"]["rrf"] for b in bodies]
        windows = [int(r.get("rank_window_size", b.get("size", 10)))
                   for r, b in zip(rrfs, bodies)]
        queries = [r["retrievers"][0]["standard"]["query"] for r in rrfs]
        knns = [r["retrievers"][1]["knn"] for r in rrfs]
        # the kNN leg holds its k, of which the window's ranks fuse
        depths = [max(w, int(k["k"])) for k, w in zip(knns, windows)]
        # enough float32 candidates a query for the deepest leg's ranks to
        # be settled in float64
        self.knn_mod.RESCORE = max(self.knn_mod.RESCORE, 2 * max(depths) + 56)
        text_legs = [self.text.answer({"query": q, "size": w}, precision)
                     for q, w in zip(queries, windows)]
        knn_legs = self.knn.answer_many(
            [{"knn": k, "size": d} for k, d in zip(knns, depths)], precision)
        out = []
        for body, rrf, window, query, text, knn in zip(
                bodies, rrfs, windows, queries, text_legs, knn_legs):
            constant = int(rrf.get("rank_constant", 60))
            fused: dict = {}
            for leg in (text, knn):
                # the leg's order is its ranks: score desc, then doc asc
                for rank, h in enumerate(leg["hits"]["hits"][:window], 1):
                    d = int(h["_id"])
                    fused[d] = fused.get(d, 0.0) + 1.0 / (constant + rank)
            order = sorted(fused.items(), key=lambda kv: (-kv[1], kv[0]))
            page = order[:window][:int(body.get("size", 10))]
            plane = self.text_plane(query)
            held = np.array([int(h["_id"]) for h in knn["hits"]["hits"]],
                            np.int64)
            n = int(plane.sum()) + int((~plane[held]).sum())
            total = ({"value": n, "relation": "eq"} if n <= TRACK_TOTAL_HITS
                     else {"value": TRACK_TOTAL_HITS, "relation": "gte"})
            out.append({"hits": {"total": total, "hits": [
                {"_id": str(d), "_score": float(s)} for d, s in page]}})
        return out
