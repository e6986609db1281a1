"""Plain reference for the kNN cell: exact brute-force cosine kNN as
Elasticsearch scores it, `_score = (1 + cos) / 2`, over the vectors as
they are stored (float16 rows normalized at index time and taken as unit
thereafter, which is what a `cosine` dense_vector field holds since ES
8.12). A float32 matrix pass in row blocks finds each query's candidates;
the candidates are then rescored in float64, so the scores compared are
exact to 1e-15. No import of the program; the data are the seeded rows.

`precision="lower"` is the control: both operands rounded to bfloat16
before the products (fp32 accumulation), the step below the float16 x
float32 products the configuration states.
"""

from __future__ import annotations

import numpy as np

from lowprec import to_bf16

BLOCK_ROWS = 65_536
RESCORE = 64  # candidates per query rescored in float64


class Reference:
    def __init__(self, data: dict, config: dict):
        self.field = data["field"]
        self.vectors = data["vectors"]  # float16[N, d]
        self.n = int(data["docs"])

    def answer_many(self, bodies: list, precision: str = "full") -> list:
        knn = [b["knn"] for b in bodies]
        q = np.array([k["query_vector"] for k in knn], np.float32)
        q64 = q.astype(np.float64)
        q64 /= np.linalg.norm(q64, axis=1, keepdims=True)
        qu = q64.astype(np.float32)
        if precision == "lower":
            qu = to_bf16(qu)
        keep = min(RESCORE, self.n)
        best_s = np.full((len(q), 0), -np.inf, np.float32)
        best_i = np.zeros((len(q), 0), np.int64)
        for lo in range(0, self.n, BLOCK_ROWS):
            blk = self.vectors[lo:lo + BLOCK_ROWS].astype(np.float32)
            if precision == "lower":
                blk = to_bf16(blk)
            s = qu @ blk.T  # float32[nq, rows]
            take = min(keep, s.shape[1])
            part = np.argpartition(-s, take - 1, axis=1)[:, :take]
            best_s = np.concatenate(
                [best_s, np.take_along_axis(s, part, axis=1)], axis=1)
            best_i = np.concatenate([best_i, part + lo], axis=1)
            if best_s.shape[1] > 4 * keep:
                sel = np.argpartition(-best_s, keep - 1, axis=1)[:, :keep]
                best_s = np.take_along_axis(best_s, sel, axis=1)
                best_i = np.take_along_axis(best_i, sel, axis=1)
        out = []
        for r, k in enumerate(knn):
            cand = best_i[r]
            if precision == "lower":
                cos = best_s[r].astype(np.float64)
            else:
                cos = self.vectors[cand].astype(np.float64) @ q64[r]
            score = (1.0 + cos) / 2.0
            kk = min(int(k["k"]), int(bodies[r].get("size", 10)), self.n)
            order = np.lexsort((cand, -score))[:kk]
            found = min(int(k["k"]), self.n)
            out.append({"hits": {
                "total": {"value": found, "relation": "eq"},
                "hits": [{"_id": str(int(cand[i])),
                          "_score": float(score[i])} for i in order],
            }})
        return out
