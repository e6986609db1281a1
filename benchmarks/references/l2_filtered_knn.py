"""Plain reference for the filtered kNN cell: exact Euclidean kNN under
a conjunction of required tags, as Elasticsearch scores an `l2_norm`
field: `_score = 1 / (1 + d^2)`, over the rows that carry every tag of
the request's `knn.filter`.

It takes nothing the program has made. From the raw row-major (bag,
tag) stream it inverts the asked tags' lists itself (one pass over the
stream for all the tags of a call), intersects a request's lists, maps
the bags to their row ids, and takes the squared distances of the
passing rows ONLY, from the uint8 rows, in whole numbers (int64: d^2 <=
192 x 255^2, exact). The page is the `k` passing rows of least d^2,
ties by row id; the score is float32(1 / (1 + d^2)) of the exact
integer; `hits.total` the number of winners, as a kNN search reports.
Asked for exactly one hit more than the winners (`compare.py`'s
`reference_body`), it adds the next nearest passing row, so that a tie
the page cut can be told from a wrong last hit.

`precision="lower"` is the control: the same distances with the
squares rounded to bfloat16 and ACCUMULATED in bfloat16 (eight squares
summed exactly, then added to a bfloat16 accumulator: 24 roundings a
row), the step below the float32 accumulation the configuration states.
"""

from __future__ import annotations

import numpy as np

from lowprec import to_bf16

BLOCK_ROWS = 65_536
GROUP = 8  # squares summed before a rounding of the control's accumulator


def _clause_tags(node: dict, tag_field: str) -> list:
    """The tags a `knn.filter` requires: a `term`, or a `bool` of
    `filter` / `must` terms, on the tag field."""
    if "term" in node:
        (name, value), = node["term"].items()
        if name != tag_field:
            raise ValueError(f"filter on [{name}], not [{tag_field}]")
        return [value["value"] if isinstance(value, dict) else value]
    if set(node) == {"bool"} and set(node["bool"]) <= {"filter", "must"}:
        return [t for part in node["bool"].values()
                for c in (part if isinstance(part, list) else [part])
                for t in _clause_tags(c, tag_field)]
    raise ValueError(f"the reference takes term filters only: {node}")


class Reference:
    def __init__(self, data: dict, config: dict):
        self.field, self.tag_field = data["field"], data["tag_field"]
        self.vectors = data["vectors"]  # uint8[N, d]
        self.bag_start, self.bag_tags = data["bag_start"], data["bag_tags"]
        self.bag_row = data["bag_row"]
        self.n_tags = 10 ** int(data["tag_width"])

    def _rows_with(self, tags: set) -> dict:
        """{tag: sorted row ids that carry it}, for the tags asked: one
        pass over the stream."""
        asked = np.zeros(self.n_tags, bool)
        asked[list(tags)] = True
        at = np.flatnonzero(asked[self.bag_tags])
        tag = self.bag_tags[at]
        by_tag = np.argsort(tag, kind="stable")
        row = self.bag_row[
            np.searchsorted(self.bag_start, at[by_tag], side="right") - 1]
        cut = np.searchsorted(tag[by_tag], sorted(tags) + [self.n_tags])
        return {t: np.sort(row[cut[i]:cut[i + 1]])
                for i, t in enumerate(sorted(tags))}

    def _d2(self, rows: np.ndarray, q: np.ndarray, lower: bool) -> np.ndarray:
        out = np.empty(len(rows), np.float64 if lower else np.int64)
        for lo in range(0, len(rows), BLOCK_ROWS):
            blk = self.vectors[rows[lo:lo + BLOCK_ROWS]].astype(np.int32)
            blk -= q[None, :]
            if not lower:
                # whole numbers: 192 x 255^2 < 2^31
                out[lo:lo + len(blk)] = np.einsum("ij,ij->i", blk, blk)
                continue
            sq = to_bf16((blk * blk).astype(np.float32))
            acc = np.zeros(len(blk), np.float32)
            for g in range(0, sq.shape[1], GROUP):
                acc = to_bf16(acc + sq[:, g:g + GROUP].sum(axis=1))
            out[lo:lo + len(blk)] = acc
        return out

    def answer_many(self, bodies: list, precision: str = "full") -> list:
        knn = [b["knn"] for b in bodies]
        asked = [[int(str(t)[1:]) for t in _clause_tags(k["filter"],
                                                        self.tag_field)]
                 for k in knn]
        lists = self._rows_with({t for ts in asked for t in ts})
        out = []
        for body, k, tags in zip(bodies, knn, asked):
            rows = lists[tags[0]]
            for t in tags[1:]:
                rows = np.intersect1d(rows, lists[t], assume_unique=True)
            # the field stores uint8 - 128: the query arrives shifted too
            q = np.asarray(k["query_vector"], np.int32) + 128
            d2 = self._d2(rows, q, precision == "lower")
            # of the per-shard candidates (one shard) the k nearest win
            found = min(int(k["k"]), int(k["num_candidates"]), len(rows))
            size = int(body.get("size", 10))
            # asked for one hit past a page the winners fill (the exact
            # rule's way to tell a cut tie), it names the next nearest
            take = found + 1 if size == found + 1 else min(found, size)
            order = np.lexsort((rows, d2))[:take]
            score = (1.0 / (1.0 + d2[order].astype(np.float64))).astype(
                np.float32)
            out.append({"hits": {
                "total": {"value": found, "relation": "eq"},
                "hits": [{"_id": str(int(rows[i])), "_score": float(s)}
                         for i, s in zip(order, score)],
            }})
        return out
