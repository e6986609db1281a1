"""Plain reference for the typo-tolerant cell: Elasticsearch's `match`
with `fuzziness` over a text field, each analyzed word rewritten as
Lucene's FuzzyQuery rewrites it (FuzzyTermsEnum under
MultiTermQuery.TopTermsBlendedFreqScoringRewrite; from memory of Lucene
8/9, the configuration's `assumed`). For a word w of m code points:

  1. k = 0 if m < 3, 1 if m < 6, else 2 (`AUTO`; `AUTO:lo,hi` and a
     plain 0..2 are read too). k = 0: the word itself, if it is a term.
  2. candidates: every term t with d(w, t) <= k, d the optimal string
     alignment distance (insert, delete, substitute, transpose two
     adjacent code points: 1 each), as a FULL table, vectorised over the
     dictionary after the one cut lengths allow (|len(t) - m| <= k).
  3. boost(t) = 1 if d = 0 else 1 - d / min(m, len(t)), float32 as
     Lucene computes it; a boost that is not positive is no candidate.
  4. the 50 of highest boost are kept, ties by term ascending: a stable
     sort on (-boost, term).
  5. df* = the largest df among the kept terms; all are scored with
     idf(df*) = ln(1 + (N - df* + 0.5) / (df* + 0.5)).
  6. score(d) = sum over words, sum over a word's kept terms in d, of
     boost(t) * idf(df*) * tf / (tf + k1 * (1 - b + b * dl(d) / avgdl)),
     dl the field length as one SmallFloat byte holds it; a document
     matches if any kept term of any word is in it.

float64 throughout but the boosts; no import of the program; the data are
the raw seeded posting stream and the list of spellings by frequency
rank, nothing the program built. A word's expansion is kept once a run
(common words repeat). `precision="lower"` is the control: every term's
contribution and the running sum rounded to bfloat16.
"""

from __future__ import annotations

import numpy as np

from lowprec import to_bf16
from plugins import load_plugin

_text = load_plugin("references", "bm25_match")
TRACK_TOTAL_HITS = _text.TRACK_TOTAL_HITS
MAX_EDITS = 2


def edits_of(fuzziness, m: int) -> int:
    text = str(fuzziness).upper()
    if text.startswith("AUTO"):
        lo, hi = (3, 6) if text == "AUTO" else (
            int(x) for x in text[5:].split(","))
        return 0 if m < lo else (1 if m < hi else 2)
    return int(text)


def osa_table(word: np.ndarray, chars: np.ndarray) -> np.ndarray:
    """D[i][j] of the optimal string alignment distance between `word`
    (m code points) and every row of `chars` [n, width] (0 past a term's
    end), the whole table: int16[m + 1, width + 1, n]."""
    m, (n, width) = len(word), chars.shape
    table = np.zeros((m + 1, width + 1, n), np.int16)
    table[:, 0, :] = np.arange(m + 1)[:, None]
    table[0, :, :] = np.arange(width + 1)[:, None]
    for i in range(1, m + 1):
        for j in range(1, width + 1):
            cost = chars[:, j - 1] != word[i - 1]
            best = np.minimum(table[i - 1, j] + 1, table[i, j - 1] + 1)
            best = np.minimum(best, table[i - 1, j - 1] + cost)
            if i > 1 and j > 1:
                swap = ((chars[:, j - 1] == word[i - 2])
                        & (chars[:, j - 2] == word[i - 1]))
                best = np.where(
                    swap, np.minimum(best, table[i - 2, j - 2] + 1), best)
            table[i, j] = best
    return table


class Reference:
    def __init__(self, data: dict, config: dict):
        g = config["guarantees"]
        self.k1, self.b = float(g["bm25_k1"]), float(g["bm25_b"])
        self.n = int(data["docs"])
        self.field = data["field"]
        self.post_start = data["post_start"]
        self.post_doc, self.post_tf = data["post_doc"], data["post_tf"]
        self.df = np.diff(self.post_start)
        dl = _text.quantized_lengths(data["lengths"]).astype(np.float64)
        avgdl = float(self.post_tf.sum(dtype=np.int64)) / self.n
        self.denom = self.k1 * (1.0 - self.b + self.b * dl / avgdl)
        # the dictionary, by frequency rank: spellings, code points, lengths
        self.spelled = np.array(data["spellings"])
        self.rank_of = {w: r for r, w in enumerate(data["spellings"])}
        self.lens = np.array([len(w) for w in data["spellings"]])
        self.expansions = int(config["shapes"]["max_expansions"])
        self._by_len: dict = {}
        self._kept: dict = {}

    def _terms_of_length(self, length: int):
        """(ranks, code points [n, length]) of the terms of one length."""
        got = self._by_len.get(length)
        if got is None:
            ranks = np.flatnonzero(self.lens == length)
            flat = np.frombuffer(
                "".join(self.spelled[ranks].tolist()).encode("utf-32-le"),
                np.uint32)
            got = (ranks, flat.reshape(len(ranks), length).astype(np.int32))
            self._by_len[length] = got
        return got

    def kept(self, word: str, fuzziness) -> tuple:
        """(ranks, float64 weights boost x idf(df*)) of a word's kept terms."""
        key = (word, str(fuzziness))
        if key in self._kept:
            return self._kept[key]
        cp = np.frombuffer(word.encode("utf-32-le"), np.uint32).astype(
            np.int32)
        m = len(cp)
        k = edits_of(fuzziness, m)
        ranks_l, dist_l = [], []
        if k == 0:
            if word in self.rank_of:
                ranks_l, dist_l = [np.array([self.rank_of[word]])], [
                    np.zeros(1, np.int16)]
        else:
            for length in range(max(1, m - k), m + k + 1):
                ranks, chars = self._terms_of_length(length)
                if len(ranks):
                    ranks_l.append(ranks)
                    dist_l.append(osa_table(cp, chars)[m, length])
        if not ranks_l:
            self._kept[key] = (np.empty(0, np.int64), np.empty(0))
            return self._kept[key]
        ranks, dist = np.concatenate(ranks_l), np.concatenate(dist_l)
        near = dist <= k
        ranks, dist = ranks[near], dist[near]
        shorter = np.minimum(m, self.lens[ranks]).astype(np.float32)
        boost = np.where(dist == 0, np.float32(1.0),
                         np.float32(1.0) - dist.astype(np.float32) / shorter)
        ok = boost > 0
        ranks, boost = ranks[ok], boost[ok]
        # a stable sort on (-boost, term)
        order = np.lexsort((self.spelled[ranks], -boost))[: self.expansions]
        ranks, boost = ranks[order], boost[order]
        if not len(ranks):
            self._kept[key] = (ranks, np.empty(0))
            return self._kept[key]
        df = float(self.df[ranks].max())
        idf = np.log(1.0 + (self.n - df + 0.5) / (df + 0.5))
        self._kept[key] = (ranks, boost.astype(np.float64) * idf)
        return self._kept[key]

    def answer_many(self, bodies: list, precision: str = "full") -> list:
        return [self.answer(b, precision) for b in bodies]

    def answer(self, body: dict, precision: str = "full") -> dict:
        spec = body["query"]["match"][self.field]
        size = int(body.get("size", 10))
        score = np.zeros(self.n, np.float64)
        low = np.zeros(self.n, np.float32)
        hit = np.zeros(self.n, bool)
        for word in spec["query"].split():
            ranks, weights = self.kept(word, spec["fuzziness"])
            for t, w in zip(ranks.tolist(), weights.tolist()):
                lo, hi = int(self.post_start[t]), int(self.post_start[t + 1])
                d = self.post_doc[lo:hi]  # a term's docs are distinct
                tf = self.post_tf[lo:hi].astype(np.float64)
                s = w * tf / (tf + self.denom[d])
                if precision == "lower":
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
        take = min(size, len(uniq))
        if take < len(uniq):
            cut = np.argpartition(-score, take - 1)[:take]
            cand = np.flatnonzero(score >= score[cut].min())
        else:
            cand = np.arange(len(uniq))
        # Lucene's order: score descending, then doc id ascending
        order = cand[np.lexsort((uniq[cand], -score[cand]))][:take]
        n = len(uniq)
        total = ({"value": n, "relation": "eq"} if n <= TRACK_TOTAL_HITS
                 else {"value": TRACK_TOTAL_HITS, "relation": "gte"})
        return {"hits": {"total": total, "hits": [
            {"_id": str(int(uniq[i])), "_score": float(score[i])}
            for i in order
        ]}}
