"""The comparison that decides `correct`: `chip_smoke.py`'s `compare()`
rule (PR 21; PR 23 verdict: sound), copied and turned from "raise at the
first breach" into numbers, each printed beside its limit.

A configuration's `guarantees.rule` picks the class:

- `exact`: the served page must hold the reference's ids, tie group by
  tie group (a tie the page boundary cut may fall either way: the
  reference is asked for one hit past the page to tell), every score
  within `score_rtol`, `hits.total` equal.
- `approx` (exact kNN in another summation order): recall of the
  reference's page >= `recall_floor`, every shared doc's score within
  `score_rtol`, every hit only one page has a near-tie (2 x rtol) with
  the other page's last score, `hits.total` equal.

Numbers reported (limit): `answers_checked` (>= 1), `total_mismatches`
(0), `page_mismatches` (0: pages whose ids break the rule),
`score_rel_max` (<= score_rtol), and for `approx` `recall_min`
(>= recall_floor).
"""

from __future__ import annotations


def close(a: float, b: float, rtol: float) -> bool:
    return abs(a - b) <= rtol * max(abs(a), abs(b), 1e-30)


def reference_body(rule: str, body: dict) -> dict:
    """What the reference is asked: for an exact rule one hit past the
    page, so a cut tie can be told from a wrong last hit."""
    size = body.get("size", 10)
    if rule == "exact" and size > 0:
        return {**body, "size": size + 1}
    return body


def _hits(resp: dict) -> list:
    return [(h["_id"], float(h["_score"])) for h in resp["hits"]["hits"]]


def _rel(a: float, b: float) -> float:
    return abs(a - b) / abs(b) if b else (0.0 if a == b else float("inf"))


def compare_one(rule: str, rtol: float, body: dict, served: dict,
                ref: dict) -> dict:
    """One served answer against the reference's answer to
    `reference_body(rule, body)`."""
    out = {"total_ok": served["hits"]["total"] == ref["hits"]["total"],
           "page_ok": True, "score_rel": 0.0, "recall": 1.0, "why": ""}
    hs, hr = _hits(served), _hits(ref)
    if rule == "exact":
        page = body.get("size", 10)
        hr, past = hr[:page], hr[page:]
        if len(hs) != len(hr):
            out.update(page_ok=False, why=f"{len(hs)} hits != {len(hr)}")
            return out
        for (_, a), (_, b) in zip(hs, hr):
            out["score_rel"] = max(out["score_rel"], _rel(a, b))
        i = 0
        while i < len(hr):
            j = i + 1
            while j < len(hr) and close(hr[j][1], hr[i][1], rtol):
                j += 1
            cut = (j == len(hr) and past
                   and close(past[0][1], hr[i][1], rtol))
            if not cut and ({d for d, _ in hs[i:j]}
                            != {d for d, _ in hr[i:j]}):
                out.update(page_ok=False,
                           why=f"ids differ at ranks {i}..{j - 1}: "
                               f"{hs[i:j]} vs {hr[i:j]}")
            i = j
        return out
    if len(hs) != len(hr):
        out.update(page_ok=False, why=f"{len(hs)} hits != {len(hr)}")
        return out
    ms, mr = dict(hs), dict(hr)
    if mr:
        out["recall"] = len(set(ms) & set(mr)) / len(mr)
    for d in set(ms) & set(mr):
        out["score_rel"] = max(out["score_rel"], _rel(ms[d], mr[d]))
    if hs and hr:
        for mine, other, other_last, who in (
            (ms, mr, hr[-1][1], "served"), (mr, ms, hs[-1][1], "reference"),
        ):
            for d in set(mine) - set(other):
                if not close(mine[d], other_last, 2 * rtol):
                    out.update(
                        page_ok=False,
                        why=f"only the {who} page has doc {d} (score "
                            f"{mine[d]}), no near-tie with {other_last}")
    return out


def compare_all(guarantees: dict, bodies: list, served: list,
                refs: list) -> dict:
    """-> {"numbers": {name: (value, relation, limit)}, "correct": bool,
    "breaches": [first few reasons]}"""
    rule, rtol = guarantees["rule"], float(guarantees["score_rtol"])
    ones = [compare_one(rule, rtol, b, s, r)
            for b, s, r in zip(bodies, served, refs)]
    numbers = {
        "answers_checked": (len(ones), ">=", 1),
        "total_mismatches": (sum(not o["total_ok"] for o in ones), "<=", 0),
        "page_mismatches": (sum(not o["page_ok"] for o in ones), "<=", 0),
        "score_rel_max": (max([o["score_rel"] for o in ones], default=0.0),
                          "<=", rtol),
    }
    if rule != "exact":
        numbers["recall_min"] = (min([o["recall"] for o in ones], default=0.0),
                                 ">=", float(guarantees["recall_floor"]))
    return {"numbers": numbers,
            "correct": all(within(v) for v in numbers.values()),
            "breaches": [o["why"] for o in ones if o["why"]][:3]}


def within(number: tuple) -> bool:
    value, relation, limit = number
    return value >= limit if relation == ">=" else value <= limit
