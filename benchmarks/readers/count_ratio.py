"""Ratio of two `_nodes/stats` counter deltas over the window, each
named by its dotted path (a path ending in `.` sums every counter under
it), times `scale` (100 for a share in %)."""


def _delta(counts: dict, path: str):
    if path.endswith("."):
        found = [v for k, v in counts.items() if k.startswith(path)]
        return sum(found) if found else None
    return counts.get(path)


def read(obs: dict, args: dict):
    num, den = _delta(obs["counts"], args["num"]), _delta(obs["counts"], args["den"])
    if num is None or not den:
        return None
    return float(args.get("scale", 1.0)) * num / den
