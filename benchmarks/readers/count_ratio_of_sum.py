"""A `_nodes/stats` counter delta over the sum of several (`den`, a list
of dotted paths), times `scale`: a share of a whole whose parts are
counted apart (dropped tiles of dropped + scored). A part the program
does not count makes the whole unknown: nothing."""


def read(obs: dict, args: dict):
    counts = obs["counts"]
    num = counts.get(args["num"])
    parts = [counts.get(p) for p in args["den"]]
    if num is None or None in parts or not sum(parts):
        return None
    return float(args.get("scale", 1.0)) * num / sum(parts)
