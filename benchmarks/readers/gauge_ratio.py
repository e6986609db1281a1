"""Ratio of two `_nodes/stats` numbers as the node reported them when
the window closed, each named by its dotted path, times `scale` (100 for
a share in %). A program that does not report one of them gives nothing."""


def read(obs: dict, args: dict):
    num, den = obs["gauges"].get(args["num"]), obs["gauges"].get(args["den"])
    if num is None or not den:
        return None
    return float(args.get("scale", 1.0)) * num / den
