"""The `q`-th percentile (ms) of one named span of the program's
per-request trace ring, over the traces polled inside the window."""

from stats import percentile


def read(obs: dict, args: dict):
    samples = obs["spans_ms"].get(args["span"])
    return percentile(samples, float(args["q"])) if samples else None
