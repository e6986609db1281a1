"""Median duration (ms) of one named span of the program's per-request
trace ring, over the traces polled inside the window."""

from stats import median


def read(obs: dict, args: dict):
    samples = obs["spans_ms"].get(args["span"])
    return median(samples) if samples else None
