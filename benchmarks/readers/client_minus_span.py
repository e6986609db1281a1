"""Median client-side latency minus the median of one named span: the
time a request spends outside that span (for `coordinator`: sockets, the
HTTP handler threads, JSON in and out, routing)."""

from stats import median


def read(obs: dict, args: dict):
    samples = obs["spans_ms"].get(args["span"])
    if not samples or not obs["latency_ms"]:
        return None
    return median(obs["latency_ms"]) - median(samples)
