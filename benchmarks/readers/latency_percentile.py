"""One percentile of the window's client-side latencies (200-answers),
in ms: the tail or the median as a caller felt it, reported beside the
end-to-end metrics in cells where it swings too widely to carry a bound."""

from stats import percentile


def read(obs: dict, args: dict):
    return percentile(obs["latency_ms"], float(args["q"])) if obs["latency_ms"] else None
