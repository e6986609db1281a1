"""Device busy time per request, in ms: the busy share of the traced
window (union of device-op intervals over first-to-last-op span, both on
the profiler's clock) over the requests answered a second meanwhile (the
host's clock). Each ratio is taken on one clock."""


def read(obs: dict, args: dict):
    p = obs["profile"]
    if not p["requests_per_s"] or not p["window_s"]:
        return None
    return 1e3 * (p["busy_s"] / p["window_s"]) / p["requests_per_s"]
