"""100 x (1 - busy / window) of the traced window, busy time and window
both on the profiler's clock (first device operation's start to the
last's end)."""


def read(obs: dict, args: dict):
    p = obs["profile"]
    return 100.0 * (1.0 - p["busy_s"] / p["window_s"]) if p["window_s"] else None
