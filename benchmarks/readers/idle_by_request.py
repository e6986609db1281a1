"""Where the device's idle time "elsewhere" falls, by where a request
was in the server meanwhile. `idle_under_annotation` gives the device's
idle time three classes by what the dispatcher WORKERS were doing
(`es.dispatch`, `es.collect`, `elsewhere`); this reader splits the third
by what the REQUEST threads were doing, from the two annotations they put
on the same host plane (`elasticsearch_tpu/rest/server.py`, `rest/actions.py`):

- `where: search`: idle, no worker inside `es.dispatch` or `es.collect`,
  and some request thread inside `es.search` (the action's
  `cluster.search` call: plan, queue wait, the waiter's wake-up, hit
  building, fetch, reduce);
- `where: front`: idle, no worker phase, some thread inside `es.http`
  (request line read -> response written) and none inside `es.search`:
  reading, parsing, routing, the action's own overhead, responding;
- `where: none`: idle, no worker phase and no request inside the server
  at all: a closed-loop client's turn-around, the loopback, the kernel's
  wake-up of the handler. No change to the program moves this share.

Shares of the traced window (%), exclusive, search first, and exclusive
after that reader's two classes, so the three sum to its `elsewhere`
share. The annotations of all threads are merged; the device's times are
shifted by that reader's `clock_offset` first, and the window and busy
time are the ones it uses (`tracereduce`'s). A trace with no `es.http`
annotation (a program that has none) gives `None`, not 0.
"""

from __future__ import annotations

from plugins import load_plugin
from tracereduce import device_lines, merge

_workers = load_plugin("readers", "idle_under_annotation")
overlap, total = _workers.overlap, _workers.total
clock_offset, newest_trace = _workers.clock_offset, _workers.newest_trace

DISPATCH, COLLECT = _workers.DISPATCH, _workers.COLLECT
HTTP, SEARCH = "es.http", "es.search"


def annotations(profile) -> dict:
    """{annotation name: merged [start, end) over every host thread}."""
    found: dict = {DISPATCH: [], COLLECT: [], HTTP: [], SEARCH: []}
    for plane in profile.planes:
        for line in plane.lines:
            for e in line.events:
                if e.name in found and e.duration_ns > 0:
                    found[e.name].append(
                        [float(e.start_ns), float(e.start_ns + e.duration_ns)])
    return {name: merge(iv) for name, iv in found.items()}


def outside(a: list, b: list) -> float:
    """Total length of the merged intervals `a` outside the merged `b`."""
    return total(a) - total(overlap(a, b))


def idle_shares(path: str, rehearsal: bool = False):
    """-> {front, search, none, elsewhere: % of the window, busy_s}, or
    None where the trace holds no `es.http` annotation."""
    from jax.profiler import ProfileData

    profile = ProfileData.from_file(path)
    per_device = device_lines(profile, rehearsal)
    ann = annotations(profile)
    if not per_device or not ann[HTTP]:
        return None
    offset = clock_offset(profile)
    workers = merge(ann[DISPATCH] + ann[COLLECT])
    busy = elsewhere = in_search = in_front = span = 0.0
    for _plane, ops, _mods in per_device:
        merged = merge([[s + offset, e + offset] for _n, s, e in ops])
        gaps = [[e0, s1] for (_s0, e0), (s1, _e1) in zip(merged, merged[1:])]
        searching = overlap(gaps, ann[SEARCH])
        serving = overlap(gaps, ann[HTTP])
        busy += total(merged)
        elsewhere += outside(gaps, workers)
        in_search += outside(searching, workers)
        in_front += (outside(serving, workers)
                     - outside(overlap(serving, ann[SEARCH]), workers))
        span = max(span, merged[-1][1] - merged[0][0])
    n = len(per_device)
    busy, elsewhere = busy / n, elsewhere / n
    in_search, in_front = in_search / n, in_front / n
    return {"front": 100.0 * in_front / span,
            "search": 100.0 * in_search / span,
            "none": 100.0 * (elsewhere - in_search - in_front) / span,
            "elsewhere": 100.0 * elsewhere / span, "busy_s": busy / 1e9}


def read(obs: dict, args: dict):
    shares = idle_shares(newest_trace(), rehearsal=obs["rehearsal"])
    if shares is None:
        return None
    if abs(shares["busy_s"] - obs["profile"]["busy_s"]) > 1e-9:
        raise RuntimeError("the newest trace under .bench_run is not this "
                           "run's: its busy time differs from the harness's")
    return shares[args["where"]]
