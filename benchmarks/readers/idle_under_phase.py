"""Where the device's idle time inside a worker's phase falls, by what
the worker was doing inside it, and how much of the idle time in front
of the search is the trace ring's own export.

`idle_under_annotation` gives the device's idle time under a dispatcher
worker's `es.dispatch` and `es.collect`; the program (PR 51) nests three
more annotations in those (`elasticsearch_tpu/search/batcher.py`
`_Group.launch` / `downloaded`, `ops/scoring.py` `_to_host`) and puts
one around the traces action (`rest/actions.py`):

- `where: launch`: idle and some worker inside `es.launch` (a jitted
  call's entry -> its return: argument handling, the staging of host
  operands, the runtime's enqueue);
- `where: download`: idle, no worker inside `es.launch`, some worker
  inside `es.download` (`_to_host`: the device has nothing to run and
  the host waits for it and for the way back);
- `where: unpack`: idle, none in either, some worker inside `es.unpack`
  (the collect's last download ended -> its last job finished);
- `where: trace_export`: idle, NO worker inside `es.dispatch` or
  `es.collect`, and a request thread inside `es.trace_export`: the part
  of `idle_by_request`'s `front` share that is the export's (the
  harness polls `GET /_internal/traces` once a second in a traced run).

Shares of the traced window (%). The first three are exclusive in that
order; with `dispatch_rest` and `collect_rest` (idle under a worker's
phase and under none of the three) they sum to `idle_under_annotation`'s
`dispatch` + `collect` (all five are taken under a worker's phase
alone: a download on a request thread is not among them). `longest_ms`
holds, for every class, the longest single stretch of idle time under
it. Device times are shifted by `idle_under_annotation`'s `clock_offset`
first; window and busy time are `tracereduce`'s. A trace without the
annotation a share reads (a program that has none) gives `None`.
"""

from __future__ import annotations

from plugins import load_plugin
from tracereduce import device_lines, merge

_workers = load_plugin("readers", "idle_under_annotation")
overlap, total = _workers.overlap, _workers.total
clock_offset, newest_trace = _workers.clock_offset, _workers.newest_trace

DISPATCH, COLLECT = _workers.DISPATCH, _workers.COLLECT
LAUNCH, DOWNLOAD, UNPACK = "es.launch", "es.download", "es.unpack"
EXPORT = "es.trace_export"
PHASES = {"launch": LAUNCH, "download": DOWNLOAD, "unpack": UNPACK}


def annotations(profile) -> dict:
    """{annotation name: merged [start, end) over every host thread}."""
    found: dict = {name: [] for name in (
        DISPATCH, COLLECT, LAUNCH, DOWNLOAD, UNPACK, EXPORT)}
    for plane in profile.planes:
        for line in plane.lines:
            for e in line.events:
                if e.name in found and e.duration_ns > 0:
                    found[e.name].append(
                        [float(e.start_ns), float(e.start_ns + e.duration_ns)])
    return {name: merge(iv) for name, iv in found.items()}


def subtract(a: list, b: list) -> list:
    """The merged intervals `a` less the merged `b`."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > s:
                out.append([s, b[k][0]])
            s = max(s, b[k][1])
            k += 1
        if s < e:
            out.append([s, e])
    return out


def shares_of(profile, rehearsal: bool = False):
    """-> {launch, download, unpack, dispatch_rest, collect_rest,
    trace_export: % of the window (a class whose annotation the trace
    lacks: None), longest_ms: {class: ms}, busy_s}, or None where the
    trace holds none of the four annotations."""
    per_device = device_lines(profile, rehearsal)
    ann = annotations(profile)
    if not per_device or not any(
            ann[name] for name in (LAUNCH, DOWNLOAD, UNPACK, EXPORT)):
        return None
    offset = clock_offset(profile)
    workers = merge(ann[DISPATCH] + ann[COLLECT])
    classes = ("launch", "download", "unpack", "dispatch_rest",
               "collect_rest", "trace_export")
    idle = {c: 0.0 for c in classes}
    longest = {c: 0.0 for c in classes}
    busy = span = 0.0
    for _plane, ops, _mods in per_device:
        merged = merge([[s + offset, e + offset] for _n, s, e in ops])
        gaps = [[e0, s1] for (_s0, e0), (s1, _e1) in zip(merged, merged[1:])]
        # under a worker's phase alone: a request thread's own download
        # (the unbatched executor's) is the search's, not a worker's
        inside = overlap(gaps, workers)
        under = {"launch": overlap(inside, ann[LAUNCH])}
        left = subtract(inside, ann[LAUNCH])
        under["download"] = overlap(left, ann[DOWNLOAD])
        left = subtract(left, ann[DOWNLOAD])
        under["unpack"] = overlap(left, ann[UNPACK])
        left = subtract(left, ann[UNPACK])
        under["dispatch_rest"] = overlap(left, ann[DISPATCH])
        under["collect_rest"] = overlap(
            subtract(left, ann[DISPATCH]), ann[COLLECT])
        under["trace_export"] = overlap(
            subtract(gaps, workers), ann[EXPORT])
        for c in classes:
            idle[c] += total(under[c])
            longest[c] = max([longest[c]] + [e - s for s, e in under[c]])
        busy += total(merged)
        span = max(span, merged[-1][1] - merged[0][0])
    n = len(per_device)
    out = {c: 100.0 * idle[c] / n / span for c in classes}
    for where, name in (*PHASES.items(), ("trace_export", EXPORT)):
        if not ann[name]:
            out[where] = None
    out["longest_ms"] = {c: longest[c] / 1e6 for c in classes}
    out["busy_s"] = busy / n / 1e9
    return out


def idle_shares(path: str, rehearsal: bool = False):
    from jax.profiler import ProfileData

    return shares_of(ProfileData.from_file(path), rehearsal)


def read(obs: dict, args: dict):
    shares = idle_shares(newest_trace(), rehearsal=obs["rehearsal"])
    if shares is None:
        return None
    if abs(shares["busy_s"] - obs["profile"]["busy_s"]) > 1e-9:
        raise RuntimeError("the newest trace under .bench_run is not this "
                           "run's: its busy time differs from the harness's")
    return shares[args["where"]]
