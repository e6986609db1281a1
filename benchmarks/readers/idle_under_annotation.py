"""Where the device's idle time of the traced window falls, by what the
program's dispatcher workers were doing meanwhile: the share of the
window (%) in which the device was idle and some worker was inside an
`es.dispatch` annotation (`where: dispatch`), inside an `es.collect` one
and none in `es.dispatch` (`collect`), or in neither (`elsewhere`: the
request was in HTTP, parse, admission, fetch or at the client).

The annotations are `jax.profiler.TraceAnnotation`s of
`elasticsearch_tpu/search/batcher.py`, on the host plane of the same
`.xplane.pb` as the device's `XLA Ops` line; those of all worker threads
are merged. The three shares are exclusive, so they sum to
`device_idle_share`. A trace with no `es.*` annotation (a program that has
none) gives `None`.

The two planes' clocks do not agree as recorded: on the chip the device
plane read 0.3 to 1.6 ms early, differently from run to run, so that
programs seemed to start before the runtime had enqueued them (PERF.md
section 6, PR 25). `clock_offset` therefore shifts the device's times by
the least amount after which no program starts before its own
`DoEnqueueProgram` event of the host plane (matched by `run_id`): the
fastest launch of the window then starts exactly when it was enqueued,
and what remains is that launch's true latency (under 0.1 ms, by the
bound the runtime's `Execute=>Done` events give from the other side).
"""

from __future__ import annotations

import glob
import os

from tracereduce import (DEVICE_PLANE_PREFIX, MODULES_LINE, device_lines,
                         find_xplane, merge)

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RUN_DIR = os.path.join(os.path.dirname(BENCH), ".bench_run")
DISPATCH, COLLECT = "es.dispatch", "es.collect"


def overlap(a: list, b: list) -> list:
    """Intersection of two merged, sorted interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append([s, e])
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def total(intervals: list) -> float:
    return sum(e - s for s, e in intervals)


def annotations(profile) -> dict:
    """{annotation name: merged [start, end) over every host thread}."""
    found: dict = {DISPATCH: [], COLLECT: []}
    for plane in profile.planes:
        for line in plane.lines:
            for e in line.events:
                if e.name in found and e.duration_ns > 0:
                    found[e.name].append(
                        [float(e.start_ns), float(e.start_ns + e.duration_ns)])
    return {name: merge(iv) for name, iv in found.items()}


def clock_offset(profile) -> float:
    """ns to add to the device planes' times (module docstring); 0.0
    where the trace has no enqueue events to hold them to. One shift
    for the trace: the cells run on one chip, and run ids are not told
    apart by device."""
    enqueued = {}
    for plane in profile.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == "DoEnqueueProgram":
                        enqueued[dict(e.stats).get("run_id")] = float(e.start_ns)
    early = [enqueued[run_id] - float(e.start_ns)
             for plane in profile.planes
             if plane.name.startswith(DEVICE_PLANE_PREFIX)
             for line in plane.lines if line.name == MODULES_LINE
             for e in line.events
             for run_id in [dict(e.stats).get("run_id")]
             if run_id in enqueued]
    return max(early) if early else 0.0


def idle_shares(path: str, rehearsal: bool = False):
    """-> {dispatch, collect, elsewhere, idle: % of the window, busy_s,
    clock_offset_ms}, or None where the trace holds no `es.*`
    annotation. The window and the busy time are
    `tracereduce.reduce_events`' own: the widest device's first operation
    to its last, the mean busy time."""
    from jax.profiler import ProfileData

    profile = ProfileData.from_file(path)
    per_device = device_lines(profile, rehearsal)
    ann = annotations(profile)
    if not per_device or not (ann[DISPATCH] or ann[COLLECT]):
        return None
    offset = clock_offset(profile)
    busy = in_dispatch = in_collect = span = 0.0
    for _plane, ops, _mods in per_device:
        merged = merge([[s + offset, e + offset] for _n, s, e in ops])
        gaps = [[e0, s1] for (_s0, e0), (s1, _e1) in zip(merged, merged[1:])]
        under_dispatch = overlap(gaps, ann[DISPATCH])
        under_collect = overlap(gaps, ann[COLLECT])
        busy += total(merged)
        in_dispatch += total(under_dispatch)
        in_collect += (total(under_collect)
                       - total(overlap(under_collect, ann[DISPATCH])))
        span = max(span, merged[-1][1] - merged[0][0])
    n = len(per_device)
    busy, in_dispatch, in_collect = busy / n, in_dispatch / n, in_collect / n
    idle = span - busy
    return {"dispatch": 100.0 * in_dispatch / span,
            "collect": 100.0 * in_collect / span,
            "elsewhere": 100.0 * (idle - in_dispatch - in_collect) / span,
            "idle": 100.0 * idle / span, "busy_s": busy / 1e9,
            "clock_offset_ms": offset / 1e6}


def newest_trace() -> str:
    """The run's own trace: `run.py` has just written it under
    `.bench_run/<cell>/profile`, so it is the newest there."""
    found = []
    for log_dir in glob.glob(os.path.join(RUN_DIR, "*", "profile")):
        try:
            found.append(find_xplane(log_dir))
        except FileNotFoundError:  # a run that took no trace
            pass
    if not found:
        raise FileNotFoundError(f"no profile under {RUN_DIR}")
    return max(found, key=os.path.getmtime)


def read(obs: dict, args: dict):
    shares = idle_shares(newest_trace(), rehearsal=obs["rehearsal"])
    if shares is None:
        return None
    if abs(shares["busy_s"] - obs["profile"]["busy_s"]) > 1e-9:
        raise RuntimeError("the newest trace under .bench_run is not this "
                           "run's: its busy time differs from the harness's")
    return shares[args["where"]]
