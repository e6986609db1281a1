"""A launch's life on one clock, from the traced window's profile: the
program's `es.launch` [`program`] and `es.download` annotations (PR 51:
`elasticsearch_tpu/search/batcher.py` `_Group.launch`, `ops/scoring.py`
`_to_host`), the runtime's `DoEnqueueProgram` events of the host plane
and the device's `XLA Modules` line, the last two joined by `run_id` as
`idle_under_annotation.clock_offset` joins them:

    es.launch opens -> DoEnqueueProgram -> the module starts -> it ends
        -> es.download closes

- `stage: launch_to_enqueue`: `es.launch`'s start -> the start of ITS
  `DoEnqueueProgram`: the jitted call's argument handling and the
  staging of its host operands in front of the runtime. The runtime may
  enqueue a program after the call that asked for it has returned, and
  on a thread of its own (a text launch's enqueue trails its call's
  return by ~0.3 ms on the chip: my chip runs, PR 51), so the event is
  not looked for inside the annotation: a module `jit_<program>(...)` is
  a launch of `<program>`, the runtime enqueues one program's launches
  in the order they were asked for, and so an enqueue event belongs to
  the OLDEST `es.launch` of its program that has none yet (one older
  than 100 ms has lost its event and is forgotten). An enqueue with no
  such launch waiting (a warm-up, the unbatched executor, a launch that
  began before the window) is left out.
- `stage: enqueue_to_start`: that `DoEnqueueProgram`'s start -> its
  module's start on the device. Device times are shifted by
  `clock_offset` first, which holds the window's FASTEST launch to zero:
  the number is a launch's latency OVER the fastest one's (itself under
  0.1 ms, that reader's docstring), and under load it holds the wait for
  the programs enqueued before it.
- `stage: done_to_host`: the end of the last module that ended inside an
  `es.download` -> that annotation's end: the device is done -> the
  bytes are on the host and the worker runs again. A download inside
  which no module ended (the awaited program was done before the worker
  asked) is left out.

Each the median over the window, in ms. A trace without the annotation a
stage reads (a program that has none) gives `None`.
"""

from __future__ import annotations

from bisect import bisect_right

from plugins import load_plugin
from stats import median
from tracereduce import DEVICE_PLANE_PREFIX, MODULES_LINE

_workers = load_plugin("readers", "idle_under_annotation")
clock_offset, newest_trace = _workers.clock_offset, _workers.newest_trace

LAUNCH, DOWNLOAD, ENQUEUE = "es.launch", "es.download", "DoEnqueueProgram"
# a launch still without its enqueue event after this long has lost it
# (the profiler dropped it, the call raised): it is forgotten, so that
# the pairing of the launches after it does not slip by one
LOST_AFTER_NS = 100e6


def host_events(profile) -> dict:
    """{name: [(start, end, key)]} of the three host events, by start:
    an `es.launch`'s key is its `program`, an enqueue event's its
    `run_id`, a download's nothing."""
    found: dict = {LAUNCH: [], DOWNLOAD: [], ENQUEUE: []}
    keys = {LAUNCH: "program", ENQUEUE: "run_id"}
    for plane in profile.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in found:
                    start = float(e.start_ns)
                    key = keys.get(e.name)
                    found[e.name].append((
                        start, start + float(e.duration_ns),
                        dict(e.stats).get(key) if key else None))
    return {name: sorted(evs, key=lambda ev: ev[:2])
            for name, evs in found.items()}


def modules(profile, offset: float) -> list:
    """[(start, end, run_id, program)] of every launch on the devices'
    `XLA Modules` lines, shifted onto the host's clock, by start; a
    module `jit_<program>(<hash>)` is a launch of `<program>`."""
    return sorted(
        (float(e.start_ns) + offset,
         float(e.start_ns + e.duration_ns) + offset,
         dict(e.stats).get("run_id"),
         e.name.split("(")[0].removeprefix("jit_"))
        for plane in profile.planes
        if plane.name.startswith(DEVICE_PLANE_PREFIX)
        for line in plane.lines if line.name == MODULES_LINE
        for e in line.events)


def stages_of(profile) -> dict:
    """-> {launch_to_enqueue, enqueue_to_start, done_to_host: [ms]} (a
    stage whose annotation the trace lacks: None)."""
    host = host_events(profile)
    mods = modules(profile, clock_offset(profile))
    out: dict = {"launch_to_enqueue": None, "enqueue_to_start": None,
                 "done_to_host": None}
    if host[LAUNCH]:
        ran = {run_id: (start, program)
               for start, _end, run_id, program in mods}
        # one walk over both in time order; first come, first enqueued
        events = sorted(
            [(start, 0, program) for start, _end, program in host[LAUNCH]]
            + [(start, 1, run_id) for start, _end, run_id in host[ENQUEUE]
               if run_id in ran])
        waiting: dict = {}  # program -> starts of its launches, oldest first
        out["launch_to_enqueue"], out["enqueue_to_start"] = [], []
        for t, is_enqueue, key in events:
            if not is_enqueue:
                waiting.setdefault(key, []).append(t)
                continue
            started, program = ran[key]
            asked = waiting.get(program, [])
            while asked and t - asked[0] > LOST_AFTER_NS:
                asked.pop(0)
            if asked:
                out["launch_to_enqueue"].append((t - asked.pop(0)) / 1e6)
                out["enqueue_to_start"].append((started - t) / 1e6)
    if host[DOWNLOAD]:
        ends = sorted(end for _start, end, _run, _program in mods)
        out["done_to_host"] = []
        for start, end, _key in host[DOWNLOAD]:
            i = bisect_right(ends, end) - 1
            if i >= 0 and ends[i] > start:
                out["done_to_host"].append((end - ends[i]) / 1e6)
    return out


def stages(path: str) -> dict:
    from jax.profiler import ProfileData

    return stages_of(ProfileData.from_file(path))


def read(obs: dict, args: dict):
    samples = stages(newest_trace())[args["stage"]]
    return median(samples) if samples else None
