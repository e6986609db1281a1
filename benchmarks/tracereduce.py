"""From a `jax.profiler` trace (`.xplane.pb`) to device busy time, the
operations that took most of it, and the longest idle gaps.

Busy time of a device is the union of the intervals in which an
operation ran on it: the events of the `XLA Ops` line of its plane
(`/device:TPU:<n>`). Nested or overlapping events are merged, so nothing
is counted twice. `busy_s` is the mean over the device planes found.
The program puts no span of its own on the profiler's clock yet, so an
idle gap is named `unknown` for what the host was doing, with the XLA
module that ended it (the `XLA Modules` line) for orientation.

`ProfileData.from_file` needs nothing but JAX. Checked by
`selfcheck.py` on `sample_trace/`.
"""

from __future__ import annotations

import glob
import os

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
# a CPU rehearsal has no device plane: XLA's CPU client threads stand in,
# so that the control flow can be rehearsed; never reported as a device
REHEARSAL_PLANE, REHEARSAL_LINE_PREFIX = "/host:CPU", "tf_XLAPjRtCpuClient"


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(
        os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def merge(intervals: list) -> list:
    """Union of [start, end) intervals, sorted."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _events(line) -> list:
    return [(e.name, float(e.start_ns), float(e.start_ns + e.duration_ns))
            for e in line.events if e.duration_ns > 0]


def device_lines(profile, rehearsal: bool = False) -> list:
    """-> [(plane name, op events, module events)] per device."""
    out = []
    for plane in profile.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            lines = {ln.name: ln for ln in plane.lines}
            if OPS_LINE in lines:
                mods = lines.get(MODULES_LINE)
                out.append((plane.name, _events(lines[OPS_LINE]),
                            _events(mods) if mods is not None else []))
    if not out and rehearsal:
        for plane in profile.planes:
            if plane.name == REHEARSAL_PLANE:
                ops = [ev for ln in plane.lines
                       if ln.name.startswith(REHEARSAL_LINE_PREFIX)
                       for ev in _events(ln)]
                if ops:
                    out.append((plane.name, ops, []))
    return out


def reduce_events(per_device: list, top: int = 10) -> dict:
    """-> busy_s (mean over devices), span_s (first op start to last op
    end, widest device), device_ops, modules, idle_gaps; in seconds."""
    if not per_device:
        raise ValueError("the trace holds no device operations")
    busy, span, by_name, gaps, by_module = [], 0.0, {}, [], {}
    for _plane, ops, mods in per_device:
        for name, s, e in mods:
            m = by_module.setdefault(name.split("(")[0], [0, 0.0])
            m[0] += 1
            m[1] += (e - s) / 1e9
        merged = merge([[s, e] for _n, s, e in ops])
        busy.append(sum(e - s for s, e in merged) / 1e9)
        span = max(span, (merged[-1][1] - merged[0][0]) / 1e9)
        for name, s, e in ops:
            by_name[name] = by_name.get(name, 0.0) + (e - s) / 1e9
        mod_starts = sorted((s, n) for n, s, _e in mods)
        for (_s0, e0), (s1, _e1) in zip(merged, merged[1:]):
            nxt = next((n for s, n in mod_starts if s >= e0), None)
            what = "unknown" if nxt is None else f"unknown.before.{nxt}"
            gaps.append((what, (s1 - e0) / 1e9))
    n_dev = len(per_device)
    ops_top = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
    return {
        "devices": n_dev,
        "busy_s": sum(busy) / n_dev,
        "span_s": span,
        "device_ops": [[n, s / n_dev] for n, s in ops_top],
        # XLA module (jitted program) -> [launches, seconds], all devices
        "modules": by_module,
        "idle_gaps": [[n, s] for n, s in
                      sorted(gaps, key=lambda g: -g[1])[:top]],
    }


def reduce_trace(path: str, rehearsal: bool = False, top: int = 10) -> dict:
    from jax.profiler import ProfileData

    return reduce_events(
        device_lines(ProfileData.from_file(path), rehearsal), top)
