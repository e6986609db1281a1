"""Prints what a `.xplane.pb` holds: planes, lines, event counts and
each line's longest events. Look at one trace by hand before trusting
the reduction (on-chip-measurement guide, section 6).

    python3 benchmarks/inspect_trace.py <file-or-log-dir>
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from tracereduce import find_xplane  # noqa: E402


def main(path: str) -> None:
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = find_xplane(path)
    print(path, os.path.getsize(path), "bytes")
    for plane in ProfileData.from_file(path).planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            ev = [(e.duration_ns, e.name, e.start_ns) for e in line.events]
            total = sum(d for d, _n, _s in ev)
            print(f"  LINE {line.name!r}: {len(ev)} events, "
                  f"{total / 1e6:.3f} ms summed")
            for d, n, s in sorted(ev, reverse=True)[:4]:
                print(f"      {d / 1e3:10.1f} us  {n[:90]}  @{s / 1e6:.3f} ms")


if __name__ == "__main__":
    main(sys.argv[1])
