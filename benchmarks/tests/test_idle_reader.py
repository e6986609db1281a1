"""The two readers PR 25 added, checked without a chip.

`idle_under_annotation` on `sample_trace/annotated.xplane.pb`, recorded on
a TPU v5e by `sample_trace/record_annotated.py`: six launches, five
pauses each made of a sleep inside `es.collect`, one outside and one
inside `es.dispatch`, a second thread's `es.collect` over the last two
pauses. The recorder timed every part on the host's clock and wrote the
sums to `annotated.expect.json`: what the recording is known to hold.
The reader's three shares must give those sums to within 4 ms of the
200 to 500 ms each holds (a result reaches the host ~0.6 ms after the
device is done, in each of the five pauses), and must sum to the idle
share the trace reducer gives for the same file. The device plane's
clock, 1.378 ms early as recorded, is held to the runtime's enqueue
events first. `span_percentile` on fixed lists.

    python3 -m pytest benchmarks/tests -q        (not part of tier-1)
"""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [HERE, os.path.dirname(HERE)]

from plugins import load_plugin  # noqa: E402
from tracereduce import reduce_trace  # noqa: E402

ANNOTATED = os.path.join(HERE, "sample_trace", "annotated.xplane.pb")
PLAIN = os.path.join(HERE, "sample_trace", "sample.xplane.pb")


@pytest.fixture(scope="module")
def reader():
    return load_plugin("readers", "idle_under_annotation")


def test_shares_are_what_the_recording_holds(reader):
    shares = reader.idle_shares(ANNOTATED)
    reduced = reduce_trace(ANNOTATED)
    with open(ANNOTATED.replace(".xplane.pb", ".expect.json")) as f:
        known = json.load(f)["idle_ms"]
    window_ms = reduced["span_s"] * 1e3
    for where, ms in known.items():
        assert abs(shares[where] * window_ms / 100.0 - ms) < 4.0, (
            where, shares, known)
    assert known["dispatch"] > known["collect"] > known["elsewhere"] > 150.0
    # exclusive and exhaustive: they sum to the reducer's idle share
    idle = 100.0 * (1.0 - reduced["busy_s"] / reduced["span_s"])
    assert abs(sum(shares[k] for k in known) - idle) < 1e-9
    assert abs(shares["idle"] - idle) < 1e-9
    assert abs(shares["busy_s"] - reduced["busy_s"]) < 1e-12


def test_device_clock_is_held_to_the_enqueue_events(reader):
    """As recorded the device plane reads 1.378 ms early: the six
    programs would start before the runtime enqueued them. After the
    shift none does, and the fastest launch starts at its enqueue."""
    from jax.profiler import ProfileData

    profile = ProfileData.from_file(ANNOTATED)
    offset = reader.clock_offset(profile)
    assert abs(offset - 1.378e6) < 1e3, offset
    enqueued = {dict(e.stats)["run_id"]: e.start_ns
                for plane in profile.planes if plane.name == "/host:CPU"
                for line in plane.lines for e in line.events
                if e.name == "DoEnqueueProgram"}
    waits = [e.start_ns + offset - enqueued[dict(e.stats)["run_id"]]
             for plane in profile.planes if plane.name == "/device:TPU:0"
             for line in plane.lines if line.name == "XLA Modules"
             for e in line.events]
    assert len(waits) == 6 and min(waits) == 0.0 and max(waits) < 0.5e6
    assert reader.idle_shares(ANNOTATED)["clock_offset_ms"] == offset / 1e6
    # a trace without enqueue events (the plain sample has them too, so
    # take them away): nothing to hold the device's clock to
    class NoHost:
        planes = [p for p in profile.planes if not p.name.startswith("/host")]
    assert reader.clock_offset(NoHost) == 0.0


def test_no_annotation_gives_nothing(reader):
    assert reader.idle_shares(PLAIN) is None


def test_interval_arithmetic(reader):
    a = [[0, 10], [20, 30]]
    assert reader.overlap(a, [[5, 25]]) == [[5, 10], [20, 25]]
    assert reader.overlap(a, [[10, 20]]) == []
    assert reader.overlap(a, []) == []
    assert reader.total(reader.overlap(a, [[-5, 40]])) == 20


def test_read_takes_the_runs_own_trace(reader, tmp_path, monkeypatch):
    import shutil

    prof = tmp_path / "a-cell" / "profile" / "plugins" / "profile" / "t0"
    prof.mkdir(parents=True)
    shutil.copy(ANNOTATED, prof / "vm.xplane.pb")
    monkeypatch.setattr(reader, "RUN_DIR", str(tmp_path))
    reduced = reduce_trace(ANNOTATED)
    obs = {"rehearsal": False, "profile": {"busy_s": reduced["busy_s"]}}
    got = [reader.read(obs, {"where": w})
           for w in ("dispatch", "collect", "elsewhere")]
    idle = 100.0 * (1.0 - reduced["busy_s"] / reduced["span_s"])
    assert abs(sum(got) - idle) < 1e-9
    # another run's trace (its busy time is not the harness's) is refused
    obs["profile"]["busy_s"] += 1e-3
    with pytest.raises(RuntimeError):
        reader.read(obs, {"where": "dispatch"})


def test_span_percentile():
    read = load_plugin("readers", "span_percentile").read
    obs = {"spans_ms": {"queue_wait": [15.0, 20.0, 35.0, 40.0, 50.0]}}
    assert read(obs, {"span": "queue_wait", "q": 95}) == pytest.approx(48.0)
    assert read(obs, {"span": "queue_wait", "q": 50}) == 35.0
    assert read(obs, {"span": "queue_wait", "q": 0}) == 15.0
    assert read(obs, {"span": "dispatch", "q": 95}) is None
    assert read({"spans_ms": {"dispatch": []}},
                {"span": "dispatch", "q": 95}) is None
