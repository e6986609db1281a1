"""The two readers PR 51 added, checked without a chip on a made-up
profile of the shape `jax.profiler.ProfileData` has (planes > lines >
events with `name`, `start_ns`, `duration_ns`, `stats`).

The made-up window, in ns on the host's clock (the device plane reads
1,000 ns early, as a recorded one does, and `clock_offset` holds it to
the enqueue events first):

    worker A  es.dispatch [ 1000, 2000)   es.launch f [1100, 1400)
                                          DoEnqueueProgram at 1500 (run 1:
                                          after the call has returned, on
                                          the runtime's own thread)
              es.collect  [ 2100, 4000)   es.download [2200, 3300)
                                          es.unpack   [3300, 3900)
    device    module run 1 [1500, 3000)   (fastest launch: held to 1500)
    worker A  es.dispatch [ 5000, 6000)   es.launch f [5100, 5800)
                                          DoEnqueueProgram at 5400 (run 2)
              es.collect  [ 6000, 9000)   es.download [6100, 8200)
                                          es.unpack   [8200, 8800)
    device    module run 2 [5600, 7700)
    worker A  es.collect  [ 9100, 9500)   es.download [9150, 9400): no
                                          module ends inside it
    request   es.http     [ 4100, 4900)   es.trace_export [4200, 4800)
    device    module run 3 [9600, 9700)   `jit_g`, enqueued at 9550: no
                                          es.launch of g

    python3 -m pytest benchmarks/tests -q        (not part of tier-1)
"""

import os
import sys
from types import SimpleNamespace as NS

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [HERE, os.path.dirname(HERE)]

from plugins import load_plugin  # noqa: E402

PLAIN = os.path.join(HERE, "sample_trace", "sample.xplane.pb")
BY_REQUEST = os.path.join(HERE, "sample_trace", "by_request.xplane.pb")
DEVICE_EARLY = 1000.0


def ev(name, start, end, **stats):
    return NS(name=name, start_ns=float(start),
              duration_ns=float(end - start), stats=list(stats.items()))


def made_up(with_phases: bool = True, with_export: bool = True):
    worker = [
        ev("es.dispatch", 1000, 2000), ev("es.collect", 2100, 4000),
        ev("es.dispatch", 5000, 6000), ev("es.collect", 6000, 9000),
        ev("es.collect", 9100, 9500),
    ]
    if with_phases:
        worker += [
            ev("es.launch", 1100, 1400, program="f"),
            ev("es.download", 2200, 3300),
            ev("es.unpack", 3300, 3900),
            ev("es.launch", 5100, 5800, program="f"),
            ev("es.download", 6100, 8200), ev("es.unpack", 8200, 8800),
            ev("es.download", 9150, 9400),
        ]
    request = [ev("es.http", 4100, 4900)]
    if with_export:
        request.append(ev("es.trace_export", 4200, 4800))
    runtime = [ev("DoEnqueueProgram", 1500, 1540, run_id=1),
               ev("DoEnqueueProgram", 5400, 5440, run_id=2),
               ev("DoEnqueueProgram", 9550, 9580, run_id=3)]
    mods = [(1, 1500, 3000, "f"), (2, 5600, 7700, "f"),
            (3, 9600, 9700, "g")]
    early = DEVICE_EARLY
    device = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[
            ev(f"jit_{name}(17)", s - early, e - early, run_id=r)
            for r, s, e, name in mods]),
        NS(name="XLA Ops", events=[
            ev("%fusion", s - early, e - early)
            for _r, s, e, _name in mods]),
    ])
    host = NS(name="/host:CPU", lines=[
        NS(name="python3", events=worker),
        NS(name="python3", events=request),
        NS(name="main/1", events=runtime),
    ])
    return NS(planes=[device, host])


@pytest.fixture(scope="module")
def phases():
    return load_plugin("readers", "idle_under_phase")


@pytest.fixture(scope="module")
def timeline():
    return load_plugin("readers", "launch_timeline")


def test_exclusive_shares_sum_to_the_workers_two(phases):
    shares = phases.shares_of(made_up())
    span = 9700.0 - 1500.0
    # the device idles [3000, 5600) and [7700, 9600)
    want = {
        # es.launch [5100, 5800) meets the first gap over [5100, 5600);
        # the first launch had returned before its program ran
        "launch": 500.0,
        # es.download: [3000, 3300), [7700, 8200), [9150, 9400)
        "download": 300.0 + 500.0 + 250.0,
        # es.unpack: [3300, 3900) and [8200, 8800)
        "unpack": 600.0 + 600.0,
        # es.dispatch [5000, 5100) outside its launch
        "dispatch_rest": 100.0,
        # es.collect [3900, 4000), [8800, 9000), [9100, 9150), [9400, 9500)
        "collect_rest": 100.0 + 200.0 + 50.0 + 100.0,
        # no worker phase open over [4200, 4800)
        "trace_export": 600.0,
    }
    for where, ns in want.items():
        assert shares[where] == pytest.approx(100.0 * ns / span), where
    assert shares["longest_ms"]["trace_export"] == pytest.approx(600e-6)
    assert shares["longest_ms"]["download"] == pytest.approx(500e-6)
    # with what is left of the two phases, the three are the idle time
    # under `es.dispatch` and `es.collect`: [3000, 4000), [5000, 5600),
    # [7700, 9000), [9100, 9500)
    under_workers = 1000.0 + 600.0 + 1300.0 + 400.0
    assert sum(shares[w] for w in (
        "launch", "download", "unpack", "dispatch_rest", "collect_rest",
    )) == pytest.approx(100.0 * under_workers / span)
    assert shares["busy_s"] == pytest.approx((1500 + 2100 + 100) / 1e9)


def test_shares_agree_with_the_workers_reader_on_a_recording(phases):
    """On PR 35's recording (no phase annotation inside the workers'
    two) the two remainders ARE `idle_under_annotation`'s shares."""
    from jax.profiler import ProfileData

    workers = load_plugin("readers", "idle_under_annotation")
    assert phases.idle_shares(BY_REQUEST) is None
    prof = ProfileData.from_file(BY_REQUEST)
    # give the recording one export so that the reader has something to
    # read: the shares of the two remainders must not move by it
    both = workers.idle_shares(BY_REQUEST)
    first = next(e for plane in prof.planes for line in plane.lines
                 for e in line.events if e.name == "es.http")
    extra = NS(name="/host:extra", lines=[NS(name="t", events=[
        ev("es.trace_export", first.start_ns, first.start_ns + 10.0)])])
    shares = phases.shares_of(NS(planes=[*prof.planes, extra]))
    assert shares["dispatch_rest"] == pytest.approx(both["dispatch"])
    assert shares["collect_rest"] == pytest.approx(both["collect"])
    assert shares["launch"] is None and shares["download"] is None
    assert shares["unpack"] is None


def test_a_trace_without_the_annotations_gives_nothing(phases, timeline):
    assert phases.idle_shares(PLAIN) is None
    assert phases.shares_of(made_up(False, False)) is None
    only_export = phases.shares_of(made_up(False, True))
    assert only_export["launch"] is None
    assert only_export["trace_export"] > 0.0
    no_export = phases.shares_of(made_up(True, False))
    assert no_export["trace_export"] is None and no_export["launch"] > 0.0
    assert timeline.stages(PLAIN) == {
        "launch_to_enqueue": None, "enqueue_to_start": None,
        "done_to_host": None}
    assert timeline.stages_of(made_up(False)) == timeline.stages(PLAIN)


def test_launch_timeline_joins_the_three_clocks(timeline):
    got = timeline.stages_of(made_up())
    # run 1 was enqueued after its call had returned and is the call's
    # all the same; run 3 is a program no `es.launch` asked for
    assert got["launch_to_enqueue"] == pytest.approx([400e-6, 300e-6])
    # the fastest launch (run 1) is held to zero; run 2 started 200 ns
    # after its enqueue
    assert got["enqueue_to_start"] == pytest.approx([0.0, 200e-6])
    # the third download holds no module's end: left out
    assert got["done_to_host"] == pytest.approx([300e-6, 500e-6])


def test_two_workers_launches_are_enqueued_first_come_first_served(
        timeline):
    prof = made_up()
    # a second worker asks for `f` at 5300, after worker A's 5100 and
    # before A's program is enqueued (5400); its own enqueue never comes
    # (the window ends): run 2 is A's, the older of the two
    prof.planes[1].lines.append(NS(name="python3", events=[
        ev("es.launch", 5300, 5500, program="f")]))
    got = timeline.stages_of(prof)
    assert got["launch_to_enqueue"] == pytest.approx([400e-6, 300e-6])
    # a launch whose enqueue event was lost does not shift the pairing
    # of those after it: one of `f` 200 ms before the window's first
    prof = made_up()
    prof.planes[1].lines.append(NS(name="python3", events=[
        ev("es.launch", -200_000_000, -199_999_000, program="f")]))
    got = timeline.stages_of(prof)
    assert got["launch_to_enqueue"] == pytest.approx([400e-6, 300e-6])


def test_subtract(phases):
    a = [[0, 10], [20, 30]]
    assert phases.subtract(a, []) == a
    assert phases.subtract(a, [[5, 25]]) == [[0, 5], [25, 30]]
    assert phases.subtract(a, [[-5, 40]]) == []
    assert phases.subtract(a, [[2, 3], [4, 6], [28, 50]]) == [
        [0, 2], [3, 4], [6, 10], [20, 28]]


def test_read_takes_the_runs_own_trace(phases, timeline, tmp_path,
                                       monkeypatch):
    import shutil

    from tracereduce import reduce_trace

    prof = tmp_path / "a-cell" / "profile" / "plugins" / "profile" / "t0"
    prof.mkdir(parents=True)
    shutil.copy(BY_REQUEST, prof / "vm.xplane.pb")
    for reader in (phases, timeline):
        monkeypatch.setattr(reader._workers, "RUN_DIR", str(tmp_path))
    obs = {"rehearsal": False,
           "profile": {"busy_s": reduce_trace(BY_REQUEST)["busy_s"]}}
    # a parent's trace: no annotation of PR 51, so nothing is reported
    assert phases.read(obs, {"where": "download"}) is None
    assert timeline.read(obs, {"stage": "done_to_host"}) is None
