"""The reader PR 35 added, checked without a chip.

`idle_by_request` on `sample_trace/by_request.xplane.pb`, recorded on a
TPU v5e by `sample_trace/record_by_request.py`: six launches, five pauses
each walked through a worker's `es.collect`, a request thread's
`es.search` alone, its `es.http` alone, no annotation at all, the next
request's `es.http`, its `es.search` and a worker's `es.dispatch`; a
second thread's `es.search` over the last two pauses. The recorder timed
every part on the host's clock (`by_request.expect.json`). The reader's
three shares must give the three request-side sums to within 2 ms of the
180 to 620 ms each holds (they are bounded by host events on both sides;
the latencies between the two clocks fall into `collect` and
`dispatch`), must each be non-negative, and must sum to the `elsewhere`
share `idle_under_annotation` gives for the same file.

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

BY_REQUEST = os.path.join(HERE, "sample_trace", "by_request.xplane.pb")
WORKERS_ONLY = os.path.join(HERE, "sample_trace", "annotated.xplane.pb")
PLAIN = os.path.join(HERE, "sample_trace", "sample.xplane.pb")
PLACES = ("front", "search", "none")


@pytest.fixture(scope="module")
def reader():
    return load_plugin("readers", "idle_by_request")


@pytest.fixture(scope="module")
def by_workers():
    return load_plugin("readers", "idle_under_annotation")


def test_shares_are_what_the_recording_holds(reader):
    shares = reader.idle_shares(BY_REQUEST)
    with open(BY_REQUEST.replace(".xplane.pb", ".expect.json")) as f:
        known = json.load(f)["idle_ms"]
    window_ms = reduce_trace(BY_REQUEST)["span_s"] * 1e3
    for where in PLACES:
        assert shares[where] >= 0.0
        assert abs(shares[where] * window_ms / 100.0 - known[where]) < 2.0, (
            where, shares, known)
    # the second request in the search turned two pauses' `front` and
    # `none` sleeps into `search`: three pauses of each are left
    assert known["search"] > 3 * known["front"] > 500.0
    assert known["none"] > 3 * 60.0


def test_exclusive_after_the_workers_two_classes(reader, by_workers):
    shares = reader.idle_shares(BY_REQUEST)
    workers = by_workers.idle_shares(BY_REQUEST)
    assert abs(sum(shares[w] for w in PLACES) - workers["elsewhere"]) < 1e-6
    assert abs(shares["elsewhere"] - workers["elsewhere"]) < 1e-6
    assert abs(shares["busy_s"] - workers["busy_s"]) < 1e-12
    # and the five classes together are the device's idle share
    assert abs(sum(shares[w] for w in PLACES) + workers["dispatch"]
               + workers["collect"] - workers["idle"]) < 1e-6


def test_no_request_annotation_gives_nothing(reader):
    # PR 25's recording holds `es.dispatch` / `es.collect` and no
    # `es.http`: a program without the request thread's annotations
    assert reader.idle_shares(WORKERS_ONLY) is None
    assert reader.idle_shares(PLAIN) is None


def test_outside(reader):
    a = [[0, 10], [20, 30]]
    assert reader.outside(a, []) == 20
    assert reader.outside(a, [[5, 25]]) == 10
    assert reader.outside(a, [[-5, 40]]) == 0
    assert reader.outside([], a) == 0


def test_read_takes_the_runs_own_trace(reader, tmp_path, monkeypatch):
    import shutil

    prof = tmp_path / "a-cell" / "profile" / "plugins" / "profile" / "t0"
    prof.mkdir(parents=True)
    shutil.copy(BY_REQUEST, prof / "vm.xplane.pb")
    # `newest_trace` is the workers' reader's, and looks where that says
    monkeypatch.setattr(reader._workers, "RUN_DIR", str(tmp_path))
    reduced = reduce_trace(BY_REQUEST)
    obs = {"rehearsal": False, "profile": {"busy_s": reduced["busy_s"]}}
    got = [reader.read(obs, {"where": w}) for w in PLACES]
    assert got == [reader.idle_shares(BY_REQUEST)[w] for w in PLACES]
    obs["profile"]["busy_s"] += 1e-3
    with pytest.raises(RuntimeError):
        reader.read(obs, {"where": "front"})
