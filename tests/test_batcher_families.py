"""The batcher's family table (search/batcher.py FAMILIES).

Contract under test:
  * which jobs share a launch is decided in one place, per kind: two
    jobs that agree in every attribute the family keys on (and in
    executor and top-k bucket) share a group; two that differ in any one
    of them do not;
  * a kind outside the table is refused at `submit_nowait`, not served
    as some other family;
  * every family is a dispatch / collect pair, and a batch launches
    its groups in the order their first jobs were submitted;
  * a raise inside a family's dispatch or collect fails that group's
    waiters only and leaves the in-flight count of its overlap class
    at 0;
  * `groups_launched_together` counts a batch's groups when it holds
    two or more uncollected at the end of its launches.

No device work: the sharing cases stub the family's dispatch / collect
pair (its `share`, overlap class and placement stay the table's), the
failure cases make the real group method raise.
"""

import dataclasses
from types import SimpleNamespace

import pytest

from elasticsearch_tpu.search.batcher import FAMILIES, QueryBatcher, _Job

# kind -> {attribute the family keys on: (a value, another value)}: the
# specification the table is held to, written out independently of it
KEYED = {
    "match": {"field": ("body", "title")},
    "serve": {
        "fields": (("title", "body"), ("body",)),
        "combine": ("sum", "max_tie"),
        "tie": (0.0, 0.3),
        # a filtered launch builds its rows' masks from ONE keyword
        # field's postings, and a negated one reads the count plane's
        # last digit as a veto: the field and WHETHER a job excludes
        # are keyed on, never the filter's values or the excluded terms
        "filter": (None, SimpleNamespace(field="tag")),
        "excluded": (0, 2),
    },
    # `filter`: bare and filtered jobs are two programs (WHETHER a job
    # is filtered is keyed on, never which filter it carries)
    "knn": {"field": ("vec", "vec2"), "ann": (None, "ivf:8"),
            "filter": (None, "tags:t1")},
    # `width`: a phrase's span in slots is one program; its words are not
    "phrase": {"field": ("body", "title"), "width": (2, 3)},
    # what is static in the expansion program (`max_expansions`,
    # `transpositions`) is keyed on; the fuzziness and the words are not
    "fuzzy": {"field": ("body", "title"),
              "params": (SimpleNamespace(max_expansions=50,
                                         transpositions=True),
                         SimpleNamespace(max_expansions=50,
                                         transpositions=False))},
    "agg": {"sig": ("terms:tag", "terms:cat")},
    "rerank": {"sig": ("m1:16:8", "m1:32:8")},
    "sparse": {"field": ("sv", "sv2"), "spec": ("fp32", "int8")},
    "mesh_match": {
        "field": ("body", "title"),
        "rescore_sig": (None, "maxsim:50"),
    },
    "mesh_serve": {
        "fields": (("title", "body"), ("body",)),
        "combine": ("sum", "max_tie"),
        "tie": (0.0, 0.3),
    },
    "mesh_knn": {"field": ("vec", "vec2"), "ann": (None, "ivf:8")},
    "mesh_sparse": {"field": ("sv", "sv2"), "spec": ("fp32", "int8")},
    "mesh_agg": {"sig": ("terms:tag", "terms:cat")},
}
OVERLAP = {
    "match": "text", "serve": "text", "phrase": "text", "fuzzy": "text",
    "knn": "knn", "agg": "agg",
    "rerank": "rerank", "sparse": "sparse", "mesh_match": "text",
    "mesh_serve": "text", "mesh_knn": "knn", "mesh_sparse": "sparse",
    "mesh_agg": "agg",
}
SHARING_CASES = [
    (kind, differs)
    for kind, attrs in KEYED.items()
    for differs in ("nothing", *attrs, "executor", "kb")
]


def plan_of(kind, **over):
    attrs = {a: vals[0] for a, vals in KEYED[kind].items()}
    attrs.update(over)
    return SimpleNamespace(**attrs)


@pytest.fixture
def batcher():
    b = QueryBatcher()  # no worker starts until a job is submitted
    yield b
    b.close()


def groups_of(batcher, monkeypatch, jobs):
    """The groups one batch of `jobs` is dispatched as: [[job, ...]]."""
    seen = []

    def dispatch(b, group, key, kb, rows, record):
        seen.append(list(group))
        return {"rows": 4, "flops": 0}  # what a mesh dispatch returns

    for kind in {j.kind for j in jobs}:
        fam = FAMILIES[kind]
        monkeypatch.setitem(FAMILIES, kind, dataclasses.replace(
            fam, dispatch=dispatch, warm=None, collect=lambda *a: None,
        ))
    batcher._collect_batch(batcher._dispatch_batch(jobs))
    assert all(n == 0 for n in batcher._inflight.values())
    return seen


class TestTheTable:
    def test_holds_the_thirteen_kinds_and_their_overlap_classes(self, batcher):
        assert {k: f.overlap for k, f in FAMILIES.items()} == OVERLAP
        assert set(batcher._inflight) == set(OVERLAP.values())
        assert {k for k, f in FAMILIES.items() if f.mesh} == {
            k for k in OVERLAP if k.startswith("mesh_")
        }
        # the kinds whose first dispatch warms the bucket ladder
        assert {k for k, f in FAMILIES.items() if f.warm} == {
            "match", "serve", "phrase", "fuzzy", "knn", "sparse",
        }

    def test_every_family_is_a_dispatch_collect_pair(self):
        for kind, fam in FAMILIES.items():
            assert callable(fam.dispatch) and callable(fam.collect), kind

    def test_unknown_kind_is_refused_at_submit(self, batcher):
        with pytest.raises(ValueError, match="unknown job kind"):
            batcher.submit_nowait(object(), plan_of("knn"), 10, kind="ann")
        assert batcher._queue.qsize() == 0
        assert batcher._threads == []  # nothing was enqueued or started
        assert batcher.stats["jobs"] == 0


class TestLaunchSharing:
    @pytest.mark.parametrize(
        "kind,differs", SHARING_CASES,
        ids=[f"{k}-{d}" for k, d in SHARING_CASES],
    )
    def test_jobs_share_a_group_only_when_the_family_says(
        self, batcher, monkeypatch, kind, differs
    ):
        ex = object()
        a = _Job(ex, plan_of(kind), 10, kind=kind)
        if differs == "nothing":
            # k 10 and 12 page from the same top-k bucket (16)
            b = _Job(ex, plan_of(kind), 12, kind=kind)
        elif differs == "executor":
            b = _Job(object(), plan_of(kind), 10, kind=kind)
        elif differs == "kb":
            b = _Job(ex, plan_of(kind), 40, kind=kind)
        else:
            other = KEYED[kind][differs][1]
            b = _Job(ex, plan_of(kind, **{differs: other}), 10, kind=kind)
        groups = groups_of(batcher, monkeypatch, [a, b])
        if differs == "nothing":
            assert groups == [[a, b]]
        else:
            assert sorted(groups, key=lambda g: g[0] is b) == [[a], [b]]

    def test_kinds_never_share_and_groups_launch_in_submission_order(
        self, batcher, monkeypatch
    ):
        """A per-shard family and its mesh placement key on the same
        attributes and still never share; a batch's groups launch in the
        order their first jobs were submitted, whatever the family."""
        ex = object()
        jobs = [
            _Job(ex, plan_of("match"), 10, kind="match"),
            _Job(ex, plan_of("knn"), 10, kind="knn"),
            _Job(ex, plan_of("mesh_knn"), 10, kind="mesh_knn"),
            _Job(ex, plan_of("match"), 10, kind="match"),
        ]
        groups = groups_of(batcher, monkeypatch, jobs)
        assert groups == [[jobs[0], jobs[3]], [jobs[1]], [jobs[2]]]
        # three groups were uncollected at the end of the launches
        assert batcher.stats["groups_launched_together"] == 3

    def test_a_batch_of_one_group_counts_no_group_launched_together(
        self, batcher, monkeypatch
    ):
        ex = object()
        jobs = [_Job(ex, plan_of("match"), 10, kind="match")
                for _ in range(3)]
        assert groups_of(batcher, monkeypatch, jobs) == [jobs]
        assert batcher.stats["groups_launched_together"] == 0
        assert batcher.batching_stats()["groups_launched_together"] == 0


class Boom(RuntimeError):
    pass


def boom(*a, **kw):
    raise Boom("injected")


def fake_agg_job(ex):
    """A job of another family beside the failing group: the agg
    family's real dispatch / collect pair calls the plan's own."""
    plan = SimpleNamespace(
        sig="terms:tag", dispatch=lambda: "pend",
        collect=lambda pend: ("result", pend), flops_estimate=lambda: 7,
    )
    return _Job(ex, plan, 10, kind="agg")


class TestFailureIsolation:
    @pytest.mark.parametrize("kind,method", [
        ("match", "_dispatch_match_group"),
        ("serve", "_dispatch_serve_group"),
        ("knn", "_dispatch_knn_group"),
        ("sparse", "_dispatch_sparse_group"),
    ])
    def test_a_raise_in_dispatch_fails_its_group_only(
        self, batcher, monkeypatch, kind, method
    ):
        batcher.warmup_enabled = True  # a failed group must warm nothing
        monkeypatch.setattr(batcher, method, boom)
        ex = object()
        failing = [_Job(ex, plan_of(kind), 10, kind=kind) for _ in range(2)]
        beside = fake_agg_job(ex)
        ctx = batcher._dispatch_batch([failing[0], beside, failing[1]])
        for j in failing:
            assert j.done() and isinstance(j.error, Boom)
        assert batcher._inflight[OVERLAP[kind]] == 0
        assert batcher._inflight["agg"] == 1  # dispatched, not collected
        assert batcher._warmed == set()
        batcher._collect_batch(ctx)
        assert beside.error is None
        assert beside.result == ("result", "pend")
        assert beside.group.launches == 1 and beside.group.flops == 7
        assert all(n == 0 for n in batcher._inflight.values())

    @pytest.mark.parametrize("kind,dispatch,collect", [
        ("match", "_dispatch_match_group", "_collect_match_group"),
        ("serve", "_dispatch_serve_group", "_collect_serve_group"),
        ("knn", "_dispatch_knn_group", "_collect_knn_group"),
        ("sparse", "_dispatch_sparse_group", "_collect_sparse_group"),
    ])
    def test_a_raise_in_collect_fails_its_group_only(
        self, batcher, monkeypatch, kind, dispatch, collect
    ):
        monkeypatch.setattr(batcher, dispatch, lambda *a, **kw: [])
        monkeypatch.setattr(batcher, collect, boom)
        ex = object()
        failing = _Job(ex, plan_of(kind), 10, kind=kind)
        beside = fake_agg_job(ex)
        ctx = batcher._dispatch_batch([failing, beside])
        assert not failing.done()
        assert batcher._inflight[OVERLAP[kind]] == 1
        batcher._collect_batch(ctx)
        assert isinstance(failing.error, Boom)
        assert beside.error is None
        assert beside.result == ("result", "pend")
        assert all(n == 0 for n in batcher._inflight.values())
