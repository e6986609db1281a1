"""Per-request span-tree tracing (lightweight, always-cheap), and the
node's host<->device transfer counters.

Reference analog: the `X-Opaque-Id` header + task-manager description
propagation in org.elasticsearch.tasks, and the APM-style span trees
the reference ships via apm-agent — here a minimal in-process recorder
so a single slow request can be decomposed (queue wait vs. kernel vs.
merge vs. fetch) without any external collector.

Design:
  * `Trace` holds a bounded list of `Span`s (monotonic nanosecond
    clocks, parent/child ids, free-form tags like index/shard/bucket).
  * `TRACE_CTX` is a contextvar: the REST layer arms it per request
    (`begin()` / `end()`), and every seam that wants a span just reads
    the var — `None` means tracing is off and costs one dict lookup.
    An HTTP handler announces itself in `REQUEST_CTX`; a trace armed
    under one is handed to it and closed after the response.
    A fan-out runs each shard under `contextvars.copy_context()`, in
    its pool or on the request thread; the Trace object itself is
    shared and thread-safe, so spans added from shard/leg worker
    threads land in the request's tree.
  * Spans are written retroactively (`add_span`) from marks the code
    took anyway. A span names the span that caused it: `PARENT_CTX`
    holds the id of the span the current code runs under and is
    `add_span`'s default parent. A span that must be a parent before
    its end is known reserves its id first (`reserve_span`, made
    current with `under`) and is written with `span_id=` later. A
    layer's self time is its duration less what its children cover.
  * Completed traces go into a bounded ring (`ES_TPU_TRACE_RING`,
    default 256) queryable via `GET /_internal/traces` — a test/smoke
    surface, not a production exporter. The answer is assembled from
    one encoding a trace (`Trace.encoded`, kept on the trace: a
    finished trace does not change) with the interpreter yielded
    between traces, so a poll of the whole ring never holds the
    request threads and the dispatcher workers for its length
    (`export`); `_nodes/stats` `tracing.*` counts the exports, their
    traces and milliseconds, and the traces the ring dropped unread.
  * `ES_TPU_TRACING=off` disables arming entirely (`begin()` → None).

The spans of a search over HTTP, parent > children (clock:
`perf_counter_ns` of the serving process; tags in brackets). The trace
starts at `http`'s start and reaches the ring when `http` ends:

    http [method, status, request_bytes, response_bytes]   the root: the
        request line read (the entry of `parse_request`; the wait in
        front of it on an idle connection is the client's) -> the
        response's last byte written. The handler thread takes the marks
        on every request (`RequestMarks`); the spans are written where
        an action armed a trace (`begin`), 4xx and 429 answers included
      > http_read   request line and headers parsed, body read
      > request_parse [bytes]   urlparse, parse_qs, the router,
            `json.loads` of the body
      > admission_wait [tier, limit]   the wait in admission.acquire,
            before the coordinator span starts
      > coordinator   (below)
      > respond [bytes, dumps_ms]   `json.dumps` (dumps_ms of it), status
            line and headers, the socket write
        http's self time is the action's own: `qs` handling, the task
        registry, arming the trace, the response's assembly
    (a search called as a library, with no handler above it, has no
    `http`: `admission_wait` and `coordinator` are its roots)
    coordinator [index, shards, took_ms]
      > parse, can_match, dfs, fan_out [inline], reduce   (tile the
        coordinator; inline true: the index's one shard ran on the
        request thread, false: in the fan-out pool — several shards, a
        `timeout`, a remote or pinned copy, a query no planner took)
    fan_out > shard_search [index, shard, backend]   (one per shard)
    coordinator of a `retriever` (or `rank: {rrf}`) search, the same
    span under the same name
      > retriever, rescore, fetch   (tile it; no fan_out: the legs of
        an rrf node go to the batcher themselves)
    retriever > rrf [index, legs]
      > plan_legs [legs, <label>_ms a leg]   the legs' common start ->
            the last leg's job submitted: `_plan_leg` and the submits,
            on the request thread, inside every `leg:<label>` span
      > leg:<label> [mode]   (bm25, knn, sparse, other; one per child)
            the legs' common start -> the leg's OWN completion mark (a
            batcher job's `t_done`, the end of a pool or inline run),
            whatever order the request thread waited in
      > wake   the latest leg's completion mark -> the request thread
            is running again (the start of `fuse`)
      > fuse [window]   -> the fused list: the host's dictionary over
            the legs' hits (ops/fusion.rrf_fuse_ranked)
    shard_search is tiled plan | queue_wait | dispatch | inflight |
    collect | wake | fetch; what is left of it is the hand-over between
    them. A `leg:<label>` of an rrf retriever (or `mesh_search`) holds
    the four job spans alone. Under a `rescore` the shard's second
    stage stands between `wake` and `fetch`:
        rescore [window, candidates]   the first stage's TopDocs -> the
            rescored page, on the request thread; tiled
            rerank_plan [candidates, query_vectors] (`build_plan`: the
            window's columns as they came down, the query matrix; ->
            the rerank job's submit mark) | the `rerank` job's
            queue_wait | dispatch | inflight | collect | wake, what is
            left the columns permuted and the page's `Hit`s made
            (`TopDocs.head`). The FIRST
            stage's `collect` span then carries window and
            ties_refilled (`QueryBatcher._window_topk`: the window's
            cut, Lucene's).
        plan [family, planned; a serve plan also filtered, negated:
            the bool brought a planned `filter` / `must_not`]   the
            shard's entry -> the job's submit
            mark in `submit_nowait` (`t_enq`, where `queue_wait` starts):
            parse_query / the kNN section, extract_*_plan; planned
            false: no plan, the unbatched executor ran what follows
        the job spans of search/batcher.py, consecutive marks of the
        dispatcher worker from submit to the worker's completion mark:
        queue_wait [family, cold_ms]  submit -> a worker starts the
            job's group; cold_ms = compile time that accrued meanwhile
        dispatch [family, jobs, rows, launches (its `launch`
            children), express, overflow; a
            fused match or serve group also rare_tiles, a serve group
            fields and hot_slots: the most tile slots / dense rows a
            job and field used; a sparse group terms, tiles_scored,
            tiles_pruned, chunk_launches, trips (the loops' trips of
            those launches), dense_rows, tiles_dense
            (sums over its jobs and segments) and quantized; a filtered knn group filtered,
            clauses (the most a job's filter holds) and filter_tiles
            (postings tiles its mask launches scattered, summed over
            jobs and segments: the tiles of terms a bit row answers are
            not among them); a phrase group words (its jobs' words)
            and occurrences (position entries its launches were handed,
            summed over jobs and segments)]
            -> the group's last kernel is enqueued
          > filter_mask [segment, launches, tiles, bitset_terms,
            bitset_rows_held]  a filtered knn group's masks on one
            segment: the filters' terms looked up and packed, the plan
            uploaded, `knn_filter_mask` enqueued (one launch; the
            device builds each row's mask from the bit rows of the
            terms that hold one, `bitset_terms` of the launch's, and
            the `tiles` postings tiles of the others; no host sync).
            `bitset_rows_held`: the rows the segment's field holds
            (built inside the field's first such span)
          > knn_lead [segment, launches, tiles, lead_rows,
            bitset_terms, bitset_rows_held]  in `filter_mask`'s place
            where every job of the group leads by postings on the
            segment (the group's `dispatch` span then carries lead):
            the terms looked up, the lead plan packed and uploaded,
            `knn_topk_lead` enqueued (the group's ONE launch there:
            `tiles` the leads' and the verified ranges' postings tiles
            it gathers, `lead_rows` the candidate slots it scores; no
            mask, no scan, no host sync)
            Under a filtered SERVE group (a `bool` with a planned
            `filter`) the span is the host's part alone, launches 0:
            the filter field's postings and bit rows fetched, the
            filters' terms looked up, the plan packed; the plan is an
            operand of the fused launch, whose program builds the
            masks (that group's `dispatch` span also carries filtered,
            filter_clauses, excluded_terms and filter_tiles)
          > fuzzy_expand [segment, words, launches]  a fuzzy group: its
            distinct words that take an edit packed, `fuzzy_expand`
            launched and the kept ordinals and distances downloaded
            (the `launch` and `download` inside it are its children's
            siblings; `es.fuzzy_expand` on the profiler's clock)
          > fuzzy_plan [segment, terms_kept, tiles, hot_terms]  the
            same group: ordinals -> boosts, blended idf, weights, dense
            rows and tiles, and the fused program at the family's slot
            budgets enqueued (`es.fuzzy_plan`)
          > phrase_plan [segment, launches, words]  a phrase group
            on one segment: the words looked up in the term dictionary,
            the plan packed and uploaded, `phrase_topk` enqueued (one
            launch; no host sync)
          > sparse_plan [segment, terms, cold_terms, tiles_kept]  a
            sparse group on one segment, from the segment's entry to
            just before its first `_impact_chunk_add` is enqueued: the
            terms looked up, the row launch enqueued, `sparse_theta`,
            the surviving tiles listed and staged (the host's share of
            the request's critical path; `cold_terms` go through
            tiles, `tiles_kept` of them after pruning)
          > sparse_theta [segment, launches, postings]  a sparse
            group's thresholds on one segment, computed on the host
            from its prunable jobs' first tiles (`postings` slots
            gathered; launches 0: no device work, no host sync);
            inside `sparse_plan`
          > launch [program, host_operands, h2d_bytes]  the entry of
            one jitted call -> its return (`launch`, the bracket every
            launch site of the query path stands in): the host's cost
            of a launch. `host_operands` the operands handed over as
            host arrays, `h2d_bytes` theirs (what `note_transfer`
            notes at the site). A child of `dispatch`, inside
            `filter_mask` / `knn_lead` / `phrase_plan` where the group
            has one (a sparse group's row launch inside `sparse_plan`,
            its chunk launch and `_finalize` after it), with the
            host's packing between the launches; the merge program a
            collect still launches (`_merge_segments`,
            `_knn_merge_segments`) is a child of `collect`
        inflight  -> the worker comes back to collect the group
        collect [d2h_bytes; a text or sparse group also merged: false
            when it downloaded the fused kernel's packed row as it was,
            true when the merge program ran]  blocking download, hits
            -> the job's `t_done`, the WORKER's mark before it writes
            the job's spans and sets the waiter's event. Tiled
            launch* | download+ | unpack up to the hand-over between
            the marks:
          > download [bytes]  `ops/scoring._to_host`'s entry -> its
            return: the worker blocked until the awaited program is
            done AND its bytes are on the host (the one site of a
            blocking download; the children's `bytes` sum to
            `d2h_bytes`). Under `dispatch` where a dispatch blocks
            (the chunked path's threshold round)
          > unpack  the group's last download ended -> the job's
            `t_done`: decode_result, rank_order, the Hits and TopDocs
        compile [program, seconds]  child of the dispatch (or collect)
            span the worker compiled in; one per program
        wake   `t_done` -> the waiter is back from `QueryBatcher.wait`:
            the worker's span writes and the hand-over of the
            interpreter to the request thread
        fetch   (sources, highlight: the folded fetch phase)

On the profiler's clock the same phases are
`jax.profiler.TraceAnnotation`s: the workers' `es.dispatch` /
`es.collect` (arguments `family`, `rows`) and, nested in them,
`es.launch` [`program`], `es.download` and `es.unpack` (the three spans
above; `es.unpack` closes with the group's collect, after its last
job); the request thread's `es.http` (the `http` span's interval),
`es.search` [`route`] (around the action's `cluster.search` call: the
`coordinator` span's thread and interval) and `es.trace_export` (the
traces action assembling its answer). Start
`jax.profiler.start_trace(dir)` on the serving process and they land on
the host plane of the `.xplane.pb`, one line per dispatcher or
connection thread, beside the device's `XLA Ops` line. With the
runtime's `DoEnqueueProgram` events and the device's `XLA Modules`
line (joined by `run_id`) a launch's life is on one clock: `es.launch`
opens -> `DoEnqueueProgram` -> the module starts -> it ends ->
`es.download` closes -> `es.unpack` closes.

`note_transfer` counts the query path's host<->device transfers where
they happen (ops/scoring.py, the kNN upload in search/batcher.py, the
serve family's per-job fallback and `JaxExecutor.segment_topk`, the
sparse family's chunk planes and theta in ops/impact.py; the rrf fuse
moves nothing);
`_nodes/stats` reports the totals as `transfer.scoring.*`. The other
counters of that document are declared, and explained, by the layer
that counts them (search/batcher.NODE_STATS, IndexService.NODE_STATS).

`OPAQUE_ID_CTX` carries the request's `X-Opaque-Id` header value so
task descriptions, slow-log records, and traces can all attribute work
to the caller's id without threading a parameter through every layer.
"""

from __future__ import annotations

import contextlib
import contextvars
import itertools
import json
import os
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional

# the CURRENT request's X-Opaque-Id header (None outside a request or
# when the client sent none)
OPAQUE_ID_CTX: contextvars.ContextVar = contextvars.ContextVar(
    "opaque_id", default=None
)

# the CURRENT request's Trace (None = tracing off / not a traced path)
TRACE_CTX: contextvars.ContextVar = contextvars.ContextVar(
    "trace_ctx", default=None
)

# id of the span the current code runs under (copy-on-thread via
# contextvars, so concurrent shards and legs each see their own chain)
PARENT_CTX: contextvars.ContextVar = contextvars.ContextVar(
    "span_parent", default=None
)

# the HTTP request the current thread is serving: its handler's
# `RequestMarks` (None: no handler above this code, as in a library call)
REQUEST_CTX: contextvars.ContextVar = contextvars.ContextVar(
    "http_request", default=None
)

# hard cap per trace: a runaway fan-out must not grow one trace without
# bound (drops are counted, not silent)
MAX_SPANS = 512

_trace_ids = itertools.count(1)


def enabled() -> bool:
    return os.environ.get("ES_TPU_TRACING", "on").lower() not in (
        "off", "0", "false",
    )


def _ring_cap() -> int:
    try:
        return max(1, int(os.environ.get("ES_TPU_TRACE_RING", "256")))
    except ValueError:
        return 256


class Span:
    __slots__ = ("id", "parent_id", "name", "start_ns", "end_ns", "tags")

    def __init__(
        self, id: int, parent_id: Optional[int], name: str,
        start_ns: int, end_ns: int, tags: Dict[str, Any],
    ):
        self.id = id
        self.parent_id = parent_id
        self.name = name
        self.start_ns = start_ns
        self.end_ns = end_ns
        self.tags = tags

    def to_dict(self) -> dict:
        return {
            "id": self.id,
            "parent_id": self.parent_id,
            "name": self.name,
            "start_ns": self.start_ns,
            "duration_ns": max(0, self.end_ns - self.start_ns),
            "tags": self.tags,
        }


class Trace:
    """One request's span tree. Thread-safe: fan-out worker threads
    append concurrently (the object rides a copied context into the
    pools). Clocks are `time.perf_counter_ns()` — monotonic, so spans
    recorded on different threads order correctly within one host."""

    def __init__(self, name: str, opaque_id: Optional[str] = None,
                 start_ns: Optional[int] = None, **tags: Any):
        self.trace_id = f"trace-{next(_trace_ids)}"
        self.name = name
        self.opaque_id = opaque_id
        self.tags = dict(tags)
        now = time.perf_counter_ns()
        # `start_ns`: a mark taken before the trace was armed (an HTTP
        # request's first line): the trace starts there, on both clocks
        self.start_ns = now if start_ns is None else start_ns
        self.end_ns: Optional[int] = None
        self.wall_start = time.time() - (now - self.start_ns) / 1e9
        self._spans: List[Span] = []
        self._dropped = 0
        self._span_ids = itertools.count(1)
        self._lock = threading.Lock()
        # the finished trace as JSON (`encoded`), and whether an export
        # has read it (the ring's `ring_overwritten` counts those it
        # drops unread)
        self._encoded: Optional[bytes] = None
        self.exported = False

    # ---- recording ----

    def reserve_span(self) -> int:
        """An id for a span that is written later, when its end is
        known, so that spans recorded meanwhile can name it as their
        parent (`under(id)`, or an explicit `parent_id`)."""
        # no lock: `next` of an `itertools.count` is one step of the
        # interpreter, and a worker reserves a job's parents ahead of
        # the one `add_spans` that writes them
        return next(self._span_ids)

    def _write(self, name, start_ns, end_ns, parent_id, span_id, tags):
        """One span, the lock held. -> its id, or None if the trace is
        full (the drop is counted)."""
        self._encoded = None  # a straggler's span after `finish`
        if len(self._spans) >= MAX_SPANS:
            self._dropped += 1
            return None
        sid = next(self._span_ids) if span_id is None else span_id
        self._spans.append(
            Span(sid, parent_id, name, int(start_ns), int(end_ns), tags)
        )
        return sid

    def add_span(
        self, name: str, start_ns: int, end_ns: int,
        parent_id: Optional[int] = None, span_id: Optional[int] = None,
        **tags: Any,
    ) -> Optional[int]:
        """Retroactive span from two already-taken perf_counter_ns
        marks (the cheap pattern for code that timed itself anyway).
        `parent_id` defaults to the span the caller runs under;
        `span_id` is an id from `reserve_span`. Returns the span id, or
        None if the trace is full."""
        if parent_id is None:
            parent_id = PARENT_CTX.get()
        with self._lock:
            return self._write(name, start_ns, end_ns, parent_id, span_id,
                               tags)

    def add_spans(self, spans) -> None:
        """Several retroactive spans in one acquisition of the lock:
        (name, start_ns, end_ns, parent_id, span_id, tags) each, parent
        and id explicit (None: a root, a fresh id)."""
        with self._lock:
            for span in spans:
                self._write(*span)

    def finish(self, end_ns: Optional[int] = None) -> None:
        """Closes the trace (at `end_ns`, a mark already taken, else
        now) and publishes it to the ring."""
        if self.end_ns is not None:
            return
        self.end_ns = time.perf_counter_ns() if end_ns is None else end_ns
        _ring_append(self)

    # ---- export ----

    def to_dict(self) -> dict:
        with self._lock:
            spans = [s.to_dict() for s in self._spans]
            dropped = self._dropped
        end = self.end_ns or time.perf_counter_ns()
        return {
            "trace_id": self.trace_id,
            "name": self.name,
            "opaque_id": self.opaque_id,
            "tags": self.tags,
            "started_at": self.wall_start,
            "duration_ns": max(0, end - self.start_ns),
            "span_count": len(spans),
            "dropped_spans": dropped,
            "spans": spans,
        }

    def encoded(self) -> bytes:
        """`to_dict()` as JSON. A finished trace does not change, so
        its encoding is made once and kept for the next export."""
        enc = self._encoded
        if enc is None:
            enc = json.dumps(self.to_dict()).encode()
            if self.end_ns is not None:
                self._encoded = enc
        return enc


@contextlib.contextmanager
def under(span_id: Optional[int]):
    """Code in the block runs under span `span_id`: spans it records,
    and jobs it submits to the batcher, name that span as their parent.
    The var rides `copy_context()` into the fan-out pools."""
    tok = PARENT_CTX.set(span_id)
    try:
        yield
    finally:
        PARENT_CTX.reset(tok)


# ---- completed-trace ring (GET /_internal/traces) ----

_ring_lock = threading.Lock()
_ring: deque = deque(maxlen=_ring_cap())
# `_nodes/stats` `tracing`: exports answered, the traces they carried,
# the milliseconds they took, and traces the ring pushed out before any
# export had read them (an operator sizing ES_TPU_TRACE_RING against
# the poll's period reads the last)
_export = {"exports": 0, "exported_traces": 0, "export_ms": 0.0,
           "ring_overwritten": 0}


def _ring_append(trace: Trace) -> None:
    with _ring_lock:
        if len(_ring) == _ring.maxlen and not _ring[0].exported:
            _export["ring_overwritten"] += 1
        _ring.append(trace)


def _newest(n: int) -> List[Trace]:
    """The last `n` completed traces, newest first."""
    with _ring_lock:
        return list(_ring)[-max(0, int(n)):][::-1] if n > 0 else []


def recent(n: int = 50) -> List[dict]:
    """Newest-first dicts of the last `n` completed traces."""
    return [t.to_dict() for t in _newest(n)]


def export(n: int = 50) -> bytes:
    """The body of `GET /_internal/traces`: the JSON document
    `{"enabled", "count", "traces": recent(n)}`, assembled from the
    traces' own encodings. The interpreter is yielded after each trace
    encoded here (one an earlier export encoded costs an append), so
    the poll of a full ring holds the serving threads for one trace's
    encoding at a time (a tenth of a millisecond), not for the
    document's (tens)."""
    t0 = time.perf_counter_ns()
    traces = _newest(n)
    parts = []
    for t in traces:
        fresh = t._encoded is None
        parts.append(t.encoded())
        t.exported = True
        if fresh:
            time.sleep(0)  # lets a waiting thread have the interpreter
    head = json.dumps({"enabled": enabled(), "count": len(parts)})
    body = b"%s, \"traces\": [%s]}" % (
        head[:-1].encode(), b", ".join(parts))
    with _ring_lock:
        _export["exports"] += 1
        _export["exported_traces"] += len(parts)
        _export["export_ms"] += (time.perf_counter_ns() - t0) / 1e6
    return body


def export_stats() -> Dict[str, Any]:
    with _ring_lock:
        return {**_export, "export_ms": round(_export["export_ms"], 3)}


def clear() -> None:
    with _ring_lock:
        _ring.clear()


# ---- REST-layer arming helpers ----

class RequestMarks:
    """What an HTTP handler thread keeps of the request it is serving:
    `perf_counter_ns` marks it takes on every request, and the trace an
    action armed meanwhile (`begin`), which the handler closes after the
    response is written (`finish`). One object a connection; a request
    that arms no trace costs the marks and nothing else."""

    __slots__ = (
        "t_line", "t_read", "t_parsed", "t_respond", "t_dumped", "t_end",
        "request_bytes", "response_bytes", "status", "trace", "http_id",
    )

    def __init__(self):
        self.t_line = self.t_read = self.t_parsed = 0
        self.t_respond = self.t_dumped = self.t_end = 0
        self.request_bytes = self.response_bytes = self.status = 0
        self.trace: Optional[Trace] = None
        self.http_id: Optional[int] = None

    def finish(self, method: str) -> None:
        """Writes the handler's spans (module docstring: `http` and its
        three own children) into the trace the action armed and
        publishes it, ending where the response's last byte was
        written. No-op on a request that armed none."""
        tr, root = self.trace, self.http_id
        if tr is None:
            return
        self.trace = None
        tr.add_spans((
            ("http", self.t_line, self.t_end, None, root, {
                "method": method, "status": self.status,
                "request_bytes": self.request_bytes,
                "response_bytes": self.response_bytes,
            }),
            ("http_read", self.t_line, self.t_read, root, None, {}),
            ("request_parse", self.t_read, self.t_parsed, root, None,
             {"bytes": self.request_bytes}),
            ("respond", self.t_respond, self.t_end, root, None, {
                "bytes": self.response_bytes,
                "dumps_ms": round(
                    (self.t_dumped - self.t_respond) / 1e6, 3),
            }),
        ))
        tr.finish(self.t_end)


def begin(name: str, **tags: Any):
    """Arms TRACE_CTX for the current context. Returns an opaque handle
    for `end()`, or None when tracing is disabled. Under an HTTP handler
    (`REQUEST_CTX`) the trace starts at the request's first line, what
    runs until `end()` runs under its `http` span, and the handler, not
    `end()`, publishes it."""
    if not enabled():
        return None
    req = REQUEST_CTX.get()
    if req is None:
        tr = Trace(name, opaque_id=OPAQUE_ID_CTX.get(), **tags)
        return (tr, TRACE_CTX.set(tr), None)
    tr = Trace(name, opaque_id=OPAQUE_ID_CTX.get(), start_ns=req.t_line,
               **tags)
    req.trace, req.http_id = tr, tr.reserve_span()
    return (tr, TRACE_CTX.set(tr), PARENT_CTX.set(req.http_id))


def end(handle) -> None:
    """Disarms the trace begun by `begin()` (no-op on None) and, unless
    an HTTP handler holds it to close after its response, finishes it."""
    if handle is None:
        return
    tr, tok, parent_tok = handle
    try:
        TRACE_CTX.reset(tok)
    except ValueError:  # pragma: no cover - cross-context reset
        TRACE_CTX.set(None)
    if parent_tok is None:
        tr.finish()
    else:
        PARENT_CTX.reset(parent_tok)


def current() -> Optional[Trace]:
    return TRACE_CTX.get()


def reserve():
    """(the current trace, an id reserved in it for a span written
    later), or (None, None) when the request is not traced."""
    tr = TRACE_CTX.get()
    return tr, (tr.reserve_span() if tr is not None else None)


# ---- host<->device transfer counters (_nodes/stats transfer.scoring) ----

class _ThreadTransfers(threading.local):
    d2h_bytes = 0
    # on a dispatcher worker: the group it is dispatching or collecting
    # right now (search/batcher.py `_Group`), which takes the marks of
    # the launches and downloads made meanwhile; else None
    group = None


_xfer_lock = threading.Lock()
_xfer = {"h2d_count": 0, "h2d_bytes": 0, "d2h_count": 0, "d2h_bytes": 0}
_xfer_thread = _ThreadTransfers()
_NO_GROUP = contextlib.nullcontext()


def note_transfer(direction: str, nbytes: int, count: int = 1) -> None:
    """`count` host<->device transfers of the query path (one launch's
    operands, `nbytes` together), noted where they happen. `direction`
    is "h2d" (an upload: an explicit `device_put` or a host array handed
    to a jitted program) or "d2h" (a blocking download, i.e. a host
    sync). Exact, so they repeat on any backend."""
    nbytes = int(nbytes)
    with _xfer_lock:
        _xfer[direction + "_count"] += count
        _xfer[direction + "_bytes"] += nbytes
    if direction == "d2h":
        _xfer_thread.d2h_bytes += nbytes


def transfer_stats() -> Dict[str, int]:
    with _xfer_lock:
        return dict(_xfer)


def thread_d2h_bytes() -> int:
    """Bytes the calling thread has downloaded so far: a dispatcher
    worker reads it around a group's collect for the span's tag."""
    return _xfer_thread.d2h_bytes


def set_worker_group(group) -> None:
    """The calling dispatcher worker starts (or, with None, leaves) a
    group's dispatch or collect."""
    _xfer_thread.group = group


def worker_group():
    return _xfer_thread.group


def launch(program: str, host_operands: int = 0, h2d_bytes: int = 0,
           flops: int = 0):
    """The bracket around ONE jitted call of the query path, wherever
    the call is made (ops/scoring.py, ops/impact.py, search/batcher.py):
    on a dispatcher worker the group's `_Group.launch` (two marks, the
    `es.launch` annotation, the launch and its `flops` counted, a
    `launch` span of every job's trace), elsewhere nothing.
    `host_operands`: the operands handed over as host arrays, which the
    call uploads; `h2d_bytes`: theirs, as `note_transfer` is told."""
    g = _xfer_thread.group
    if g is None:
        return _NO_GROUP
    return g.launch(program, host_operands, h2d_bytes, flops)


def note_download(start_ns: int, nbytes: int) -> None:
    """One blocking download that began at `start_ns` and has just
    ended (`ops/scoring._to_host`): counted as a "d2h" transfer, and on
    a dispatcher worker a `download` span of its group."""
    end_ns = time.perf_counter_ns()
    note_transfer("d2h", nbytes)
    g = _xfer_thread.group
    if g is not None:
        g.downloaded(start_ns, end_ns, nbytes)
