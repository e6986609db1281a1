"""Cross-request micro-batching dispatcher — the serving-path bridge to
the batched TPU kernels.

Reference analog: there is none in Elasticsearch — Lucene scores one
query per thread. This is the north-star departure (BASELINE.json:
"score query batches in parallel"): concurrent `_search` requests whose
query compiles to a flat weighted-term plan are collected into shared
fixed-shape kernel launches per (segment, field) instead of B separate
launches. The dispatcher uses continuous batching: while one batch is
executing on device, arriving requests queue; the worker drains the
whole queue the moment it frees up, so there is no linger timer and no
added idle latency for a lone request.

Launch shapes ride a pad-bucket LADDER (common/settings.batch_buckets,
default 1/4/8/16/32): each dispatched group pads its query rows to the
smallest compiled bucket >= its occupancy instead of the full BPAD
width, so a batch of 3 pays a 4-wide launch and a lone query a 1-wide
one — the continuous-batching half of the tail-latency work (the PR 6
admission layer is the QoS half). Lone queries arriving on an idle
worker additionally take a depth-1 EXPRESS LANE: dispatched immediately
at bucket 1 and collected before the next dequeue, skipping the
in-flight ring entirely. Every ladder bucket of a kernel family is
eagerly warmed after that family's first collect (`_warm_ladder`, gated
by ES_TPU_BUCKET_WARMUP), so bucket selection never compiles on the
steady-state hot path.

Collection mode follows ES semantics (QueryPhase + WANDScorer:
totalHitsThreshold defaults to 10_000): unless the caller asks for
exact totals (`track_total_hits: true`), block-max pruning is the
DEFAULT — hot-term postings blocks that cannot reach the top-k floor
are never gathered. Pruning is engaged per shard only when the capped
total can still be reported truthfully (some term's doc_freq minus the
shard's deleted docs already proves ≥ cap matches); the response then
carries relation "gte" exactly like Lucene's TotalHits.GREATER_THAN_OR_
EQUAL_TO.
"""

from __future__ import annotations

import logging
import queue
import threading
import time
import weakref
from collections import deque
from dataclasses import dataclass, field
from typing import Callable, Deque, Dict, List, Optional, Tuple, Union

import numpy as np
from jax import monitoring as jax_monitoring
from jax.profiler import TraceAnnotation

from ..common.faults import faults
from ..common.settings import batch_buckets, bucket_for, bucket_warmup
from ..common.settings import pipeline_depth as _default_depth
from ..common.tracing import (
    PARENT_CTX,
    TRACE_CTX,
    note_transfer,
    set_worker_group,
    thread_d2h_bytes,
    worker_group,
)
from ..index.mapping import KEYWORD, SPARSE_VECTOR, TEXT
from ..models import fuzzy as fuzzy_model
from ..ops import fuzzy as fuzzy_ops
from ..ops import phrase as phrase_ops
from ..ops import scoring
from ..ops.scoring import BPAD
from . import dsl
from .admission import admission
from .executor import Hit, TopDocs
from .failures import SearchTimeoutError

logger = logging.getLogger(__name__)

MAX_BATCH = BPAD

# ---- cold clock ------------------------------------------------------
# Seconds a batcher's dispatcher workers have spent inside JAX's compile
# pipeline (trace, lowering, backend compile: the first use of a launch
# shape, or a bucket warm-up). A job that sat in the queue behind such a
# worker waited for the compiler, not for load, and shedding the next
# request does not make a compiler faster. So the congestion signal the
# admission layer steers on is the enqueue→dispatch wait LESS the cold
# seconds that accrued meanwhile (`_dispatch_batch`): a cold node whose
# requests fan out wider than its workers must not read its own compiles
# as congestion and answer 429. A node that compiles nothing (steady
# state) reports the raw wait.
# Seconds are summed over workers, so concurrent compiles over-subtract:
# the correction only ever errs towards admitting while compiling.
_COMPILE_EVENTS = "/jax/core/compile/"
_BACKEND_COMPILE = _COMPILE_EVENTS + "backend_compile_duration"
# on dispatcher workers: .batcher (the owner). The _Group a worker is
# dispatching or collecting right now is `tracing.worker_group()`
_worker_tl = threading.local()


def _on_compile_seconds(
    event: str, duration: float, fun_name: str = "?", **_kw
) -> None:
    b = getattr(_worker_tl, "batcher", None)
    if b is None or not event.startswith(_COMPILE_EVENTS):
        return
    built = event == _BACKEND_COMPILE
    with b._cold_lock:
        b._cold_s += float(duration)
        b.stats["worker_compiles"] += built
    g = worker_group()
    if g is not None:
        g.note_compile(fun_name, float(duration), built)


jax_monitoring.register_event_duration_secs_listener(_on_compile_seconds)

# every live QueryBatcher (tier-1 leak fixture: a CLOSED batcher must
# leave no worker threads behind)
live_batchers: "weakref.WeakSet[QueryBatcher]" = weakref.WeakSet()

# bounded dispatcher queue: ES's search threadpool has a bounded queue
# (default 1000) and rejects overflow with EsRejectedExecutionException
# (HTTP 429) rather than buffering unboundedly
QUEUE_CAPACITY = 2048


class EsRejectedExecutionError(Exception):
    """search queue overflow → HTTP 429 (EsRejectedExecutionException).
    Deliberately NOT a RuntimeError: the shard search path treats
    RuntimeError as 'batcher closed, fall back to unbatched', which
    would defeat the backpressure."""

    status = 429
    err_type = "es_rejected_execution_exception"


@dataclass(frozen=True)
class MatchPlan:
    """A query reduced to flat weighted terms over one text field."""

    field: str
    terms: Tuple[str, ...]
    msm: int  # minimum matching terms (1 = OR, len(terms) = AND)
    boost: float
    # None = exact totals required; 0 = totals not tracked at all
    # (track_total_hits: false); N > 0 = totals capped at N (the ES
    # default is 10_000)
    tth_cap: Optional[int]

    @property
    def wand_ok(self) -> bool:
        """Pruning is sound only for pure disjunctions without an exact
        total requirement (WANDScorer: minShouldMatch == 1)."""
        return self.msm == 1 and self.tth_cap is not None


def _tth_cap(tth: Union[bool, int]) -> Optional[int]:
    """A request's `track_total_hits` as a plan's `tth_cap`."""
    if tth is True:
        return None
    if tth is False:
        return 0
    return max(1, int(tth))


def extract_match_plan(
    query, mappings, analysis, tth: Union[bool, int] = 10_000
) -> Optional[MatchPlan]:
    """Returns a MatchPlan when `query` is a match query over a text
    field (the hot REST shape), else None → normal executor path."""
    if not isinstance(query, dsl.MatchQuery) or query.fuzzy is not None:
        # a `match` with `fuzziness` is no flat list of the query's own
        # words: `extract_fuzzy_plan`
        return None
    mf = mappings.get(query.field)
    if mf is None or mf.type != TEXT:
        return None
    analyzer_name = query.analyzer or mf.search_analyzer or mf.analyzer
    try:
        terms = analysis.get(analyzer_name).terms(query.query)
    except ValueError:
        return None
    if not terms:
        return None
    if query.operator == "and":
        msm = len(terms)
    else:
        msm = max(
            1, dsl.parse_minimum_should_match(query.minimum_should_match, len(terms))
        )
    return MatchPlan(
        field=query.field,
        terms=tuple(terms),
        msm=msm,
        boost=query.boost,
        tth_cap=_tth_cap(tth),
    )


@dataclass(frozen=True)
class FuzzyPlan:
    """A `match` with `fuzziness` (operator or) or a `fuzzy` query over
    one text field: the analyzed words, each to be expanded to its kept
    dictionary terms (models/fuzzy.py) and every kept term scored.
    `params.max_expansions` and `params.transpositions` are static in
    the expansion program and ride the group key."""

    field: str
    words: Tuple[str, ...]
    params: fuzzy_model.FuzzyParams
    boost: float


def extract_fuzzy_plan(query, mappings, analysis) -> Optional[FuzzyPlan]:
    """A FuzzyPlan when `query` is a `match` with `fuzziness` that any
    word's kept term satisfies (operator or, no `minimum_should_match`
    past 1) or a `fuzzy` query, over a text field, at `prefix_length` 0
    and a positive boost, of 1..WORDS_PER_ROW words none longer than the
    dictionary plane answers; else None -> the unbatched executor's
    rewrite (the caller counts it in `unplanned_queries`)."""
    if isinstance(query, dsl.FuzzyQuery):
        params, words = query.params, [query.value]
    elif isinstance(query, dsl.MatchQuery) and query.fuzzy is not None:
        params, words = query.fuzzy, None
    else:
        return None
    mf = mappings.get(query.field)
    if mf is None or mf.type != TEXT:
        return None
    if words is None:
        analyzer_name = query.analyzer or mf.search_analyzer or mf.analyzer
        try:
            words = analysis.get(analyzer_name).terms(query.query)
        except ValueError:
            return None
        if query.operator == "and" and len(words) > 1:
            return None
        if dsl.parse_minimum_should_match(
                query.minimum_should_match, len(words)) > 1:
            return None
    if (not 1 <= len(words) <= fuzzy_ops.WORDS_PER_ROW
            or params.prefix_length or query.boost <= 0
            or params.max_expansions > fuzzy_ops.KEEP_MAX
            or any(not 1 <= len(fuzzy_model.code_points(w))
                   <= fuzzy_ops.MAX_WORD_LEN for w in words)):
        return None
    return FuzzyPlan(query.field, tuple(words), params, float(query.boost))


@dataclass(frozen=True)
class PhrasePlan:
    """A bare exact `match_phrase` over one text field: the analyzed
    words in the query's order, each at its position relative to the
    first (the analyzer's position increments: a removed stop word
    leaves a hole no word fills). `width`, the phrase's span in slots
    (the last relative position + 1), rides the group key: one program
    a span, so phrases of one span share a launch whatever their words
    (ops/phrase.py). Scored as Lucene's PhraseWeight scores: one
    pseudo-term, tf the phrase's frequency in the document, idf the sum
    of the words' idfs."""

    field: str
    terms: Tuple[str, ...]
    rel: Tuple[int, ...]
    boost: float
    width: int


def extract_phrase_plan(query, mappings, analysis) -> Optional[PhrasePlan]:
    """A PhrasePlan when `query` is a bare `match_phrase` at slop 0 over
    a text field whose words span 2..PHRASE_TERMS_MAX slots, each slot
    holding at most one word; else None -> the unbatched executor's
    `_exec_phrase` (the caller counts it in `unplanned_queries`): a
    sloppy phrase, a phrase that analyzes to one word or to none, a
    longer one, two tokens at one position (a synonym filter)."""
    if not isinstance(query, dsl.MatchPhraseQuery) or query.slop != 0:
        return None
    mf = mappings.get(query.field)
    if mf is None or mf.type != TEXT:
        return None
    analyzer_name = query.analyzer or mf.search_analyzer or mf.analyzer
    try:
        toks = analysis.get(analyzer_name).analyze(query.query)
    except ValueError:
        return None
    if len(toks) < 2:
        return None
    rel = tuple(t.position - toks[0].position for t in toks)
    if any(b <= a for a, b in zip(rel, rel[1:])):
        return None
    width = phrase_ops.phrase_width(rel[-1] + 1)
    if width is None:
        return None
    return PhrasePlan(
        field=query.field, terms=tuple(t.text for t in toks), rel=rel,
        boost=query.boost, width=width,
    )


@dataclass(frozen=True)
class FieldGroup:
    """One field's flat term list: (term, boost_multiplier, count).
    `count` says what a term's hit adds to the match count: 0 nothing,
    the term only scores (bool SHOULD next to a must); 1 one, the term
    is a counted clause of its own; 2 + d the term belongs to counted
    clause d of several terms, which counts once however many of its
    terms a document holds (d < scoring.CLAUSE_DIGITS, shared by the
    plan's fields)."""

    field: str
    terms: Tuple[Tuple[str, float, int], ...]


@dataclass(frozen=True)
class KeywordFilter:
    """A knn section's `filter`, or a bool's `filter` clauses, as the
    knn and serve families plan them: a conjunction of clauses over ONE
    keyword field, each clause the terms of which a document must hold
    at least one (a `term`: one; a `terms`: any of its values). The
    device builds each job's mask from the field's postings tiles and
    the bit rows of its commonest terms (`scoring.filter_row_masks`: a
    launch of its own in front of the knn scan, inside the fused text
    program for a serve job); `query`, a knn section's parsed filter,
    serves a segment whose mask build failed, on the unbatched executor
    (a serve job's whole query does)."""

    field: str
    clauses: Tuple[Tuple[str, ...], ...]
    query: object = field(default=None, compare=False)

    @property
    def n_terms(self) -> int:
        return sum(len(c) for c in self.clauses)


@dataclass(frozen=True)
class ServePlan:
    """A bool / multi_match query reduced to per-field weighted-term
    groups for the multi-field fused kernel (round-5 extension of
    MatchPlan; BASELINE configs 2 and 3). Every term of every group
    scores; a document matches when at least `msm` of the plan's
    COUNTED CLAUSES hold a term of theirs in it, as BooleanQuery counts
    (`FieldGroup` says which terms are which clause), and, where the
    bool brings them, when it passes `filter` (keyword `term` / `terms`
    clauses on one field: a mask of the job's own, built inside the
    launch) and holds none of its `excluded` terms (`must_not`: text
    terms, which ride the groups with count scoring.VETO_COUNT and
    feed the count plane's last digit, the veto). Neither scores.
    Whether a plan is filtered (and on which field) and whether it is
    negated ride the group key: a launch of neither is the program it
    was."""

    groups: Tuple[FieldGroup, ...]
    msm: int  # counted clauses a document must match
    combine: str  # "sum" (bool, most_fields) | "max_tie" (best_fields)
    tie: float
    boost: float
    # the query's counted clauses, and those of more than one term (a
    # should-only bool at msm 1 and a multi_match count them term by
    # term: any hit passes)
    clauses: int = 1
    multi_term_clauses: int = 0
    filter: Optional[KeywordFilter] = None
    excluded: int = 0  # `must_not` terms among the groups' terms

    @property
    def fields(self) -> Tuple[str, ...]:
        return tuple(g.field for g in self.groups)

    @property
    def counts_clauses(self) -> bool:
        """Some clause of several terms counts once, or some term is
        excluded (the count plane's digits): a plan the term-counting
        mesh twin cannot take."""
        return any(t[2] > 1 for g in self.groups for t in g.terms)


@dataclass(frozen=True)
class KnnPlan:
    """A single top-level knn section with no similarity threshold,
    bare or under a `filter` the planner took (`KeywordFilter`): batched
    brute-force matmul per segment (BASELINE config 4), or — when `ann`
    carries a resolved search/ann.AnnSpec — the IVF probed path over
    the same launch/merge plumbing. `ann` rides the group key, so exact
    and probed jobs (or different probe widths) never share a launch;
    so does WHETHER a job is filtered (bare jobs keep the program with
    one shared candidate mask), never the filter itself: filtered jobs
    of any filters share a launch, each row under its own mask."""

    field: str
    vector: Tuple[float, ...]
    k: int
    num_candidates: int
    boost: float
    ann: Optional[object] = None
    filter: Optional[KeywordFilter] = None


@dataclass(frozen=True)
class SparsePlan:
    """A bare `sparse_vector` query: batched impact-tile launches per
    segment (ops/impact.py) with impact-ordered block-max pruning.
    Query weights arrive boost-folded (float32, exactly as the host
    oracle folds them) and term-sorted — the canonical accumulation
    order both paths share, which is what keeps the fp32 device path
    bit-equal to the oracle: the float32 column scores every term
    through its tiles, in term order. The int8 column's hot terms hold
    a dense row of their stored impacts on the device (one int8 a
    document, -128 where the term has no posting; a term wants one from
    df >= max(1024, n / 128) on the segment, held by df rank inside the
    text family's row budget): a document's score is then its rows'
    products first, in term order, then its tiles' in term order — the
    same float32 addends, within (T - 1) x 2^-24 of any other order.
    `spec` (search/sparse.SparseSpec) rides
    the group key, so int8 and fp32 servings never share a launch.
    `tth_cap` is MatchPlan's: what `hits.total` has to be exact up to
    (None: exact whatever it is; 0: not tracked; N: up to N, the
    request's `track_total_hits`), which decides whether the job may
    drop tiles at all (`_dispatch_sparse_group`)."""

    field: str
    terms: Tuple[str, ...]
    weights: Tuple[float, ...]
    spec: object
    tth_cap: Optional[int] = 10_000


def extract_sparse_plan(
    query, mappings, tth: Union[bool, int] = 10_000
) -> Optional[SparsePlan]:
    """Returns a SparsePlan when `query` is a bare sparse_vector query
    over a sparse_vector field with a resolved SparseSpec (the hot REST
    shape), else None → normal executor path (host oracle)."""
    if not isinstance(query, dsl.SparseVectorQuery):
        return None
    mf = mappings.get(query.field)
    if mf is None or mf.type != SPARSE_VECTOR:
        return None
    spec = getattr(query, "sparse", None)
    if spec is None:
        return None
    boost = np.float32(query.boost)
    items = sorted(query.query_vector.items())
    return SparsePlan(
        field=query.field,
        terms=tuple(t for t, _ in items),
        weights=tuple(
            float(np.float32(boost * np.float32(w))) for _, w in items
        ),
        spec=spec,
        tth_cap=_tth_cap(tth),
    )


def _clause_terms(
    q, mappings, analysis, nested: bool = True
) -> Optional[List[Tuple[str, str, float]]]:
    """[(field, analyzed term, boost)] of one bool clause that matches
    on ANY of its terms and scores all of them: a `term` or a `match`
    (operator or) on a text field, or (`nested`) a bool of only such
    `should` clauses at its default minimum_should_match. None when the
    clause can't ride the fused plan."""
    if isinstance(q, dsl.MatchQuery):
        mf = mappings.get(q.field)
        if mf is None or mf.type != TEXT:
            return None
        if q.minimum_should_match is not None or q.fuzzy is not None:
            # (a fuzzy clause scores its words' kept terms, not its words)
            return None
        analyzer_name = q.analyzer or mf.search_analyzer or mf.analyzer
        try:
            terms = analysis.get(analyzer_name).terms(q.query)
        except ValueError:
            return None
        if not terms or (q.operator == "and" and len(terms) > 1):
            # every word required INSIDE a clause: not a count of clauses
            return None
        return [(q.field, t, q.boost) for t in terms]
    if isinstance(q, dsl.TermQuery):
        mf = mappings.get(q.field)
        if mf is None or mf.type != TEXT:
            return None
        return [(q.field, dsl.term_token(q.value), q.boost)]
    if nested and isinstance(q, dsl.BoolQuery):
        if (q.must or q.filter or q.must_not or not q.should
                or q.minimum_should_match is not None):
            return None
        out: List[Tuple[str, str, float]] = []
        for c in q.should:
            got = _clause_terms(c, mappings, analysis, nested=False)
            if got is None:
                return None
            out.extend((f, t, b * q.boost) for f, t, b in got)
        return out
    return None


def extract_serve_plan(
    query, mappings, analysis
) -> Optional[ServePlan]:
    """Reduces a bool (must/should of text clauses, under `filter`
    clauses on a keyword field and `must_not` text clauses) or a
    multi_match (best_fields/most_fields, operator=or) to a ServePlan
    for the multi-field fused kernel. None → normal executor path (the
    caller counts it in `unplanned_queries`).

    Count semantics (BooleanQuery's: clauses are counted, not terms):
      * a clause is a term, a match (operator or) of any number of
        words, or a bool of such should clauses (`_clause_terms`): it
        matches on any of its terms and all of them score;
      * every must clause is counted, msm = #must; should clauses beside
        them only score;
      * with no must, every should clause is counted and msm =
        minimum_should_match (default 1). At msm 1 any hit passes, so
        the terms are counted one by one (a flat plan, as a multi_match
        is); above it a clause of several terms counts once;
      * a counted clause of several terms takes one of the count
        plane's scoring.CLAUSE_DIGITS digits and holds at most
        scoring.CLAUSE_TERMS_MAX terms: a query past either is turned
        away, as are `operator: and` inside a clause,
        minimum_should_match beside must and anything that is no text
        clause;
      * `filter`: what `extract_knn_filter` takes of a knn section,
        `term` / `terms` clauses on ONE keyword field, bare or inside a
        bool of only those, every one required (a `range`, `exists` or
        `prefix`, two fields, a text or numeric field, more terms than
        scoring.filter_slot_bucket holds: turned away). It masks and
        does not score. A bool with `filter` and no `must` has
        minimum_should_match 0 by default (every passing document
        matches): turned away, not answered at 1;
      * `must_not`: text clauses as `_clause_terms` takes them; a
        document holding ANY of their terms is dropped, none of them
        scores. They take the count plane's last digit
        (scoring.VETO_DIGIT), so beside them a plan holds one
        counted clause of several terms fewer, and at most
        scoring.CLAUSE_TERMS_MAX distinct excluded terms; `must_not`
        of anything else, or with no positive clause, is turned away.
    """
    if isinstance(query, dsl.TermQuery):
        # a bare term on a text field is a one-term plan — without this
        # it would take the unbatched path and pay the full per-segment
        # mask download (VERDICT r3 weak #3)
        got = _clause_terms(query, mappings, analysis)
        if got is None:
            return None
        field, term, _ = got[0]
        return ServePlan(
            groups=(
                FieldGroup(field=field, terms=((term, 1.0, 1),)),
            ),
            msm=1,
            combine="sum",
            tie=0.0,
            boost=query.boost,
        )
    if isinstance(query, dsl.BoolQuery):
        if query.must and query.minimum_should_match is not None:
            return None  # msm-on-should next to must: two thresholds
        flt = None
        if query.filter:
            flt = _keyword_filter(_filter_leaves(query.filter), mappings)
            if flt is None:
                return None
        if query.must:
            counted, scored, msm = query.must, query.should, len(query.must)
        else:
            if not query.should:
                return None
            if flt is not None and query.minimum_should_match is None:
                return None  # should beside a filter: msm 0, all pass
            msm_req = dsl.parse_minimum_should_match(
                query.minimum_should_match, len(query.should)
            )
            if query.minimum_should_match is not None and msm_req <= 0:
                # explicit msm of 0 means every doc matches (the oracle
                # applies no count mask) — not expressible here
                return None
            counted, scored, msm = query.should, [], max(1, msm_req)
        groups: Dict[str, List[Tuple[str, float, int]]] = {}
        multi = 0  # counted clauses of several terms
        digits = 0  # those that take a digit of the count plane
        free = scoring.CLAUSE_DIGITS - bool(query.must_not)
        for c in counted:
            got = _clause_terms(c, mappings, analysis)
            if got is None:
                return None
            count = 1
            if len(got) > 1:
                multi += 1
                if msm > 1:
                    if (digits == free
                            or len(got) > scoring.CLAUSE_TERMS_MAX):
                        return None
                    count = 2 + digits
                    digits += 1
            for field, t, cb in got:
                groups.setdefault(field, []).append((t, cb, count))
        for c in scored:
            got = _clause_terms(c, mappings, analysis)
            if got is None:
                return None
            for field, t, cb in got:
                groups.setdefault(field, []).append((t, cb, 0))
        banned: Dict[Tuple[str, str], None] = {}  # distinct, in order
        for c in query.must_not:
            got = _clause_terms(c, mappings, analysis)
            if got is None:
                return None
            banned.update(((field, t), None) for field, t, _cb in got)
        if len(banned) > scoring.CLAUSE_TERMS_MAX:
            return None
        for field, t in banned:
            groups.setdefault(field, []).append(
                (t, 1.0, scoring.VETO_COUNT))
        return ServePlan(
            groups=tuple(
                FieldGroup(field=f, terms=tuple(ts))
                for f, ts in groups.items()
            ),
            msm=msm,
            combine="sum",
            tie=0.0,
            boost=query.boost,
            clauses=len(counted),
            multi_term_clauses=multi,
            filter=flt,
            excluded=len(banned),
        )
    if isinstance(query, dsl.MultiMatchQuery):
        if query.type not in ("best_fields", "most_fields"):
            return None
        if query.operator == "and":
            return None
        from .executor import expand_match_fields

        groups_l: List[FieldGroup] = []
        for field, fboost in expand_match_fields(mappings, query.fields):
            mf = mappings.get(field)
            if mf is None or mf.type != TEXT:
                return None
            analyzer_name = mf.search_analyzer or mf.analyzer
            try:
                terms = analysis.get(analyzer_name).terms(query.query)
            except ValueError:
                return None
            if not terms:
                continue
            groups_l.append(
                FieldGroup(
                    field=field,
                    terms=tuple((t, fboost, 1) for t in terms),
                )
            )
        if not groups_l:
            return None
        return ServePlan(
            groups=tuple(groups_l),
            msm=1,
            combine=(
                "sum" if query.type == "most_fields" else "max_tie"
            ),
            tie=float(query.tie_breaker or 0.0),
            boost=query.boost,
            clauses=len(groups_l),
            multi_term_clauses=sum(len(g.terms) > 1 for g in groups_l),
        )
    return None


def split_filtered_bool(query):
    """(scoring-only bool, filter clauses) when `query` is a bool whose
    filter clauses can be peeled off into a cached bitset while the
    scoring part keeps its exact semantics; None otherwise.

    The split is semantics-preserving only when the effective
    minimum_should_match does not depend on the filters' presence:
    with must clauses (or an explicit msm) the default is identical
    either way; a should-only bool with filters defaults to msm 0,
    which the stripped bool would flip to 1 — not splittable."""
    if not isinstance(query, dsl.BoolQuery) or not query.filter:
        return None
    if query.must_not:
        return None
    if not (query.must or query.should):
        return None  # pure filter: constant-score, generic path covers it
    if not query.must and query.minimum_should_match is None:
        return None
    stripped = dsl.BoolQuery(
        boost=query.boost,
        must=list(query.must),
        should=list(query.should),
        filter=[],
        must_not=[],
        minimum_should_match=query.minimum_should_match,
    )
    return stripped, list(query.filter)


def extract_knn_filter(query, mappings) -> Optional[KeywordFilter]:
    """A knn `filter` the device can build a mask for from postings
    tiles: a `term` or `terms` on a keyword field, or a `bool` whose
    `filter` / `must` hold only those, all on one field (a conjunction
    of counted clauses). None for anything else (`range`, `must_not`,
    `should`, nested bools, another field type) and for a filter past
    the mask program's counters (`_keyword_filter`)."""
    return _keyword_filter(_filter_leaves([query]), mappings, query)


def _filter_leaves(clauses) -> list:
    """The required leaves of filter clauses: a clause as it is, or,
    of a `bool` that holds only `filter` / `must` clauses, those. A
    bool that brings anything else (`should`, `must_not`, a
    minimum_should_match) is a leaf no filter plan takes."""
    leaves = []
    for q in clauses:
        if isinstance(q, dsl.BoolQuery):
            if not (q.should or q.must_not
                    or q.minimum_should_match is not None):
                leaves += list(q.filter) + list(q.must)
                continue
        leaves.append(q)
    return leaves


def _keyword_filter(leaves, mappings, query=None) -> Optional[KeywordFilter]:
    """The conjunction of `leaves` as a KeywordFilter: every leaf a
    `term` or `terms` on the same keyword field. None for any other
    leaf or field and for a filter past the mask program's counters:
    more than scoring.CLAUSE_DIGITS clauses of several terms, one of
    more than scoring.CLAUSE_TERMS_MAX terms, more terms than its
    widest plan."""
    clauses: List[Tuple[str, ...]] = []
    fields = set()
    for q in leaves:
        if isinstance(q, dsl.TermQuery):
            values = [q.value]
        elif isinstance(q, dsl.TermsQuery):
            values = q.values
        else:
            return None
        fields.add(q.field)
        # distinct values, in the request's order
        clauses.append(tuple(dict.fromkeys(
            dsl.term_token(v) for v in values)))
    if len(fields) != 1 or not all(clauses):
        return None
    fname = fields.pop()
    mf = mappings.get(fname)
    if mf is None or mf.type != KEYWORD:
        return None
    multi = [c for c in clauses if len(c) > 1]
    if (len(multi) > scoring.CLAUSE_DIGITS
            or any(len(c) > scoring.CLAUSE_TERMS_MAX for c in multi)
            or scoring.filter_slot_bucket(sum(map(len, clauses))) is None):
        return None
    return KeywordFilter(field=fname, clauses=tuple(clauses), query=query)


def extract_knn_plan(knn_sections, mappings) -> Optional[KnnPlan]:
    """A single knn section with no similarity threshold rides the
    batched matmul launch: bare, or with a `filter` that
    `extract_knn_filter` plans. A dims mismatch stays OFF the shared
    launch so one malformed request can't fail a whole group."""
    if knn_sections is None or len(knn_sections) != 1:
        return None
    sec = knn_sections[0]
    if sec.similarity is not None:
        return None
    flt = None
    if sec.filter is not None:
        flt = extract_knn_filter(sec.filter, mappings)
        if flt is None:
            return None
    mf = mappings.get(sec.field)
    dims = getattr(mf, "dims", None) if mf is not None else None
    if dims is not None and len(sec.query_vector) != int(dims):
        return None
    return KnnPlan(
        field=sec.field,
        vector=tuple(float(x) for x in sec.query_vector),
        k=int(sec.k),
        num_candidates=int(sec.num_candidates),
        boost=float(sec.boost),
        ann=getattr(sec, "ann", None),
        filter=flt,
    )


class _Job:
    """A submitted query: the batcher's FUTURE handle. `submit_nowait`
    returns one immediately; `QueryBatcher.wait(job)` blocks for the
    result. One request thread can hold several in-flight jobs (the
    hybrid BM25 + kNN legs) and collect them in any order."""

    __slots__ = (
        "executor", "kind", "plan", "k", "query", "event", "result",
        "error", "deadline", "t_enq", "cold0", "prof", "trace", "parent",
        "group", "t_done", "window",
    )

    def __init__(
        self, executor, plan, k: int, kind: str = "match", query=None,
        deadline: Optional[float] = None, prof=None, window: int = 0,
    ):
        self.executor = executor
        self.kind = kind  # a key of FAMILIES
        self.plan = plan
        self.k = k
        # > 0: the job's first `window` ranks feed a rescore window and
        # are cut Lucene's way, exact ties at the cut by lowest doc id
        # (the match family: `_window_topk`); rides the group key
        self.window = int(window)
        self.query = query  # parsed Query node for per-segment fallback
        self.event = threading.Event()
        self.result: Optional[TopDocs] = None
        self.error: Optional[BaseException] = None
        # monotonic deadline (the shard's search-timeout budget): a job
        # still queued past it is dropped at dequeue, never dispatched
        self.deadline = deadline
        # the job's one clock: perf_counter_ns, the request trace's
        self.t_enq = time.perf_counter_ns()
        # the owning batcher's cold clock at enqueue (submit_nowait)
        self.cold0 = 0.0
        # "profile": true — a shared mutable dict the job's breakdown
        # is written into when it finishes (None = unprofiled; the
        # submitter owns the dict and reads it after wait())
        self.prof = prof
        # the submitting request's trace and the span that caused the
        # job (None on an untraced request, and on the worker threads
        # that make warm-up jobs)
        self.trace = TRACE_CTX.get()
        self.parent = PARENT_CTX.get()
        # the dispatched group's marks, set when a worker starts it
        self.group: Optional[_Group] = None
        # the job's completion mark (`finish`): its `collect` span's end.
        # A waiter that holds several jobs reads each one's own end here,
        # whatever order it waits in. 0: no worker finished the job
        self.t_done = 0

    def done(self) -> bool:
        return self.event.is_set()

    def finish(self) -> None:
        """Wakes the waiter, after the job's spans and profile entry
        are written: a request never sees its own job missing from
        them. A job no worker started (shed, cancelled, closed) has no
        marks and records nothing."""
        g = self.group
        self.t_done = time.perf_counter_ns()
        if g is not None and (
            self.trace is not None or self.prof is not None
        ):
            g.record(self, self.t_done)
        self.event.set()


class _Launch:
    """`_Group.launch`: the bracket around one jitted call. Two marks,
    `es.launch` on the profiler's clock between them, and on the way out
    the launch counted with its flops and written as a `launch` span of
    the group (a call that raised counts nothing)."""

    __slots__ = ("group", "flops", "tags", "start_ns", "on_profiler")

    def __init__(self, group: "_Group", program: str, host_operands: int,
                 h2d_bytes: int, flops: int):
        self.group = group
        self.flops = flops  # may be set inside the block (a mesh launch)
        self.tags = {"program": program, "host_operands": host_operands,
                     "h2d_bytes": int(h2d_bytes)}
        self.on_profiler = TraceAnnotation("es.launch", program=program)

    def __enter__(self) -> "_Launch":
        self.on_profiler.__enter__()
        self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        end_ns = time.perf_counter_ns()
        self.on_profiler.__exit__(exc_type, exc, tb)
        if exc_type is None:
            g = self.group
            g.flops += int(self.flops)
            g.launches += not g.t_dispatched
            g.sub_spans.append(("launch", self.start_ns, end_ns, self.tags))


class _Group:
    """One dispatched group's marks: consecutive `perf_counter_ns`
    readings the worker takes once, whatever reads them. They tile each
    job's life from `submit_nowait` to its completion mark `t_done` (the
    waiter's wake-up after it is the waiter's own `wake` span) — queue_wait
    | dispatch | inflight | collect — and are written, when a job
    finishes, as spans of the submitting request's trace and as the
    `"profile": true` breakdown (common/tracing.py lists the spans)."""

    __slots__ = (
        "family", "jobs", "rows", "express", "cold_s", "t_start",
        "t_dispatched", "t_collect", "t_unpack", "d2h0", "launches",
        "flops", "overflow", "compiles", "plan_tags", "merged",
        "sub_spans", "unpacking", "collect_tags",
    )

    def __init__(self, family: str, jobs: int, rows: Optional[int],
                 express: bool = False, cold_s: float = 0.0):
        self.family = family  # the jobs' kind: match, serve, knn, ...
        self.jobs = jobs
        self.rows = int(rows or 0)  # the launch's bucket (mesh: set later)
        self.express = express
        self.cold_s = cold_s  # the batcher's cold clock at t_start
        self.t_dispatched = self.t_collect = 0
        # the end of the collect's last download: where `unpack` starts
        self.t_unpack = 0
        self.d2h0 = 0
        self.launches = 0  # made in dispatch: its `launch` children
        self.flops = 0
        self.overflow = False  # the group left the fused kernel
        # a group's fused plans, for its `dispatch` span: `rare_tiles`
        # (most tile slots a job and field used) and, of a serve group,
        # `fields` and `hot_slots` (most dense rows a job and field used)
        # of a sparse group also `terms`, `tiles_scored`, `tiles_pruned`,
        # `chunk_launches`, `trips`, `dense_rows`, `tiles_dense` (sums
        # over its jobs and segments: the tile pass's launches and their
        # loops' trips, row slots used, the tiles they stand for),
        # `quantized`
        self.plan_tags: Dict[str, int] = {}
        # (name, start_ns, end_ns, tags): spans inside `dispatch` or
        # `collect`, children of the one they ended in (`launch`,
        # `download`, a segment's `filter_mask` / `phrase_plan` /
        # `sparse_plan`, `sparse_theta`)
        self.sub_spans: List[Tuple] = []
        # a text or sparse group's `collect` span: whether its candidates
        # went through the merge program (`_group_topk`), else None
        self.merged: Optional[bool] = None
        # more of the `collect` span: a rescore window's cut (`window`,
        # `ties_refilled`: `_window_topk`)
        self.collect_tags: Dict[str, int] = {}
        # program -> [start_ns, end_ns, seconds, built] compiled meanwhile
        self.compiles: Dict[str, list] = {}
        # `es.unpack` on the profiler's clock, open from the collect's
        # last download to `unpacked`
        self.unpacking: Optional[TraceAnnotation] = None
        self.t_start = time.perf_counter_ns()

    def dispatched(self) -> None:
        """The group's last kernel is enqueued."""
        self.t_dispatched = time.perf_counter_ns()

    def collecting(self) -> None:
        """The worker is back to collect the group."""
        self.t_collect = time.perf_counter_ns()
        self.d2h0 = thread_d2h_bytes()

    def launch(self, program: str, host_operands: int = 0,
               h2d_bytes: int = 0, flops: int = 0) -> _Launch:
        """The bracket around one jitted call (`tracing.launch` leads
        the launch sites of ops/ here): one recorded launch and its
        estimated useful flops (the `dispatch` span's `launches`, the
        profile breakdown's `flops`), a `launch` span, `es.launch`."""
        return _Launch(self, program, host_operands, h2d_bytes, flops)

    def downloaded(self, start_ns: int, end_ns: int, nbytes: int) -> None:
        """One blocking download of this worker (`tracing.note_download`):
        a `download` span, and in a collect the start of `unpack`."""
        self.sub_spans.append(
            ("download", start_ns, end_ns, {"bytes": int(nbytes)}))
        if self.t_collect:
            self.unpacked()
            self.t_unpack = end_ns
            self.unpacking = TraceAnnotation("es.unpack")
            self.unpacking.__enter__()

    def unpacked(self) -> None:
        """Closes `es.unpack`: the next download has come down, or the
        collect has finished its last job."""
        ann, self.unpacking = self.unpacking, None
        if ann is not None:
            ann.__exit__(None, None, None)

    def phase(self, name: str) -> TraceAnnotation:
        """The worker's phase on the profiler's clock (`es.dispatch`,
        `es.collect`): outside a profiler session a flag test, inside
        one an event on this thread's line of the host plane."""
        return TraceAnnotation(name, family=self.family, rows=self.rows)

    def note_compile(self, program: str, seconds: float,
                     built: bool) -> None:
        """One of a program's compile events (trace, lowering, backend
        compile: JAX names the first `f`, the others `jit(f)`). A name
        that never reaches the backend was traced inside another
        program, whose events cover it."""
        if program.endswith(")") and "(" in program:
            program = program[program.index("(") + 1:-1]
        end = time.perf_counter_ns()
        start = end - int(seconds * 1e9)
        c = self.compiles.get(program)
        if c is None:
            self.compiles[program] = [start, end, seconds, built]
        else:
            c[0], c[1], c[2] = min(c[0], start), end, c[2] + seconds
            c[3] = c[3] or built

    def record(self, j: _Job, t_done: int) -> None:
        # a group that failed on the way has its later marks missing
        t1 = self.t_dispatched or t_done
        t2 = self.t_collect or t1
        tr = j.trace
        if tr is not None:
            # every span of the job in ONE write, on the request's
            # critical path (the waiter's event is set after it): the
            # two parents' ids are reserved without the lock
            up = j.parent
            disp, coll = tr.reserve_span(), tr.reserve_span()
            spans = [
                ("queue_wait", j.t_enq, self.t_start, up, None, {
                    "family": self.family,
                    "cold_ms": round((self.cold_s - j.cold0) * 1000.0, 3),
                }),
                ("dispatch", self.t_start, t1, up, disp, {
                    "family": self.family, "jobs": self.jobs,
                    "rows": self.rows, "launches": self.launches,
                    "express": self.express, "overflow": self.overflow,
                    **self.plan_tags,
                }),
                ("inflight", t1, t2, up, None, {}),
                ("collect", t2, t_done, up, coll, {
                    "d2h_bytes": thread_d2h_bytes() - self.d2h0,
                    **({} if self.merged is None
                       else {"merged": self.merged}),
                    **self.collect_tags,
                }),
            ]
            # a child falls under the phase it ended in
            spans += [
                (name, s0, s1, disp if s1 <= t1 else coll, None, tags)
                for name, s0, s1, tags in self.sub_spans
            ]
            if self.t_collect:
                spans.append(("unpack", self.t_unpack or t2, t_done, coll,
                              None, {}))
            spans += [
                ("compile", c0, c1, disp if c1 <= t1 else coll, None,
                 {"program": program, "seconds": round(secs, 6)})
                for program, (c0, c1, secs, built) in self.compiles.items()
                if built
            ]
            tr.add_spans(spans)
        p = j.prof
        if p is not None:
            # built aside and dict-swapped in, so a reader that races
            # the write never observes a half-built entry
            fams = p.setdefault("families", {})
            prev = fams.get(j.kind)
            e = dict(prev) if prev else {
                "launches": 0, "dispatch_ns": 0, "collect_ns": 0,
                "queue_wait_ns": 0, "flops": 0, "bucket": 0,
                "batch_jobs": 0, "express_lane": False, "pruned": False,
            }
            e["launches"] += 1
            e["dispatch_ns"] += t1 - self.t_start
            e["collect_ns"] += t_done - t2
            e["queue_wait_ns"] += max(0, self.t_start - j.t_enq)
            e["flops"] += self.flops // max(self.jobs, 1)
            e["bucket"] = self.rows
            e["batch_jobs"] = self.jobs
            if self.express:
                e["express_lane"] = True
            if p.get("pruned_jobs"):
                e["pruned"] = True
            fams[j.kind] = e


class _BatchCtx:
    """One dispatched batch: the jobs it carries plus its launched
    groups awaiting collect, in launch order."""

    __slots__ = ("batch", "pending")

    def __init__(self, batch: List[_Job]):
        self.batch = batch
        self.pending: List[Tuple] = []  # (family, key, kb, jobs, pend)


def _group_now() -> _Group:
    """The group this worker is dispatching or collecting. Off a worker
    and in a warm-up (a dispatch / collect pair driven by hand) a
    throwaway nothing reads."""
    return worker_group() or _Group("", 0, 0)


@dataclass(frozen=True)
class _Family:
    """All the batcher knows about one job kind (an entry of FAMILIES):
    which jobs may share a launch, how a group of them is launched and
    collected, whether its first group warms the bucket ladder. The
    calls name the batcher's group methods when they run, not before:
    a subclass or a test may replace one."""

    # what runs beside what: a class of `_inflight`, and the `family` of
    # the `batcher.dispatch` / `batcher.collect` fault sites
    overlap: str  # text | knn | agg | rerank | sparse
    # plan -> the attributes two jobs must agree in to share a launch;
    # the batcher adds the executor, the kind and the top-k bucket `kb`
    share: Callable[[object], Tuple]
    # (batcher, jobs, key, kb, rows, record) -> pend: the group's device
    # work, enqueued without a host sync (`record=False`: a warm-up
    # launch, which appears in no counter and fires no fault site). One
    # dispatch blocks all the same, on a threshold download: a match
    # group that leaves the fused kernel for the chunked block-max path
    # (`cs.threshold`)
    dispatch: Callable
    # (batcher, jobs, key, kb, pend, record): the blocking downloads and
    # the waiters' wake-up
    collect: Callable
    # a placement over all the index's shards (MeshExecutor): the launch
    # width is not a ladder bucket but what dispatch returns as
    # pend["rows"] (the mesh's data axis must divide it)
    mesh: bool = False
    bucket_recorded: bool = True  # in `launches_by_bucket`
    # jobs -> (what the programs specialize on beyond the key, the job a
    # warm-up dummy is cloned from). None: the family warms no ladder
    warm: Optional[Callable] = None


def _mesh_family(overlap: str, share: Callable, dispatch: str,
                 collect: str) -> _Family:
    """A family placed on the mesh: `dispatch` / `collect` name the
    MeshExecutor's pair (B queries x all shards in one SPMD program)."""

    def launch(b, jobs, key, kb, rows, record):
        with _group_now().launch(dispatch) as launched:
            pend = getattr(jobs[0].executor, dispatch)(jobs, kb)
            launched.flops = pend["flops"]
        with b._lock:
            b.stats["launches"] += 1
            b.stats["fused_jobs"] += len(jobs)
        return pend

    def download(b, jobs, key, kb, pend, record):
        getattr(jobs[0].executor, collect)(jobs, pend)

    return _Family(overlap, share, launch, download, mesh=True)


def _mesh_serve_share(p) -> Tuple:
    return p.fields, p.combine, p.tie


def _serve_share(p) -> Tuple:
    # a launch's filters read one field's postings, and both whether it
    # masks a row and whether it vetoes are static in its program
    return (*_mesh_serve_share(p),
            p.filter.field if p.filter else None, p.excluded > 0)


def _warm_serve(jobs: List[_Job]) -> Tuple[Tuple, _Job]:
    # a filtered group's program specializes on its mask plan's width
    def terms(j: _Job) -> int:
        return j.plan.filter.n_terms if j.plan.filter else 0

    j0 = max(jobs, key=terms)
    return (scoring.filter_slot_bucket(terms(j0)) if terms(j0) else 0,), j0


def _warm_first(jobs: List[_Job]) -> Tuple[Tuple, _Job]:
    return (), jobs[0]


def _warm_match(jobs: List[_Job]) -> Tuple[Tuple, _Job]:
    # the match kernels specialize on the count plane too
    with_cnt = [j for j in jobs if j.plan.msm > 1]
    return (bool(with_cnt),), (with_cnt or jobs)[0]


def _warm_knn(jobs: List[_Job]) -> Tuple[Tuple, _Job]:
    # the kNN candidate page is a compile bucket of its own, and so is
    # the width of a filtered group's mask plan; a filtered group that
    # led by postings (its `lead` tag) ran another program than one that
    # scanned, and a job of it leads at any bucket
    def terms(j: _Job) -> int:
        return j.plan.filter.n_terms if j.plan.filter else 0

    j0 = max(jobs, key=lambda j: (j.plan.num_candidates, terms(j)))
    led = bool(jobs[0].group and jobs[0].group.plan_tags.get("lead"))
    return (scoring.next_bucket(j0.plan.num_candidates, 16),
            scoring.filter_slot_bucket(terms(j0)) if terms(j0) else 0,
            led), j0


FAMILIES: Dict[str, _Family] = {
    "match": _Family(
        "text", lambda p: (p.field,),
        lambda b, jobs, key, kb, rows, record: b._dispatch_match_group(
            jobs, key[2], kb, rows=rows, record=record),
        lambda b, jobs, key, kb, pend, record: b._collect_match_group(
            jobs, kb, pend, record=record),
        warm=_warm_match,
    ),
    "serve": _Family(
        "text", _serve_share,
        lambda b, jobs, key, kb, rows, record: b._dispatch_serve_group(
            jobs, kb, rows=rows, record=record),
        lambda b, jobs, key, kb, pend, record: b._collect_serve_group(
            jobs, kb, pend, record=record),
        warm=_warm_serve,
    ),
    # typo-tolerant text: what is static in the expansion program rides
    # the key; the scoring program is the fused one at the family's own
    # slot budgets (a program of its own)
    "fuzzy": _Family(
        "text", lambda p: (p.field, p.params.max_expansions,
                           p.params.transpositions),
        lambda b, jobs, key, kb, rows, record: b._dispatch_fuzzy_group(
            jobs, kb, rows=rows, record=record),
        lambda b, jobs, key, kb, pend, record: b._collect_fuzzy_group(
            jobs, kb, pend, record=record),
        warm=_warm_first,
    ),
    # exact phrases: the span in slots rides the key (one program a
    # span); the words do not
    "phrase": _Family(
        "text", lambda p: (p.field, p.width),
        lambda b, jobs, key, kb, rows, record: b._dispatch_phrase_group(
            jobs, kb, rows=rows, record=record),
        lambda b, jobs, key, kb, pend, record: b._collect_phrase_group(
            jobs, kb, pend, record=record),
        warm=_warm_first,
    ),
    # `ann` rides the key: exact and IVF-probed jobs never share; nor
    # do bare and filtered jobs (two programs: one mask, a mask a row)
    "knn": _Family(
        "knn", lambda p: (p.field, p.ann, p.filter is not None),
        lambda b, jobs, key, kb, rows, record: b._dispatch_knn_group(
            jobs, rows=rows, record=record),
        lambda b, jobs, key, kb, pend, record: b._collect_knn_group(
            jobs, pend, record=record),
        warm=_warm_knn,
    ),
    # device aggregations: identical dashboard shapes (the compiled
    # plan's structural signature) share one dispatch slot
    "agg": _Family(
        "agg", lambda p: (p.sig,),
        lambda b, jobs, key, kb, rows, record: b._dispatch_agg_group(jobs),
        lambda b, jobs, key, kb, pend, record: b._collect_agg_group(
            jobs, pend),
        bucket_recorded=False,
    ),
    # second-stage rerank: jobs share a maxsim launch when model, padded
    # window / query-token shapes, static window and blend weights agree
    "rerank": _Family(
        "rerank", lambda p: (p.sig,),
        lambda b, jobs, key, kb, rows, record: b._dispatch_rerank_group(
            jobs, rows=rows),
        lambda b, jobs, key, kb, pend, record: b._collect_rerank_group(
            jobs, pend),
    ),
    # learned sparse: the frozen SparseSpec rides the key, so int8 and
    # fp32 servings of one field never share a launch
    "sparse": _Family(
        "sparse", lambda p: (p.field, p.spec),
        lambda b, jobs, key, kb, rows, record: b._dispatch_sparse_group(
            jobs, kb, rows=rows, record=record),
        lambda b, jobs, key, kb, pend, record: b._collect_sparse_group(
            jobs, kb, pend, record=record),
        warm=_warm_first,
    ),
    # a fused mesh rescore rides the plan (`rescore_sig`, None for plain
    # match): different specs / page sizes never share an SPMD launch
    "mesh_match": _mesh_family(
        "text", lambda p: (p.field, getattr(p, "rescore_sig", None)),
        "dispatch_match", "collect_match"),
    "mesh_serve": _mesh_family(
        "text", _mesh_serve_share, "dispatch_serve", "collect_match"),
    "mesh_knn": _mesh_family(
        "knn", lambda p: (p.field, p.ann), "dispatch_knn", "collect_knn"),
    "mesh_sparse": _mesh_family(
        "sparse", lambda p: (p.field, p.spec),
        "dispatch_sparse", "collect_sparse"),
    "mesh_agg": _mesh_family(
        "agg", lambda p: (p.sig,), "dispatch_agg", "collect_agg"),
}


# ---- the node's counters -------------------------------------------------
# What a batcher counts, declared ONCE: a block's FINAL dotted path in the
# node's document (`GET /_nodes/stats`) -> its leaves at zero, each with
# the one comment that explains it. `QueryBatcher.__init__` builds its
# counters from this table (`stats`, `knn_filtered`, `serve_filtered`,
# `phrase`, `fuzzy`; all under the batcher's `_lock`),
# `QueryBatcher.node_stats()` hands them back under these paths, and a node
# with no index reports the table itself (`node_stats_zeros`): a counter
# that exists here is a counter the node reports. The node SUMS a leaf
# over its batchers (a histogram key by key) unless NODE_STATS_FOLD or
# NODE_STATS_DERIVED below names it. A callable stands for a setting's
# value, read when it is asked for. Device time is not here: the
# profiler's device plane measures it (PERF.md §3).
NODE_STATS: Dict[str, dict] = {
    # the `search` pool (ES's threadpool analog: a bounded queue that
    # rejects overflow)
    "thread_pool.search": {
        # the bound of the dispatcher queue
        "queue_capacity": QUEUE_CAPACITY,
        # jobs completed (`stats["jobs"]`), submits the full queue turned
        # away (429), and kernel-group launches
        "completed": 0,
        "rejected": 0,
        "launches": 0,
        # match jobs the fused kernel scored, those of them under block-max
        # pruning, and those that left it for the chunked path: a
        # fused-slot overflow silently falling there would hide a
        # Zipf-tail regression (VERDICT r3 weak #9)
        "fused_jobs": 0,
        "pruned_jobs": 0,
        "fused_overflow_jobs": 0,
        # overload protection: jobs dropped at dequeue because their
        # deadline budget was already spent (never launched) and jobs
        # cancelled while still queued (task cancel)
        "shed_dead_jobs": 0,
        "cancelled_jobs": 0,
        # the serve family (bool / multi_match): jobs whose segment ran
        # per job on the unbatched executor (`segment_topk`: a slot
        # overflow, or a segment under FUSED_MIN_DOCS), fused launches, and
        # what their plans carried, summed over jobs and fields (the bytes
        # a launch must move follow from them)
        "serve_fallback_jobs": 0,
        "serve_launches": 0,
        "serve_rare_tiles": 0,
        "serve_hot_rows": 0,
        # counted clauses over all serve jobs, and those of more than one
        # term (ServePlan.clauses / .multi_term_clauses)
        "serve_clauses": 0,
        "serve_multi_term_clauses": 0,
        # the match family's twin of `serve_rare_tiles`
        "fused_rare_tiles": 0,
    },
    # the workers' in-flight ring bound (ES_TPU_PIPELINE_DEPTH)
    "pipeline": {"depth": _default_depth},
    # continuous batching: padding waste is a measured number
    "pipeline.batching": {
        # the pad-bucket ladder, launches by padded width, and the sums
        # behind `avg_occupancy` = jobs / slots (raw, so windows can diff)
        "buckets": lambda: list(batch_buckets(BPAD)),
        "launches_by_bucket": {},
        "occupancy_jobs": 0,
        "occupancy_slots": 0,
        "avg_occupancy": 0.0,
        # lone queries dispatched depth-1 on an idle worker (bucket-1
        # launch, collected before the next dequeue: the
        # interactive-latency fast path)
        "express_lane_hits": 0,
        # groups whose result was downloaded as the fused kernel packed
        # it: one scoring segment, no merge program
        "direct_collect_groups": 0,
        # groups launched beside another of their batch: at the end of a
        # batch's launches, the groups it holds uncollected when they are
        # two or more (a hybrid request's legs), of `launches_by_bucket`'s
        "groups_launched_together": 0,
        # searches of a jax shard that no planner gave a plan (a query
        # neither extract_match_plan nor extract_serve_plan took, a knn
        # section extract_knn_plan turned away): they ran on the unbatched
        # executor (`note_unplanned`; 0 from the node's start, so a window
        # without one reads 0)
        "unplanned_queries": 0,
        # how far the rare-term pass's loop engages, over fused launches of
        # both text families and their fields: tile slots the launches
        # gathered and scattered (rows x the trips' slots) of those they
        # would have at the whole budget (rows x t_rare)
        "rare_slots_scattered": 0,
        "rare_slots_budget": 0,
        # bucket warm-up launches that raised (a launch shape the device
        # refused): the bucket compiles lazily on its first live hit
        # instead, but the failure is counted and the first one logged: a
        # bring-up run requires this to read zero
        "warmup_failures": 0,
        # fused match jobs by dense hot-term slots used, 0..FUSED_H (how
        # much of the kernel's slot budget real questions take), and the
        # same for serve jobs, one count a field's plan section
        "fused_hot_slots": {},
        "serve_hot_slots": {},
        # the cold clock (module comment above): compile time on the
        # dispatcher workers, kept out of the admission layer's
        # queue-delay signal, and the programs they built or fetched
        "worker_compile_ms": 0.0,
        "worker_compiles": 0,
    },
    # jobs of the families that ride the dispatch/collect pipeline beside
    # a module's own block: device aggregations (size:0 / agg bodies as
    # segment-sum launches), the second-stage rerank (rescore bodies as
    # maxsim launches between merge and fetch), learned-sparse retrieval
    # (bare sparse_vector bodies as impact-tile launches with block-max
    # pruning)
    "aggs": {"batched_jobs": 0},
    "rescore": {"batched_jobs": 0},
    "sparse": {"batched_jobs": 0},
    # the knn family's filtered groups: (job x segment) searches the
    # device planned (under a mask it built, or led by postings), the rows
    # scored (every stored row of a scan, the candidate slots of a lead)
    # and the rows the filters passed (counted on the device, read at
    # collect; both count a fallback's rows too), the postings tiles the
    # mask launches scattered and the lead launches gathered, the terms of
    # the planned filters and those of them a bit row of the segment
    # answered (DevicePostings.filter_bits), the mask launches, those of
    # them whose scan selected its top k from block maxima (a segment wide
    # enough: scoring.knn_block_select), the (job x segment) searches that
    # left the planned path for the unbatched executor, and those a lead
    # launch served (`scoring.knn_topk_lead`) with the candidate slots they
    # scored
    "knn_filtered": {
        "searches": 0, "rows_scanned": 0, "rows_passed": 0,
        "filter_tiles": 0, "filter_terms": 0, "bitset_terms": 0,
        "mask_launches": 0, "block_select_launches": 0, "fallbacks": 0,
        "lead_searches": 0, "lead_rows": 0,
    },
    # the serve family's filtered and negated groups: (job x segment) scans
    # of the fused program under a `filter` mask of the row's own or a
    # veto, the fused launches that built masks inside themselves, the
    # planned filters' terms, those of them a bit row of the segment
    # answered and the postings tiles the others scattered, the documents
    # the masked launches scored and those their filters passed (counted
    # on the device, read at collect), the `must_not` terms the launches
    # carried and the postings tiles of those that hold no dense row
    # (scattered into the veto counter), and the (job x segment) scans that
    # left the planned path for the unbatched executor (the filter field's
    # postings or bit rows not to be had, a slot overflow, a small segment)
    "serve_filtered": {
        "searches": 0, "mask_launches": 0, "filter_terms": 0,
        "bitset_terms": 0, "filter_tiles": 0, "rows_scanned": 0,
        "rows_passed": 0, "excluded_terms": 0, "excluded_tiles": 0,
        "fallbacks": 0,
    },
    # the phrase family: (job x segment) scans on the device, their
    # launches, the words they held, the position entries the scans were
    # handed (the whole plane a job), and of each job and segment: the
    # documents holding every word and the occurrences of the phrase's
    # words inside them (both counted on the device, read at collect), the
    # documents matched, the bytes no exact search could leave unread
    # (ops/phrase.least_bytes), and the scans that left the planned path
    # for the unbatched executor's `_exec_phrase`
    "phrase": {
        "searches": 0, "launches": 0, "words": 0,
        "occurrences_read": 0, "candidates": 0,
        "candidate_occurrences": 0, "matches": 0, "least_bytes": 0,
        "fallbacks": 0,
    },
    # the fuzzy family: jobs served, their words, the words that took an
    # edit (expanded on the device), terms kept and words that kept all
    # `max_expansions`, of the plans: dense rows and tiles, jobs that
    # passed a slot budget (`overflows`) or found no plane (`fallbacks`)
    # and were served by the unbatched executor; expansion launches (and
    # those the blocked kernel served: a plane on a TPU), the scoring
    # launches, and what ANY exact expansion of the words would read and
    # compute (ops/fuzzy.least_work: the benchmark's roofline)
    "fuzzy": {
        "requests": 0, "words": 0, "words_expanded": 0,
        "terms_kept": 0, "words_saturated": 0,
        "hot_terms": 0, "tiles": 0, "overflows": 0, "fallbacks": 0,
        "launches": 0, "blocked_launches": 0, "score_launches": 0,
        "least_bytes": 0, "least_cells": 0,
    },
}

# leaves that are no sums: dotted path -> what the node reports for a list
# of values, one a batcher (with no batcher, the leaf's value in
# NODE_STATS: the setting's, QUEUE_CAPACITY): the deepest ring, the widest
# queue, the longest ladder
NODE_STATS_FOLD: Dict[str, Callable] = {
    "pipeline.depth": max,
    "thread_pool.search.queue_capacity": max,
    "pipeline.batching.buckets": lambda ladders: max(ladders, key=len),
}


def _avg_occupancy(block: dict) -> float:
    slots = block["occupancy_slots"]
    return round(block["occupancy_jobs"] / slots, 4) if slots else 0.0


# leaves computed from their block's other leaves once those are folded:
# dotted path -> f(block)
NODE_STATS_DERIVED: Dict[str, Callable] = {
    "pipeline.batching.avg_occupancy": _avg_occupancy,
}

# `QueryBatcher.stats` is one flat dictionary: it holds every integer that
# these five blocks declare at 0, four of them under names of its own
_STATS_NAMES = {
    ("thread_pool.search", "completed"): "jobs",
    ("aggs", "batched_jobs"): "agg_jobs",
    ("rescore", "batched_jobs"): "rerank_jobs",
    ("sparse", "batched_jobs"): "sparse_jobs",
}
_STATS_LEAVES: Dict[str, Dict[str, str]] = {
    path: {leaf: _STATS_NAMES.get((path, leaf), leaf)
           for leaf, zero in NODE_STATS[path].items()
           if type(zero) is int and zero == 0}
    for path in ("thread_pool.search", "pipeline.batching",
                 "aggs", "rescore", "sparse")
}
_FAMILY_BLOCKS = ("knn_filtered", "serve_filtered", "phrase", "fuzzy")


def node_stats_zeros() -> Dict[str, dict]:
    """What a node with no batcher reports: NODE_STATS, a leaf that a
    setting decides read now."""
    return {path: {leaf: (zero() if callable(zero) else
                          dict(zero) if isinstance(zero, dict) else zero)
                   for leaf, zero in block.items()}
            for path, block in NODE_STATS.items()}


WORKERS = 6  # parallel dispatcher pipelines, so several batches' round
# trips are in flight at once (see the fused-scorer comment in
# ops/scoring.py). The count was tuned to the transfer costs of hardware
# no longer present; on the attached chip it is "not measured".


class QueryBatcher:
    """Dispatcher pipelines per index: REST worker threads submit jobs
    and block; workers score whole groups in shared one-round-trip
    launches. Several workers run concurrently so device round trips
    overlap (continuous batching × pipelining).

    Submission is a FUTURE API: `submit_nowait()` returns a job handle
    immediately and `wait(handle)` collects, so one request can hold
    several legs in flight at once (hybrid BM25 + kNN). A worker
    launches every group of a batch (a family's `dispatch`) before it
    collects any (its `collect`, the blocking downloads), so the legs'
    kernels launch back-to-back with no host sync between them."""

    def __init__(
        self,
        max_batch: int = MAX_BATCH,
        workers: int = WORKERS,
        queue_capacity: int = QUEUE_CAPACITY,
        pipeline_depth: Optional[int] = None,
    ):
        # pad-bucket launch ladder (ES_TPU_BATCH_BUCKETS): dispatched
        # groups pad to the smallest bucket >= occupancy; the top of
        # the ladder bounds how many jobs one batch may carry
        self.buckets = batch_buckets(BPAD)
        # eager per-family bucket warmup after a first collect (mutable
        # per instance; tier-1 pins the env off, tests re-arm per batcher)
        self.warmup_enabled = bucket_warmup()
        self.max_batch = min(max_batch, BPAD, self.buckets[-1])
        self.workers = workers
        # in-flight ring bound per worker (ES_TPU_PIPELINE_DEPTH):
        # depth=1 is the classic dispatch→collect loop; depth=2 double-
        # buffers so batch N+1's kernels launch while batch N's hits are
        # built on the host. Mutable at runtime (bench A/B runs).
        self.pipeline_depth = (
            max(1, int(pipeline_depth))
            if pipeline_depth is not None
            else _default_depth()
        )
        self._queue: "queue.Queue[_Job]" = queue.Queue(maxsize=queue_capacity)
        self._threads: List[threading.Thread] = []
        self._closed = False
        self._lock = threading.Lock()
        live_batchers.add(self)
        # dense hot-term slots each fused match job used, 0..FUSED_H:
        # how much of the kernel's slot budget real questions take
        self._fused_hot_slots = [0] * (scoring.FUSED_H + 1)
        # the same for serve jobs, one count a field's plan section
        self._serve_hot_slots = [0] * (scoring.FUSED_H + 1)
        # the counters, from their declaration (NODE_STATS, which says what
        # each counts); `max_batch_seen` (the widest batch a worker
        # drained) is the one the node does not report
        self.stats = {"max_batch_seen": 0}
        for leaves in _STATS_LEAVES.values():
            self.stats.update(dict.fromkeys(leaves.values(), 0))
        self.knn_filtered = dict(NODE_STATS["knn_filtered"])
        self.serve_filtered = dict(NODE_STATS["serve_filtered"])
        self.phrase = dict(NODE_STATS["phrase"])
        self.fuzzy = dict(NODE_STATS["fuzzy"])
        # launches by padded width (guarded by self._lock)
        self._bucket_launches: Dict[int, int] = {}
        # (family-signature) keys whose bucket ladder is already warmed,
        # plus a count of warm loops still running (the warm runs on the
        # worker AFTER the triggering group's waiters complete, so it is
        # asynchronous to every caller; wait_warm_idle() lets tests and
        # benchmarks quiesce before probing compile caches)
        self._warmed: set = set()
        self._warm_inflight = 0
        # cold clock (module comment above): compile seconds spent on
        # this batcher's workers, fed by the jax.monitoring listener
        # (which counts `stats["worker_compiles"]` under this lock too)
        self._cold_lock = threading.Lock()
        self._cold_s = 0.0
        # overlap class → groups currently dispatched-but-not-collected,
        # across ALL workers (guarded by self._lock)
        self._inflight = {f.overlap: 0 for f in FAMILIES.values()}

    def _ensure_thread(self):
        with self._lock:
            self._threads = [t for t in self._threads if t.is_alive()]
            while len(self._threads) < self.workers:
                t = threading.Thread(
                    target=self._run,
                    name=f"query-batcher-{len(self._threads)}",
                    daemon=True,
                )
                t.start()
                self._threads.append(t)

    def close(self):
        self._closed = True
        # fail anything still queued so no submitter blocks forever —
        # BEFORE posting wake sentinels, so the drain cannot eat them
        # and leave a worker blocked in queue.get() forever
        self._drain_queue(RuntimeError("query batcher closed"))
        for _ in self._threads:
            try:
                self._queue.put_nowait(None)  # wake blocked workers
            except queue.Full:  # pragma: no cover - submitters raced
                break
        # wait the workers out (bounded): a daemon worker still inside
        # a device dispatch when the interpreter finalizes takes the
        # process down with a C++ terminate, not a Python exception
        for t in self._threads:
            if t is not threading.current_thread():
                t.join(timeout=5.0)
        self._threads = []

    def _drain_queue(self, err: BaseException):
        while True:
            try:
                j = self._queue.get_nowait()
            except queue.Empty:
                break
            if j is not None and not j.event.is_set():
                j.error = err
                j.event.set()

    # ---- client side (async future API) ----

    def submit_nowait(
        self, executor, plan, k: int, kind: str = "match", query=None,
        deadline: Optional[float] = None, prof=None, window: int = 0,
    ) -> _Job:
        """Enqueues a job and returns its future handle WITHOUT waiting.
        Raises EsRejectedExecutionError (429) on queue overflow — the
        async path gets the same backpressure as the blocking one. A
        request thread submits every leg it needs first, then collects
        with `wait(handle)`, so independent legs (hybrid BM25 + kNN)
        execute concurrently. `deadline` (monotonic seconds) is the
        shard's timeout budget: a job still queued past it is dropped
        at dequeue instead of dispatched dead."""
        if self._closed:
            raise RuntimeError("query batcher closed")
        if kind not in FAMILIES:
            raise ValueError(f"unknown job kind [{kind}]")
        job = _Job(executor, plan, k, kind=kind, query=query,
                   deadline=deadline, prof=prof, window=window)
        job.cold0 = self._cold_s
        self._ensure_thread()
        try:
            self._queue.put_nowait(job)
        except queue.Full:
            with self._lock:
                self.stats["rejected"] += 1
            raise EsRejectedExecutionError(
                f"rejected execution: search queue capacity "
                f"[{self._queue.maxsize}] reached"
            )
        if self._closed:
            # lost the race with close(): make sure nobody hangs
            self.close()
        return job

    submit = submit_nowait  # the older name

    @staticmethod
    def wait(job: _Job, timeout: Optional[float] = None) -> TopDocs:
        if not job.event.wait(timeout):
            raise TimeoutError("batched query did not complete in time")
        if job.error is not None:
            raise job.error
        return job.result

    def wait_or_cancel(
        self, job: _Job, timeout: Optional[float] = None
    ) -> TopDocs:
        """wait() that never abandons the job on timeout: a bare
        wait(timeout) leaves a timed-out job queued, where it can later
        dispatch into a waiter that already gave up — wasted device work
        and a completion nobody reads. Here the timeout cancels the job
        first (the dequeue-time gate then drops it — it never launches)
        and only then propagates TimeoutError."""
        try:
            return self.wait(job, timeout)
        except TimeoutError:
            self.cancel(
                job,
                error=TimeoutError(
                    "batched query did not complete in time"
                ),
            )
            raise

    def cancel(self, job: _Job, error: Optional[BaseException] = None) -> bool:
        """Fails a still-pending job's waiter (a task cancel landing
        before dispatch): the dequeue-time gate then drops the job from
        the queue, so it never launches. Returns False when the job
        already completed. A job whose dispatch already started still
        runs on device, but its waiter is failed and the completion
        paths leave the error in place (error wins in wait())."""
        if job.event.is_set():
            return False
        if error is None:
            from ..tasks import TaskCancelledException

            error = TaskCancelledException(
                "task cancelled [search job cancelled before dispatch]"
            )
        with self._lock:
            self.stats["cancelled_jobs"] += 1
        job.error = error
        job.event.set()  # wake AFTER the stats update (observable order)
        return True

    def _admit_job(self, j: _Job) -> bool:
        """Dequeue-time gate: cancelled jobs (waiter already failed) are
        dropped, and a job whose deadline budget is already spent fails
        its waiter with a timeout instead of dispatching dead — the
        overload-protection contract that queued work past its deadline
        never reaches the device."""
        if j.event.is_set():
            return False
        if j.deadline is not None and time.monotonic() > j.deadline:
            with self._lock:
                self.stats["shed_dead_jobs"] += 1
            j.error = SearchTimeoutError(
                "batched query deadline expired while queued"
            )
            j.event.set()  # wake AFTER the stats update (observable order)
            return False
        return True

    # ---- worker side (pipelined: dispatch ring + deferred collect) ----

    def _run(self):
        # bounded in-flight ring: each entry is a dispatched batch whose
        # device results have not been collected yet. With
        # pipeline_depth=1 this is exactly the classic loop (dispatch,
        # then immediately collect); with depth=2 the worker dispatches
        # batch N+1 while batch N's kernels are still on device and
        # collects N afterwards, so the device never waits for the
        # host-side hit building of the previous batch.
        inflight: Deque[_BatchCtx] = deque()
        _worker_tl.batcher = self  # compiles on this thread are ours
        try:
            while not self._closed:
                if inflight:
                    # never block on the queue while batches are in
                    # flight: their waiters come first when idle
                    try:
                        job = self._queue.get_nowait()
                    except queue.Empty:
                        self._collect_batch(inflight.popleft())
                        continue
                else:
                    job = self._queue.get()
                if job is None:
                    continue
                if self._closed:
                    if not job.event.is_set():
                        job.error = RuntimeError("query batcher closed")
                        job.event.set()
                    continue
                if not self._admit_job(job):
                    continue
                batch = [job]
                while len(batch) < self.max_batch:
                    try:
                        j = self._queue.get_nowait()
                    except queue.Empty:
                        break
                    if j is not None and self._admit_job(j):
                        batch.append(j)
                if len(batch) == 1 and not inflight:
                    # express lane: a lone query on an idle worker skips
                    # the in-flight ring — dispatch at bucket 1, collect
                    # before the next dequeue. Depth-1 semantics for the
                    # latency-critical empty-queue case; under load the
                    # drain above yields batch > 1 and the ring engages.
                    with self._lock:
                        self.stats["express_lane_hits"] += 1
                    self._collect_batch(
                        self._dispatch_batch(batch, express=True)
                    )
                    continue
                inflight.append(self._dispatch_batch(batch))
                while len(inflight) >= max(1, self.pipeline_depth):
                    self._collect_batch(inflight.popleft())
        finally:
            # the dispatcher thread is exiting (close() or a crash
            # outside the per-group guard): nobody may block forever —
            # in-flight batches fail their waiters instead of hanging
            err = RuntimeError("query batcher closed")
            while inflight:
                ctx = inflight.popleft()
                for fam, *_ in ctx.pending:
                    self._exit_kind(fam.overlap)
                for j in ctx.batch:
                    if not j.event.is_set():
                        j.error = err
                        j.finish()
            self._drain_queue(RuntimeError("query batcher worker exited"))
            if self._closed:
                # the drain above may have eaten peers' wake sentinels:
                # cascade one forward so every blocked worker exits
                try:
                    self._queue.put_nowait(None)
                except queue.Full:  # pragma: no cover
                    pass

    def _dispatch_batch(
        self, batch: List[_Job], express: bool = False
    ) -> "_BatchCtx":
        """Groups a batch and launches all its device work, a group
        after the other in the order their first jobs were submitted;
        `_collect_batch` collects them in that order. Never raises:
        failures surface to the affected jobs' waiters."""
        ctx = _BatchCtx(batch)
        try:
            # congestion signal for the admission layer's AIMD limit:
            # the worst enqueue→dispatch wait in this batch (the
            # "queue delay vs target" the adaptive limit steers on),
            # less the seconds the workers spent compiling meanwhile
            now = time.perf_counter_ns()
            cold = self._cold_s
            admission.observe_queue_delay(
                max(
                    (now - j.t_enq) / 1e9 - (cold - j.cold0)
                    for j in batch
                )
            )
            with self._lock:
                self.stats["jobs"] += len(batch)
                self.stats["max_batch_seen"] = max(
                    self.stats["max_batch_seen"], len(batch)
                )
            # group jobs that can share launches: same reader generation
            # (the executor), kind, whatever the family keys on, and
            # top-k compile bucket
            groups: Dict[Tuple, Tuple[_Family, int, List[_Job]]] = {}
            for j in batch:
                fam = FAMILIES[j.kind]
                kb = 16 if j.k <= 16 else scoring.next_bucket(j.k, 16)
                key = (id(j.executor), j.kind, *fam.share(j.plan), kb,
                       j.window)
                groups.setdefault(key, (fam, kb, []))[2].append(j)
            for key, (fam, kb, jobs) in groups.items():
                # pad-bucket ladder: the group's launch width is the
                # smallest compiled bucket covering its occupancy
                rows = (
                    None if fam.mesh
                    else bucket_for(len(jobs), self.buckets)
                )
                # the group's marks start here: its jobs' queue wait
                # ends, and compiles on this thread are the group's
                g = _Group(key[1], len(jobs), rows, express, self._cold_s)
                for j in jobs:
                    j.group = g
                set_worker_group(g)
                self._enter_kind(fam.overlap)
                dispatched = False
                try:
                    # fault site: an injected dispatch failure surfaces
                    # to exactly this group's waiters, not the batch
                    faults.check(
                        "batcher.dispatch", family=fam.overlap,
                        jobs=len(jobs), mesh=int(fam.mesh),
                    )
                    if fam.bucket_recorded and not fam.mesh:
                        self._record_bucket(rows, len(jobs))
                    with g.phase("es.dispatch"):
                        pend = fam.dispatch(self, jobs, key, kb, rows, True)
                        if fam.mesh:
                            g.rows = int(pend.get("rows", BPAD))
                            self._record_bucket(g.rows, len(jobs))
                    g.dispatched()
                    ctx.pending.append((fam, key, kb, jobs, pend))
                    dispatched = True
                except BaseException as e:  # surface to waiters
                    for j in jobs:
                        if not j.event.is_set():
                            j.error = e
                            j.finish()
                finally:
                    set_worker_group(None)
                    if not dispatched:
                        self._exit_kind(fam.overlap)
            if len(ctx.pending) >= 2:
                with self._lock:
                    self.stats["groups_launched_together"] += len(
                        ctx.pending)
        except BaseException as e:
            # stats/grouping crash between dequeue and the per-group
            # guard: already-dequeued jobs are not in the queue, so the
            # finally-drain can't reach them — fail them here so no
            # submitter blocks forever (already-dispatched groups still
            # collect normally)
            for j in batch:
                if not j.event.is_set():
                    j.error = e
                    j.finish()
        return ctx

    def _collect_batch(self, ctx: "_BatchCtx"):
        """Host side of one dispatched batch: transfer the merged device
        results and finish the waiters, then warm the ladder of a family
        seen for the first time (compile time, not these queries' time).
        Never raises."""
        warm: List[Tuple] = []
        try:
            for fam, key, kb, jobs, pend in ctx.pending:
                g = jobs[0].group
                set_worker_group(g)
                g.collecting()
                try:
                    # claimed before the waiters wake: `wait_warm_idle`
                    # never reads idle between a first request's answer
                    # and its ladder's warm-up
                    j0 = self._warm_due(fam, key, jobs)
                    if j0 is not None:
                        warm.append((fam, key, kb, g.rows, j0))
                    with g.phase("es.collect"):
                        try:
                            # fault site: a collect-phase failure (device→
                            # host transfer) fails this group's waiters only
                            faults.check(
                                "batcher.collect", family=fam.overlap,
                                jobs=len(jobs), mesh=int(fam.mesh),
                            )
                            fam.collect(self, jobs, key, kb, pend, True)
                        finally:
                            g.unpacked()  # `es.unpack` ends inside it
                except BaseException as e:
                    for j in jobs:
                        if not j.event.is_set():
                            j.error = e
                            j.finish()
                finally:
                    set_worker_group(None)
                    self._exit_kind(fam.overlap)
        finally:
            ctx.pending = []
            for w in warm:
                self._warm_ladder(*w)

    def _count_rare_slots(self, rows: int, t_rare: int, tiles: List[int]):
        """One fused launch's rare-term pass over one field (`tiles`: a
        job's tile count each), under the lock: the slots it scattered
        of the slots of its budget."""
        self.stats["rare_slots_scattered"] += scoring.rare_slots_scattered(
            rows, tiles)
        self.stats["rare_slots_budget"] += rows * t_rare

    def _count_overflow(self, fplans: list):
        """Jobs whose plan does not fit the fused kernel's slots send
        their whole group down the slower path: counted, and flagged on
        the group this worker is dispatching (its `dispatch` span)."""
        _group_now().overflow = True
        with self._lock:
            self.stats["fused_overflow_jobs"] += sum(
                1 for p in fplans if p is None
            )

    # ---- continuous-batching accounting + bucket warmup ----

    def _record_bucket(self, rows: int, njobs: int):
        """One dispatched group: `rows` padded launch width, `njobs`
        real query rows. avg_occupancy = Σjobs / Σslots measures the
        padding waste the bucket ladder leaves behind."""
        rows = int(rows)
        with self._lock:
            self._bucket_launches[rows] = (
                self._bucket_launches.get(rows, 0) + 1
            )
            self.stats["occupancy_jobs"] += njobs
            self.stats["occupancy_slots"] += rows

    def note_unplanned(self) -> None:
        """A query-only or knn-only search of a jax shard left for the
        unbatched executor: no planner gave it a plan
        (cluster/indices.py, the shard path, which the mesh twin and a
        retriever's leg fall through to)."""
        with self._lock:
            self.stats["unplanned_queries"] += 1

    def node_stats(self) -> Dict[str, dict]:
        """This batcher's blocks of the node's document under their
        dotted paths (NODE_STATS, which explains each leaf), one snapshot
        under the lock the counters are kept under."""
        with self._lock:
            out = {path: {leaf: self.stats[name]
                          for leaf, name in leaves.items()}
                   for path, leaves in _STATS_LEAVES.items()}
            for path in _FAMILY_BLOCKS:
                out[path] = dict(getattr(self, path))
            out["thread_pool.search"]["queue_capacity"] = self._queue.maxsize
            out["pipeline"] = {"depth": self.pipeline_depth}
            batching = out["pipeline.batching"]
            batching["buckets"] = list(self.buckets)
            batching["launches_by_bucket"] = {
                str(b): n for b, n in sorted(self._bucket_launches.items())
            }
            batching["fused_hot_slots"] = {
                str(h): n for h, n in enumerate(self._fused_hot_slots)
            }
            batching["serve_hot_slots"] = {
                str(h): n for h, n in enumerate(self._serve_hot_slots)
            }
        with self._cold_lock:
            batching["worker_compile_ms"] = round(self._cold_s * 1000.0, 3)
        batching["avg_occupancy"] = _avg_occupancy(batching)
        return out

    def batching_stats(self) -> dict:
        """The continuous-batching block (`pipeline.batching`)."""
        return self.node_stats()["pipeline.batching"]

    def _warm_due(self, fam: _Family, key,
                  jobs: List[_Job]) -> Optional[_Job]:
        """The job `_warm_ladder` clones its dummies from, the first
        time a group of this kernel family (the key and what its
        programs specialize on) is collected, else None; counted in
        `_warm_inflight` until that `_warm_ladder` ends. Gated by
        ES_TPU_BUCKET_WARMUP / `warmup_enabled` (tier-1 pins it off)."""
        if (fam.warm is None or not self.warmup_enabled
                or len(self.buckets) <= 1):
            return None
        specialized, j0 = fam.warm(jobs)
        warm_key = key + specialized
        with self._lock:
            if warm_key in self._warmed:
                return None
            self._warmed.add(warm_key)
            self._warm_inflight += 1
        return j0

    def _warm_ladder(self, fam: _Family, key, kb: int, rows: int,
                     j0: _Job):
        """Eagerly compiles the remaining ladder buckets of a group's
        kernel family, by running one dummy job (cloned from the live
        group's `j0`) through the real dispatch / collect pair at every
        bucket but the group's own `rows`. Steady-state bucket selection
        then never compiles. Best-effort and stat-silent (record=False):
        warm launches appear in no histogram, flop or fault
        accounting."""
        try:
            for b in self.buckets:
                if b == rows:
                    continue
                dummy = [
                    _Job(j0.executor, j0.plan, j0.k, kind=j0.kind,
                         query=j0.query, window=j0.window)
                ]
                try:
                    fam.collect(
                        self, dummy, key, kb,
                        fam.dispatch(self, dummy, key, kb, b, False), False)
                except BaseException as e:
                    # warmup is opportunistic: a failed bucket just
                    # compiles lazily on its first live hit instead —
                    # counted, and logged once per batcher
                    with self._lock:
                        self.stats["warmup_failures"] += 1
                        first = self.stats["warmup_failures"] == 1
                    if first:
                        logger.warning(
                            "bucket warm-up launch failed (family %r, "
                            "rows=%d): %r", j0.kind, b, e,
                        )
        finally:
            with self._lock:
                self._warm_inflight -= 1

    def wait_warm_idle(self, timeout: float = 60.0) -> bool:
        """Blocks until no bucket-warmup loop is running (the warm is
        asynchronous to the triggering request — its waiters complete
        BEFORE the remaining ladder buckets compile). Test/benchmark
        hook: compile-cache probes must quiesce first or they race the
        warm tail. Returns False on timeout."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            with self._lock:
                if self._warm_inflight == 0:
                    return True
            time.sleep(0.01)
        return False

    def _dispatch_match_group(self, jobs: List[_Job], field: str, kb: int,
                              rows: Optional[int] = None,
                              record: bool = True) -> Tuple:
        """Every segment's scoring, up to the device-resident candidate
        buffers: the fused kernel, enqueued without a host sync, or the
        chunked block-max path with its one host-dependent threshold
        round (a download: that dispatch blocks). `rows` is the group's
        padded launch width (a ladder bucket >= len(jobs); default
        BPAD); `record=False` (bucket warmup) skips all stats/flop
        accounting."""
        ex = jobs[0].executor
        reader = ex.reader
        nj = len(jobs)
        rows = rows or BPAD
        staging = getattr(ex, "staging_slab", None)
        # shard-level pruning eligibility: a capped total may only be
        # shortcut to (cap, gte) when ≥ cap live matches are guaranteed
        # up front (doc_freq of some term minus deleted docs)
        prune: List[bool] = []
        for j in jobs:
            ok = j.plan.wand_ok
            if ok and j.plan.tth_cap:
                max_df = max(
                    (ex.shard_df(field, t) for t in j.plan.terms), default=0
                )
                ok = max_df - ex.deleted_count >= j.plan.tth_cap
            prune.append(ok)
        with_cnt = any(j.plan.msm > 1 for j in jobs)
        # the group key's: every job feeds a rescore window of this
        # width, or none does (0: the programs are the ones they were)
        tie_window = jobs[0].window
        windows_block_selected = 0
        # per-segment candidates STAY on device, a fused launch's as the
        # kernel packed them: the collect is one download (`_group_topk`)
        dev_items: List[Tuple] = []  # (si, packed | (s, d, tot))
        pruned_flags = [False] * nj
        empty_i = np.empty(0, np.int64)
        empty_w = np.empty(0, np.float32)
        for si in range(len(reader.segments)):
            # ---- fused single-round-trip path (large segments) ----
            fs = ex.fused_scorer_mf(si, (field,))
            if fs is not None:
                # every term a counted clause of its own; a boost <= 0
                # cannot ride weights whose sign says whether a term counts
                fplans = [
                    ex.fused_plan_field(
                        si, field, fs.parts[0],
                        [(t, 1.0, 1) for t in j.plan.terms], j.plan.boost,
                    ) if j.plan.boost > 0 else None
                    for j in jobs
                ]
                if all(p is not None for p in fplans):
                    pend = fs.search_async(
                        [([p], j.plan.msm) for p, j in zip(fplans, jobs)],
                        kb, "sum", None, staging=staging, rows=rows,
                        counted=with_cnt, tie_window=tie_window,
                    )
                    if record:
                        rare = [len(p[0]) for p in fplans]
                        with self._lock:
                            self.stats["launches"] += 1
                            self.stats["fused_jobs"] += nj
                            self.stats["fused_rare_tiles"] += sum(rare)
                            self._count_rare_slots(rows, fs.t_rare, rare)
                            for p in fplans:
                                self._fused_hot_slots[len(p[2])] += 1
                        t = _group_now().plan_tags
                        t["rare_tiles"] = max(t.get("rare_tiles", 0), *rare)
                    if tie_window and scoring.topk_block_rows(fs.n_docs, kb):
                        windows_block_selected += nj
                    dev_items.append((si, pend[0]))
                    continue
                if record:
                    self._count_overflow(fplans)
            # ---- chunked path (small segments / slot overflow) ----
            bmx = ex.block_index(si, field)
            cs = ex.chunked_scorer(si, field)
            if bmx is None or cs is None:
                continue
            acc, cnt = cs.new_acc(with_cnt, rows=rows)
            a_tiles: List[np.ndarray] = []
            a_w: List[np.ndarray] = []
            deferred: List[list] = []
            for ji, j in enumerate(jobs):
                plans = bmx.plan(list(j.plan.terms), j.plan.boost)
                tl, wl, hots = [], [], []
                for p in plans:
                    if prune[ji] and p.hot:
                        hots.append(p)
                    else:
                        tl.append(
                            np.arange(
                                p.tile_start, p.tile_start + p.tile_count, dtype=np.int64
                            )
                        )
                        wl.append(np.full(p.tile_count, p.weight, np.float32))
                if not tl and hots:
                    # the essential set must be non-empty or θ is -inf
                    # and nothing prunes: promote the cheapest hot term
                    hots.sort(key=lambda p: p.tile_count)
                    p = hots.pop(0)
                    tl.append(
                        np.arange(
                            p.tile_start, p.tile_start + p.tile_count, dtype=np.int64
                        )
                    )
                    wl.append(np.full(p.tile_count, p.weight, np.float32))
                a_tiles.append(np.concatenate(tl) if tl else empty_i)
                a_w.append(np.concatenate(wl) if wl else empty_w)
                deferred.append(hots)
            acc, cnt = cs.score_into(acc, cnt, a_tiles, a_w, staging=staging)
            if record:
                with self._lock:
                    self.stats["launches"] += 1
            if any(deferred):
                # ---- the threshold broadcast + survival test (the one
                # host-dependent round: only runs when pruning engages) ----
                theta, accmax = cs.threshold(acc, kb)
                b_tiles: List[np.ndarray] = []
                b_w: List[np.ndarray] = []
                for ji, hots in enumerate(deferred):
                    tl, wl = [], []
                    if hots:
                        sum_bounds = np.zeros(bmx.tiling.n_blocks, np.float32)
                        for p in hots:
                            sum_bounds += bmx.block_bounds(p)
                        potential = accmax[ji] + sum_bounds
                        for p in hots:
                            kept = bmx.surviving_tiles(p, potential, theta[ji])
                            if len(kept) < p.tile_count:
                                pruned_flags[ji] = True
                            if len(kept):
                                tl.append(kept)
                                wl.append(
                                    np.full(len(kept), p.weight, np.float32)
                                )
                    b_tiles.append(np.concatenate(tl) if tl else empty_i)
                    b_w.append(np.concatenate(wl) if wl else empty_w)
                acc, cnt = cs.score_into(
                    acc, cnt, b_tiles, b_w, staging=staging
                )
                if record:
                    with self._lock:
                        self.stats["launches"] += 1
            msm = np.ones(rows, np.int32)
            msm[:nj] = [j.plan.msm for j in jobs]
            dev_items.append(
                (si, cs.finalize_device(acc, cnt, msm, kb,
                                        tie_window=tie_window)))
            if tie_window and scoring.topk_block_rows(
                    cs.n_docs, min(kb, cs.n_docs)):
                windows_block_selected += nj
        if record and windows_block_selected:
            # a window's bucket (deep: the window's own width and more)
            # selected from block maxima, not by a sort of the plane
            from ..models import rerank as rerank_model

            rerank_model.note("window_block_selected", windows_block_selected)
        return dev_items, pruned_flags

    def _collect_match_group(self, jobs: List[_Job], kb: int, pend: Tuple,
                             record: bool = True):
        """The group's ONE blocking download (`_group_topk`: the fused
        kernel's packed row as it is when it is the only item, else the
        cross-segment merge program's; score desc, (segment, doc) asc,
        selection only → float-exact), then each job's hits."""
        dev_items, pruned_flags = pend
        reader = jobs[0].executor.reader
        nj = len(jobs)
        if dev_items and jobs[0].window:
            ms, mseg, mdoc, mtot = self._window_topk(
                dev_items, jobs[0].window, record)
        elif dev_items:
            ms, mseg, mdoc, mtot = self._group_topk(dev_items, kb, record)
        else:
            ms = np.full((nj, 0), -np.inf, np.float32)
            mseg = mdoc = np.zeros((nj, 0), np.int32)
            mtot = np.zeros((nj, 0), np.int64)
        for ji, j in enumerate(jobs):
            finite = np.isfinite(ms[ji])
            # a rescore window stays the columns it was downloaded as
            # (`TopDocs.of_columns`): `Hit`s are made for its page alone
            hits = None if j.window else [
                Hit(
                    score=float(s),
                    segment=int(si),
                    local_doc=int(d),
                    doc_id=reader.segments[int(si)].doc_ids[int(d)],
                )
                for s, si, d in zip(
                    ms[ji][finite][: j.k],
                    mseg[ji][finite][: j.k],
                    mdoc[ji][finite][: j.k],
                )
            ]
            total = int(mtot[ji].sum())
            relation = "eq"
            if pruned_flags[ji]:
                if record:
                    with self._lock:
                        self.stats["pruned_jobs"] += 1
                if record and j.prof is not None:
                    j.prof["pruned_jobs"] = (
                        j.prof.get("pruned_jobs", 0) + 1
                    )
                # pruned tiles mean the collected count is a lower bound —
                # never report it as exact, even at tth_cap == 0 where the
                # REST layer omits totals (internal consumers of TopDocs
                # would otherwise see an exact-looking undercount)
                relation = "gte"
                if j.plan.tth_cap:
                    # eligibility proof guaranteed ≥ cap live matches
                    total = max(total, j.plan.tth_cap)
            j.result = TopDocs(
                total=total,
                hits=hits,
                max_score=hits[0].score if hits else None,
                relation=relation,
            ) if not j.window else TopDocs.of_columns(
                total, reader, ms[ji][finite][: j.k],
                mseg[ji][finite][: j.k], mdoc[ji][finite][: j.k], relation,
            )
            j.finish()

    def _group_topk(self, items: List[Tuple], kb: int, record: bool,
                    extra: int = 0):
        """A text or sparse group's device candidates, [(si, part)]
        (segment asc), merged on the host after ONE blocking download:
        (scores, segments, docs, totals[B, len(items)]) as
        `scoring.merge_segment_topk` returns them. What it launches
        follows from what the group holds: a lone fused launch's packed
        row is the answer already and is downloaded as the kernel wrote
        it (no program, no upload); anything else goes through the one
        merge program, which unpacks packed rows in its own trace.
        `extra`: the packed rows end in that many int32 counters (a
        phrase group's); their sums over the segments are returned
        last, i64[B, extra]."""
        direct = len(items) == 1 and scoring.is_packed(items[0][1])
        more = (extra,) if extra else ()  # a bare group: the calls it made
        if direct:
            ms, mseg, mdoc, mtot, *counters = scoring.packed_segment_topk(
                *items[0], *more)
        else:
            ms, mseg, mdoc, mtot, *counters = scoring.merge_segment_topk(
                items, kb, *more)
        _group_now().merged = not direct
        if record and direct:
            with self._lock:
                self.stats["direct_collect_groups"] += 1
        # exact ties in (segment, doc) order: the device's top-k does not
        # promise it
        return (*scoring.rank_order(ms, mseg, mdoc), mtot, *counters)

    def _window_topk(self, items: List[Tuple], window: int, record: bool):
        """`_group_topk` for a first stage that feeds a rescore window:
        the group's first `window` by (score desc, (segment, doc) asc),
        EXACTLY, exact ties at the cut included (`scoring.window_cut`).
        Every segment's candidates are downloaded as their launch left
        them, with its tie refill (a packed row's trailing k, the
        chunked path's fourth array: one blocking download a segment,
        no merge program), cut to the segment's own exact first
        `window`, and merged on the host. A window whose tie group ran
        past the fetched bucket counts in `rescore.window_ties_refilled`
        and on the `collect` span (`window`, `ties_refilled`)."""
        scores_l, segs_l, docs_l, tots = [], [], [], []
        refilled = None
        for si, part in items:
            if scoring.is_packed(part):
                k = (int(part.shape[1]) - 1) // 3
                s, sg, d, tot, fill = scoring.packed_segment_topk(
                    si, part, k)
            else:
                s, d, tot, fill = (scoring._to_host(x) for x in part)
                sg, tot = np.full_like(d, si), tot.astype(np.int64)[:, None]
            s, sg, d = scoring.rank_order(s, sg, d)
            rows = [scoring.window_cut(s[b], d[b], fill[b], window)
                    for b in range(len(s))]
            flags = np.array([r[2] for r in rows], bool)
            refilled = flags if refilled is None else refilled | flags
            scores_l.append([r[0] for r in rows])
            docs_l.append([r[1] for r in rows])
            segs_l.append(si)
            tots.append(tot)
        B = len(refilled)
        ms = np.full((B, window), -np.inf, np.float32)
        mseg = np.zeros((B, window), np.int32)
        mdoc = np.zeros((B, window), np.int32)
        for b in range(B):
            s = np.concatenate([sl[b] for sl in scores_l])
            d = np.concatenate([dl[b] for dl in docs_l])
            sg = np.repeat(segs_l, [len(sl[b]) for sl in scores_l])
            keep = (np.lexsort((d, sg, -s))[:window] if len(items) > 1
                    else slice(None))  # one segment: cut and in order
            n = len(s[keep])
            ms[b, :n], mseg[b, :n], mdoc[b, :n] = s[keep], sg[keep], d[keep]
        g = _group_now()
        g.merged = False  # no merge program, whatever the segments
        if record and len(items) == 1 and scoring.is_packed(items[0][1]):
            with self._lock:
                self.stats["direct_collect_groups"] += 1
        g.collect_tags = {"window": int(window),
                          "ties_refilled": int(refilled.sum())}
        if record and refilled.any():
            from ..models import rerank as rerank_model

            rerank_model.note("window_ties_refilled", int(refilled.sum()))
        return ms, mseg, mdoc, np.concatenate(tots, axis=1)

    # ---- dispatch/collect pairs (device work launches in dispatch;
    # only collect blocks on host transfers) ----

    def _enter_kind(self, fam: str):
        with self._lock:
            self._inflight[fam] += 1

    def _exit_kind(self, fam: str):
        with self._lock:
            self._inflight[fam] -= 1

    def _dispatch_serve_group(self, jobs: List[_Job], kb: int,
                              rows: Optional[int] = None,
                              record: bool = True) -> List[Tuple]:
        """Launches the multi-field fused kernels for ServePlan jobs
        (bool / multi_match) on every eligible segment WITHOUT host
        sync. Segments without a fused scorer (below FUSED_MIN_DOCS) or
        jobs overflowing slot budgets are marked for the per-job
        fallback, which runs at collect time. `rows` pads the launch to
        one ladder bucket; `record=False` (warmup) mutes stats.

        A FILTERED group (every job under a `KeywordFilter` on one
        field: the group key's) hands the launch the filter field's
        doc-id tiles, the rows' filter plan and the field's bit rows,
        and the program builds every row's own mask itself
        (`scoring.filter_row_masks`): no launch in front of the fused
        one, one more host operand (the plan, 100 B a row); what the
        host does for it is the `filter_mask` span (`launches: 0`). It
        neither reads nor feeds the node's filter-bitset cache. A
        segment whose filter field cannot be had on the device (the
        `serve.filter` fault site, an upload the HBM breaker refuses)
        runs per job on the unbatched executor at collect; one without
        the field passes nothing and is skipped. A NEGATED group (the
        key's too) launches the program that reads the count plane's
        last digit as a veto; its plans carry the excluded terms.
        `serve_filtered` counts both kinds' scans, and in `fallbacks`
        those of them that left the planned path."""
        ex = jobs[0].executor
        nj = len(jobs)
        rows = rows or BPAD
        staging = getattr(ex, "staging_slab", None)
        plan0 = jobs[0].plan
        fields = plan0.fields
        flt0, negated = plan0.filter, plan0.excluded > 0  # the key's
        special = flt0 is not None or negated
        if record and special:
            # a group of neither writes no tag more
            tags = _group_now().plan_tags
            tags["filtered"] = flt0 is not None
            tags["filter_clauses"] = max(
                len(j.plan.filter.clauses) if j.plan.filter else 0
                for j in jobs)
            tags["excluded_terms"] = max(j.plan.excluded for j in jobs)
        items: List[Tuple] = []
        for si, seg in enumerate(ex.reader.segments):
            if flt0 is not None and seg.postings.get(flt0.field) is None:
                continue  # no document holds the field: none passes
            fs = ex.fused_scorer_mf(si, fields)
            fplans = None
            if fs is not None:
                fplans = []
                for j in jobs:
                    sections = []
                    for g in j.plan.groups:
                        parts = ex.fused_parts(si, g.field)
                        sec = (
                            ex.fused_plan_field(
                                si, g.field, parts, g.terms, j.plan.boost
                            )
                            if parts is not None
                            else None
                        )
                        if sec is None:
                            sections = None
                            break
                        sections.append(sec)
                    fplans.append(
                        (sections, j.plan.msm) if sections is not None else None
                    )
            fused = fs is not None and all(p is not None for p in fplans)
            if record and fs is not None and not fused:
                self._count_overflow(fplans)
            fmask = fp = None
            if fused and flt0 is not None:
                fmask, fp = self._serve_filter_plan(jobs, si, rows, record)
                fused = fmask is not None
            if fused:
                pend = fs.search_async(
                    fplans, kb, plan0.combine, plan0.tie, staging=staging,
                    rows=rows, fmask=fmask, negated=negated,
                )
                if record and special:
                    self._count_serve_filtered(jobs, si, fp, fplans)
                if record:
                    secs = [sec for sections, _ in fplans for sec in sections]
                    rare = [len(sec[0]) for sec in secs]
                    hot = [len(sec[2]) for sec in secs]
                    with self._lock:
                        self.stats["launches"] += 1
                        self.stats["fused_jobs"] += nj
                        self.stats["serve_launches"] += 1
                        self.stats["serve_rare_tiles"] += sum(rare)
                        self.stats["serve_hot_rows"] += sum(hot)
                        self.stats["serve_clauses"] += sum(
                            j.plan.clauses for j in jobs)
                        self.stats["serve_multi_term_clauses"] += sum(
                            j.plan.multi_term_clauses for j in jobs)
                        for f in range(len(fields)):  # secs: job-major
                            self._count_rare_slots(
                                rows, fs.t_rare, rare[f::len(fields)])
                        for h in hot:
                            self._serve_hot_slots[h] += 1
                    t = _group_now().plan_tags
                    t["fields"] = len(fields)
                    t["hot_slots"] = max(t.get("hot_slots", 0), *hot)
                    t["rare_tiles"] = max(t.get("rare_tiles", 0), *rare)
                    t["clauses"] = max(j.plan.clauses for j in jobs)
                    t["msm"] = max(j.plan.msm for j in jobs)
                items.append(("fused", si, pend[0]))
            else:
                if record and special:
                    with self._lock:
                        self.serve_filtered["fallbacks"] += nj
                items.append(("fallback", si, None))
        return items

    def _serve_filter_plan(self, jobs: List[_Job], si: int, rows: int,
                           record: bool):
        """One segment of a filtered serve group: ((doc-id tiles, plan,
        bit rows) for the fused launch, the packed FilterPlans); (None,
        None) where the field's postings or bit rows are not to be had
        on the device and the segment is left to the unbatched executor
        at collect. The `filter_mask` span (the knn family's name,
        here with `launches: 0`): the field's postings and bit rows
        fetched (built at the field's first filtered search), the
        filters' terms looked up, the plan packed; its upload and the
        mask itself ride the fused launch."""
        ex = jobs[0].executor
        t0 = time.perf_counter_ns()
        fname = jobs[0].plan.filter.field
        try:
            if record:
                faults.check("serve.filter", field=fname, segment=si)
            dp = ex.device_segments[si].postings[fname]
            bits = dp.filter_bits
        except Exception:
            return None, None
        fp = scoring.pack_filter_plans(
            ex.reader.segments[si].postings[fname],
            [j.plan.filter.clauses for j in jobs], rows, bits)
        note_transfer("h2d", fp.plan.nbytes)
        if record:
            g = _group_now()
            g.plan_tags["filter_tiles"] = (
                g.plan_tags.get("filter_tiles", 0) + fp.tiles)
            g.sub_spans.append((
                "filter_mask", t0, time.perf_counter_ns(),
                {"segment": si, "launches": 0, "tiles": fp.tiles,
                 "bitset_terms": fp.bit_terms,
                 "bitset_rows_held": len(bits.row_of_term)},
            ))
        return (dp.doc_ids, fp.plan, bits.plane), fp

    def _count_serve_filtered(self, jobs: List[_Job], si: int, fp,
                              fplans) -> None:
        """A filtered or negated fused launch over one segment, in
        `serve_filtered`: `fp` its packed filters (None for a launch
        that only excludes), `fplans` its jobs' (sections, msm). An
        excluded term on tiles has rare slots that carry the veto's
        counter above their ids (`scoring.clause_slot_ids`); one on a
        dense row takes a hot slot and no tile."""
        veto = scoring.VETO_COUNT - 1
        tiles = sum(
            int(np.count_nonzero(sec[0] >> scoring.SLOT_ID_BITS == veto))
            for sections, _msm in fplans for sec in sections)
        with self._lock:
            sf = self.serve_filtered
            sf["searches"] += len(jobs)
            sf["excluded_terms"] += sum(j.plan.excluded for j in jobs)
            sf["excluded_tiles"] += tiles
            if fp is not None:
                sf["mask_launches"] += 1
                sf["filter_terms"] += fp.terms
                sf["bitset_terms"] += fp.bit_terms
                sf["filter_tiles"] += fp.tiles
                sf["rows_scanned"] += len(jobs) * (
                    jobs[0].executor.reader.segments[si].num_docs)

    def _collect_serve_group(self, jobs: List[_Job], kb: int, items,
                             record: bool = True):
        """Host side of the serve group: one blocking download covers
        every fused segment (`_group_topk`: one segment's packed row as
        the kernel wrote it, several through the merge program);
        fallback segments (below FUSED_MIN_DOCS / slot overflow) run per
        job on the host and join the final merge. Totals are exact (the
        fused program scores exactly — no pruning on this path)."""
        ex = jobs[0].executor
        reader = ex.reader
        per_job_cands: List[List[Tuple[float, int, int]]] = [[] for _ in jobs]
        totals = np.zeros(len(jobs), np.int64)
        fused_items = [
            (si, packed) for tag, si, packed in items if tag == "fused"
        ]
        if fused_items:
            # a filtered group's packed rows end in the documents each
            # row's filter passed
            filtered = jobs[0].plan.filter is not None
            ms, mseg, mdoc, mtot, *passed = self._group_topk(
                fused_items, kb, record, extra=int(filtered))
            if filtered and record:
                with self._lock:
                    self.serve_filtered["rows_passed"] += int(
                        passed[0][:len(jobs)].sum())
            for ji in range(len(jobs)):
                finite = np.isfinite(ms[ji])
                for s, si, d in zip(
                    ms[ji][finite], mseg[ji][finite], mdoc[ji][finite]
                ):
                    per_job_cands[ji].append((float(s), int(si), int(d)))
                totals[ji] += int(mtot[ji].sum())
        for tag, si, _packed in items:
            if tag != "fallback":
                continue
            for ji, j in enumerate(jobs):
                s1, d1, t1 = ex.segment_topk(j.query, si, kb)
                if record:
                    with self._lock:
                        self.stats["launches"] += 1
                        self.stats["serve_fallback_jobs"] += 1
                self._collect(
                    [j], [per_job_cands[ji]], totals[ji: ji + 1],
                    si, s1[None, :], d1[None, :], np.array([t1]),
                )
        self._finish_jobs(jobs, per_job_cands, totals, reader)

    def _dispatch_phrase_group(self, jobs: List[_Job], kb: int,
                               rows: Optional[int] = None,
                               record: bool = True) -> List[Tuple]:
        """One launch of the phrase kernel a segment (ops/phrase.py
        `phrase_topk`) for a group of PhrasePlan jobs of one field and
        span, enqueued WITHOUT a host sync: the words are looked up in
        the segment's term dictionary, the plan (a row a job: term ids
        by slot, the weight) is packed and uploaded with the launch, and
        the packed result stays on the device until collect. The
        `phrase_plan` span, a child of `dispatch`, covers a segment's
        look-ups, packing and enqueue.

        A segment whose positions plane cannot be held (the
        `phrase.score` fault site, an upload the HBM breaker refuses, a
        segment indexed before positions were columnar) is served per
        job by the unbatched executor's `_exec_phrase` at collect, and
        counted (`phrase.fallbacks`)."""
        ex = jobs[0].executor
        reader = ex.reader
        nj = len(jobs)
        rows = rows or BPAD
        plan0 = jobs[0].plan
        field, width = plan0.field, plan0.width
        phrases = [
            (j.plan.terms, j.plan.rel,
             ex.phrase_weight(field, list(j.plan.terms), j.plan.boost))
            for j in jobs
        ]
        words = sum(len(j.plan.terms) for j in jobs)
        tags = _group_now().plan_tags
        if record:
            tags["words"] = words
        items: List[Tuple] = []
        for si, seg in enumerate(reader.segments):
            pf = seg.postings.get(field)
            if pf is None or seg.num_docs == 0:
                continue
            t0 = time.perf_counter_ns()
            try:
                if record:
                    faults.check("phrase.score", field=field, segment=si)
                held = ex.phrase_plane(si, field)
            except Exception:
                held = None
            if held is None:
                if record:
                    with self._lock:
                        self.phrase["fallbacks"] += nj
                items.append(("fallback", si, None))
                continue
            dev, inv_norm, live = held
            plan, df_min = phrase_ops.pack_phrase_plans(
                pf, phrases, rows, width)
            note_transfer("h2d", plan.nbytes)
            g = _group_now()
            occ = dev.occurrences * nj
            with g.launch("phrase_topk", 1, plan.nbytes, 2 * width * occ):
                out = phrase_ops.phrase_topk(
                    dev.mats, dev.order, inv_norm, live, plan,
                    k=min(kb, int(dev.order.shape[0])),
                )
            if record:
                tags["occurrences"] = tags.get("occurrences", 0) + occ
                g.sub_spans.append((
                    "phrase_plan", t0, time.perf_counter_ns(),
                    {"segment": si, "launches": 1, "words": words},
                ))
                with self._lock:
                    self.stats["launches"] += 1
                    self.stats["fused_jobs"] += nj
                    ph = self.phrase
                    ph["searches"] += nj
                    ph["launches"] += 1
                    ph["words"] += words
                    ph["occurrences_read"] += occ
                    ph["least_bytes"] += sum(
                        phrase_ops.least_bytes(df, seg.num_docs, 0)
                        for df in df_min)
            items.append(("dev", si, out))
        return items

    def _collect_phrase_group(self, jobs: List[_Job], kb: int, items,
                              record: bool = True):
        """Host side of a phrase group: ONE blocking download covers
        every device segment (`_group_topk`: one segment's packed row as
        the kernel wrote it, several through the merge program, the
        rows' two trailing counters summed beside the totals); fallback
        segments run per job on the unbatched executor and join the
        final merge. Totals are exact."""
        ex = jobs[0].executor
        nj = len(jobs)
        per_job_cands: List[List[Tuple[float, int, int]]] = [[] for _ in jobs]
        totals = np.zeros(nj, np.int64)
        dev_items = [(si, out) for tag, si, out in items if tag == "dev"]
        if dev_items:
            ms, mseg, mdoc, mtot, counters = self._group_topk(
                dev_items, kb, record, extra=phrase_ops.PHRASE_EXTRA)
            for ji in range(nj):
                finite = np.isfinite(ms[ji])
                for s, si, d in zip(
                    ms[ji][finite], mseg[ji][finite], mdoc[ji][finite]
                ):
                    per_job_cands[ji].append((float(s), int(si), int(d)))
                totals[ji] += int(mtot[ji].sum())
            if record:
                held, occ = (int(c) for c in counters[:nj].sum(axis=0))
                with self._lock:
                    ph = self.phrase
                    ph["candidates"] += held
                    ph["candidate_occurrences"] += occ
                    ph["least_bytes"] += occ
                    ph["matches"] += int(totals.sum())
        for tag, si, _out in items:
            if tag != "fallback":
                continue
            for ji, j in enumerate(jobs):
                s1, d1, t1 = ex.segment_topk(j.query, si, kb)
                if record:
                    with self._lock:
                        self.stats["launches"] += 1
                self._collect(
                    [j], [per_job_cands[ji]], totals[ji: ji + 1],
                    si, s1[None, :], d1[None, :], np.array([t1]),
                )
        self._finish_jobs(jobs, per_job_cands, totals, ex.reader)

    def _dispatch_fuzzy_group(self, jobs: List[_Job], kb: int,
                              rows: Optional[int] = None,
                              record: bool = True):
        """A group of FuzzyPlan jobs of one field: TWO dependent device
        steps, the second planned from the first's output.

        `fuzzy_expand` (span, `es.fuzzy_expand` on the profiler's clock):
        the group's distinct (word, edits) pairs go up as code points
        (a job's fuzziness is its own: the key holds none), ONE launch
        of ops/fuzzy.py `fuzzy_expand` walks each against the field's
        dictionary plane and the kept ordinals and distances come down
        (a blocking download: this dispatch waits, as a chunked match
        group's threshold round does). A word that takes no edit in its
        job is looked up on the host.

        `fuzzy_plan` (span, `es.fuzzy_plan`): boosts, the word's blended
        idf and the weights (models/fuzzy.py, float32 as the oracle
        computes them), then every kept term's dense row or tiles
        (`fuzzy_plan_field`), and the fused program at the family's slot
        budgets is enqueued; collect downloads its packed row.

        A job whose plan passes a budget (`fuzzy.overflows`), and every
        job of a shard the device path does not hold (several scoring
        segments, a segment under FUSED_MIN_DOCS: `fuzzy.fallbacks`), is
        served by the unbatched executor at collect: never by leaving
        kept terms out."""
        ex = jobs[0].executor
        reader = ex.reader
        nj = len(jobs)
        rows = rows or BPAD
        plan0 = jobs[0].plan
        field, params = plan0.field, plan0.params
        segs = [si for si, seg in enumerate(reader.segments)
                if seg.num_docs and seg.postings.get(field) is not None]
        fz = ex.fuzzy_parts(segs[0], field) if len(segs) == 1 else None
        if fz is None:
            if record and segs:
                with self._lock:
                    self.fuzzy["fallbacks"] += nj
            return None, [bool(segs)] * nj
        si = segs[0]
        pf = reader.segments[si].postings[field]
        g = _group_now()
        # ---- expand
        t0 = time.perf_counter_ns()
        with TraceAnnotation("es.fuzzy_expand"):
            # (the fuzziness is the job's own, not the group's key: a
            # word is expanded once for each number of edits it is asked)
            todo: Dict[Tuple[str, int], int] = {}
            sent: List[Tuple[np.ndarray, int]] = []
            for j in jobs:
                for w in j.plan.words:
                    k = j.plan.params.edits(w)
                    if k and (w, k) not in todo:
                        todo[w, k] = len(sent)
                        sent.append((fuzzy_model.code_points(w), k))
            found = []
            if sent:
                out = fuzzy_ops.expand_async(
                    fz["plane"], sent, fuzzy_ops.word_slots(rows),
                    params.max_expansions, params.transpositions)
                found = fuzzy_ops.decode(
                    scoring._to_host(out), len(sent), params.max_expansions)
        t1 = time.perf_counter_ns()
        # ---- plan
        with TraceAnnotation("es.fuzzy_plan"):
            doc_count = reader.field_stats(field)[0]
            lens = pf.term_plane().lens
            fplans, kept_n, sat_n = [], 0, 0
            for j in jobs:
                ords_l, w_l = [], []
                for w in j.plan.words:
                    at = todo.get((w, j.plan.params.edits(w)))
                    if at is None:
                        tid = pf.term_id(w)
                        ords = np.array([tid] if tid >= 0 else [], np.int64)
                        boosts = np.ones(len(ords), np.float32)
                    else:
                        ords, dist = found[at]
                        boosts = fuzzy_model.boosts_of(
                            dist, len(sent[at][0]), lens[ords])
                    if not len(ords):
                        continue
                    kept_n += len(ords)
                    sat_n += len(ords) >= params.max_expansions
                    ords_l.append(ords)
                    w_l.append(fuzzy_model.term_weights(
                        j.plan.boost,
                        fuzzy_model.blended_idf(doc_count, pf.term_df[ords]),
                        boosts))
                fplans.append(ex.fuzzy_plan_field(
                    si, field, fz,
                    np.concatenate(ords_l or [np.empty(0, np.int64)]),
                    np.concatenate(w_l or [np.empty(0, np.float32)])))
            empty = (np.empty(0, np.int64), np.empty(0, np.float32)) * 2
            fs = fz["scorer"]
            pend = fs.search_async(
                [([p if p is not None else empty], 1) for p in fplans],
                kb, "sum", None,
                staging=getattr(ex, "staging_slab", None), rows=rows,
                counted=False)
        t2 = time.perf_counter_ns()
        if record:
            n_words = sum(len(j.plan.words) for j in jobs)
            rare = [len(p[0]) for p in fplans if p is not None]
            hot = [len(p[2]) for p in fplans if p is not None]
            g.sub_spans.append(("fuzzy_expand", t0, t1, {
                "segment": si, "words": len(sent),
                "launches": int(bool(sent))}))
            g.sub_spans.append(("fuzzy_plan", t1, t2, {
                "segment": si, "terms_kept": kept_n,
                "tiles": sum(rare), "hot_terms": sum(hot)}))
            g.plan_tags.update(words=n_words, terms_kept=kept_n,
                               rare_tiles=max(rare, default=0),
                               hot_slots=max(hot, default=0))
            nbytes, cells = fuzzy_ops.least_work(
                [len(cp) for cp, _k in sent], [k for _cp, k in sent],
                fz["terms_by_len"], fz["plane"].chars.dtype.itemsize)
            if any(p is None for p in fplans):
                g.overflow = True
            with self._lock:
                self.stats["launches"] += 1 + bool(sent)
                self.stats["fused_jobs"] += nj
                self._count_rare_slots(rows, fs.t_rare, rare)
                fzs = self.fuzzy
                fzs["requests"] += nj
                fzs["words"] += n_words
                fzs["words_expanded"] += len(sent)
                fzs["terms_kept"] += kept_n
                fzs["words_saturated"] += sat_n
                fzs["hot_terms"] += sum(hot)
                fzs["tiles"] += sum(rare)
                fzs["overflows"] += sum(p is None for p in fplans)
                fzs["launches"] += bool(sent)
                fzs["blocked_launches"] += bool(sent) and fz["plane"].blocked
                fzs["score_launches"] += 1
                fzs["least_bytes"] += nbytes
                fzs["least_cells"] += cells
        return (si, pend[0]), [p is None for p in fplans]

    def _collect_fuzzy_group(self, jobs: List[_Job], kb: int, pend,
                             record: bool = True):
        """The group's one blocking download of collect (the fused
        program's packed row: `_group_topk`), then each job's hits; a
        job the device path turned away runs on the unbatched executor
        (the oracle's rewrite), segment by segment."""
        item, turned_away = pend
        ex = jobs[0].executor
        nj = len(jobs)
        per_job_cands: List[List[Tuple[float, int, int]]] = [[] for _ in jobs]
        totals = np.zeros(nj, np.int64)
        if item is not None:
            # (a job turned away rode an empty plan: no hit, total 0)
            ms, _mseg, mdoc, mtot = self._group_topk([item], kb, record)
            self._collect(jobs, per_job_cands, totals, item[0], ms, mdoc,
                          mtot.sum(axis=1))
        for ji, j in enumerate(jobs):
            if not turned_away[ji]:
                continue
            for si in range(len(ex.reader.segments)):
                s1, d1, t1 = ex.segment_topk(j.query, si, kb)
                self._collect(
                    [j], [per_job_cands[ji]], totals[ji: ji + 1],
                    si, s1[None, :], d1[None, :], np.array([t1]))
        self._finish_jobs(jobs, per_job_cands, totals, ex.reader)

    def _dispatch_agg_group(self, jobs: List[_Job]) -> List[Tuple]:
        """Launches the device-aggregation plans (search/aggs_device
        segment-sum kernels) for a group of same-signature agg jobs
        WITHOUT host sync; downloads happen at collect. Per-job failure
        isolation: one body's injected fault or column surprise fails
        only that job's waiter (the shard path then reruns it on the
        host collector), not its group."""
        out: List[Tuple] = []
        for j in jobs:
            try:
                with _group_now().launch(
                        "agg_plan", flops=j.plan.flops_estimate()):
                    pend = j.plan.dispatch()
            except BaseException as e:
                out.append(("err", e))
                continue
            with self._lock:
                self.stats["launches"] += 1
                self.stats["agg_jobs"] += 1
            out.append(("ok", pend))
        return out

    def _collect_agg_group(self, jobs: List[_Job], pends: List[Tuple]):
        for j, (tag, pend) in zip(jobs, pends):
            if j.event.is_set():
                continue
            if tag == "err":
                j.error = pend
                j.finish()
                continue
            try:
                j.result = j.plan.collect(pend)  # (TopDocs, partials)
            except BaseException as e:
                j.error = e
            j.finish()

    def _dispatch_rerank_group(self, jobs: List[_Job],
                               rows: Optional[int] = None) -> Tuple:
        """Launches one maxsim rescore kernel for a group of same-sig
        rerank jobs (search/rescorer.RerankPlan) WITHOUT host sync; the
        one packed download happens at collect. The `rerank.score`
        fault site fires here — an injected error surfaces to exactly
        this group's waiters, whose requests then keep their
        first-stage ranking (the deterministic rerank fallback). A
        missing column (HBM degrade-to-skip) completes the group with a
        "skip" marker instead of device work."""
        from ..models import rerank as rerank_model
        from ..ops import rerank as rerank_ops

        ex = jobs[0].executor
        plan0 = jobs[0].plan
        nj = len(jobs)
        rows = rows or BPAD
        faults.check("rerank.score", field=plan0.field, jobs=nj)
        col = ex.rerank_column(plan0.model)
        if col is None:
            return ("skip", None, 0.0)
        wb, qb = plan0.wb, plan0.qb
        dims = col["dims"]
        staging = getattr(ex, "staging_slab", None)
        if staging is not None:
            qtoks = staging("rerank_q", (rows, qb, dims), np.float32)
            qvalid = staging("rerank_qv", (rows, qb), np.bool_)
            docs = staging("rerank_d", (rows, wb), np.int32)
            first = staging("rerank_s", (rows, wb), np.float32)
            valid = staging("rerank_v", (rows, wb), np.bool_)
        else:
            qtoks = np.zeros((rows, qb, dims), np.float32)
            qvalid = np.zeros((rows, qb), bool)
            docs = np.zeros((rows, wb), np.int32)
            first = np.zeros((rows, wb), np.float32)
            valid = np.zeros((rows, wb), bool)
        # staging buffers are reused: fully rewrite every plane
        qtoks[:] = 0.0
        qvalid[:] = False
        docs[:] = 0
        first[:] = -np.inf
        valid[:] = False
        cands = tokens = 0
        for ji, j in enumerate(jobs):
            p = j.plan
            qtoks[ji, : len(p.qtoks)] = p.qtoks
            qvalid[ji, : len(p.qtoks)] = True
            w = len(p.first)
            docs[ji, :w] = p.gdocs.astype(np.int32)
            first[ji, :w] = p.first
            valid[ji, :w] = True
            # the launch's work, from the host's copy of the CSR counts
            rescored = p.gdocs[: min(w, p.win_static)]
            cands += len(rescored)
            tokens += int(col["counts_host"][rescored].sum())
        rerank_model.note_launch(
            cands, tokens, rows * wb * col["tmax"], dims,
            plan0.model.element_bytes)
        planes = (qtoks, qvalid, docs, first, valid)
        h2d = sum(a.nbytes for a in planes) + 8  # and the two weights
        note_transfer("h2d", h2d, count=len(planes) + 1)
        t0 = time.perf_counter()
        with _group_now().launch(
                "maxsim_rescore_batch", len(planes) + 1, h2d,
                rerank_ops.rerank_flops(nj, qb, wb, col["tmax"], dims)):
            out = rerank_ops.maxsim_rescore_batch(
                qtoks, qvalid, col["starts"], col["counts"], col["toks"],
                col["scales"], docs, first, valid,
                plan0.spec.query_weight, plan0.spec.rescore_query_weight,
                col["tmax"], plan0.win_static,
            )
        with self._lock:
            self.stats["launches"] += 1
            self.stats["rerank_jobs"] += nj
        return ("ok", out, t0)

    def _collect_rerank_group(self, jobs: List[_Job], pend: Tuple):
        """Host side: the ONE packed download, then each waiter gets
        its (scores, perm, kernel_ms) triple — the shard applies the
        permutation to its first-stage TopDocs before fetch."""
        from ..ops import rerank as rerank_ops

        tag, out, t0 = pend
        if tag == "skip":
            for j in jobs:
                if not j.event.is_set():
                    j.result = ("skip", None, None, 0.0)
                    j.finish()
            return
        scores, perm = rerank_ops.unpack_rescore(out)
        kernel_ms = (time.perf_counter() - t0) * 1000.0
        for ji, j in enumerate(jobs):
            if j.event.is_set():
                continue
            w = len(j.plan.first)
            j.result = ("ok", scores[ji][:w], perm[ji][:w], kernel_ms)
            j.finish()

    def _dispatch_knn_group(self, jobs: List[_Job],
                            rows: Optional[int] = None,
                            record: bool = True) -> List[Tuple]:
        """Launches the batched brute-force kNN matmul per segment
        (BASELINE config 4); results stay on device until collect.
        `rows` pads the query-row dimension to one ladder bucket.

        A FILTERED group (every job carries a `KeywordFilter`; the group
        key keeps them apart from bare jobs) takes one of two paths a
        segment, chosen from what the launch can read of its own input
        (`_dispatch_knn_filtered`). Where every job LEADS BY POSTINGS
        (its rarest tag is short and its other clauses can be checked
        a candidate at a time: `scoring.pack_lead_plans`) one launch of
        `scoring.knn_topk_lead` scores the lead tag's documents and
        nothing else (the `knn_lead` span, a child of `dispatch`; the
        group's `lead` tag). Any other launch gives each row a
        candidate mask of its own, built on the device by one launch of
        `scoring.knn_filter_mask` (the `filter_mask` span), and scores
        every stored row under it (`knn_topk_filtered`). Either way the
        rows each filter passed are counted on the device and come
        down with the collect's packed download. The planned path
        neither reads nor feeds the node's filter-bitset cache (a byte
        a document for every distinct filter): a common tag (df >=
        dense_row_min_df on the segment) is read from the bit row the
        segment holds for it, an eighth of a byte a document, built
        once from the postings when the field first serves such a
        search (`DevicePostings.filter_bits`); any other tag's
        postings tiles are gathered (a lead, a verified range) or
        scattered (a mask: the launch's 0.96 ms and 13 ns a posting
        slot at 10M rows). A segment whose filter cannot be planned on
        the device (the `knn.filter` fault site, a postings upload the
        HBM breaker refuses) is served per job by the unbatched
        executor at collect, and counted (`knn_filtered.fallbacks`);
        the IVF tier is not asked for filtered jobs."""
        ex = jobs[0].executor
        reader = ex.reader
        nj = len(jobs)
        rows = rows or BPAD
        staging = getattr(ex, "staging_slab", None)
        field = jobs[0].plan.field
        spec = jobs[0].plan.ann  # shared: ann rides the group key
        filtered = jobs[0].plan.filter is not None  # shared: the key's
        if filtered:
            spec = None
            if record:
                tags = _group_now().plan_tags
                tags["filtered"] = True
                tags["clauses"] = max(
                    len(j.plan.filter.clauses) for j in jobs)
        items: List[Tuple] = []
        for si, seg in enumerate(reader.segments):
            if seg.vectors.get(field) is None:
                continue
            vf = seg.vectors[field]
            n = seg.num_docs
            # IVF tier: probe-path failures (the `ann.probe` fault
            # site, HBM degrade) fall back DETERMINISTICALLY to the
            # exact brute-force launch below; segments under the
            # small-segment floor never build an index and stay exact
            idx = None
            if spec is not None and getattr(ex, "ann_index", None):
                from . import ann as ann_mod

                try:
                    if record:
                        faults.check("ann.probe", field=field, segment=si)
                    idx = ex.ann_index(si, field, spec)
                except BaseException:
                    ann_mod.note("exact_fallbacks")
                    idx = None
            dims = int(vf.vectors.shape[1])
            if staging is not None:
                q = staging("knn_q", (rows, dims), np.float32)
                valid = staging("knn_valid", (rows,), np.bool_)
                valid[:] = False  # stale rows are masked, not re-scored
            else:
                q = np.zeros((rows, dims), np.float32)
                valid = np.zeros(rows, bool)
            for ji, j in enumerate(jobs):
                q[ji] = np.asarray(j.plan.vector, np.float32)
                valid[ji] = True
            kc = min(
                max(
                    scoring.next_bucket(
                        max(min(j.plan.num_candidates, n) for j in jobs), 16
                    ),
                    16,
                ),
                max(n, 1),
            )
            live = reader.live_docs[si]
            if idx is not None:
                from ..ops import ivf

                cand = None
                if live is not None or not bool(vf.exists.all()):
                    cand = vf.exists
                    if live is not None:
                        cand = cand & np.asarray(live)
                with _group_now().launch(
                        "ann_topk_batch", flops=ivf.ann_flops(
                            nj, idx.nlist, spec.nprobe, idx.cmax, dims)):
                    s, d = ivf.ann_topk_batch(
                        idx, np.asarray(q), np.asarray(valid), cand,
                        spec.nprobe, kc, quantized=spec.quantized,
                    )
                if record:
                    from . import ann as ann_mod

                    ann_mod.note_search(spec.nprobe, idx.nlist, jobs=nj)
                    with self._lock:
                        self.stats["launches"] += 1
                        self.stats["fused_jobs"] += nj
                items.append((si, n, s, d, None))
                continue
            vectors, exists, norms = ex.device_segments[si].vectors[field]
            cand_mask = exists
            if live is not None:
                live = np.asarray(live)
                note_transfer("h2d", live.nbytes)
                cand_mask = cand_mask & live
            if filtered:
                item = self._dispatch_knn_filtered(
                    jobs, si, n, np.asarray(q), vectors, norms, cand_mask,
                    vf.similarity, kc, rows, record)
                if item is not None:
                    items.append(item)
                continue
            # host rows handed to the jitted program: the launch uploads
            # them (noted here, the program itself cannot)
            note_transfer("h2d", q.nbytes)
            note_transfer("h2d", valid.nbytes)
            with _group_now().launch(
                    "knn_topk_batch", 2, q.nbytes + valid.nbytes,
                    scoring.knn_flops(nj, n, dims)):
                s, d, _ = scoring.knn_topk_batch(
                    np.asarray(q), np.asarray(valid),
                    vectors, cand_mask, vf.similarity, kc,
                )
            if record:
                with self._lock:
                    self.stats["launches"] += 1
                    self.stats["fused_jobs"] += nj
            items.append((si, n, s, d, None))
        return items

    def _dispatch_knn_filtered(self, jobs: List[_Job], si: int, n: int,
                               q: np.ndarray, vectors, norms, cand_mask,
                               similarity: str, kc: int, rows: int,
                               record: bool) -> Tuple:
        """One segment of a filtered group (`norms`: an integer field's
        norm plane, None for a float field, whose programs compute
        their own). A launch all of whose jobs lead by postings
        (`scoring.pack_lead_plans`: read from the field's tile counts
        on this segment and its rows) is ONE launch of
        `scoring.knn_topk_lead` over the leads' documents; any other
        launch, a mixed one too, is the mask launch and then the scan
        under the masks (splitting a mixed launch is
        `yfcc10m-filtered-knn.load4`'s business, PERF.md section 7 row
        9: no cell sends one).
        -> (si, n, scores, docs, passed), the three on the device;
        (si, n, None, None, None) where the segment is left to the
        unbatched executor at collect; None where no document of the
        segment holds the filter's field (nothing passes, nothing is
        launched)."""
        ex = jobs[0].executor
        t0 = time.perf_counter_ns()
        fname = jobs[0].plan.filter.field
        pf = ex.reader.segments[si].postings.get(fname)
        if pf is None:
            return None
        try:
            if record:
                faults.check("knn.filter", field=fname, segment=si)
            dp = ex.device_segments[si].postings[fname]
            bits = dp.filter_bits  # built at the field's first such search
        except Exception:
            if record:
                with self._lock:
                    self.knn_filtered["fallbacks"] += len(jobs)
            return si, n, None, None, None
        filters = [j.plan.filter.clauses for j in jobs]
        fp = scoring.pack_lead_plans(pf, filters, rows, n, bits)
        lead = fp is not None
        if not lead:
            fp = scoring.pack_filter_plans(pf, filters, rows, bits)
        note_transfer("h2d", fp.plan.nbytes)
        note_transfer("h2d", q.nbytes)
        g = _group_now()
        # what the segment's scoring launch scores: the leads' slots, or
        # every row
        scored = fp.lead_rows if lead else len(jobs) * n
        flops = scoring.knn_flops(1, scored, int(q.shape[1]))
        if lead:
            with g.launch("knn_topk_lead", 2, fp.plan.nbytes + q.nbytes,
                          flops):
                s, d, passed = scoring.knn_topk_lead(
                    q, vectors, norms, cand_mask, dp.doc_ids, bits.plane,
                    fp.plan, similarity=similarity, k=kc,
                    blocks=scoring.rows_on_lanes(vectors))
        else:
            with g.launch("knn_filter_mask", 1, fp.plan.nbytes):
                mask, passed = scoring.knn_filter_mask(
                    dp.doc_ids, cand_mask, fp.plan, bits.plane)
        if record:
            g.plan_tags["filter_tiles"] = (
                g.plan_tags.get("filter_tiles", 0) + fp.tiles)
            tags = {"segment": si, "launches": 1, "tiles": fp.tiles,
                    "bitset_terms": fp.bit_terms,
                    "bitset_rows_held": len(bits.row_of_term)}
            if lead:
                g.plan_tags["lead"] = True
                tags["lead_rows"] = fp.lead_rows
            g.sub_spans.append((
                "knn_lead" if lead else "filter_mask", t0,
                time.perf_counter_ns(), tags))
        if not lead:
            with g.launch("knn_topk_filtered", 1, q.nbytes, flops):
                s, d = scoring.knn_topk_filtered(
                    q, vectors, mask, similarity, kc, norms)
        if record:
            with self._lock:
                self.stats["launches"] += 1
                self.stats["fused_jobs"] += len(jobs)
                kf = self.knn_filtered
                kf["searches"] += len(jobs)
                kf["rows_scanned"] += scored
                kf["filter_tiles"] += fp.tiles
                kf["filter_terms"] += fp.terms
                kf["bitset_terms"] += fp.bit_terms
                if lead:
                    kf["lead_searches"] += len(jobs)
                    kf["lead_rows"] += fp.lead_rows
                else:
                    kf["mask_launches"] += 1
                    if scoring.knn_block_select(n, kc):
                        kf["block_select_launches"] += 1
        return si, n, s, d, passed

    def _collect_knn_group(self, jobs: List[_Job], items,
                           record: bool = True):
        """Per-segment top num_candidates, then a global per-job k cut —
        the coordinator merge of DfsPhase.executeKnnVectorQuery. The
        per-segment candidate buffers never leave the device: one merge
        kernel applies the per-(job, segment) num_candidates rank cut
        and selects the global winners in a single packed download.
        Boost multiplies AFTER selection on the host (a per-job
        strictly-positive constant cannot change the order), so scores
        are float-identical to the host merge; a job carrying a zero or
        negative boost would reorder, so that group merges on host."""
        if record:
            faults.check("knn.collect", jobs=len(jobs))
        ex = jobs[0].executor
        reader = ex.reader
        nj = len(jobs)
        # items: (segment, its docs, scores, docs, rows passed); the
        # last is a filtered group's (device int32[rows], None on a bare
        # one); a filtered segment whose mask was not built holds None
        # for all three and is served per job below
        filtered = jobs[0].plan.filter is not None
        on_device = all(s is not None for _si, _n, s, _d, _p in items)
        passed_rows = 0
        per_job_cands: List[List[Tuple[float, int, int]]] = [[] for _ in jobs]
        if items and on_device and all(j.plan.boost > 0.0 for j in jobs):
            # the device buffers' row bucket; padded query rows keep
            # nc=0 (their scores are -inf anyway)
            rows = int(items[0][2].shape[0])
            nc_rows = np.zeros((rows, len(items)), np.int32)
            for ii, (_si, n, *_rest) in enumerate(items):
                for ji, j in enumerate(jobs):
                    nc_rows[ji, ii] = min(j.plan.num_candidates, n)
            k_out = max(max(j.k, 1) for j in jobs)
            ms, mseg, mdoc, counts, *passed = scoring.knn_merge_segment_topk(
                [(si, s, d) for si, _n, s, d, _p in items], nc_rows, k_out,
                passed=[p for *_rest, p in items] if filtered else None,
            )
            if filtered and record:
                with self._lock:
                    self.knn_filtered["rows_passed"] += int(
                        passed[0][:nj].sum())
            ms, mseg, mdoc = scoring.rank_order(ms, mseg, mdoc)
            for ji, j in enumerate(jobs):
                finite = np.isfinite(ms[ji])
                cap = min(j.plan.k, j.k)
                boost = j.plan.boost
                hits = [
                    Hit(
                        score=float(s) * boost,
                        segment=int(si),
                        local_doc=int(d),
                        doc_id=reader.segments[int(si)].doc_ids[int(d)],
                    )
                    for s, si, d in zip(
                        ms[ji][finite][:cap],
                        mseg[ji][finite][:cap],
                        mdoc[ji][finite][:cap],
                    )
                ]
                j.result = TopDocs(
                    total=min(int(counts[ji]), j.plan.k),
                    hits=hits,
                    max_score=hits[0].score if hits else None,
                    relation="eq",
                )
                j.finish()
            return
        for si, n, s, d, passed in items:
            if s is not None:
                s = scoring._to_host(s)
                d = scoring._to_host(d)
                if filtered:
                    passed_rows += int(scoring._to_host(passed)[:nj].sum())
            for ji, j in enumerate(jobs):
                nc = min(j.plan.num_candidates, n)
                if s is None:
                    # the unbatched executor's filter evaluation and
                    # one-row scan (`fallbacks` counted at dispatch)
                    row_s, row_d, n_pass = ex.knn_filtered_segment(
                        j.plan.field, j.plan.vector, j.plan.filter.query,
                        si, nc)
                    passed_rows += n_pass
                    if record:
                        with self._lock:
                            self.stats["launches"] += 1
                            self.knn_filtered["rows_scanned"] += n
                else:
                    row_s, row_d = s[ji][:nc], d[ji][:nc]
                finite = np.isfinite(row_s)
                boost = j.plan.boost
                for sc, doc in zip(row_s[finite], row_d[finite]):
                    per_job_cands[ji].append(
                        (float(sc) * boost, si, int(doc))
                    )
        if filtered and record:
            with self._lock:
                self.knn_filtered["rows_passed"] += passed_rows
        # global k cut; totals = number of winners (knn semantics)
        totals = np.asarray(
            [min(len(per_job_cands[ji]), j.plan.k)
             for ji, j in enumerate(jobs)],
            np.int64,
        )
        self._finish_jobs(
            jobs, per_job_cands, totals, reader,
            page_caps=[j.plan.k for j in jobs],
        )

    def _dispatch_sparse_group(self, jobs: List[_Job], kb: int,
                               rows: Optional[int] = None,
                               record: bool = True) -> List[Tuple]:
        """Launches the impact-tile kernels (ops/impact.py) for a group
        of same-(field, spec) sparse_vector jobs on every segment
        carrying the column. Per segment: the host reads each prunable
        job's theta from its query terms' FIRST tiles (where impact
        ordering puts the term maxima) of the planes it holds
        (`SparseBlockMax.host_theta`; the `sparse_theta` span, a child
        of `dispatch`), then the surviving block-max tile lists score
        in ONE device pass whose finalize triple stays ON DEVICE until
        collect: the dispatch never waits for the device. Where the
        scorer holds dense rows (the int8 column's hot terms,
        ops/impact.ImpactRows), a query's hot terms are added from them
        by one launch BEFORE the host computes theta, and only its
        other terms go through tile lists.

        `hits.total` follows Elasticsearch's rule whatever was dropped
        (exact up to `track_total_hits`, then a `gte` bound): matches
        are counted over the tiles that were scored, so a job may drop
        tiles only where the total it has to report cannot move by it:
        totals untracked (`tth_cap` 0), or one query term's postings
        on this shard, less the shard's deleted docs, already MORE than
        the cap (each posting a distinct matching doc: collect then
        reports the cap and `gte`). Exact totals (`tth_cap` None) and
        jobs with no such term score every tile and count exactly.

        The `sparse.score` fault site fires per segment — an injected
        error (like an HBM degrade or missing column) falls back
        DETERMINISTICALLY to the host dense oracle for that segment at
        collect time, exact answers included."""
        from ..ops import impact as impact_ops
        from . import sparse as sparse_mod

        t_plan = time.perf_counter_ns()
        ex = jobs[0].executor
        reader = ex.reader
        nj = len(jobs)
        rows = rows or BPAD
        plan0 = jobs[0].plan
        field = plan0.field
        spec = plan0.spec
        may_drop = []
        for j in jobs:
            cap = j.plan.tth_cap
            ok = cap is not None
            if ok and cap:
                max_df = ex.sparse_shard_max_df(field, j.plan.terms)
                ok = max_df - ex.deleted_count > cap
            may_drop.append(ok)
        group = _group_now()
        tags = group.plan_tags
        if record:
            tags["quantized"] = bool(spec.quantized)
            tags["terms"] = sum(len(j.plan.terms) for j in jobs)
        items: List[Tuple] = []
        for si, seg in enumerate(reader.segments):
            sfh = (getattr(seg, "sparse", None) or {}).get(field)
            if sfh is None or not sfh.n_tiles:
                continue
            sc = None
            try:
                if record:
                    faults.check("sparse.score", field=field, segment=si)
                sc = ex.impact_scorer(si, field, spec.quantized)
            except BaseException:
                sc = None
            if sc is None:
                if record:
                    sparse_mod.note("fallbacks", nj)
                items.append(("fallback", si, None))
                continue
            # int8 serving prunes against the DEQUANTIZED tile maxima
            # (tile_qmax): a dequantized slot can exceed the fp32 tile
            # max by up to scale/2, so the fp32 bounds alone would be
            # unsound against quantized scores
            bound = sfh.tile_qmax if spec.quantized else sfh.tile_max
            bms = []
            theta_jobs = []  # jobs a threshold can drop tiles of
            row_lists: List[np.ndarray] = []
            row_weights: List[np.ndarray] = []
            tiles_dense = 0
            terms_here = 0
            for ji, j in enumerate(jobs):
                tids, tws, bws, _, counts = impact_ops.impact_tile_lists(
                    sfh, j.plan.terms, j.plan.weights, spec.quantized
                )
                # the terms that hold a dense row (the int8 column's hot
                # terms) are scored whole from it and leave the tile
                # lists; the others keep their tiles
                slots = sc.row_slots(tids)
                hot = slots >= 0
                row_lists.append(slots[hot])
                row_weights.append(tws[hot])
                tiles_dense += int(counts[hot].sum())
                terms_here += len(tids)
                bm = impact_ops.SparseBlockMax(
                    sfh.term_tile_start, sfh.term_tile_count,
                    bound, tids, tws, bws, dense=hot,
                )
                bms.append(bm)
                # block-max upper bounds assume non-negative tile
                # weights; a negative query weight keeps the job exact
                # but unpruned
                if may_drop[ji] and (tws >= 0).all() and bm.n_tail_tiles:
                    theta_jobs.append(ji)
            dense_rows = sum(len(r) for r in row_lists)
            # the scoring's FIRST program, which zeroes the planes it
            # adds into: it needs nothing of theta or `kept`, so the
            # device adds rows while the host computes both; a warm
            # launch (`record` False) compiles it whatever its dummy holds
            dense_launch = sc.rows is not None and (
                dense_rows > 0 or not record)
            if dense_launch and record:
                acc, cnt = sc.add_rows(rows, row_lists, row_weights)
            else:
                # no row to add: the planes come from the one fill
                # program. A warm launch compiles both, and waits for
                # the row launch so that its planes are gone before the
                # fill's are made (256 MB a set at the widest bucket)
                if dense_launch:
                    for plane in sc.add_rows(rows, row_lists, row_weights):
                        plane.block_until_ready()
                acc, cnt = sc.new_acc(rows)
            thetas = np.full(len(jobs), -np.inf, np.float32)
            if theta_jobs:
                t_theta = time.perf_counter_ns()
                values = sfh.qweights if spec.quantized else sfh.weights
                for ji in theta_jobs:
                    thetas[ji] = bms[ji].host_theta(
                        sfh.doc_ids, values, reader.live_docs[si], kb
                    )
                if record:
                    group.sub_spans.append((
                        "sparse_theta", t_theta, time.perf_counter_ns(),
                        {"segment": si, "launches": 0,
                         "postings": impact_ops.TILE_WIDTH * sum(
                             len(bms[ji].starts) for ji in theta_jobs)},
                    ))
            tile_lists: List[np.ndarray] = []
            weight_lists: List[np.ndarray] = []
            pruned_flags = np.zeros(len(jobs), bool)
            tiles_scored = 0
            tiles_pruned = 0
            for ji, bm in enumerate(bms):
                t, w, dropped = bm.kept(float(thetas[ji]))
                tile_lists.append(t)
                weight_lists.append(w)
                pruned_flags[ji] = dropped > 0
                tiles_scored += len(t)
                tiles_pruned += dropped
            if not record and not tiles_scored:
                # a warm launch whose dummy is all rows compiles the
                # tile pass too: one tile at weight 0, answer discarded
                tile_lists[0] = np.zeros(1, np.int64)
                weight_lists[0] = np.zeros(1, np.float32)
            staged = sc.stage_chunks(rows, tile_lists, weight_lists)
            if record:
                # the host's share of the segment's critical path: all
                # it does before the first chunk launch can be enqueued
                group.sub_spans.append((
                    "sparse_plan", t_plan, time.perf_counter_ns(),
                    {"segment": si, "terms": terms_here,
                     "cold_terms": terms_here - dense_rows,
                     "tiles_kept": tiles_scored},
                ))
            acc, cnt = sc.add_chunks(acc, cnt, staged)
            launches = len(staged)
            trips = impact_ops.tile_trips(tile_lists)
            pend = sc.finalize_device(acc, cnt, kb)
            if record:
                sparse_mod.note_search(
                    nj, spec.quantized, tiles_scored, tiles_pruned,
                    chunk_launches=launches, theta_host=len(theta_jobs),
                    dense_rows=dense_rows, tiles_dense=tiles_dense,
                    dense_launches=int(dense_launch), tile_trips=trips,
                )
                with self._lock:
                    self.stats["launches"] += 1
                    self.stats["sparse_jobs"] += nj
                for name, n in (("tiles_scored", tiles_scored),
                                ("tiles_pruned", tiles_pruned),
                                ("chunk_launches", launches),
                                ("trips", trips),
                                ("dense_rows", dense_rows),
                                ("tiles_dense", tiles_dense)):
                    tags[name] = tags.get(name, 0) + n
            items.append(("dev", si, (pend, pruned_flags)))
            t_plan = time.perf_counter_ns()  # the next segment's entry
        return items

    def _collect_sparse_group(self, jobs: List[_Job], kb: int, items,
                              record: bool = True):
        """Host side of the sparse group: one merge program + one packed
        download (`_group_topk`) covers every device segment; fallback
        segments (fault / degrade) run per job through the executor's generic
        per-segment top-k — which routes SparseVectorQuery to the host
        dense oracle — and join the final merge. Hits are exact either
        way, and so is the total up to the job's `tth_cap`: a job that
        dropped tiles was let to only because more than `tth_cap`
        matches were proved beforehand (`_dispatch_sparse_group`), so
        its count over the scored tiles is raised to that proof and the
        relation turns "gte", as Lucene's does once counting stops."""
        ex = jobs[0].executor
        reader = ex.reader
        per_job_cands: List[List[Tuple[float, int, int]]] = [
            [] for _ in jobs
        ]
        totals = np.zeros(len(jobs), np.int64)
        pruned_any = np.zeros(len(jobs), bool)
        dev_items = []
        for tag, si, payload in items:
            if tag != "dev":
                continue
            pend, pruned_flags = payload
            dev_items.append((si, pend))
            pruned_any |= pruned_flags
        if dev_items:
            ms, mseg, mdoc, mtot = self._group_topk(dev_items, kb, record)
            for ji in range(len(jobs)):
                finite = np.isfinite(ms[ji])
                for s, si, d in zip(
                    ms[ji][finite], mseg[ji][finite], mdoc[ji][finite]
                ):
                    per_job_cands[ji].append((float(s), int(si), int(d)))
                totals[ji] += int(mtot[ji].sum())
        for tag, si, _payload in items:
            if tag != "fallback":
                continue
            for ji, j in enumerate(jobs):
                s1, d1, t1 = ex.segment_topk(j.query, si, kb)
                if record:
                    with self._lock:
                        self.stats["launches"] += 1
                self._collect(
                    [j], [per_job_cands[ji]], totals[ji : ji + 1],
                    si, s1[None, :], d1[None, :], np.array([t1]),
                )
        for ji, j in enumerate(jobs):
            cands = per_job_cands[ji]
            cands.sort(key=lambda c: (-c[0], c[1], c[2]))
            page = cands[: j.k]
            hits = [
                Hit(
                    score=s,
                    segment=si,
                    local_doc=d,
                    doc_id=reader.segments[si].doc_ids[d],
                )
                for s, si, d in page
            ]
            total = int(totals[ji])
            relation = "eq"
            if pruned_any[ji]:
                if record:
                    with self._lock:
                        self.stats["pruned_jobs"] += 1
                if record and j.prof is not None:
                    j.prof["pruned_jobs"] = (
                        j.prof.get("pruned_jobs", 0) + 1
                    )
                # the count over the scored tiles is a lower bound; the
                # job dropped tiles on the proof of MORE than `tth_cap`
                # live matches (or tracks no total at all)
                relation = "gte"
                if j.plan.tth_cap:
                    total = max(total, j.plan.tth_cap + 1)
            j.result = TopDocs(
                total=total,
                hits=hits,
                max_score=hits[0].score if hits else None,
                relation=relation,
            )
            j.finish()

    def _finish_jobs(self, jobs, per_job_cands, totals, reader,
                     page_caps=None):
        """Exact (non-pruned) cross-segment merge: score desc,
        (segment, doc) asc. page_caps optionally bounds the candidate
        pool before the per-job k cut (knn's global num_candidates)."""
        for ji, j in enumerate(jobs):
            cands = per_job_cands[ji]
            cands.sort(key=lambda c: (-c[0], c[1], c[2]))
            if page_caps is not None:
                cands = cands[: page_caps[ji]]
            page = cands[: j.k]
            hits = [
                Hit(
                    score=s,
                    segment=si,
                    local_doc=d,
                    doc_id=reader.segments[si].doc_ids[d],
                )
                for s, si, d in page
            ]
            j.result = TopDocs(
                total=int(totals[ji]),
                hits=hits,
                max_score=hits[0].score if hits else None,
                relation="eq",
            )
            j.finish()

    @staticmethod
    def _collect(jobs, per_job_cands, totals, si, s, d, t):
        for ji in range(len(jobs)):
            srow = s[ji]
            drow = d[ji]
            finite = np.isfinite(srow)
            for sc, doc in zip(srow[finite], drow[finite]):
                per_job_cands[ji].append((float(sc), si, int(doc)))
            totals[ji] += int(t[ji])
