"""Query DSL: JSON → query node tree.

Parity target: org.elasticsearch.index.query — AbstractQueryBuilder
parsing and the concrete builders (MatchQueryBuilder, BoolQueryBuilder,
TermQueryBuilder, TermsQueryBuilder, MultiMatchQueryBuilder,
RangeQueryBuilder, ExistsQueryBuilder, MatchAllQueryBuilder,
ConstantScoreQueryBuilder, MatchPhraseQueryBuilder), plus the top-level
`knn` search section (KnnSearchBuilder, server/.../search/vectors/).

The tree is executor-agnostic; both the NumPy oracle and the JAX executor
walk it producing dense (match-mask, score) pairs per segment — the
TPU-native replacement for Lucene's Weight/Scorer pull iterators.
"""

from __future__ import annotations

import math

from dataclasses import dataclass, field as dc_field
from typing import Any, Dict, List, Optional

from ..models.fuzzy import FuzzinessError, FuzzyParams, parse_fuzziness


class QueryParseError(ValueError):
    pass


@dataclass
class Query:
    boost: float = 1.0


@dataclass
class MatchAllQuery(Query):
    pass


@dataclass
class MatchNoneQuery(Query):
    pass


@dataclass
class MatchQuery(Query):
    field: str = ""
    query: str = ""
    operator: str = "or"  # or | and
    minimum_should_match: Optional[str] = None
    analyzer: Optional[str] = None
    # `fuzziness` set: every analyzed word is a FuzzyQuery (models/fuzzy.py)
    fuzziness: Optional[str] = None  # AUTO | AUTO:lo,hi | 0 | 1 | 2
    prefix_length: int = 0
    max_expansions: int = 50
    fuzzy_transpositions: bool = True

    @property
    def fuzzy(self) -> Optional[FuzzyParams]:
        if self.fuzziness is None:
            return None
        return FuzzyParams(self.fuzziness, self.prefix_length,
                           self.max_expansions, self.fuzzy_transpositions)


@dataclass
class MatchPhraseQuery(Query):
    field: str = ""
    query: str = ""
    slop: int = 0
    analyzer: Optional[str] = None


@dataclass
class TermQuery(Query):
    field: str = ""
    value: Any = None


@dataclass
class TermsQuery(Query):
    field: str = ""
    values: List[Any] = dc_field(default_factory=list)


@dataclass
class RangeQuery(Query):
    field: str = ""
    gte: Any = None
    gt: Any = None
    lte: Any = None
    lt: Any = None


@dataclass
class ExistsQuery(Query):
    field: str = ""


@dataclass
class MultiMatchQuery(Query):
    query: str = ""
    fields: List[str] = dc_field(default_factory=list)  # may carry ^boost
    type: str = "best_fields"  # best_fields | most_fields | cross_fields
    operator: str = "or"
    tie_breaker: float = 0.0


@dataclass
class BoolQuery(Query):
    must: List[Query] = dc_field(default_factory=list)
    should: List[Query] = dc_field(default_factory=list)
    filter: List[Query] = dc_field(default_factory=list)
    must_not: List[Query] = dc_field(default_factory=list)
    minimum_should_match: Optional[Any] = None


@dataclass
class ConstantScoreQuery(Query):
    filter_query: Query = None  # type: ignore[assignment]


@dataclass
class ScoreFunction:
    """One function_score entry: optional filter + weight and/or
    field_value_factor (FunctionScoreQueryBuilder.FilterFunctionBuilder)."""

    filter: Optional[Query] = None
    weight: Optional[float] = None
    field_value_factor: Optional[dict] = None  # {field, factor, modifier, missing}
    random_score: Optional[dict] = None  # {seed, field}
    script_score: Optional[dict] = None  # {"script": {...}} (ScriptScoreFunction)


@dataclass
class FunctionScoreQuery(Query):
    query: Query = None  # type: ignore[assignment]
    functions: List[ScoreFunction] = dc_field(default_factory=list)
    score_mode: str = "multiply"  # multiply | sum | avg | max | min | first
    boost_mode: str = "multiply"  # multiply | sum | replace | avg | max | min
    max_boost: Optional[float] = None
    min_score: Optional[float] = None


@dataclass
class MatchPhrasePrefixQuery(Query):
    """match_phrase_prefix: phrase whose LAST term is a prefix expanded
    against the term dictionary (MatchPhrasePrefixQueryBuilder)."""

    field: str = ""
    query: str = ""
    slop: int = 0
    max_expansions: int = 50
    analyzer: Optional[str] = None


@dataclass
class SpanTermQuery(Query):
    field: str = ""
    value: str = ""


@dataclass
class SpanNearQuery(Query):
    """span_near over span_term clauses on one field: proximity with
    slop + in_order (SpanNearQueryBuilder)."""

    clauses: List[SpanTermQuery] = dc_field(default_factory=list)
    slop: int = 0
    in_order: bool = True


@dataclass
class MoreLikeThisQuery(Query):
    """more_like_this: select interesting terms from the liked text/docs
    by tf-idf, rewrite to a should-bool (MoreLikeThisQueryBuilder)."""

    fields: List[str] = dc_field(default_factory=list)
    like: List[Any] = dc_field(default_factory=list)  # strings | {"_id": x}
    max_query_terms: int = 25
    min_term_freq: int = 2
    min_doc_freq: int = 5
    minimum_should_match: str = "30%"


@dataclass
class GeoDistanceQuery(Query):
    field: str = ""
    lat: float = 0.0
    lon: float = 0.0
    distance_m: float = 0.0


@dataclass
class GeoBoundingBoxQuery(Query):
    field: str = ""
    top: float = 0.0
    left: float = 0.0
    bottom: float = 0.0
    right: float = 0.0


@dataclass
class NestedQuery(Query):
    """nested query: the inner query must match WITHIN one nested
    object (NestedQueryBuilder). Objects are evaluated per document
    against _source — the semantics the reference gets from separate
    hidden Lucene docs."""

    path: str = ""
    query: dict = dc_field(default_factory=dict)  # raw DSL, per-object eval
    score_mode: str = "avg"
    inner_hits: Optional[dict] = None  # {name?, size?, _source?}


@dataclass
class PercolateQuery(Query):
    """percolate query: match STORED queries against provided docs
    (modules/percolator — PercolateQueryBuilder)."""

    field: str = "query"
    documents: List[dict] = dc_field(default_factory=list)


@dataclass
class ScriptScoreQuery(Query):
    """script_score query: base query matches, the script replaces the
    score (ScriptScoreQueryBuilder — the reference's brute-force kNN
    vehicle via cosineSimilarity, SURVEY.md §3.4)."""

    query: Query = None  # type: ignore[assignment]
    script: Any = None
    min_score: Optional[float] = None


@dataclass
class ScriptQuery(Query):
    """script query (filter context): the script decides matching per
    doc (ScriptQueryBuilder)."""

    script: Any = None


@dataclass
class IdsQuery(Query):
    values: List[str] = dc_field(default_factory=list)


@dataclass
class PrefixQuery(Query):
    field: str = ""
    value: str = ""
    case_insensitive: bool = False


@dataclass
class WildcardQuery(Query):
    field: str = ""
    value: str = ""
    case_insensitive: bool = False


@dataclass
class RegexpQuery(Query):
    field: str = ""
    value: str = ""
    case_insensitive: bool = False


@dataclass
class FuzzyQuery(Query):
    field: str = ""
    value: str = ""
    fuzziness: str = "AUTO"
    prefix_length: int = 0
    max_expansions: int = 50
    transpositions: bool = True

    @property
    def params(self) -> FuzzyParams:
        return FuzzyParams(self.fuzziness, self.prefix_length,
                           self.max_expansions, self.transpositions)


@dataclass
class DisMaxQuery(Query):
    queries: List[Query] = dc_field(default_factory=list)
    tie_breaker: float = 0.0


@dataclass
class BoostingQuery(Query):
    positive: Query = None  # type: ignore[assignment]
    negative: Query = None  # type: ignore[assignment]
    negative_boost: float = 0.0


@dataclass
class QueryStringQuery(Query):
    """query_string / simple_query_string lite: terms, field:term,
    quoted phrases, AND/OR/NOT (query_string) — no grouping parens."""

    query: str = ""
    default_field: Optional[str] = None
    fields: List[str] = dc_field(default_factory=list)
    default_operator: str = "or"
    simple: bool = False


@dataclass
class KnnSection:
    """Top-level `knn` search element (can also appear as a query clause)."""

    field: str
    query_vector: List[float]
    k: int = 10
    num_candidates: int = 100
    filter: Optional[Query] = None
    boost: float = 1.0
    similarity: Optional[float] = None  # min-similarity cutoff
    nprobe: Optional[int] = None  # per-request IVF probe override
    # resolved search/ann.AnnSpec (set by IndexService when the index
    # routes this section through the IVF tier; None = exact path)
    ann: Optional[object] = None


_SINGLE_KEY_ERR = "[%s] query malformed, no start_object after query name"


def parse_query(body: Any) -> Query:
    """Parses one query object ({"match": {...}} etc.)."""
    if not isinstance(body, dict) or len(body) != 1:
        if isinstance(body, dict) and len(body) == 0:
            raise QueryParseError("query malformed, empty clause found")
        raise QueryParseError(
            "[bool] malformed query, expected a single query name"
        )
    name, params = next(iter(body.items()))
    parser = _PARSERS.get(name)
    if parser is None:
        raise QueryParseError(f"unknown query [{name}]")
    node = parser(params)
    # ES rejects negative boost at parse time (AbstractQueryBuilder
    # .boost); a negative weight would also corrupt the fused kernel's
    # sign-encoded count flag
    if getattr(node, "boost", 1.0) < 0:
        raise QueryParseError(
            f"[{name}] negative [boost] is not allowed"
        )
    return node


def _field_params(params: dict, qname: str) -> tuple:
    if not isinstance(params, dict) or len(params) != 1:
        raise QueryParseError(f"[{qname}] query doesn't support multiple fields")
    fname, cfg = next(iter(params.items()))
    return fname, cfg


# MatchQueryBuilder's fields: another key is a parsing error upstream
# ("[match] query does not support [x]"). `fuzzy_rewrite`, `lenient`,
# `zero_terms_query`, `auto_generate_synonyms_phrase_query` and `_name`
# are accepted and not acted on (ROADMAP C11).
_MATCH_KEYS = frozenset((
    "query", "operator", "analyzer", "boost", "minimum_should_match",
    "fuzziness", "prefix_length", "max_expansions", "fuzzy_transpositions",
    "fuzzy_rewrite", "lenient", "zero_terms_query",
    "auto_generate_synonyms_phrase_query", "_name",
))


def _fuzzy_keys(cfg: dict, qname: str, transpositions_key: str) -> dict:
    """The four fuzzy settings of a `match` or a `fuzzy` body, checked."""
    out = {}
    if cfg.get("fuzziness") is not None:
        try:
            parse_fuzziness(cfg["fuzziness"])
        except FuzzinessError as e:
            raise QueryParseError(f"[{qname}] {e}")
        out["fuzziness"] = str(cfg["fuzziness"])
    for key in ("prefix_length", "max_expansions"):
        if key in cfg:
            try:
                out[key] = int(cfg[key])
            except (TypeError, ValueError):
                raise QueryParseError(f"[{qname}] [{key}] must be a number")
            if out[key] < 0 or (key == "max_expansions" and out[key] < 1):
                raise QueryParseError(
                    f"[{qname}] [{key}] cannot be {out[key]}")
    if transpositions_key in cfg:
        out[transpositions_key] = bool(cfg[transpositions_key])
    return out


def _parse_match(params):
    fname, cfg = _field_params(params, "match")
    if isinstance(cfg, dict):
        for key in cfg:
            if key not in _MATCH_KEYS:
                raise QueryParseError(
                    f"[match] query does not support [{key}]")
        return MatchQuery(
            field=fname,
            query=str(cfg.get("query", "")),
            operator=str(cfg.get("operator", "or")).lower(),
            minimum_should_match=cfg.get("minimum_should_match"),
            analyzer=cfg.get("analyzer"),
            boost=float(cfg.get("boost", 1.0)),
            **_fuzzy_keys(cfg, "match", "fuzzy_transpositions"),
        )
    return MatchQuery(field=fname, query=str(cfg))


def _parse_match_phrase(params):
    fname, cfg = _field_params(params, "match_phrase")
    if isinstance(cfg, dict):
        return MatchPhraseQuery(
            field=fname,
            query=str(cfg.get("query", "")),
            slop=int(cfg.get("slop", 0)),
            analyzer=cfg.get("analyzer"),
            boost=float(cfg.get("boost", 1.0)),
        )
    return MatchPhraseQuery(field=fname, query=str(cfg))


def _parse_term(params):
    fname, cfg = _field_params(params, "term")
    if isinstance(cfg, dict):
        return TermQuery(
            field=fname, value=cfg.get("value"), boost=float(cfg.get("boost", 1.0))
        )
    return TermQuery(field=fname, value=cfg)


def _parse_terms(params):
    params = dict(params)
    boost = float(params.pop("boost", 1.0))
    if len(params) != 1:
        raise QueryParseError("[terms] query requires exactly one field")
    fname, values = next(iter(params.items()))
    if not isinstance(values, list):
        raise QueryParseError("[terms] query requires an array of values")
    return TermsQuery(field=fname, values=values, boost=boost)


def _parse_range(params):
    fname, cfg = _field_params(params, "range")
    if not isinstance(cfg, dict):
        raise QueryParseError("[range] query malformed")
    known = {"gte", "gt", "lte", "lt", "boost", "format", "relation", "time_zone"}
    for k in cfg:
        if k not in known:
            raise QueryParseError(f"[range] query does not support [{k}]")
    return RangeQuery(
        field=fname,
        gte=cfg.get("gte"),
        gt=cfg.get("gt"),
        lte=cfg.get("lte"),
        lt=cfg.get("lt"),
        boost=float(cfg.get("boost", 1.0)),
    )


def _parse_exists(params):
    if "field" not in params:
        raise QueryParseError("[exists] query requires [field]")
    return ExistsQuery(field=params["field"], boost=float(params.get("boost", 1.0)))


def _parse_multi_match(params):
    if "query" not in params:
        raise QueryParseError("[multi_match] query requires [query]")
    return MultiMatchQuery(
        query=str(params["query"]),
        fields=list(params.get("fields", [])),
        type=params.get("type", "best_fields"),
        operator=str(params.get("operator", "or")).lower(),
        tie_breaker=float(params.get("tie_breaker", 0.0)),
        boost=float(params.get("boost", 1.0)),
    )


def _as_list(v):
    return v if isinstance(v, list) else [v]


def _parse_bool(params):
    return BoolQuery(
        must=[parse_query(q) for q in _as_list(params.get("must", []))],
        should=[parse_query(q) for q in _as_list(params.get("should", []))],
        filter=[parse_query(q) for q in _as_list(params.get("filter", []))],
        must_not=[parse_query(q) for q in _as_list(params.get("must_not", []))],
        minimum_should_match=params.get("minimum_should_match"),
        boost=float(params.get("boost", 1.0)),
    )


def _parse_constant_score(params):
    if "filter" not in params:
        raise QueryParseError("[constant_score] requires a filter")
    return ConstantScoreQuery(
        filter_query=parse_query(params["filter"]),
        boost=float(params.get("boost", 1.0)),
    )


def _parse_match_all(params):
    params = params or {}
    return MatchAllQuery(boost=float(params.get("boost", 1.0)))


def _parse_match_none(params):
    return MatchNoneQuery()


def _parse_knn_query(params):
    return KnnQueryWrapper(parse_knn(params))


@dataclass
class SparseVectorQuery(Query):
    """`sparse_vector` query over a learned term→weight map (ES 8.15
    SparseVectorQueryBuilder shape): score = Σ query_weight · impact
    over the terms both sides share. Served from the device-resident
    impact-ordered postings (ops/impact.py) with the dense fp32 host
    scorer as oracle."""

    field: str = ""
    query_vector: Dict[str, float] = dc_field(default_factory=dict)
    boost: float = 1.0
    # resolved search/sparse.SparseSpec (set by IndexService from the
    # index's sparse.quantization setting + body-level exact flag)
    sparse: Optional[object] = None


def parse_sparse_vector(params) -> SparseVectorQuery:
    if not isinstance(params, dict) or "field" not in params:
        raise QueryParseError("[sparse_vector] requires [field]")
    qv = params.get("query_vector")
    if not isinstance(qv, dict) or not qv:
        # missing, wrong-shaped and {}-empty maps are all the same
        # request bug; catching it at parse keeps it a 400, not a
        # shard-side 500
        raise QueryParseError(
            "[sparse_vector] requires a non-empty [query_vector] "
            "term→weight object"
        )
    terms: Dict[str, float] = {}
    for t, w in qv.items():
        if isinstance(w, bool) or not isinstance(w, (int, float)):
            raise QueryParseError(
                f"[sparse_vector] weight for term [{t}] must be a "
                f"number, got [{w!r}]"
            )
        w = float(w)
        if not math.isfinite(w):
            raise QueryParseError(
                f"[sparse_vector] weight for term [{t}] must be finite"
            )
        terms[str(t)] = w
    return SparseVectorQuery(
        field=str(params["field"]),
        query_vector=terms,
        boost=float(params.get("boost", 1.0)),
    )


@dataclass
class KnnQueryWrapper(Query):
    """`knn` used as a query clause (ES 8.12+)."""

    knn: KnnSection = None  # type: ignore[assignment]


def parse_knn(params: dict) -> KnnSection:
    if "field" not in params or "query_vector" not in params:
        raise QueryParseError("[knn] requires [field] and [query_vector]")
    try:
        k = int(params.get("k", 10))
    except (TypeError, ValueError):
        raise QueryParseError(f"[knn] failed to parse [k]: {params.get('k')!r}")
    if k < 1:
        raise QueryParseError(f"[knn] [k] must be greater than 0, got [{k}]")
    try:
        num_candidates = int(params.get("num_candidates", max(100, k)))
    except (TypeError, ValueError):
        raise QueryParseError(
            "[knn] failed to parse [num_candidates]: "
            f"{params.get('num_candidates')!r}"
        )
    if num_candidates < k:
        # request-scoped 400 (KnnSearchBuilder's "[num_candidates] cannot
        # be less than [k]"), not a server-side error downstream
        raise QueryParseError(
            f"[knn] [num_candidates] cannot be less than [k]; got "
            f"num_candidates=[{num_candidates}], k=[{k}]"
        )
    nprobe = params.get("nprobe")
    if nprobe is not None:
        try:
            nprobe = int(nprobe)
        except (TypeError, ValueError):
            raise QueryParseError(
                f"[knn] failed to parse [nprobe]: {params.get('nprobe')!r}"
            )
        if nprobe < 1:
            raise QueryParseError(
                f"[knn] [nprobe] must be greater than 0, got [{nprobe}]"
            )
    return KnnSection(
        field=params["field"],
        query_vector=[float(x) for x in params["query_vector"]],
        k=k,
        num_candidates=num_candidates,
        filter=parse_query(params["filter"]) if params.get("filter") else None,
        boost=float(params.get("boost", 1.0)),
        similarity=params.get("similarity"),
        nprobe=nprobe,
    )


def _parse_ids(params):
    values = params.get("values")
    if not isinstance(values, list):
        raise QueryParseError("[ids] query requires [values] array")
    return IdsQuery(values=[str(v) for v in values], boost=float(params.get("boost", 1.0)))


def _parse_simple_pattern(cls, qname):
    def parse(params):
        fname, cfg = _field_params(params, qname)
        if isinstance(cfg, dict):
            value = cfg.get("value", cfg.get(qname, ""))
            if qname == "wildcard" and value == "" and "wildcard" in cfg:
                value = cfg["wildcard"]
            return cls(
                field=fname,
                value=str(value),
                case_insensitive=bool(cfg.get("case_insensitive", False)),
                boost=float(cfg.get("boost", 1.0)),
            )
        return cls(field=fname, value=str(cfg))

    return parse


def _parse_fuzzy(params):
    fname, cfg = _field_params(params, "fuzzy")
    if isinstance(cfg, dict):
        return FuzzyQuery(
            field=fname,
            value=str(cfg.get("value", "")),
            boost=float(cfg.get("boost", 1.0)),
            **_fuzzy_keys(cfg, "fuzzy", "transpositions"),
        )
    return FuzzyQuery(field=fname, value=str(cfg))


def _parse_dis_max(params):
    qs = params.get("queries")
    if not isinstance(qs, list) or not qs:
        raise QueryParseError("[dis_max] query requires [queries] array")
    return DisMaxQuery(
        queries=[parse_query(q) for q in qs],
        tie_breaker=float(params.get("tie_breaker", 0.0)),
        boost=float(params.get("boost", 1.0)),
    )


def _parse_boosting(params):
    if "positive" not in params or "negative" not in params:
        raise QueryParseError("[boosting] requires [positive] and [negative]")
    return BoostingQuery(
        positive=parse_query(params["positive"]),
        negative=parse_query(params["negative"]),
        negative_boost=float(params.get("negative_boost", 0.0)),
        boost=float(params.get("boost", 1.0)),
    )


def _parse_function_score(params):
    inner = (
        parse_query(params["query"]) if "query" in params else MatchAllQuery()
    )
    functions: List[ScoreFunction] = []
    raw_fns = params.get("functions")
    if raw_fns is None:
        raw_fns = []
        # single-function shorthand at the top level
        single = {
            k: params[k]
            for k in ("weight", "field_value_factor", "random_score", "script_score")
            if k in params
        }
        if single:
            raw_fns = [single]
    for fn in raw_fns:
        if not isinstance(fn, dict):
            raise QueryParseError("[function_score] malformed function")
        known = {
            "filter", "weight", "field_value_factor", "random_score",
            "script_score",
        }
        unknown = set(fn) - known
        if unknown:
            raise QueryParseError(
                f"[function_score] unsupported function [{sorted(unknown)[0]}]"
            )
        functions.append(
            ScoreFunction(
                filter=parse_query(fn["filter"]) if "filter" in fn else None,
                weight=float(fn["weight"]) if "weight" in fn else None,
                field_value_factor=fn.get("field_value_factor"),
                random_score=fn.get("random_score"),
                script_score=fn.get("script_score"),
            )
        )
    return FunctionScoreQuery(
        query=inner,
        functions=functions,
        score_mode=str(params.get("score_mode", "multiply")),
        boost_mode=str(params.get("boost_mode", "multiply")),
        max_boost=float(params["max_boost"]) if "max_boost" in params else None,
        min_score=params.get("min_score"),
        boost=float(params.get("boost", 1.0)),
    )


def _parse_match_phrase_prefix(params):
    if not isinstance(params, dict) or len(params) != 1:
        raise QueryParseError("[match_phrase_prefix] requires one field")
    field, spec = next(iter(params.items()))
    if isinstance(spec, dict):
        return MatchPhrasePrefixQuery(
            field=field,
            query=str(spec.get("query", "")),
            slop=int(spec.get("slop", 0)),
            max_expansions=int(spec.get("max_expansions", 50)),
            analyzer=spec.get("analyzer"),
            boost=float(spec.get("boost", 1.0)),
        )
    return MatchPhrasePrefixQuery(field=field, query=str(spec))


def _parse_span_term(params):
    if not isinstance(params, dict) or len(params) != 1:
        raise QueryParseError("[span_term] requires one field")
    field, spec = next(iter(params.items()))
    if isinstance(spec, dict):
        return SpanTermQuery(
            field=field,
            value=str(spec.get("value", "")),
            boost=float(spec.get("boost", 1.0)),
        )
    return SpanTermQuery(field=field, value=str(spec))


def _parse_span_near(params):
    raw = params.get("clauses")
    if not isinstance(raw, list) or not raw:
        raise QueryParseError("[span_near] requires [clauses]")
    clauses = []
    for c in raw:
        q = parse_query(c)
        if not isinstance(q, SpanTermQuery):
            raise QueryParseError(
                "[span_near] clauses must be span_term queries (this build)"
            )
        clauses.append(q)
    if len({c.field for c in clauses}) != 1:
        raise QueryParseError("[span_near] clauses must target one field")
    return SpanNearQuery(
        clauses=clauses,
        slop=int(params.get("slop", 0)),
        in_order=bool(params.get("in_order", True)),
        boost=float(params.get("boost", 1.0)),
    )


def _parse_more_like_this(params):
    like = params.get("like")
    if like is None:
        raise QueryParseError("[more_like_this] requires [like]")
    return MoreLikeThisQuery(
        fields=list(params.get("fields", [])),
        like=like if isinstance(like, list) else [like],
        max_query_terms=int(params.get("max_query_terms", 25)),
        min_term_freq=int(params.get("min_term_freq", 2)),
        min_doc_freq=int(params.get("min_doc_freq", 5)),
        minimum_should_match=str(params.get("minimum_should_match", "30%")),
        boost=float(params.get("boost", 1.0)),
    )


def _geo_point(v):
    try:
        if isinstance(v, dict):
            return float(v["lat"]), float(v["lon"])
        if isinstance(v, str):
            parts = v.split(",")
            if len(parts) != 2:
                raise QueryParseError(f"malformed geo point [{v}]")
            return float(parts[0]), float(parts[1])
        if isinstance(v, (list, tuple)) and len(v) == 2:
            return float(v[1]), float(v[0])  # GeoJSON [lon, lat]
    except (TypeError, ValueError, KeyError):
        raise QueryParseError(f"malformed geo point [{v}]")
    raise QueryParseError(f"malformed geo point [{v}]")


_DIST_UNITS = {
    "mm": 0.001, "cm": 0.01, "m": 1.0, "km": 1000.0,
    "mi": 1609.344, "miles": 1609.344, "yd": 0.9144, "ft": 0.3048,
    "in": 0.0254, "nmi": 1852.0, "NM": 1852.0,
}


def parse_distance_meters(s) -> float:
    if isinstance(s, (int, float)):
        return float(s)
    txt = str(s).strip()
    try:
        for unit in sorted(_DIST_UNITS, key=len, reverse=True):
            if txt.endswith(unit):
                return float(txt[: -len(unit)]) * _DIST_UNITS[unit]
        return float(txt)
    except ValueError:
        raise QueryParseError(f"failed to parse distance [{s}]")


def _parse_geo_distance(params):
    dist = params.get("distance")
    if dist is None:
        raise QueryParseError("[geo_distance] requires [distance]")
    field = None
    point = None
    for k, v in params.items():
        if k in ("distance", "distance_type", "validation_method", "boost"):
            continue
        field, point = k, v
    if field is None:
        raise QueryParseError("[geo_distance] requires a field")
    lat, lon = _geo_point(point)
    return GeoDistanceQuery(
        field=field,
        lat=lat,
        lon=lon,
        distance_m=parse_distance_meters(dist),
        boost=float(params.get("boost", 1.0)),
    )


def _parse_geo_bounding_box(params):
    field = None
    spec = None
    for k, v in params.items():
        if k in ("validation_method", "type", "boost"):
            continue
        field, spec = k, v
    if field is None or not isinstance(spec, dict):
        raise QueryParseError("[geo_bounding_box] requires a field")
    tl = spec.get("top_left")
    br = spec.get("bottom_right")
    if tl is None or br is None:
        raise QueryParseError(
            "[geo_bounding_box] requires [top_left] and [bottom_right]"
        )
    top, left = _geo_point(tl)
    bottom, right = _geo_point(br)
    return GeoBoundingBoxQuery(
        field=field, top=top, left=left, bottom=bottom, right=right,
        boost=float(params.get("boost", 1.0)),
    )


def _parse_nested(params):
    if "path" not in params or "query" not in params:
        raise QueryParseError("[nested] requires [path] and [query]")
    return NestedQuery(
        path=str(params["path"]),
        query=params["query"],
        score_mode=str(params.get("score_mode", "avg")),
        inner_hits=params.get("inner_hits"),
        boost=float(params.get("boost", 1.0)),
    )


def _parse_percolate(params):
    field = params.get("field")
    if not field:
        raise QueryParseError("[percolate] requires [field]")
    docs = params.get("documents")
    if docs is None:
        doc = params.get("document")
        if doc is None:
            raise QueryParseError(
                "[percolate] requires [document] or [documents]"
            )
        docs = [doc]
    return PercolateQuery(
        field=str(field),
        documents=list(docs),
        boost=float(params.get("boost", 1.0)),
    )


def _parse_script_score(params):
    if "query" not in params or "script" not in params:
        raise QueryParseError("[script_score] requires [query] and [script]")
    return ScriptScoreQuery(
        query=parse_query(params["query"]),
        script=params["script"],
        min_score=(
            float(params["min_score"]) if "min_score" in params else None
        ),
        boost=float(params.get("boost", 1.0)),
    )


def _parse_script_query(params):
    if "script" not in params:
        raise QueryParseError("[script] requires [script]")
    return ScriptQuery(script=params["script"], boost=float(params.get("boost", 1.0)))


def _parse_query_string(params):
    if "query" not in params:
        raise QueryParseError("[query_string] requires [query]")
    return QueryStringQuery(
        query=str(params["query"]),
        default_field=params.get("default_field"),
        fields=list(params.get("fields", [])),
        default_operator=str(params.get("default_operator", "or")).lower(),
        boost=float(params.get("boost", 1.0)),
    )


def _parse_simple_query_string(params):
    q = _parse_query_string(params)
    q.simple = True
    return q


_PARSERS = {
    "match": _parse_match,
    "match_phrase": _parse_match_phrase,
    "term": _parse_term,
    "terms": _parse_terms,
    "range": _parse_range,
    "exists": _parse_exists,
    "multi_match": _parse_multi_match,
    "bool": _parse_bool,
    "constant_score": _parse_constant_score,
    "match_all": _parse_match_all,
    "match_none": _parse_match_none,
    "knn": _parse_knn_query,
    "sparse_vector": parse_sparse_vector,
    "ids": _parse_ids,
    "prefix": lambda p: _parse_simple_pattern(PrefixQuery, "prefix")(p),
    "wildcard": lambda p: _parse_simple_pattern(WildcardQuery, "wildcard")(p),
    "regexp": lambda p: _parse_simple_pattern(RegexpQuery, "regexp")(p),
    "fuzzy": _parse_fuzzy,
    "dis_max": _parse_dis_max,
    "boosting": _parse_boosting,
    "function_score": _parse_function_score,
    "match_phrase_prefix": _parse_match_phrase_prefix,
    "span_term": _parse_span_term,
    "span_near": _parse_span_near,
    "more_like_this": _parse_more_like_this,
    "geo_distance": _parse_geo_distance,
    "geo_bounding_box": _parse_geo_bounding_box,
    "nested": _parse_nested,
    "percolate": _parse_percolate,
    "script_score": _parse_script_score,
    "script": _parse_script_query,
    "query_string": _parse_query_string,
    "simple_query_string": _parse_simple_query_string,
}


def term_token(value: Any) -> str:
    """Normalizes a term-query value to its index token: JSON booleans
    index as "true"/"false" (shared by executors, the serve-plan
    extractor, and the can_match prefilter — str(True) would probe the
    nonexistent token "True")."""
    if isinstance(value, bool):
        return "true" if value else "false"
    return str(value)


def parse_minimum_should_match(msm: Any, num_clauses: int) -> int:
    """Lucene Queries.calculateMinShouldMatch subset: integers, negatives,
    and percentages (incl. negative percentages)."""
    if msm is None:
        return 0
    s = str(msm).strip()
    try:
        if s.endswith("%"):
            pct = float(s[:-1])
            if pct < 0:
                return num_clauses - int(-pct / 100.0 * num_clauses)
            return int(pct / 100.0 * num_clauses)
        v = int(s)
        if v < 0:
            return max(0, num_clauses + v)
        return min(v, num_clauses)
    except ValueError as e:
        raise QueryParseError(f"invalid minimum_should_match [{msm}]") from e


# ---------------------------------------------------------------------------
# Canonical cache keys (query & request caching, search/query_cache.py)
# ---------------------------------------------------------------------------

def canonical_key(q: Any) -> str:
    """Stable canonical serialization of a parsed query node — the
    filter-bitset cache key. Keying the PARSED tree (not the raw JSON)
    makes equivalent spellings share one bitset: {"term": {"f": "x"}}
    and {"term": {"f": {"value": "x"}}} parse identically, so they hit
    the same cache entry (the shape Lucene gets from Query.equals)."""
    from dataclasses import fields as dc_fields

    def enc(v: Any):
        if isinstance(v, Query):
            return [
                type(v).__name__,
                {f.name: enc(getattr(v, f.name)) for f in dc_fields(v)},
            ]
        if isinstance(v, (list, tuple)):
            return [enc(x) for x in v]
        if isinstance(v, dict):
            return {str(k): enc(x) for k, x in v.items()}
        if isinstance(v, (str, int, float, bool)) or v is None:
            return v
        return repr(v)

    import json

    return json.dumps(enc(q), sort_keys=True, separators=(",", ":"))


def canonical_body_key(body: dict, exclude: tuple = ("request_cache",
                                                     "preference",
                                                     "_cache_only",
                                                     "allow_degraded")) -> str:
    """Canonical request bytes for the shard request cache: the search
    body minus per-request control flags that don't change the result
    (`_cache_only` is the tier-3 brownout marker — the degraded request
    must hit the same entry the healthy one populated)."""
    import json

    return json.dumps(
        {k: v for k, v in body.items() if k not in exclude},
        sort_keys=True,
        separators=(",", ":"),
        default=repr,
    )


# Node types that never enter the filter-bitset cache (the analog of
# UsageTrackingQueryCachingPolicy's never-cache list):
#   * scripted / stateful nodes — not a pure function of the segment;
#   * match_all / match_none — trivially cheap, caching wastes slots;
#   * multi_match / query_string — field expansion reads the LIVE
#     mappings dict, which dynamic mapping can grow without a refresh
#     generation bump, so a cached bitset could go stale;
#   * knn wrappers / function_score — per-request candidate cuts and
#     score functions (random_score, scripts) aren't segment-pure;
#   * percolate / more_like_this — evaluate against other documents.
_UNCACHEABLE_FILTERS = (
    "MatchAllQuery", "MatchNoneQuery", "MultiMatchQuery",
    "QueryStringQuery", "FunctionScoreQuery", "ScriptScoreQuery",
    "ScriptQuery", "PercolateQuery", "MoreLikeThisQuery",
    "KnnQueryWrapper",
)


def is_cacheable_filter(q: Any) -> bool:
    """True when a filter-context node is a pure function of one
    segment's immutable data + the shard's searchable generation — the
    gate for the filter-bitset cache. Compounds are cacheable iff every
    child is."""
    if not isinstance(q, Query):
        return False
    if type(q).__name__ in _UNCACHEABLE_FILTERS:
        return False
    if isinstance(q, BoolQuery):
        kids = (
            list(q.must) + list(q.should) + list(q.filter) + list(q.must_not)
        )
        return bool(kids) and all(is_cacheable_filter(c) for c in kids)
    if isinstance(q, ConstantScoreQuery):
        return is_cacheable_filter(q.filter_query)
    if isinstance(q, DisMaxQuery):
        return bool(q.queries) and all(is_cacheable_filter(c) for c in q.queries)
    if isinstance(q, BoostingQuery):
        return is_cacheable_filter(q.positive) and is_cacheable_filter(
            q.negative
        )
    if isinstance(q, SpanNearQuery):
        return all(is_cacheable_filter(c) for c in q.clauses)
    return True
