"""Field mappings and document parsing.

Parity targets: org.elasticsearch.index.mapper — MapperService (mapping
merge), DocumentParser.parseDocument (JSON doc → indexable fields),
TextFieldMapper / KeywordFieldMapper / NumberFieldMapper /
BooleanFieldMapper / DateFieldMapper / DenseVectorFieldMapper
(server/src/main/java/org/elasticsearch/index/mapper/, .../mapper/vectors/).

Unlike the reference's per-field Lucene IndexableField objects, parsing
here produces columnar-friendly intermediates: term lists with positions
(text), exact terms (keyword), numeric doc values, and dense vectors —
inputs to the tiled segment builder (segment.py).
"""

from __future__ import annotations

import datetime as _dt
import math
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

from ..analysis import AnalysisRegistry

TEXT = "text"
KEYWORD = "keyword"
LONG = "long"
INTEGER = "integer"
SHORT = "short"
BYTE = "byte"
DOUBLE = "double"
FLOAT = "float"
HALF_FLOAT = "half_float"
BOOLEAN = "boolean"
DATE = "date"
DENSE_VECTOR = "dense_vector"
RANK_VECTORS = "rank_vectors"
SPARSE_VECTOR = "sparse_vector"
GEO_POINT = "geo_point"
NESTED = "nested"
PERCOLATOR = "percolator"

NUMERIC_TYPES = (LONG, INTEGER, SHORT, BYTE, DOUBLE, FLOAT, HALF_FLOAT)
_INT_TYPES = (LONG, INTEGER, SHORT, BYTE)
VECTOR_ELEMENT_TYPES = ("float", "byte")


def byte_vector_error(values) -> Optional[str]:
    """Why `values` is no vector of an `element_type: byte` field, in
    DenseVectorFieldMapper's words, or None: every element a whole
    number in [-128, 127]. The same check holds a stored vector and a
    query vector."""
    for dim, x in enumerate(values):
        if isinstance(x, bool) or not isinstance(x, (int, float)):
            return (
                "element_type [byte] vectors only support numbers but "
                f"found [{x!r}] at dim [{dim}]"
            )
        if not math.isfinite(x) or x != int(x):
            return (
                "element_type [byte] vectors only support non-decimal "
                f"values but found decimal value [{x}] at dim [{dim}]"
            )
        if not -128 <= x <= 127:
            return (
                "element_type [byte] vectors only support integers between "
                f"[-128, 127] but found [{int(x)}] at dim [{dim}]"
            )
    return None


@dataclass
class MappedField:
    name: str  # full dotted path
    type: str
    analyzer: str = "standard"
    search_analyzer: Optional[str] = None
    index: bool = True
    doc_values: bool = True
    boost: float = 1.0
    # dense_vector options
    dims: int = 0
    similarity: str = "cosine"  # cosine | dot_product | l2_norm
    # float (float32 rows) | byte (int8 rows: one byte an element on the
    # host and on the device, integers -128..127 at index and query time)
    element_type: str = "float"
    # date format (subset: epoch_millis and ISO handled)
    format: Optional[str] = None
    # keyword ignore_above
    ignore_above: Optional[int] = None
    # copy_to targets (values also indexed into these fields)
    copy_to: tuple = ()
    # sparse_vector static pruning: per term, drop the lowest-impact
    # tail keeping ceil((1 - ratio) * df) postings (0.0 = keep all)
    pruning_ratio: float = 0.0

    def is_numeric(self) -> bool:
        return self.type in NUMERIC_TYPES or self.type in (DATE, BOOLEAN)


class MappingParseError(ValueError):
    pass


class Mappings:
    """Parsed index mappings: flat dotted-path → MappedField registry, plus
    dynamic mapping of unseen fields (ES default dynamic:true semantics:
    strings → text + .keyword subfield, ints → long, floats → float,
    bools → boolean)."""

    def __init__(self, mapping_json: Optional[dict] = None, dynamic: bool = True):
        self.fields: Dict[str, MappedField] = {}
        # parent path → sub-field names declared via "fields" (multi-fields)
        self.multi_fields: Dict[str, List[str]] = {}
        self.dynamic = dynamic
        mapping_json = mapping_json or {}
        if "dynamic" in mapping_json:
            self.dynamic = mapping_json["dynamic"] not in (False, "false", "strict")
            self.strict = mapping_json["dynamic"] == "strict"
        else:
            self.strict = False
        # dynamic_templates: [{name: {match/path_match/
        # match_mapping_type, mapping}}] applied by dynamic_map
        self.dynamic_templates: List[dict] = list(
            mapping_json.get("dynamic_templates", [])
        )
        self._parse_properties(mapping_json.get("properties", {}), prefix="")

    def _parse_properties(self, props: dict, prefix: str):
        for name, cfg in props.items():
            path = f"{prefix}{name}"
            if "properties" in cfg and "type" not in cfg:
                # object field
                self._parse_properties(cfg["properties"], prefix=f"{path}.")
                continue
            ftype = cfg.get("type", "object")
            if ftype == "object":
                self._parse_properties(cfg.get("properties", {}), prefix=f"{path}.")
                continue
            if ftype == NESTED:
                # register the nested root AND its children — children
                # carry analyzers/types for the per-object evaluator but
                # are never flattened into parent columns
                self._add_field(path, ftype, cfg)
                self._parse_properties(
                    cfg.get("properties", {}), prefix=f"{path}."
                )
                continue
            self._add_field(path, ftype, cfg)
            for sub, subcfg in cfg.get("fields", {}).items():
                self._add_field(f"{path}.{sub}", subcfg.get("type", KEYWORD), subcfg)
                self.multi_fields.setdefault(path, []).append(sub)

    def _add_field(self, path: str, ftype: str, cfg: dict):
        known = (
            TEXT, KEYWORD, BOOLEAN, DATE, DENSE_VECTOR, RANK_VECTORS,
            SPARSE_VECTOR, GEO_POINT, NESTED, PERCOLATOR,
        ) + NUMERIC_TYPES
        if ftype not in known:
            raise MappingParseError(f"No handler for type [{ftype}] declared on field [{path}]")
        f = MappedField(
            name=path,
            type=ftype,
            analyzer=cfg.get("analyzer", "standard"),
            search_analyzer=cfg.get("search_analyzer"),
            index=cfg.get("index", True),
            doc_values=cfg.get("doc_values", True),
            boost=float(cfg.get("boost", 1.0)),
            dims=int(cfg.get("dims", 0)),
            similarity=cfg.get("similarity", "cosine"),
            element_type=cfg.get("element_type", "float"),
            format=cfg.get("format"),
            ignore_above=cfg.get("ignore_above"),
            copy_to=tuple(
                [cfg["copy_to"]]
                if isinstance(cfg.get("copy_to"), str)
                else cfg.get("copy_to", ())
            ),
            pruning_ratio=float(cfg.get("pruning_ratio", 0.0)),
        )
        if ftype == SPARSE_VECTOR and not (0.0 <= f.pruning_ratio < 1.0):
            raise MappingParseError(
                f"pruning_ratio on field [{path}] must be in [0, 1), "
                f"got [{f.pruning_ratio}]"
            )
        if (
            ftype in (DENSE_VECTOR, RANK_VECTORS)
            and f.element_type not in VECTOR_ELEMENT_TYPES
        ):
            raise MappingParseError(
                f"invalid element_type [{f.element_type}] on field [{path}]; "
                f"available types are {list(VECTOR_ELEMENT_TYPES)}"
            )
        if (
            ftype == RANK_VECTORS
            and f.element_type == "byte"
            and f.similarity != "dot_product"
        ):
            # the stored bytes ARE the values (no unit-normalized twin,
            # no scales): only the raw dot product reads them as they are
            raise MappingParseError(
                f"rank_vectors field [{path}] with element_type [byte] "
                f"supports similarity [dot_product] only, got "
                f"[{f.similarity}]"
            )
        if ftype == DENSE_VECTOR and f.dims <= 0:
            # ES infers dims from the first vector if unset; we allow that too
            f.dims = int(cfg.get("dims", 0))
        self.fields[path] = f

    def get(self, name: str) -> Optional[MappedField]:
        return self.fields.get(name)

    def dynamic_map(self, name: str, value: Any) -> Optional[MappedField]:
        """ES dynamic-mapping rules for an unseen field."""
        if not self.dynamic:
            if self.strict:
                raise MappingParseError(
                    f"mapping set to strict, dynamic introduction of [{name}] is not allowed"
                )
            return None
        tpl = self._match_dynamic_template(name, value)
        if tpl is not None:
            cfg = dict(tpl)
            dynamic_type = _json_type_name(value)
            ftype = cfg.pop("type", None)
            if ftype in (None, "{dynamic_type}"):
                ftype = _DYNAMIC_TYPE_MAP.get(dynamic_type, TEXT)
            self._add_field(name, ftype, cfg)
            # template "fields" blocks declare multi-fields exactly as
            # explicit mappings do (the canonical text+.keyword shape)
            for sub, subcfg in cfg.get("fields", {}).items():
                self._add_field(
                    f"{name}.{sub}", subcfg.get("type", KEYWORD), subcfg
                )
                self.multi_fields.setdefault(name, []).append(sub)
            return self.fields[name]
        if isinstance(value, bool):
            ftype = BOOLEAN
        elif isinstance(value, int):
            ftype = LONG
        elif isinstance(value, float):
            ftype = FLOAT
        elif isinstance(value, str):
            # ES maps strings to text with a .keyword multi-field
            self._add_field(name, TEXT, {})
            self._add_field(f"{name}.keyword", KEYWORD, {"ignore_above": 256})
            self.multi_fields.setdefault(name, []).append("keyword")
            return self.fields[name]
        else:
            return None
        self._add_field(name, ftype, {})
        return self.fields[name]

    def _match_dynamic_template(self, name: str, value) -> Optional[dict]:
        """First dynamic template whose match/path_match/
        match_mapping_type conditions all hold (DynamicTemplate)."""
        import fnmatch

        def fn_any(patterns, target: str) -> bool:
            # ES accepts a single pattern or an array for match/unmatch/
            # path_match
            pats = patterns if isinstance(patterns, list) else [patterns]
            return any(fnmatch.fnmatch(target, str(p)) for p in pats)

        vtype = _json_type_name(value)
        leaf = name.rsplit(".", 1)[-1]
        for entry in self.dynamic_templates:
            if not isinstance(entry, dict) or len(entry) != 1:
                continue
            tpl = next(iter(entry.values()))
            if not isinstance(tpl, dict) or "mapping" not in tpl:
                continue
            if "match" in tpl and not fn_any(tpl["match"], leaf):
                continue
            if "unmatch" in tpl and fn_any(tpl["unmatch"], leaf):
                continue
            if "path_match" in tpl and not fn_any(tpl["path_match"], name):
                continue
            if (
                "match_mapping_type" in tpl
                and tpl["match_mapping_type"] not in ("*", vtype)
            ):
                continue
            return tpl["mapping"]
        return None

    def merge(self, mapping_json: dict):
        """MapperService.merge subset: add new fields; reject type changes
        and changes to index-time parameters (analyzer, dims, similarity)
        on existing fields, as the reference does."""
        other = Mappings(mapping_json)
        for name, f in other.fields.items():
            mine = self.fields.get(name)
            if mine is not None:
                if mine.type != f.type:
                    raise MappingParseError(
                        f"mapper [{name}] cannot be changed from type "
                        f"[{mine.type}] to [{f.type}]"
                    )
                for param in ("analyzer", "dims", "similarity",
                              "element_type", "pruning_ratio"):
                    theirs = getattr(f, param)
                    if param == "dims" and not theirs:
                        # dims omitted in the incoming mapping: keep the
                        # (possibly doc-inferred) existing value — an
                        # idempotent PUT-mapping must be a no-op
                        continue
                    if getattr(mine, param) != theirs:
                        raise MappingParseError(
                            f"Mapper for [{name}] conflicts: cannot update "
                            f"parameter [{param}] from "
                            f"[{getattr(mine, param)}] to [{theirs}]"
                        )
                continue  # keep the existing (richer) field object
            self.fields[name] = f
        for parent, subs in other.multi_fields.items():
            mine_subs = self.multi_fields.setdefault(parent, [])
            for s in subs:
                if s not in mine_subs:
                    mine_subs.append(s)
        if "dynamic_templates" in mapping_json:
            # ES replaces the template list wholesale on merge
            self.dynamic_templates = list(other.dynamic_templates)

    def to_json(self) -> dict:
        out = self._to_json_props()
        if self.dynamic_templates:
            out["dynamic_templates"] = self.dynamic_templates
        return out

    def _to_json_props(self) -> dict:
        props: dict = {}
        mf_children = {
            f"{p}.{s}" for p, subs in self.multi_fields.items() for s in subs
        }
        for name, f in sorted(self.fields.items()):
            if name in mf_children:
                continue  # rendered under the parent's "fields"
            parts = name.split(".")
            node = props
            for p in parts[:-1]:
                parent = node.setdefault(p, {"properties": {}})
                node = parent.setdefault("properties", {})
            entry = self._field_json(f)
            for sub in self.multi_fields.get(name, []):
                subf = self.fields.get(f"{name}.{sub}")
                if subf is not None:
                    entry.setdefault("fields", {})[sub] = self._field_json(subf)
            node[parts[-1]] = entry
        return {"properties": props}

    @staticmethod
    def _field_json(f: "MappedField") -> dict:
        entry: dict = {"type": f.type}
        if f.type == TEXT and f.analyzer != "standard":
            entry["analyzer"] = f.analyzer
        if f.type in (DENSE_VECTOR, RANK_VECTORS):
            entry["dims"] = f.dims
            entry["similarity"] = f.similarity
            if f.element_type != "float":
                entry["element_type"] = f.element_type
        if f.type == SPARSE_VECTOR and f.pruning_ratio:
            entry["pruning_ratio"] = f.pruning_ratio
        if f.ignore_above is not None:
            entry["ignore_above"] = f.ignore_above
        if f.copy_to:
            entry["copy_to"] = list(f.copy_to)
        return entry


_DYNAMIC_TYPE_MAP = {
    "string": TEXT,
    "long": LONG,
    "double": FLOAT,
    "boolean": BOOLEAN,
}


def _json_type_name(value) -> str:
    """ES match_mapping_type vocabulary for a JSON value."""
    if isinstance(value, bool):
        return "boolean"
    if isinstance(value, int):
        return "long"
    if isinstance(value, float):
        return "double"
    if isinstance(value, str):
        return "string"
    if isinstance(value, dict):
        return "object"
    return "*"


@dataclass
class ParsedDocument:
    """Columnar-friendly parse result for one document."""

    doc_id: str  # _id
    source: dict
    # field → list of (term, position) for indexed text fields
    text_terms: Dict[str, List[Tuple[str, int]]] = field(default_factory=dict)
    # field → exact terms (keyword); list to support arrays
    keyword_terms: Dict[str, List[str]] = field(default_factory=dict)
    # field → numeric doc value(s) as float64-compatible numbers
    numeric_values: Dict[str, List[float]] = field(default_factory=dict)
    # field → vector
    vectors: Dict[str, List[float]] = field(default_factory=dict)
    # field → per-doc token-embedding matrix (rank_vectors: one row per
    # token, the late-interaction reranker's document side)
    multi_vectors: Dict[str, List[List[float]]] = field(default_factory=dict)
    # field → term→weight map (sparse_vector: SPLADE-shaped learned
    # sparse representations, input to the impact-ordered postings)
    sparse_vectors: Dict[str, Dict[str, float]] = field(default_factory=dict)
    # field → field length (token count incl. duplicates) for norms
    field_lengths: Dict[str, int] = field(default_factory=dict)


def parse_date_millis(value: Any, fmt: Optional[str] = None) -> float:
    """Date → epoch millis. Supports epoch_millis numbers and ISO-8601."""
    if isinstance(value, (int, float)) and not isinstance(value, bool):
        return float(value)
    s = str(value)
    if s.isdigit():
        return float(int(s))
    iso = s.replace("Z", "+00:00")
    try:
        dt = _dt.datetime.fromisoformat(iso)
    except ValueError as e:
        raise MappingParseError(f"failed to parse date field [{value}]") from e
    if dt.tzinfo is None:
        dt = dt.replace(tzinfo=_dt.timezone.utc)
    return dt.timestamp() * 1000.0


class DocumentParser:
    """DocumentParser.parseDocument analog: walks the source JSON, resolves
    each leaf against the mappings (dynamically mapping unseen fields), and
    emits analyzer output / doc values / vectors."""

    def __init__(self, mappings: Mappings, analysis: AnalysisRegistry):
        self.mappings = mappings
        self.analysis = analysis

    def parse(self, doc_id: str, source: dict) -> ParsedDocument:
        out = ParsedDocument(doc_id=doc_id, source=source)
        self._walk(source, "", out)
        return out

    def _walk(self, obj: Any, prefix: str, out: ParsedDocument):
        for key, value in obj.items():
            path = f"{prefix}{key}"
            if isinstance(value, dict):
                f = self.mappings.get(path)
                if f is not None:
                    if f.type == GEO_POINT:
                        self._index_values(f, path, [value], out)
                        continue
                    if f.type == SPARSE_VECTOR:
                        # term→weight maps arrive as JSON objects; the
                        # weights must be finite numbers (the reference's
                        # SparseVectorFieldMapper rejects anything else)
                        self._index_values(f, path, [value], out)
                        continue
                    if f.type == PERCOLATOR:
                        # stored queries live in _source; validate NOW so
                        # a malformed query is rejected at index time
                        # (PercolatorFieldMapper parses at index time)
                        from ..search import dsl as _dsl

                        try:
                            _dsl.parse_query(value)
                        except _dsl.QueryParseError as e:
                            raise MappingParseError(
                                f"percolator field [{path}]: {e}"
                            )
                        continue
                    if f.type == NESTED:
                        # nested objects stay whole in _source: they are
                        # NOT flattened into parent columns, which is
                        # exactly why cross-object queries can't match
                        # (the reference stores them as separate docs)
                        continue
                    # leaf/object conflict — the reference rejects this at
                    # parse time rather than silently corrupting fields
                    raise MappingParseError(
                        f"object mapping for [{path}] tried to parse field "
                        f"as object, but found a concrete value"
                        if f.type != DENSE_VECTOR
                        else f"dense_vector field [{path}] must be an array of numbers"
                    )
                self._walk(value, f"{path}.", out)
                continue
            values = value if isinstance(value, list) else [value]
            if not values:
                continue
            f = self.mappings.get(path)
            if f is not None and f.type == NESTED:
                continue  # list-of-objects form; see the dict branch
            if f is not None and f.type == GEO_POINT:
                # [lon, lat] array form is one point, not multi-values
                geo_vals = (
                    [value]
                    if isinstance(value, list)
                    and len(value) == 2
                    and all(isinstance(x, (int, float)) for x in value)
                    else values
                )
                self._index_values(f, path, geo_vals, out)
                continue
            if f is None:
                probe = values[0]
                if isinstance(probe, (int, float, str, bool)):
                    f = self.mappings.dynamic_map(path, probe)
                elif probe is None:
                    continue
                else:
                    continue
            if f is None:
                continue
            self._index_with_multifields(f, path, values, out)
            # copy_to: values also index into the target fields (one
            # level — the reference rejects copy_to chains), including
            # the targets' own multi-fields (e.g. a dynamic .keyword)
            for target in f.copy_to:
                tf = self.mappings.get(target)
                if tf is None:
                    tf = self.mappings.dynamic_map(target, values[0])
                if tf is not None:
                    self._index_with_multifields(tf, target, values, out)

    def _index_with_multifields(
        self, f: MappedField, path: str, values: List[Any], out: ParsedDocument
    ):
        self._index_values(f, path, values, out)
        # multi-fields explicitly declared via "fields" (or dynamic
        # .keyword) — never object children that merely share a prefix
        for sub in self.mappings.multi_fields.get(path, ()):
            sub_field = self.mappings.get(f"{path}.{sub}")
            if sub_field is not None:
                self._index_values(sub_field, f"{path}.{sub}", values, out)

    def _index_values(self, f: MappedField, path: str, values: List[Any], out: ParsedDocument):
        if f.type == TEXT:
            if not f.index:
                return
            analyzer = self.analysis.get(f.analyzer)
            terms = out.text_terms.setdefault(path, [])
            pos = (max(p for _, p in terms) + 101) if terms else 0
            length = out.field_lengths.get(path, 0)
            for v in values:
                if v is None:
                    continue
                toks = analyzer.analyze(str(v))
                for t in toks:
                    terms.append((t.text, pos + t.position))
                if toks:
                    pos += toks[-1].position + 101  # ES position_increment_gap=100
                length += len(toks)
            out.field_lengths[path] = length
        elif f.type == KEYWORD:
            kws = out.keyword_terms.setdefault(path, [])
            for v in values:
                if v is None:
                    continue
                s = str(v) if not isinstance(v, bool) else ("true" if v else "false")
                if f.ignore_above is not None and len(s) > f.ignore_above:
                    continue
                kws.append(s)
        elif f.type in NUMERIC_TYPES:
            nums = out.numeric_values.setdefault(path, [])
            for v in values:
                if v is None:
                    continue
                try:
                    x = float(v)
                except (TypeError, ValueError) as e:
                    raise MappingParseError(
                        f"failed to parse field [{path}] of type [{f.type}]"
                    ) from e
                if f.type in _INT_TYPES and not isinstance(v, bool):
                    x = float(int(x))
                if math.isnan(x) or math.isinf(x):
                    raise MappingParseError(f"illegal value for field [{path}]: {v}")
                nums.append(x)
        elif f.type == BOOLEAN:
            nums = out.numeric_values.setdefault(path, [])
            for v in values:
                if v is None:
                    continue
                if isinstance(v, bool):
                    nums.append(1.0 if v else 0.0)
                elif v in ("true", "false", ""):
                    nums.append(1.0 if v == "true" else 0.0)
                else:
                    raise MappingParseError(
                        f"Failed to parse value [{v}] as only [true] or [false] are allowed."
                    )
        elif f.type == DATE:
            nums = out.numeric_values.setdefault(path, [])
            for v in values:
                if v is None:
                    continue
                nums.append(parse_date_millis(v, f.format))
        elif f.type == GEO_POINT:
            lats = out.numeric_values.setdefault(f"{path}.lat", [])
            lons = out.numeric_values.setdefault(f"{path}.lon", [])
            for v in values:
                if v is None:
                    continue
                if isinstance(v, dict):
                    lat, lon = v.get("lat"), v.get("lon")
                elif isinstance(v, str):
                    parts = [p.strip() for p in v.split(",")]
                    if len(parts) != 2:
                        raise MappingParseError(
                            f"failed to parse geo_point [{path}]: [{v}]"
                        )
                    lat, lon = parts[0], parts[1]
                elif isinstance(v, (list, tuple)) and len(v) == 2:
                    lon, lat = v[0], v[1]  # GeoJSON order
                else:
                    raise MappingParseError(
                        f"failed to parse geo_point [{path}]: [{v}]"
                    )
                try:
                    lat_f, lon_f = float(lat), float(lon)
                except (TypeError, ValueError) as e:
                    raise MappingParseError(
                        f"failed to parse geo_point [{path}]"
                    ) from e
                if not (-90 <= lat_f <= 90) or not (-180 <= lon_f <= 180):
                    raise MappingParseError(
                        f"geo_point [{path}] out of bounds: "
                        f"{lat_f},{lon_f}"
                    )
                lats.append(lat_f)
                lons.append(lon_f)
        elif f.type == NESTED:
            pass  # nested objects live in _source only (see _walk)
        elif f.type == PERCOLATOR:
            # a non-dict value reached here (dicts are intercepted in
            # _walk): the reference rejects such docs at index time
            raise MappingParseError(
                f"percolator field [{path}] must hold a query object"
            )
        elif f.type == SPARSE_VECTOR:
            weights: Dict[str, float] = dict(out.sparse_vectors.get(path, {}))
            for v in values:
                if v is None:
                    continue
                if not isinstance(v, dict):
                    raise MappingParseError(
                        f"sparse_vector field [{path}] must hold a "
                        "term→weight object"
                    )
                for term, w in v.items():
                    if isinstance(w, bool) or not isinstance(w, (int, float)):
                        raise MappingParseError(
                            f"sparse_vector field [{path}] weight for term "
                            f"[{term}] must be a number, got [{w!r}]"
                        )
                    wf = float(w)
                    if math.isnan(wf) or math.isinf(wf):
                        raise MappingParseError(
                            f"sparse_vector field [{path}] weight for term "
                            f"[{term}] must be finite, got [{w}]"
                        )
                    if wf <= 0.0:
                        # non-positive weights can never contribute to a
                        # max-score top-k; drop them like the reference
                        # drops zero-weight features
                        continue
                    weights[str(term)] = wf
            if weights:
                out.sparse_vectors[path] = weights
        elif f.type == DENSE_VECTOR:
            if f.element_type == "byte":
                why = byte_vector_error(values)
                if why is not None:
                    raise MappingParseError(f"[{path}]: {why}")
            vec = [float(x) for x in values]
            if f.dims and len(vec) != f.dims:
                raise MappingParseError(
                    f"The [{path}] field has dims [{f.dims}] but the indexed "
                    f"vector has [{len(vec)}] dimensions"
                )
            if not f.dims:
                f.dims = len(vec)
            out.vectors[path] = vec
        elif f.type == RANK_VECTORS:
            # one matrix per doc: [[...], ...] (a flat vector is accepted
            # as a one-token matrix). Rows all share the mapped dims —
            # the padded per-segment column needs a rectangular gather.
            rows = values
            if rows and all(
                isinstance(x, (int, float)) and not isinstance(x, bool)
                for x in rows
            ):
                rows = [rows]
            mat: List[List[float]] = []
            for row in rows:
                if row is None:
                    continue
                if not isinstance(row, (list, tuple)):
                    raise MappingParseError(
                        f"rank_vectors field [{path}] must hold an array "
                        "of vectors"
                    )
                if f.element_type == "byte":
                    why = byte_vector_error(row)
                    if why is not None:
                        raise MappingParseError(f"[{path}]: {why}")
                vec = [float(x) for x in row]
                if f.dims and len(vec) != f.dims:
                    raise MappingParseError(
                        f"The [{path}] field has dims [{f.dims}] but an "
                        f"indexed vector has [{len(vec)}] dimensions"
                    )
                if not f.dims:
                    f.dims = len(vec)
                mat.append(vec)
            if mat:
                out.multi_vectors[path] = mat
