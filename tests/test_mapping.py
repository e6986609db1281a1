import numpy as np
import pytest

from elasticsearch_tpu.analysis import AnalysisRegistry
from elasticsearch_tpu.index.mapping import DocumentParser, MappingParseError, Mappings


class TestMappingsMerge:
    def test_add_new_field(self):
        m = Mappings({"properties": {"a": {"type": "text"}}})
        m.merge({"properties": {"b": {"type": "long"}}})
        assert m.get("b").type == "long"

    def test_reject_type_change(self):
        m = Mappings({"properties": {"a": {"type": "text"}}})
        with pytest.raises(MappingParseError, match="cannot be changed"):
            m.merge({"properties": {"a": {"type": "long"}}})

    def test_reject_analyzer_change(self):
        m = Mappings({"properties": {"a": {"type": "text"}}})
        with pytest.raises(MappingParseError, match="analyzer"):
            m.merge({"properties": {"a": {"type": "text", "analyzer": "whitespace"}}})

    def test_reject_dims_change(self):
        m = Mappings({"properties": {"v": {"type": "dense_vector", "dims": 4}}})
        with pytest.raises(MappingParseError, match="dims"):
            m.merge({"properties": {"v": {"type": "dense_vector", "dims": 8}}})

    def test_reject_element_type_change(self):
        m = Mappings({"properties": {"v": {
            "type": "dense_vector", "dims": 4, "element_type": "byte"}}})
        with pytest.raises(MappingParseError, match="element_type"):
            m.merge({"properties": {"v": {"type": "dense_vector", "dims": 4}}})
        m.merge({"properties": {"v": {  # the same mapping again: a no-op
            "type": "dense_vector", "dims": 4, "element_type": "byte"}}})
        assert m.to_json()["properties"]["v"]["element_type"] == "byte"


class TestByteVectors:
    @pytest.mark.parametrize("values, why", [
        ([1, -128, 127, 0], None),
        ([1.0, 2.0], None),  # whole numbers, however JSON wrote them
        ([1, 128], "between [-128, 127] but found [128] at dim [1]"),
        ([-129], "between [-128, 127] but found [-129] at dim [0]"),
        ([0, 0.5], "decimal value [0.5] at dim [1]"),
        ([float("nan")], "decimal value [nan]"),
        ([True], "only support numbers"),
        (["7"], "only support numbers"),
    ], ids=["ints", "whole_floats", "high", "low", "decimal", "nan", "bool",
            "string"])
    def test_byte_vector_error(self, values, why):
        from elasticsearch_tpu.index.mapping import byte_vector_error

        got = byte_vector_error(values)
        assert (got is None) if why is None else (why in got)

    def test_byte_rows_are_stored_as_int8(self):
        from elasticsearch_tpu.index.segment import SegmentBuilder

        m = Mappings({"properties": {
            "b": {"type": "dense_vector", "dims": 3, "element_type": "byte",
                  "similarity": "l2_norm"},
            "f": {"type": "dense_vector", "dims": 3, "similarity": "l2_norm"}}})
        p = DocumentParser(m, AnalysisRegistry())
        docs = [p.parse(str(i), {"b": [i, -i, 127], "f": [i, -i, 127]})
                for i in range(4)]
        builder = SegmentBuilder(m)
        for d in docs:
            builder.add(d)
        seg = builder.build()
        assert seg.vectors["b"].vectors.dtype == np.int8
        assert seg.vectors["f"].vectors.dtype == np.float32
        assert (seg.vectors["b"].vectors == seg.vectors["f"].vectors).all()
        with pytest.raises(MappingParseError, match="non-decimal|between"):
            p.parse("x", {"b": [1, 2, 300]})


class TestLeafObjectConflicts:
    def test_object_value_on_leaf_field_rejected(self):
        m = Mappings({})
        p = DocumentParser(m, AnalysisRegistry())
        p.parse("1", {"a": "hello"})  # dynamically maps a: text
        with pytest.raises(MappingParseError, match="object"):
            p.parse("2", {"a": {"b": "world"}})

    def test_multi_field_not_leaked_to_object_children(self):
        m = Mappings(
            {
                "properties": {
                    "a": {"type": "object", "properties": {"b": {"type": "text"}}},
                }
            }
        )
        p = DocumentParser(m, AnalysisRegistry())
        d = p.parse("1", {"a": {"b": "world"}})
        assert "a.b" in d.text_terms
        assert "a" not in d.text_terms

    def test_declared_multi_fields_indexed(self):
        m = Mappings(
            {
                "properties": {
                    "name": {
                        "type": "text",
                        "fields": {"raw": {"type": "keyword"}},
                    }
                }
            }
        )
        p = DocumentParser(m, AnalysisRegistry())
        d = p.parse("1", {"name": "Alice Smith"})
        assert [t for t, _ in d.text_terms["name"]] == ["alice", "smith"]
        assert d.keyword_terms["name.raw"] == ["Alice Smith"]
