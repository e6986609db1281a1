"""One seeded segment holding BOTH fields of the hybrid deployment: the
`text` field at MS MARCO passage's published shapes and the 768-d cosine
`dense_vector` field beside it, over the same passages (passage i of the
text is row i of the vectors).

Nothing is drawn here. The text comes from `corpora/zipf_text.py` under
`corpus.args.text` (exactly `msmarco-passage-bm25`'s arguments but for the
field's name), the rows from `corpora/unit_vectors.py` under
`corpus.args.vector` plus `corpus.args.dims` (exactly `msmarco-knn768`'s;
`dims` stands one level up because the accepted roofline reader looks for
it there). Each builder is loaded by name and handed a configuration
holding its own arguments; this file only puts the two fields into one
`Segment`, one `mappings`, one `body_context` and one `reference` payload.

Device memory of the segment at 1,000,000 passages: the text field's
layout ~2.03 GB (tiles, norms, 500 dense hot-term rows; PERF.md section
4) + 1,000,000 x 768 float16 rows = 1.536 GB: ~3.6 GB of the chip's
16.9 GB. Nothing is padded to fill memory (the contract's floor is 0%).
"""

from __future__ import annotations

from plugins import load_plugin


def build(config: dict, seed: int, docs: int) -> dict:
    p = config["corpus"]["args"]
    text = load_plugin("corpora", "zipf_text").build(
        {"corpus": {"args": p["text"]}}, seed, docs)
    rows = load_plugin("corpora", "unit_vectors").build(
        {"corpus": {"args": {**p["vector"], "dims": p["dims"]}}}, seed, docs)
    segment = text["segment"]
    segment.vectors = rows["segment"].vectors
    return {
        "segment": segment,
        "mappings": {"properties": {**text["mappings"]["properties"],
                                    **rows["mappings"]["properties"]}},
        "reference": {"docs": docs, "text": text["reference"],
                      "vector": rows["reference"]},
        "body_context": {"text": text["body_context"],
                         "vector": rows["body_context"]},
    }
