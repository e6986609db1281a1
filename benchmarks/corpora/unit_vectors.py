"""Seeded dense corpus: `docs` x `dims` float16 unit vectors, unclustered.

From `bench.py` `build_corpus` (PR 23 verdict: sound generator), cut to
the one field the kNN cell serves and made from `--seed`. Rows are drawn
in chunks on a few threads (NumPy's generators release the GIL), each
chunk from its own child of the seed, so the data depends on the seed
and the chunk size in the config and on nothing else.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np


def build(config: dict, seed: int, docs: int) -> dict:
    from elasticsearch_tpu.index.segment import Segment, VectorField

    p = config["corpus"]["args"]
    field, dims, chunk = p["field"], int(p["dims"]), int(p["chunk_rows"])
    starts = list(range(0, docs, chunk))
    children = np.random.SeedSequence([int(seed), 2]).spawn(len(starts))
    vecs16 = np.empty((docs, dims), np.float16)

    def fill(i: int) -> None:
        lo = starts[i]
        hi = min(docs, lo + chunk)
        x = np.random.default_rng(children[i]).standard_normal(
            (hi - lo, dims), dtype=np.float32
        )
        x /= np.linalg.norm(x, axis=1, keepdims=True)
        vecs16[lo:hi] = x

    with ThreadPoolExecutor(max_workers=int(p.get("threads", 8))) as pool:
        list(pool.map(fill, range(len(starts))))
    segment = Segment(
        num_docs=docs,
        doc_ids=[str(i) for i in range(docs)],
        sources=[None] * docs,
        postings={},
        numerics={},
        ordinals={},
        vectors={
            field: VectorField(
                vectors=vecs16,
                exists=np.ones(docs, bool),
                similarity=p["similarity"],
                unit_vectors=vecs16,
            )
        },
    )
    return {
        "segment": segment,
        "mappings": {"properties": {field: {
            "type": "dense_vector", "dims": dims,
            "similarity": p["similarity"],
        }}},
        "reference": {"field": field, "docs": docs, "vectors": vecs16},
        "body_context": {"field": field, "dims": dims},
    }
