"""One seeded segment holding BOTH fields of the late-interaction
deployment: the `text` field at MS MARCO passage's published shapes and a
byte `rank_vectors` field beside it, one matrix of token vectors a
passage (passage i of the text owns rows `tok_offsets[i] :
tok_offsets[i + 1]` of the flat plane).

The text comes from `corpora/zipf_text.py` under `corpus.args.text`
(exactly `msmarco-passage-bm25`'s arguments but for the field's name),
loaded by name as `text_and_vectors.py` loads it. The token plane is this
file's, under `corpus.args.tokens`:

- how many vectors a passage holds follows ITS OWN word count under this
  seed: min(`max_tokens`, round(`per_word` x words) + `markers`). The
  multiset of word counts is the configuration's (`stats_seed`), so the
  plane has one size on every seed and the program's shapes do not move;
- every component is a whole number drawn i.i.d. from normal(0, `sigma`),
  rounded and clipped to +-`clip`: the law of a component of a unit
  128-d vector scaled by 127 (sigma 127 / sqrt(128)), one byte an
  element, drawn from `--seed` in chunks of `chunk_rows` rows on a few
  threads, each chunk from its own child of the seed
  (`byte_vectors_tags.py`'s way).

The program gets what its engine holds after a refresh of a byte
`rank_vectors` field: the int8 plane and its CSR offsets, wrapped by the
engine's own constructor of the byte form (imported at module level: a
program without the byte form cannot hold 8.8e9 bytes of token vectors
as float32, and fails here, at once, before anything is built). The plain
reference is handed the same bytes and offsets as plain arrays; it takes
nothing the program has made.

Device memory at 1,000,000 passages: ~69M rows x 128 B = ~8.8e9 bytes
beside the text field's ~2.03e9 (PERF.md section 4).
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

from elasticsearch_tpu.index.segment import byte_multi_vector_field
from plugins import load_plugin


def token_counts(lengths: np.ndarray, law: dict) -> np.ndarray:
    """Token vectors a passage of `lengths` words holds."""
    n = np.rint(float(law["per_word"]) * lengths).astype(np.int64)
    return np.minimum(n + int(law["markers"]), int(law["max_tokens"]))


def draw_token_bytes(rng, law: dict, shape) -> np.ndarray:
    """int8 components: normal(0, `sigma`), rounded, clipped."""
    x = rng.standard_normal(shape, dtype=np.float32)
    x *= np.float32(law["sigma"])
    np.rint(x, out=x)
    clip = float(law["clip"])
    np.clip(x, -clip, clip, out=x)
    return x.astype(np.int8)


def build(config: dict, seed: int, docs: int) -> dict:
    p = config["corpus"]["args"]
    tp, dims = p["tokens"], int(p["dims"])
    text = load_plugin("corpora", "zipf_text").build(
        {"corpus": {"args": p["text"]}}, seed, docs)
    counts = token_counts(text["reference"]["lengths"], tp)
    offsets = np.zeros(docs + 1, np.int64)
    np.cumsum(counts, out=offsets[1:])
    total, chunk = int(offsets[-1]), int(tp["chunk_rows"])
    starts = list(range(0, total, chunk))
    children = np.random.SeedSequence([int(seed), 2]).spawn(len(starts))
    rows = np.empty((total, dims), np.int8)

    def fill(i: int) -> None:
        lo = starts[i]
        hi = min(total, lo + chunk)
        rows[lo:hi] = draw_token_bytes(
            np.random.default_rng(children[i]), tp["components"],
            (hi - lo, dims))

    with ThreadPoolExecutor(max_workers=int(tp.get("threads", 8))) as pool:
        list(pool.map(fill, range(len(starts))))

    segment = text["segment"]
    segment.multi_vectors = {tp["field"]: byte_multi_vector_field(
        rows, offsets.astype(np.int32), tp["similarity"])}
    return {
        "segment": segment,
        "mappings": {"properties": {
            **text["mappings"]["properties"],
            tp["field"]: {"type": "rank_vectors", "element_type": "byte",
                          "dims": dims, "similarity": tp["similarity"]},
        }},
        "reference": {"docs": docs, "text": text["reference"],
                      "tok_field": tp["field"], "tok_rows": rows,
                      "tok_offsets": offsets},
        "body_context": {"text": text["body_context"],
                         "tokens": {"field": tp["field"], "dims": dims}},
    }
