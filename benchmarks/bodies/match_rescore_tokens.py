"""Late-interaction bodies (~41 KB each): the passage cell's question (a
`match` on the text field) with a `rescore` of `window_size` over it
whose rescore query is a `rank_vectors` MaxSim of `query_vectors` query
token vectors (exactly that many: ColBERT pads its queries), each of the
field's dimensions, unit length, six decimals; `query_weight` and
`rescore_query_weight` as the configuration states them, page `size`, no
source.

The words are the ones `bodies/match_terms.py` draws (the configuration's
words histogram, the collection's unigram law), loaded by name and taken
apart; the vectors are independent unit vectors, independent of the
words (the configuration's `assumed`). A chunk's vectors are encoded in
one vectorized pass, each component a fixed-width token (`-0.012346` or
` 0.012346`: JSON allows the blank) as `bodies/knn_vector.py` does it: a
Python-level encoder would cost ~2 ms a body and set-up pays it every run.
The bytes are the request: the reference parses them as the server does.
"""

from __future__ import annotations

import json

import numpy as np

from plugins import load_plugin


def encode_matrices(q: np.ndarray, dec: int) -> list:
    """[n, rows, dims] floats -> n byte strings `[[...],[...],...]`."""
    n, rows, dims = q.shape
    micro = np.rint(q.astype(np.float64) * 10**dec).astype(np.int32)
    np.clip(micro, -(10**dec - 1), 10**dec - 1, out=micro)  # |x| < 1
    width = dec + 4  # sign, "0", ".", digits, separator
    tok = np.empty((n, rows, dims, width), np.uint8)
    tok[..., 0] = np.where(micro < 0, ord("-"), ord(" "))
    tok[..., 1], tok[..., 2], tok[..., -1] = ord("0"), ord("."), ord(",")
    mag = np.abs(micro)
    for d in range(dec):
        mag, digit = np.divmod(mag, 10)
        tok[..., 2 + dec - d] = digit + ord("0")
    tok[:, :, -1, -1] = ord("]")  # a row's last component closes it
    out = np.empty((n, rows, 2 + dims * width), np.uint8)
    out[:, :, 0] = ord("[")
    out[:, :, 1:-1] = tok.reshape(n, rows, dims * width)
    out[:, :, -1] = ord(",")
    out[:, -1, -1] = ord("]")  # the last row closes the matrix
    return [b"[" + m.tobytes() for m in out]


def make(context: dict, args: dict, rng: np.random.Generator, n: int) -> list:
    text_ctx, tok_ctx = context["text"], context["tokens"]
    texts = load_plugin("bodies", "match_terms").make(
        text_ctx, {"size": args["size"],
                   "words_histogram": args["words_histogram"]}, rng, n)
    q = rng.standard_normal(
        (n, int(args["query_vectors"]), int(tok_ctx["dims"])),
        dtype=np.float32)
    q /= np.linalg.norm(q, axis=2, keepdims=True)
    matrices = encode_matrices(q, int(args["decimals"]))
    mid = (',"size":%d,"_source":false,"rescore":{"window_size":%d,"query":'
           '{"rescore_query":{"rank_vectors":{"field":%s,"query_vectors":'
           % (args["size"], args["window_size"],
              json.dumps(tok_ctx["field"]))).encode()
    tail = ('}},"query_weight":%s,"rescore_query_weight":%s}}}'
            % (json.dumps(args["query_weight"]),
               json.dumps(args["rescore_query_weight"]))).encode()
    out = []
    for text, matrix in zip(texts, matrices):
        query = json.loads(text)["query"]
        head = ('{"query":%s' % json.dumps(query, separators=(",", ":"))
                ).encode()
        out.append(head + mid + matrix + tail)
    return out
