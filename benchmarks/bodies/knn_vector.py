"""kNN bodies: fresh seeded unit query vectors with six decimals, the
digits a client's JSON encoder gives a float32 (~7.7 KB a body). A whole
chunk is encoded in one vectorized pass, each component a fixed-width
token (`-0.012346` or ` 0.012346`: JSON allows the blank), because a
Python-level encoder costs 0.4 ms a body and set-up pays it every run.
The bytes are the request: the reference parses them as the server does.
"""

from __future__ import annotations

import json

import numpy as np


def make(context: dict, args: dict, rng: np.random.Generator, n: int) -> list:
    dims, dec = int(context["dims"]), int(args["decimals"])
    q = rng.standard_normal((n, dims), dtype=np.float32)
    q /= np.linalg.norm(q, axis=1, keepdims=True)
    micro = np.rint(q.astype(np.float64) * 10**dec).astype(np.int32)
    np.clip(micro, -(10**dec - 1), 10**dec - 1, out=micro)  # |x| < 1
    width = dec + 4  # sign, "0", ".", digits, ","
    tok = np.empty((n, dims, width), np.uint8)
    tok[:, :, 0] = np.where(micro < 0, ord("-"), ord(" "))
    tok[:, :, 1], tok[:, :, 2], tok[:, :, -1] = ord("0"), ord("."), ord(",")
    mag = np.abs(micro)
    for d in range(dec):
        mag, digit = np.divmod(mag, 10)
        tok[:, :, 2 + dec - d] = digit + ord("0")
    tok[:, -1, -1] = ord("]")
    head = ('{"knn":{"field":%s,"k":%d,"num_candidates":%d,"query_vector":['
            % (json.dumps(context["field"]), args["k"],
               args["num_candidates"])).encode()
    tail = ('},"size":%d,"_source":false}' % args["size"]).encode()
    flat = tok.reshape(n, dims * width)
    return [head + row.tobytes() + tail for row in flat]
