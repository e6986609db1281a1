"""Roofline share of the fuzzy family's scoring step (`module`, the fused
text program at the family's slot budgets): the least time its launches
could take over the time they took. Bound: bytes; `fused_serve_roofline`'s
model of the same program, read from the fuzzy family's own counters
(a launch here is uncounted, one field):

- a request: the float32 score plane over the segment, written once and
  read once (2 x 4 x docs bytes);
- a used rare tile: 128 postings x (doc id + tf + the document's norm
  factor), 12 bytes each;
- a used dense hot row: one byte a document.

The program moves more (the planes' further passes, the top-k): the
share cannot pass 100%. Requests the unbatched executor served
(`overflows`) scored nothing there and are left out. The counters are
averaged over the window's counted launches (`launches`) and applied to
the launches of the traced window. An unknown
`device_kind` is an error, not a default. A program without the
counters gives nothing."""

TILE = 128


def read(obs: dict, args: dict):
    launches, seconds = obs["profile"]["modules"].get(args["module"], (0, 0.0))
    counts = obs["counts"]
    named = [counts.get(args[k]) for k in
             ("requests", "tiles", "hot_rows", "overflows", "launches")]
    if not launches or not seconds or None in named or not named[4]:
        return None
    requests, tiles, hot_rows, overflows, counted = named
    served = requests - overflows
    kind = obs["device"]["kind"]
    if kind not in obs["peaks"]:
        if obs["rehearsal"]:
            return None
        raise KeyError(f"no peak for device kind {kind!r} in peaks.json")
    docs = obs["docs"]
    window_bytes = served * 2 * 4 * docs + tiles * TILE * 12 + hot_rows * docs
    least_s = (launches * window_bytes / counted
               / obs["peaks"][kind]["hbm_bytes_per_s"])
    return 100.0 * least_s / seconds
