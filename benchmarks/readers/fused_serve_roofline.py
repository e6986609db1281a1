"""Roofline share of the multi-field fused text program (`module`, the
serve family's `_fused_query_mf`): the least time its launches could
take over the time they took. Bound: bytes. The least is a stated LOWER
bound on what a launch must move through HBM, from the program's own
counters of what its plans carried (deltas over the window, averaged a
launch and applied to the launches of the traced window):

- a query row and field: the field's float32 score plane over the
  segment, written once and read once (2 x 4 x docs bytes);
- a query row: the int32 count plane, written once and read once;
- a used rare tile: 128 postings x (doc id + tf + the document's norm
  factor), 12 bytes each;
- a used dense hot row: one byte a document (a uint16 row moves two;
  the bound takes one).

The program moves more (the tile slots it does not use, the planes'
further passes, top-k): the share says how far from a pure stream of its
operands the kernel runs, and cannot pass 100%. The peak comes from
`peaks.json` by `device_kind`; an unknown kind is an error, not a
default. A program without the counters (`serve_launches`, ...) gives
nothing."""

TILE = 128


def read(obs: dict, args: dict):
    launches, seconds = obs["profile"]["modules"].get(args["module"], (0, 0.0))
    counts = obs["counts"]
    named = [counts.get(args[k]) for k in
             ("launches", "jobs", "rare_tiles", "hot_rows")]
    if not launches or not seconds or None in named or not named[0]:
        return None
    kind = obs["device"]["kind"]
    if kind not in obs["peaks"]:
        if obs["rehearsal"]:
            return None
        raise KeyError(f"no peak for device kind {kind!r} in peaks.json")
    counted, jobs, rare_tiles, hot_rows = named
    docs = obs["docs"]
    fields = len(obs["config"]["body"]["args"]["fields"])
    window_bytes = (jobs * (fields + 1) * 2 * 4 * docs
                    + rare_tiles * TILE * 12 + hot_rows * docs)
    least_s = (launches * window_bytes / counted
               / obs["peaks"][kind]["hbm_bytes_per_s"])
    return 100.0 * least_s / seconds
