"""Roofline share of the sparse family's chunk kernel (`module`,
ops/impact.py `_impact_chunk_add`): the least time its launches could
take over the time they took. Bound: bytes (a posting costs four
operations, under 1% of that time at the chip's peak). The least is a
stated LOWER bound on what those launches must move through HBM, from
the program's own counters (deltas over the window, averaged a launch
and applied to the launches of the traced window):

- a scored tile of 128 postings: the doc id (4 bytes) and the stored
  impact (1 byte of the int8 twin, 4 of float32 weights: the
  configuration's `guarantees.stored`) gathered, and a cell of each of
  the two planes (float32 score, int32 count) read and written once,
  16 bytes: `tile_bytes`.

Left out, so the bound stays one: the tiles phase A scores before the
final pass scores them again (a tile a query term, counted nowhere), the
tile slots a launch carries and does not use, and the planes' zero fill,
threshold and top-k passes, which other programs make in time of their
own. A scatter is bound by latency, not by bytes: expect a low share;
it is what a denser kernel is judged against. The peak comes from
`peaks.json` by `device_kind`; an unknown kind is an error, not a
default. A program without the counters gives nothing."""

TILE = 128
STORED_BYTES = {"int8": 1, "float32": 4}


def tile_bytes(stored: str) -> int:
    """Bytes a scored tile must move: ids and impacts in, two plane
    cells read and written a posting."""
    return TILE * (4 + STORED_BYTES[stored] + 2 * 2 * 4)


def read(obs: dict, args: dict):
    launches, seconds = obs["profile"]["modules"].get(args["module"], (0, 0.0))
    counted = obs["counts"].get(args["launches"])
    tiles = obs["counts"].get(args["tiles"])
    if not launches or not seconds or not counted or tiles is None:
        return None
    kind = obs["device"]["kind"]
    if kind not in obs["peaks"]:
        if obs["rehearsal"]:
            return None
        raise KeyError(f"no peak for device kind {kind!r} in peaks.json")
    window_bytes = tiles * tile_bytes(obs["config"]["guarantees"]["stored"])
    least_s = (launches * window_bytes / counted
               / obs["peaks"][kind]["hbm_bytes_per_s"])
    return 100.0 * least_s / seconds
