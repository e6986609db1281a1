"""Roofline share of the phrase family's device work: the least time its
launches could take over the time they took. Bound: bytes.

The least is a stated LOWER bound on what ANY exact implementation of
`match_phrase` must read through HBM for one phrase over one segment, so
that the share means the same whichever kernel serves (`least_bytes`):

- the document set of the phrase's RAREST word in its smaller encoding,
  min(4 B x df_min, docs / 8 B): a sorted list of ids or a bitset (the
  other words' sets could be skipped through; this one cannot);
- ONE byte for every occurrence of the phrase's words inside the
  documents that hold every word: adjacency cannot be told without them,
  and a position of a passage of <= 256 tokens cannot be stored in less.

Both are the query's and the corpus's numbers, not the kernel's. The
program sums them into `phrase.least_bytes` (the first from the term
dictionary when it plans, the second counted on the device and read at
collect); the reader averages the counter over the window's counted
launches and applies it to the launches of `module` in the traced
window, over `peaks.json`'s `hbm_bytes_per_s`, over the summed device
time of those launches.

Left out, so the bound stays one: the other words' document sets, the
norms, the planes of scores, the top-k. Expect a low share while the
kernel streams the whole positions plane for every phrase: it is what a
later kernel is judged against, and it stays under 100 however that
kernel is built. An unknown `device_kind` is an error, not a default. A
program without the counters or the program gives nothing."""


def least_bytes(df_min: float, docs: int, candidate_occurrences: float) -> float:
    """Bytes an exact phrase search must read: its rarest word's
    document set, and its words' occurrences in the candidate documents."""
    return min(4.0 * df_min, docs / 8.0) + candidate_occurrences


def read(obs: dict, args: dict):
    launches, seconds = obs["profile"]["modules"].get(args["module"], (0, 0.0))
    least = obs["counts"].get(args["least_bytes"])
    counted = obs["counts"].get(args["launches"])
    if not launches or not seconds or least is None or not counted:
        return None
    kind = obs["device"]["kind"]
    if kind not in obs["peaks"]:
        if obs["rehearsal"]:
            return None
        raise KeyError(f"no peak for device kind {kind!r} in peaks.json")
    least_s = (launches * least / counted
               / obs["peaks"][kind]["hbm_bytes_per_s"])
    return 100.0 * least_s / seconds
