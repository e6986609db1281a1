"""Roofline share of the fuzzy expansion's device work: the least time
its launches could take over the time they took.

The least is a stated LOWER bound on what ANY exact expansion of a word
must do, so that the share means the same whichever kernel serves: a
word of m code points that takes k edits can only be within k of a term
of m - k .. m + k code points, so

- bytes (`least_bytes`): those terms' code points (one byte each where
  the dictionary's fit a byte) and one byte of length a term, read once;
- operations (`least_cells`): telling a distance <= k takes the 2k + 1
  diagonals around the main one of an m-row table a term: m x (2k + 1)
  cells, OPS_PER_CELL integer operations each (a compare, three adds,
  three minima: the recurrence's substitution, insertion and deletion;
  the transposition's compares and the boost are left out).

Both are the words' and the dictionary's numbers, not the kernel's. The
program sums them as it plans (`fuzzy.least_bytes`, `fuzzy.least_cells`,
ops/fuzzy.py `least_work`); the reader averages the counters over the
window's counted launches, applies them to the launches of `module` in
the traced window, and takes the LARGER of bytes over `peaks.json`'s
`hbm_bytes_per_s` and operations over its `int8_ops_per_s` (the only
integer rate the table has: the matrix unit's, which no vector
recurrence reaches, so the operations' bound is a loose one and the
bytes' usually binds). Expect a low share while the program walks every
term of the dictionary whatever its length, every diagonal whatever the
word's edits, and selects with a sort: it is what a later kernel is
judged against, and stays under 100 however that kernel is built. An
unknown `device_kind` is an error, not a default. A program without the
counters or the program gives nothing."""

OPS_PER_CELL = 7


def least_bytes(terms_in_reach: float, code_points_in_reach: float,
                bytes_per_code_point: int = 1) -> float:
    """Bytes an exact expansion of one word must read: the code points
    and the lengths of the terms whose length lies within its edits."""
    return code_points_in_reach * bytes_per_code_point + terms_in_reach


def least_ops(terms_in_reach: float, m: int, k: int) -> float:
    """Integer operations an exact expansion of one word must make."""
    return terms_in_reach * m * (2 * k + 1) * OPS_PER_CELL


def read(obs: dict, args: dict):
    launches, seconds = obs["profile"]["modules"].get(args["module"], (0, 0.0))
    nbytes = obs["counts"].get(args["least_bytes"])
    cells = obs["counts"].get(args["least_cells"])
    counted = obs["counts"].get(args["launches"])
    if (not launches or not seconds or nbytes is None or cells is None
            or not counted):
        return None
    kind = obs["device"]["kind"]
    if kind not in obs["peaks"]:
        if obs["rehearsal"]:
            return None
        raise KeyError(f"no peak for device kind {kind!r} in peaks.json")
    peak = obs["peaks"][kind]
    least_s = launches / counted * max(
        nbytes / peak["hbm_bytes_per_s"],
        cells * OPS_PER_CELL / peak["int8_ops_per_s"])
    return 100.0 * least_s / seconds
