"""Roofline share of the late-interaction rescore's device work: the
least time its launches could take over the time they took. Bound: bytes
(the contraction is 4.5e9 operations a window of 1,000, 23 us at the
chip's bf16 peak; the rows it reads are ~8.8e6 B, 11 us at the HBM peak,
and every gather so far takes far longer than either).

The least is a stated LOWER bound on what ANY implementation of the
MaxSim rescore must read through HBM for one launch, so that the share
means the same whichever kernel gathers (`least_bytes`):

- every token row the window's candidates own, once, at the field's
  stored width (`dims` x one byte an element for a byte field): the sum
  runs over all of them, so none can stay unread;
- each candidate's CSR bounds (two int32): where its rows start and how
  many there are.

Both are the request's and the corpus's numbers, not the kernel's: pads,
the rectangular gather's repeated rows, the query matrix (16 KB), the
products and the sort are left out, so the bound stays one and the share
stays under 100 however the gather is built. The program counts the
token rows and the candidates a launch (`rescore.tokens_scored`,
`rescore.windows_docs`, `rescore.launches`); the reader averages them
over the window's counted launches and applies them to the launches of
`module` in the traced window, over `peaks.json`'s `hbm_bytes_per_s`,
over the summed device time of those launches. An unknown `device_kind`
is an error, not a default. A program without the counters or the
program gives nothing."""


def least_bytes(tokens: float, candidates: float, dims: int,
                element_bytes: int = 1) -> float:
    """Bytes a MaxSim rescore must read: its candidates' own token rows
    and their CSR bounds."""
    return tokens * dims * element_bytes + 8.0 * candidates


def read(obs: dict, args: dict):
    launches, seconds = obs["profile"]["modules"].get(args["module"], (0, 0.0))
    counts = obs["counts"]
    tokens, cands = counts.get(args["tokens"]), counts.get(args["candidates"])
    counted = counts.get(args["launches"])
    if (not launches or not seconds or tokens is None or cands is None
            or not counted):
        return None
    kind = obs["device"]["kind"]
    if kind not in obs["peaks"]:
        if obs["rehearsal"]:
            return None
        raise KeyError(f"no peak for device kind {kind!r} in peaks.json")
    dims = int(obs["config"]["corpus"]["args"]["dims"])
    least = least_bytes(tokens / counted, cands / counted, dims)
    least_s = launches * least / obs["peaks"][kind]["hbm_bytes_per_s"]
    return 100.0 * least_s / seconds
