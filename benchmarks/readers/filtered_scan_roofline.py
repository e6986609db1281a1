"""Roofline share of the filtered kNN family's device work: the least
time its launches could take over the time they took. Bound: bytes.
The least is a stated LOWER bound on what ANY exact implementation must
read through HBM: every row a filter passes, once (`bytes_passed`: rows
x dims x the stored bytes an element), from the program's own counter
of passing rows (counted on the device; a delta over the window,
averaged a counted mask launch and applied to the scan launches of the
traced window). The time is the summed device time of the family's two
programs in the traced window: the one that builds the masks from the
postings tiles (`mask_module`) and the one that scans the stored rows
under them (`scan_module`).

Left out, so the bound stays one: the postings of the required tags, the
rows a filter turns away (today every stored row is scanned whatever
passes), the planes of scores and masks. Expect a low share while the
whole matrix is read for every request: it is what a kernel that reads
passing rows only is judged against, and it stays under 100 whichever
way that kernel is built. The peak comes from `peaks.json` by
`device_kind`; an unknown kind is an error, not a default. A program
without the counters or the programs gives nothing."""


def bytes_passed(rows: float, dims: int, bytes_per_element: int) -> float:
    """Bytes an exact filtered search must read: its passing rows."""
    return rows * dims * bytes_per_element


def read(obs: dict, args: dict):
    modules = obs["profile"]["modules"]
    scans, scan_s = modules.get(args["scan_module"], (0, 0.0))
    _masks, mask_s = modules.get(args["mask_module"], (0, 0.0))
    passed = obs["counts"].get(args["passed"])
    counted = obs["counts"].get(args["launches"])
    if not scans or not scan_s or passed is None or not counted:
        return None
    kind = obs["device"]["kind"]
    if kind not in obs["peaks"]:
        if obs["rehearsal"]:
            return None
        raise KeyError(f"no peak for device kind {kind!r} in peaks.json")
    dims = int(obs["config"]["corpus"]["args"]["dims"])
    least_s = (bytes_passed(scans * passed / counted, dims,
                            int(args["bytes_per_element"]))
               / obs["peaks"][kind]["hbm_bytes_per_s"])
    return 100.0 * least_s / (scan_s + mask_s)
