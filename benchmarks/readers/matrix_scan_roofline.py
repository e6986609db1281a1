"""Roofline share of the kernel that scans the stored matrix: the least
time its launches could take over the time they took. Least time: each
launch of the jitted program named `module` (the program's kernel name,
read from the `XLA Modules` line of the device trace) reads every stored
row once, docs x dims x bytes per element, at the HBM peak. Bound: bytes
(a launch's 2 x rows x docs x dims operations take under 1% of that time
at the bf16 peak for <= 32 rows). Kernel time: the summed durations of
those same launches. The peak comes from `peaks.json` by `device_kind`;
an unknown kind is an error, not a default."""


def read(obs: dict, args: dict):
    field = obs["config"]["corpus"]["args"]
    launches, seconds = obs["profile"]["modules"].get(args["module"], (0, 0.0))
    if not launches or not seconds:
        return None
    kind = obs["device"]["kind"]
    if kind not in obs["peaks"]:
        if obs["rehearsal"]:
            return None
        raise KeyError(f"no peak for device kind {kind!r} in peaks.json")
    least_s = (launches * obs["docs"] * int(field["dims"])
               * int(args["bytes_per_element"])
               / obs["peaks"][kind]["hbm_bytes_per_s"])
    return 100.0 * least_s / seconds
