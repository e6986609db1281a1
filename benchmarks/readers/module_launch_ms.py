"""Device milliseconds a launch of one jitted program: the summed
durations of the launches of `module` on the `XLA Modules` line of the
traced window over their count. No launch of it, nothing."""


def read(obs: dict, args: dict):
    launches, seconds = obs["profile"]["modules"].get(args["module"], (0, 0.0))
    if not launches or not seconds:
        return None
    return 1e3 * seconds / launches
