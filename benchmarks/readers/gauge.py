"""One number of `_nodes/stats`, named by its dotted path, as the node
reported it when the window closed."""


def read(obs: dict, args: dict):
    return obs["gauges"].get(args["path"])
