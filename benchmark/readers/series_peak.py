"""100 * the peak of a series sampled as each step returned, over its
capacity."""


def read(obs, trace, args):
    series = obs["series"].get(args["series"])
    if not series:
        return None
    return 100.0 * max(series) / obs[args["over"]]
