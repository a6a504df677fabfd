"""Time in collective operations during which no compute operation ran on
that chip, over the traced window."""


def read(obs, trace, args):
    if trace is None:
        return None
    return 100.0 * trace["collective_exposed_s"] / trace["window_s"]
