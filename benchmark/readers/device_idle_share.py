"""1 - the union of the device's operation intervals over the traced
window, averaged over the chips."""


def read(obs, trace, args):
    if trace is None:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
