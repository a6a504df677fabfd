"""Device time in the operations whose trace name matches, over the device's
busy time in the traced window."""
import xplane


def read(obs, trace, args):
    if trace is None or not trace["busy_s"]:
        return None
    return 100.0 * xplane.matching(trace["ops"], args["match"]) \
        / trace["busy_s"]
