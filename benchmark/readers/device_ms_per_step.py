"""Device busy milliseconds per traced step."""


def read(obs, trace, args):
    if trace is None or not obs.get("traced_spans"):
        return None
    return 1e3 * trace["busy_s"] / len(obs["traced_spans"])
