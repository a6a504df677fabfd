"""Peak device memory of the fullest chip over its limit, after the window."""


def read(obs, trace, args):
    if not obs.get("memory_limit_bytes"):
        return None
    return 100.0 * obs["memory_peak_bytes"] / obs["memory_limit_bytes"]
