"""100 * one counter over another, both taken by difference across the
window (`stats()` of the scheduler, and the benchmark's own token counts)."""


def read(obs, trace, args):
    den = obs["counters"].get(args["den"], 0)
    if not den:
        return None
    return 100.0 * obs["counters"][args["num"]] / den
