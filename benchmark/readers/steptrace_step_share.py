"""100 * the steps whose record holds `equals` in `field`, over the steps
that ended with a request still queued (the program's step ring)."""
import steprings


def read(obs, trace, args):
    queued = [s for s in steprings.steps(obs, args["subsystem"]) if s.queued]
    if not queued:
        return None
    return 100.0 * sum(getattr(s, args["field"]) == args["equals"]
                       for s in queued) / len(queued)
