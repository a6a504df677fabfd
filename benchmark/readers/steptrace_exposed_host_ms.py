"""Mean exposed host milliseconds a step: the step's wall time less the time
a device call of it was in flight (dispatch to blocking read-back), as the
program's step ring recorded it. The time the chip provably had nothing of
the step to run."""
import steprings


def read(obs, trace, args):
    steps = steprings.steps(obs, args["subsystem"])
    if not steps:
        return None
    return 1e3 * sum(s.exposed_s for s in steps) / len(steps)
