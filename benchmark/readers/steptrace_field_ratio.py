"""100 * one field of the program's step records over another, both summed
over the steps that ended inside the measured window (the program's step
ring). A program whose records lack either field, or whose `den` sums to
nothing, gives None."""
import steprings


def read(obs, trace, args):
    steps = steprings.steps(obs, args["subsystem"])
    den = sum(getattr(s, args["den"], 0) for s in steps)
    if not den:
        return None
    return 100.0 * sum(getattr(s, args["num"], 0) for s in steps) / den
