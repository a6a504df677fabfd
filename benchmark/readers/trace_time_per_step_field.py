"""Device time in the operations whose trace name matches, over one field of
the program's step records summed over the steps that ended inside the
traced seconds, times `args["scale"]` (1e6: microseconds a unit of the
field). A program whose records lack the field, or whose field sums to
nothing there, gives None."""
import steprings
import xplane


def read(obs, trace, args):
    t0, t1 = obs["traced"]
    if trace is None or t0 is None:
        return None
    seconds = xplane.matching(trace["ops"], args["match"])
    units = sum(getattr(s, args["field"], 0)
                for s in steprings.steps(obs, args["subsystem"])
                if t0 < s.t_end <= t1)
    if not seconds or not units:
        return None
    return args["scale"] * seconds / units
