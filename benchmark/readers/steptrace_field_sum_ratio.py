"""`steptrace_field_ratio` over SUMS of fields: 100 * (the `num` fields of the
program's step records, added up) over (the `den` fields, added up), all
summed over the steps that ended inside the measured window. A program whose
records lack the fields, or whose `den` sums to nothing, gives None."""
import steprings


def read(obs, trace, args):
    steps = steprings.steps(obs, args["subsystem"])

    def total(fields):
        return sum(getattr(s, f, 0) for s in steps for f in fields)

    den = total(args["den"])
    if not den:
        return None
    return 100.0 * total(args["num"]) / den
