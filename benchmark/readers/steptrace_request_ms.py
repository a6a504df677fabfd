"""A percentile of the milliseconds between two stamps of the program's
request records (`t_submit`, `t_admit`, `t_first_token`, `t_finish`), over
the requests whose later stamp lies inside the window."""
import estimators
import steprings


def read(obs, trace, args):
    reqs = steprings.requests(obs, args["subsystem"], args["to"])
    spans = [1e3 * (getattr(r, args["to"]) - getattr(r, args["from"]))
             for r in reqs if getattr(r, args["from"]) is not None]
    return estimators.percentile(spans, args["percentile"])
