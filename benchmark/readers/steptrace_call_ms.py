"""Milliseconds between two stamps of the program's call records
(`t_launch0`, `t_launch1`: the host enters and leaves a call's dispatch;
`t_wait0`, `t_wait1`: it enters and leaves the blocking read of its output),
over the calls read inside the window: `stat` "mean", or "p<q>" for a
percentile."""
import callring
import estimators


def read(obs, trace, args):
    calls = callring.calls(obs, args["subsystem"])
    if not calls:
        return None
    spans = [1e3 * (getattr(c, args["to"]) - getattr(c, args["from"]))
             for c in calls]
    if args["stat"] == "mean":
        return sum(spans) / len(spans)
    return estimators.percentile(spans, float(args["stat"][1:]))
