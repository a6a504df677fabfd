"""The share of the window lost to STALLED device calls, from the program's
call ring. A call's service time runs from the later of its launch's end and
the read of the call before it (the device takes calls in order) to the
return of its own blocking read. Calls are compared within their kind
(`program`, `chunks`, `win`: the same program doing the same amount of
work): one is stalled when its service time passes the kind's median by more
than `mads` median absolute deviations and by more than `floor` of the
median; its excess over the median counts. 100 x the excesses summed, over
the window's length: 0 on a run no stall hit."""
import collections
import statistics

import callring
import steprings


def read(obs, trace, args):
    ring = callring.ring(args["subsystem"])
    if ring is None:
        return None
    since, until = steprings.window(obs)
    kinds = collections.defaultdict(list)
    before = None
    for c in ring.calls(None, until):
        if c.t_wait1 > since:
            start = c.t_launch1 if before is None \
                else max(c.t_launch1, before)
            kinds[c.program, c.chunks, c.win].append(c.t_wait1 - start)
        before = c.t_wait1
    if not kinds:
        return None
    lost = 0.0
    for times in kinds.values():
        median = statistics.median(times)
        spread = statistics.median(abs(t - median) for t in times)
        limit = median + max(args["mads"] * spread, args["floor"] * median)
        lost += sum(t - median for t in times if t > limit)
    return 100.0 * lost / (until - since)
