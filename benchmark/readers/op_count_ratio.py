"""Executions of the operations matching one pattern over those matching
another, in the traced window (how often a kernel runs per run of the kernel
it serves: 2.0 where a forward is made again for its backward, 1.0 where
its results are held)."""
import xplane


def read(obs, trace, args):
    if trace is None:
        return None
    per = xplane.matching(trace["op_counts"], args["per"])
    if not per:
        return None
    return xplane.matching(trace["op_counts"], args["count"]) / per
