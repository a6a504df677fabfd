"""The grouped expert matmul's share of its roofline: the least time the
chip could take for the assignments the router made and the experts it
touched (the program's step ring, cut to the traced seconds), over the
kernel's time in the traced window."""
import roofline
import roofline_moe
import xplane
from readers import moe_counters


def read(obs, trace, args):
    t0, t1 = obs["traced"]
    if trace is None or t0 is None:
        return None
    kernel_s = xplane.matching(trace["ops"], args["match"])
    counted = moe_counters.sums(obs, args["subsystem"], t0, t1)
    if not kernel_s or counted is None:
        return None
    cfg = obs["config"]
    _calls, assignments, active, _largest = counted
    flops, nbytes = roofline_moe.gmm(assignments, active, cfg["hidden_size"],
                                     cfg["intermediate_size"])
    return roofline.share(flops, nbytes, kernel_s, obs["device_kind"])
