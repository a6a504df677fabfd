"""`moe_gmm_roofline` for a configuration whose `intermediate_size` is not
the routed experts' width (K-EXAONE: `intermediate_size` is the dense
layer's 18432, an expert's is `moe_intermediate_size`): the SAME counters,
`roofline_moe.gmm` and `roofline.share`, with the configuration keys of the
hidden and the expert widths named in `args` (`hidden_key`,
`expert_width_key`)."""
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
    flops, nbytes = roofline_moe.gmm(assignments, active,
                                     cfg[args["hidden_key"]],
                                     cfg[args["expert_width_key"]])
    return roofline.share(flops, nbytes, kernel_s, obs["device_kind"])
