"""`moe_gmm_roofline` for plain experts in a latent space (LatentMoE): the
SAME counters and `roofline.share`, with `roofline_latent_moe.gmm`'s count
and the configuration keys of the latent and the expert widths named in
`args` (`latent_key`, `expert_width_key`)."""
import roofline
import roofline_latent_moe
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
    flops, nbytes = roofline_latent_moe.gmm(
        assignments, active, cfg[args["latent_key"]],
        cfg[args["expert_width_key"]])
    return roofline.share(flops, nbytes, kernel_s, obs["device_kind"])
