"""The gated delta rule's decode update's share of its roofline, beside
`ssm_update_roofline.py`: the least time the chip could take to read and
write the state its decode tokens touched (the program's step ring,
`ssm_state_bytes`, cut to the traced seconds; `roofline_gdn.update` counts
its seven operations an element), over the kernel's time in the traced
window. A program whose step records lack the field, that moved no state, or
that has no such kernel (a parent without the family) gives None."""
import roofline
import roofline_gdn
import steprings
import xplane


def read(obs, trace, args):
    t0, t1 = obs["traced"]
    if trace is None or t0 is None:
        return None
    kernel_s = xplane.matching(trace["ops"], args["match"])
    moved = sum(getattr(s, "ssm_state_bytes", 0)
                for s in steprings.steps(obs, args["subsystem"])
                if t0 < s.t_end <= t1)
    if not kernel_s or not moved:
        return None
    return roofline.share(*roofline_gdn.update(moved), kernel_s,
                          obs["device_kind"])
