"""The paged decode kernel's share of its roofline: the least time the chip
could take to read the live context of every decoding row, over the
kernel's time in the traced window."""
import roofline
import xplane


def read(obs, trace, args):
    if trace is None:
        return None
    kernel_s = xplane.matching(trace["ops"], args["match"])
    t0, t1 = obs["traced"]
    if not kernel_s or t0 is None:
        return None
    cfg = obs["config"]
    context = rows = 0
    steps_before = None
    for t, decode_steps, live, ctx in obs["decode_samples"]:
        ran = steps_before is not None and decode_steps > steps_before
        steps_before = decode_steps
        if ran and t0 < t <= t1:
            context += ctx
            rows += live
    heads = cfg["num_attention_heads"]
    flops, nbytes = roofline.paged_decode(
        context, rows, cfg["num_hidden_layers"], heads,
        cfg.get("num_key_value_heads", heads), cfg["hidden_size"] // heads)
    return roofline.share(flops, nbytes, kernel_s, obs["device_kind"])
