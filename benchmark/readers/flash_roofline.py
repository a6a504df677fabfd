"""The flash kernels' share of their roofline in training: the least time
for the products each call makes, by the calls the trace counted, over the
kernels' time in the traced window."""
import roofline
import xplane


def read(obs, trace, args):
    if trace is None:
        return None
    calls, kernel_s = {}, 0.0
    for kind, pattern in args["kernels"].items():
        calls[kind] = xplane.matching(trace["op_counts"], pattern)
        kernel_s += xplane.matching(trace["ops"], pattern)
    if not kernel_s:
        return None
    cfg = obs["config"]
    heads = cfg["num_attention_heads"]
    flops, nbytes = roofline.flash_causal(
        calls, obs["micro_batch_per_chip"], heads, obs["seq_len"],
        cfg["hidden_size"] // heads)
    return roofline.share(flops, nbytes, kernel_s, obs["device_kind"])
