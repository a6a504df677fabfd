"""The paged decode walk's share of its roofline on a pool of two kinds: the
least time the chip could take to read the blocks the walks visited (the
program's step ring, a kind of layer, cut to the traced seconds:
`decode_live_blocks` a full layer, `decode_window_live_blocks` a window
layer), over the kernel's time in the traced window. A program whose step
records lack the window fields, or whose pool has one kind, gives None."""
import roofline
import roofline_walk
import steprings
import xplane


def read(obs, trace, args):
    t0, t1 = obs["traced"]
    if trace is None or t0 is None:
        return None
    kernel_s = xplane.matching(trace["ops"], args["match"])
    steps = [s for s in steprings.steps(obs, args["subsystem"])
             if t0 < s.t_end <= t1]
    if not kernel_s or not steps \
            or not hasattr(steps[0], "decode_window_live_blocks"):
        return None
    cfg = obs["config"]
    kinds = cfg["layer_types"]
    full = sum(s.decode_live_blocks for s in steps)
    window = sum(s.decode_window_live_blocks for s in steps)
    if not full + window:
        return None
    flops, nbytes = roofline_walk.paged_walk(
        [(kinds.count("full_attention"), cfg["serving"]["kv_block_size"],
          full),
         (kinds.count("sliding_attention"), cfg["window_block"], window)],
        cfg["num_attention_heads"], cfg["num_key_value_heads"],
        cfg["head_dim"])
    return roofline.share(flops, nbytes, kernel_s, obs["device_kind"])
