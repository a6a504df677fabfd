"""The paged decode walk's share of its roofline where the attention layers
are ONE kind and a pattern string says how many there are (a hybrid stack:
`args["letter"]` counted in the configuration's `args["pattern_key"]`): the
least time the chip could take to read the blocks the walks visited (the
program's step ring, `decode_live_blocks` a layer, cut to the traced
seconds; `roofline_walk.paged_walk` counts whole blocks), over the kernel's
time in the traced window. A program whose step records lack the field, a
configuration without the key, or a window with no walk gives None."""
import roofline
import roofline_walk
import steprings
import xplane


def read(obs, trace, args):
    t0, t1 = obs["traced"]
    if trace is None or t0 is None:
        return None
    kernel_s = xplane.matching(trace["ops"], args["match"])
    cfg = obs["config"]
    layers = cfg.get(args["pattern_key"], "").count(args["letter"])
    visited = sum(getattr(s, "decode_live_blocks", 0)
                  for s in steprings.steps(obs, args["subsystem"])
                  if t0 < s.t_end <= t1)
    if not kernel_s or not layers or not visited:
        return None
    flops, nbytes = roofline_walk.paged_walk(
        [(layers, cfg["serving"]["kv_block_size"], visited)],
        cfg["num_attention_heads"], cfg["num_key_value_heads"],
        cfg["head_dim"])
    return roofline.share(flops, nbytes, kernel_s, obs["device_kind"])
