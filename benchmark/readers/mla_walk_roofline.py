"""A latent-attention walk's share of its roofline (`roofline_mla.py`): the
least time the chip could take for what the program's step ring says the
walks of `args["walk"]` did in the traced seconds — `decode`: the (slot,
block) pairs `latent_walk_blocks`; `chunk`: the chunks and the cached
positions under their frontiers, `latent_chunk_positions` — over the
kernel's time in the traced window. A program whose step records lack the
latent fields, or that ran no such walk, gives None."""
import roofline
import roofline_mla
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
            or not hasattr(steps[0], "latent_walk_blocks"):
        return None
    cfg = obs["config"]
    widths = (cfg["num_attention_heads"], cfg["kv_lora_rank"],
              cfg["qk_rope_head_dim"])
    layers = cfg["num_hidden_layers"]
    if args["walk"] == "decode":
        pairs = sum(s.latent_walk_blocks for s in steps)
        if not pairs:
            return None
        flops, nbytes = roofline_mla.decode_walk(
            pairs, layers, cfg["serving"]["kv_block_size"], *widths)
    else:
        positions = sum(s.latent_chunk_positions for s in steps)
        if not positions:
            return None
        flops, nbytes = roofline_mla.chunk_walk(
            sum(s.prefill_chunks for s in steps), positions, layers,
            cfg["serving"]["prefill_chunk"], *widths)
    return roofline.share(flops, nbytes, kernel_s, obs["device_kind"])
