"""A paged walk's share of its roofline on a pool of two kinds that differ
in their KV heads, with keys and values of different widths
(`roofline_walk_kinds.py`): the least time the chip could take for what the
program's step ring says the walks of `args["walk"]` did in the traced
seconds — `decode`: the (slot, block) pairs a kind (`decode_live_blocks` a
full layer, `decode_window_live_blocks` a window layer); `chunk`: the (query,
position) pairs the masks kept and the blocks under the chunks' frontiers, a
kind (`prefill_kept_pairs` / `prefill_live_blocks`, `prefill_window_kept_
pairs` / `prefill_window_live_blocks`) — over the kernel's time in the
traced window. The configuration's keys are named in `args`: `pattern_key`
(a list, `window_flag` marking a window layer), `heads_key`, `key_dim_key`,
`value_dim_key`, the KV heads a kind (`full_kv_heads_key`,
`window_kv_heads_key`) and `window_block_key`. A program whose step records
lack the fields, a pool of one kind, or a window with no such walk gives
None."""
import roofline
import roofline_walk_kinds
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
            or not hasattr(steps[0], "prefill_window_kept_pairs"):
        return None
    cfg = obs["config"]
    pattern = list(cfg[args["pattern_key"]])
    window_layers = pattern.count(args["window_flag"])
    widths = (cfg[args["heads_key"]],)
    dims = (cfg[args["key_dim_key"]], cfg[args["value_dim_key"]])
    full = (len(pattern) - window_layers, cfg["serving"]["kv_block_size"],
            *widths, cfg[args["full_kv_heads_key"]], *dims)
    window = (window_layers, cfg[args["window_block_key"]], *widths,
              cfg[args["window_kv_heads_key"]], *dims)
    total = lambda field: sum(getattr(s, field) for s in steps)
    if args["walk"] == "decode":
        pairs = (total("decode_live_blocks"),
                 total("decode_window_live_blocks"))
        if not sum(pairs):
            return None
        flops, nbytes = roofline_walk_kinds.decode_walk(
            [(full, pairs[0]), (window, pairs[1])])
    else:
        kept = (total("prefill_kept_pairs"),
                total("prefill_window_kept_pairs"))
        if not sum(kept):
            return None
        flops, nbytes = roofline_walk_kinds.chunk_walk(
            [(full, kept[0], total("prefill_live_blocks")),
             (window, kept[1], total("prefill_window_live_blocks"))],
            total("prefill_chunks"), cfg["serving"]["prefill_chunk"])
    return roofline.share(flops, nbytes, kernel_s, obs["device_kind"])
