"""A sparse-attention kernel's share of its roofline (`roofline_sparse.py`):
the least time the chip could take for what the program's step ring says the
indexer did in the traced seconds — `index_scored_positions`,
`selected_positions` — over the matched kernels' time in the traced window.
A program whose step records lack those fields (a parent of PR 60), or that
ran no such kernel, gives None.

`BENCHMARK.json`'s per-layer table is full (128 of 128), so no entry names
this reader yet; the arguments its entries will take when a `benchmark` PR
folds the table (PERF.md section 7) are kept HERE, because the benchmark's
own check refuses a `layer_metrics/*.json` that no entry names:

    index_scores_roofline   {"reader": "sparse_roofline", "args": {
        "what": "index_scores", "match": "^dstpu_sparse_index_scores",
        "subsystem": "serving"}}                    unit %, layer "sparse
        attention indexer", moves serve_tokens_per_s, source device_trace
    sparse_walk_roofline    {"reader": "sparse_roofline", "args": {
        "what": "sparse_walk", "match": "^dstpu_paged_(decode|prefill)_sparse",
        "subsystem": "serving"}}                    likewise
    sparse_index_time_share  {"reader": "kernel_time_share", "args": {
        "match": "^dstpu_sparse_index_scores"}}
    sparse_select_time_share {"reader": "kernel_time_share", "args": {
        "match": "^dstpu_sparse_select"}}
    kv_pool_copy_time_share.sparse, moe_dispatch_time_share.sparse: the
        shape-matched readers of the other cells, at this pool's leaf shapes
        (`[9360, 4, 512, 128]`, `[9360, 1, 512, 128]` bfloat16) and this
        model's dispatch fusions (rows 1024 + 16 | 1024 | 16 of 2048)
"""
import roofline
import roofline_sparse
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
            or not hasattr(steps[0], "index_scored_positions"):
        return None
    cfg = obs["config"]
    layers = cfg["num_hidden_layers"]
    sa = cfg["sa_config"]
    serving = cfg["serving"]
    # cached positions under the calls' frontiers, in the whole blocks the
    # walks read: a chunk's once, a decode token's a slot
    under_chunks = sum(s.prefill_live_blocks for s in steps) \
        * serving["kv_block_size"]
    under_tokens = sum(s.decode_live_blocks for s in steps) \
        * serving["kv_block_size"]
    if args["what"] == "index_scores":
        scored = sum(s.index_scored_positions for s in steps)
        if not scored:
            return None
        flops, nbytes = roofline_sparse.index_scores(
            scored, under_chunks + under_tokens, layers,
            sa["indexer_num_heads"], sa["indexer_head_dim"])
    else:
        selected = sum(s.selected_positions for s in steps)
        if not selected:
            return None
        # a decode token's selected entries are its own (`topk` a row of a
        # slot: the cell's contexts are all past `topk`); a chunk's rows
        # cover the positions under its frontier between them
        token_rows = sum(s.decoding for s in steps) \
            * serving["decode_steps_per_sync"]
        flops, nbytes = roofline_sparse.sparse_walk(
            selected, min(token_rows * sa["topk"], under_tokens)
            + under_chunks, layers, cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"])
    return roofline.share(flops, nbytes, kernel_s, obs["device_kind"])
