"""A learned SPARSE attention layer (DeepSeek-V3.2's "DSA", the `sa_config`
of Keye-VL-2.0): grouped-query attention whose every query attends only the
`topk` positions a small second scorer, the INDEXER, ranks highest for it.

The layer, as `benchmark/references/keye_vl2.py` computes it in float32
(`u` the half's input, the normed stream; positions s <= t of one sequence):

    main heads: `gpt._decode_qkv` — q [H x hd], k, v [Hkv x hd]
    indexer:    qI_{t,j} = RoPE((u_t W_qI)_j) in R^d,  j = 1 .. Hi
                kI_s     = RoPE(LayerNorm(u_s W_kI)) in R^d   (ONE key head)
                w_{t,j}  = (u_t W_wI)_j * d^-1/2 * Hi^-1/2
                I_{t,s}  = sum_j w_{t,j} * relu(qI_{t,j} . kI_s)    (float32)
    selection:  S_t = the `topk` positions s <= t with the largest I_{t,s},
                ties to the EARLIER position; every s <= t while t < topk;
                ONE set a token a layer, shared by all heads
    attention:  o_{t,h} = sum_{s in S_t} softmax_{s in S_t}(q.k / sqrt(hd)) v

Where each piece lives: the projections, the whole-sequence half and the paged
half here (a kind of `models/exaone_moe.py::ATTN_KINDS`, as `models/mla.py`
is); the index key `ik` a THIRD LEAF of the full kind's entry in the pool,
`[L, N, 1, block, 128]` — its `d` = 64 values in half a lane tile, stored in
a whole one — written in place beside K and V by the same writer; the score
walk over a slot's cached `ik` and the exact selection in
`ops/pallas/sparse_index.py`; the attention itself the paged walks of
`ops/pallas/decode_attention.py` / `prefill_attention.py` with the selection
as one more input (`selected=`: a bias a (block, slot) for the slots' rows,
an int8 mask a (block, query tile) for a chunk's). Both walks still VISIT
every block under the frontier: with the chosen positions scattered (a
512-position block of a 28k context holds ~38 of the 2048) no block is
skipped and a gather of single entries of this `[Hkv, block, hd]` layout is
eight 256-byte descriptors a position (PERF.md section 6, PR 60).

At a context of `topk` or less the selection is every position and the layer
IS the dense one (`tests/test_keye_vl2.py` holds the paged programs to the
same configuration with the indexer off).

Off the TPU (and wherever the walks have no runner: `attention_dispatch`)
the same half gathers the row's table, scores and selects in `jax.numpy`
(`sparse_index.index_scores` / `select_topk`, the kernels' oracles) and
attends densely under the mask.
"""

import jax
import jax.numpy as jnp

from deepspeed_tpu.models.gpt import (MixedTables, _decode_attn_site,
                                      _decode_qkv, _half_input, _norm, _rope,
                                      score_scale, sm_scale)
from deepspeed_tpu.ops import attention_dispatch as attn_dispatch
from deepspeed_tpu.ops.pallas import sparse_index

INDEX_LEAF = "ik"


def index_shapes(cfg):
    """The indexer's leaves of a layer -> (shape, init scale)."""
    D, Hi, d = cfg.d_model, cfg.index_n_head, cfg.index_head_dim
    return {"idx_q_w": ((D, Hi * d), 0.02), "idx_k_w": ((D, d), 0.02),
            "idx_w_w": ((D, Hi), 0.02),
            "idx_k_norm_scale": ((d,), 1.0), "idx_k_norm_bias": ((d,), 0.0)}


def _index_proj(x, p, positions, cfg):
    """x [B, C, D], positions [B, C] -> (qI [B, C, Hi, d], kI [B, C, d], w
    [B, C, Hi] float32 with the scale d^-1/2 Hi^-1/2 folded in)."""
    B, C, _ = x.shape
    Hi, d = cfg.index_n_head, cfg.index_head_dim
    u = _half_input(x, p, cfg)
    qi = _rope((u @ p["idx_q_w"]).reshape(B, C, Hi, d), positions, d,
               cfg.rope_theta)
    ki = _norm(u @ p["idx_k_w"], p["idx_k_norm_scale"],
               p["idx_k_norm_bias"], False, cfg.norm_eps)
    ki = _rope(ki[:, :, None], positions, d, cfg.rope_theta)[:, :, 0]
    w = (u @ p["idx_w_w"]).astype(jnp.float32) * (d ** -0.5 * Hi ** -0.5)
    return qi, ki, w


def _attend_selected(q, k_ctx, v_ctx, chosen, cfg):
    """q [B, C, H, hd] over k_ctx / v_ctx [B, Hkv, S, hd] at the positions
    `chosen` [B, C, S] (bool; causal already) -> [B, C, H * hd]; float32
    softmax, as `gpt._paged_attend`."""
    B, C, H, hd = q.shape
    Hkv = k_ctx.shape[1]
    qg = q.reshape(B, C, Hkv, H // Hkv, hd)
    logits = jnp.einsum("bckgd,bksd->bkgcs", qg, k_ctx).astype(jnp.float32) \
        * score_scale(cfg, hd)
    logits = jnp.where(chosen[:, None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgcs,bksd->bckgd", probs, v_ctx)
    return out.reshape(B, C, H * v_ctx.shape[-1])


def sparse_attn_half(x, p, cfg, positions, attn_fn=None, constrain=True,
                     local_flag=None, probed=None):
    """The whole-sequence half (`gpt._attn_half`'s signature and result): x
    [B, T, D] -> (attn_out, k, v). `probed`: a list that takes (index scores
    [B, T, T] float32, the selection [B, T, T] bool)."""
    del attn_fn, constrain, local_flag
    q, k, v, _ = _decode_qkv(x, p, positions, cfg)
    with jax.named_scope("index_proj"):
        qi, ki, w = _index_proj(x, p, positions, cfg)
    with jax.named_scope("index_scores"):
        scores = sparse_index.index_scores(qi, w, ki)
    with jax.named_scope("select"):
        chosen = sparse_index.select_topk(scores, positions + 1,
                                          cfg.index_topk)
    if probed is not None:
        probed.append((scores, chosen))
    with jax.named_scope("walk"):
        attn = _attend_selected(q, jnp.swapaxes(k, 1, 2),
                                jnp.swapaxes(v, 1, 2), chosen, cfg)
    with jax.named_scope("out"):
        return attn @ p["attn_out_w"] + p["attn_out_b"], k, v


def paged_sparse_half(x, p, pool_l, positions, block_tables, cfg,
                      local_flag=None, phase=None, block_base=None,
                      decode_work=None, attn_programs=None, probe=None,
                      counted=None):
    """The attention half against one sparse layer's paged pool
    (`gpt._paged_attn_half`'s signature and result).

    pool_l: `{"k", "v": [N, Hkv, block, hd], "ik": [N, 1, block, 128]}` (in
    the in-place form the whole flat stacks, this layer's blocks from
    `block_base`). Writes the rows' K, V and index keys through the tables,
    scores each row against its sequence's cached index keys, selects, and
    attends the selected positions. A mixed call (`MixedTables`, one chunk)
    runs the chunk's rows, then the slots', between ONE set of projections
    and one output matmul.

    `probe`: None, or (a chunk row's index, traced int32 scalar; a list):
    the list takes, a group of rows, (index scores, selection) `[rows, nb *
    block]` of that row of a chunk / of every slot's row — what a check holds
    against the reference's (positions past a row's own are garbage).
    `counted`: None, or a list that takes, a group of rows the selection
    KERNEL ran for, its int32 `[3]` (`sparse_index.SELECT_COUNTERS`)."""
    del local_flag, phase
    T = x.shape[1]
    mixed = isinstance(block_tables, MixedTables)
    # a mixed call's fused product has readers on both sides of the barrier
    # below: held where it is made, or XLA computes it again for the second
    # group (`fusion.N.remat`; `gpt._paged_attn_half` has the story)
    q, k, v, _ = _decode_qkv(x, p, positions, cfg, hold=mixed)
    with jax.named_scope("index_proj"):
        qi, ki, w = _index_proj(x, p, positions, cfg)

    def group(rows, pool_l, positions, tables, site, work=None, record=None):
        return _write_attend(*(rows(a) for a in (q, k, v, qi, ki, w)),
                             pool_l, positions, tables, cfg, site, block_base,
                             work, attn_programs, record, probe, counted)

    if mixed:
        assert block_tables.chunk.shape[0] == 1     # `chunk_groups` False
        S = block_tables.decode.shape[0]
        C = T - S
        o_c, pool_l = group(lambda a: a[:, :C], pool_l, positions[:, :C],
                            block_tables.chunk, "prefill_chunk",
                            record="mixed/prefill_chunk")
        # the chunk's walks have READ the pool before the slots' rows are
        # written into it in place (`gpt._paged_attn_half` has the story)
        o_c, pool_l = jax.lax.optimization_barrier((o_c, pool_l))
        # a row a slot, [S, 1, ...] as the decode program has them
        o_d, pool_l = group(
            lambda a: jnp.swapaxes(a[:, C:], 0, 1), pool_l,
            positions[:, C:].T, block_tables.decode, "paged_decode",
            work=decode_work, record="mixed/paged_decode")
        attn = jnp.concatenate([o_c, jnp.swapaxes(o_d, 0, 1)], axis=1)
    else:
        attn, pool_l = group(lambda a: a, pool_l, positions,
                             block_tables,
                             "paged_decode" if T == 1 else "prefill_chunk",
                             work=decode_work)
    with jax.named_scope("out"):
        return attn @ p["attn_out_w"] + p["attn_out_b"], pool_l


def _write_attend(q, k, v, qi, ki, w, pool_l, positions, block_tables, cfg,
                  phase, block_base, work, attn_programs, record, probe,
                  counted):
    """Rows that share a dispatch site, as `positions` [B, C] lays them out
    (a chunk's [1, C]; the slots' [S, 1]): write, score, select, attend ->
    (attn [B, C, H * hd], pool_l)."""
    from deepspeed_tpu.inference.kv_cache import gather_block_leaf
    from deepspeed_tpu.ops.pallas.kv_pool import kv_pool_gather, kv_pool_write
    B, C = positions.shape
    bs = pool_l["k"].shape[2]
    nb = block_tables.shape[1]
    in_place = block_base is not None
    new = {"k": k, "v": v,
           INDEX_LEAF: sparse_index.pad_lanes(ki)[:, :, None]}
    with jax.named_scope("kv_pool_write"):
        pool_l = dict(pool_l)
        if in_place:
            block_tables = block_tables + block_base
            for leaf, rows in new.items():
                pool_l[leaf] = kv_pool_write(pool_l[leaf], rows,
                                             positions[:, 0], block_tables)
        else:
            blk = jnp.take_along_axis(block_tables, positions // bs, axis=1)
            for leaf, rows in new.items():
                pool_l[leaf] = pool_l[leaf].at[blk, :, positions % bs, :].set(
                    rows.astype(pool_l[leaf].dtype))

    site = _decode_attn_site(
        cfg, phase, C, nb * bs, kv_dtype=str(jnp.dtype(pool_l["k"].dtype)),
        block_size=bs, pool_in_place=in_place)
    program = attn_dispatch.select(site)
    if attn_programs is not None:
        attn_programs[record or phase] = program
    runner = attn_dispatch.get_program(program).runner
    limit = positions + 1
    kv = {"k": pool_l["k"], "v": pool_l["v"]}
    if runner is not None:
        # the kernels: scores and selection block-major, as the walks read
        decode = phase == "paged_decode"
        with jax.named_scope("index_scores"):
            if decode:
                scores = sparse_index.paged_index_scores_decode(
                    qi[:, 0], w[:, 0], pool_l[INDEX_LEAF], block_tables,
                    positions[:, 0], work=work)
                # [B, nb, 1, block] -> the selection's rows are the slots
                scores = jnp.swapaxes(scores[:, :, 0], 0, 1)[None]
            else:
                scores = sparse_index.paged_index_scores(
                    qi, w, pool_l[INDEX_LEAF], block_tables, positions[:, 0])
        with jax.named_scope("select"):
            if decode:
                chosen, sweeps = sparse_index.sparse_select(
                    scores, limit[:, 0][None], cfg.index_topk, bias=True)
                selected = chosen[0][:, :, None]        # [nb, B, 1, block]
            else:
                chosen, sweeps = sparse_index.sparse_select(
                    scores, limit, cfg.index_topk)
                selected = chosen
            if counted is not None:
                counted.append(sweeps)
        with jax.named_scope("walk"):
            attn = runner(q, kv, block_tables, positions[:, 0],
                          sm_scale=sm_scale(cfg), window=None, work=work,
                          selected=selected)
        if probe is not None:
            row, probed = probe

            def flat(a):
                if decode:  # [1, nb, B, block] -> [B, nb * block]
                    return jnp.swapaxes(a[0], 0, 1).reshape(B, nb * bs)
                # [1, nb, C, block] -> the row's [1, nb * block]
                return jax.lax.dynamic_index_in_dim(
                    a[0], row, 1, keepdims=False).reshape(1, nb * bs)
            # (the slots' selection is a bias: 0 where selected)
            probed.append((flat(scores), flat(chosen) == 0 if decode
                           else flat(chosen) > 0))
        return attn, pool_l

    # the site's oracle: the row's whole table gathered (reads of a carried
    # pool are Mosaic calls too), scored, selected and attended densely
    gather = kv_pool_gather if in_place else gather_block_leaf
    with jax.named_scope("kv_pool_read"):
        ctx = {leaf: gather(rows, block_tables)
               for leaf, rows in pool_l.items()}
    with jax.named_scope("index_scores"):
        scores = sparse_index.index_scores(
            qi, w, ctx[INDEX_LEAF][:, 0, :, :cfg.index_head_dim])
    with jax.named_scope("select"):
        chosen = sparse_index.select_topk(scores, limit, cfg.index_topk)
    if probe is not None:
        row, probed = probe
        # the slots' rows [S, 1, nb * block], or the chunk's one
        probed.append(tuple(
            a[:, 0] if C == 1 else jax.lax.dynamic_index_in_dim(a[0], row, 0)
            for a in (scores, chosen)))
    with jax.named_scope("walk"):
        attn = _attend_selected(q, ctx["k"], ctx["v"], chosen, cfg)
    return attn, pool_l
