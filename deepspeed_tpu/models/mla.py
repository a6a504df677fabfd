"""Multi-head latent attention (MLA; DeepSeek-V2's, as `glm4_moe_lite` and
the DeepSeek-V3 family publish it) — the attention half of a layer whose
cache is ONE latent entry a token, beside `gpt.py::_attn_half` /
`_paged_attn_half` and with their signatures, so a family's layer loop
selects it by the layer's kind as data (`exaone_moe.py::ATTN_KINDS`).

With `H` heads, `d_n` / `d_r` a head's un-rotated / rotated query-key
columns, `d_v` its value columns, `r_q` / `r` the query's / the keys'
low-rank widths:

    c_q = RMSNorm(x W_qa)                    [r_q]
    [q_n | q_r]_h = c_q W_qb                 H x (d_n + d_r); q_r <- RoPE
    [c' | k'] = x W_kva                      r + d_r
    CACHED a token: c = RMSNorm(c'), k_r = RoPE(k')      (one for all heads)
    [k_n | v]_h = c W_kb                     H x (d_n + d_v)
    s_h(t, j) = (q_n,h(t) . k_n,h(j) + q_r,h(t) . k_r(j)) / sqrt(d_n + d_r)
    o_h = sum_j softmax_j(s_h)(t, j) v_h(j);   y = [o_1 .. o_H] W_o

`mla_attn_half` (no cache: the whole-sequence forward) computes exactly
that, the EXPANDED form. `paged_mla_half` computes the same numbers in the
ABSORBED form: `q~_h = W_kb,h^K q_n,h` (r columns), `s_h = q~_h . c(j) +
q_r,h . k_r(j)`, `u_h = sum_j p_h c(j)`, `o_h = (W_kb,h^V)^T u_h` — so what
is read of the cached context is the pool's entries and nothing derived
from them (`ops/pallas/mla_attention.py`). Where the two differ in rounding:
the expanded form rounds `k_n,h(j)` to the activation type once a cached
position, the absorbed form rounds `q~_h` once a query (and `u_h` once);
both are one rounding of a length-`r` (or `d_n`) dot product's operand.

A layer's leaves: `attn_q_a_w [D, r_q]`, `q_a_norm_scale [r_q]`,
`attn_q_b_w [r_q, H (d_n + d_r)]`, `attn_kv_a_w [D, r + d_r]`,
`kv_a_norm_scale [r]`, `attn_kv_b_w [r, H (d_n + d_v)]`, `attn_out_w
[H d_v, D]`; no biases.
"""

import math

import jax
import jax.numpy as jnp

from deepspeed_tpu.models.gpt import (MixedTables, _half_input, _norm, _rope,
                                      score_scale)
from deepspeed_tpu.ops import attention_dispatch as attn_dispatch
from deepspeed_tpu.ops.pallas.mla_attention import (gather_latent,
                                                    latent_entry_width,
                                                    mla_attend_gathered)

LATENT_LEAF = "ckv"


def mla_shapes(cfg):
    """A latent layer's attention leaves -> (shape, init scale; 1.0 = ones)."""
    D, H = cfg.d_model, cfg.n_head
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    down = 0.02 / math.sqrt(2 * cfg.n_layer)
    return {
        "attn_q_a_w": ((D, cfg.q_lora_rank), 0.02),
        "q_a_norm_scale": ((cfg.q_lora_rank,), 1.0),
        "attn_q_b_w": ((cfg.q_lora_rank, H * (dn + dr)), 0.02),
        "attn_kv_a_w": ((D, cfg.kv_lora_rank + dr), 0.02),
        "kv_a_norm_scale": ((cfg.kv_lora_rank,), 1.0),
        "attn_kv_b_w": ((cfg.kv_lora_rank, H * (dn + dv)), 0.02),
        "attn_out_w": ((H * dv, D), down),
    }


def entry_width(cfg) -> int:
    return latent_entry_width(cfg.kv_lora_rank, cfg.qk_rope_head_dim)


def _projections(x, p, positions, cfg):
    """x [B, C, D] -> (q_n [B, C, H, d_n], q_r [B, C, H, d_r] rotated,
    c [B, C, r] normed, k_r [B, C, d_r] rotated): everything of the half
    that is the same in both forms."""
    B, C, _ = x.shape
    H, dn, dr = cfg.n_head, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    r = cfg.kv_lora_rank
    h = _half_input(x, p, cfg)
    with jax.named_scope("mla/q_proj"):
        c_q = _norm(h @ p["attn_q_a_w"], p["q_a_norm_scale"], None, True,
                    cfg.norm_eps)
        q = (c_q @ p["attn_q_b_w"]).reshape(B, C, H, dn + dr)
        q_n = q[..., :dn]
        q_r = _rope(q[..., dn:], positions, dr, cfg.rope_theta)
    with jax.named_scope("mla/kv_down"):
        kv = h @ p["attn_kv_a_w"]
        c = _norm(kv[..., :r], p["kv_a_norm_scale"], None, True, cfg.norm_eps)
        k_r = _rope(kv[..., None, r:], positions, dr, cfg.rope_theta)[:, :, 0]
    return q_n, q_r, c, k_r


def _kv_b(p, cfg):
    """`W_kb` by head: (keys' [r, H, d_n], values' [r, H, d_v])."""
    w = p["attn_kv_b_w"].reshape(cfg.kv_lora_rank, cfg.n_head,
                                 cfg.qk_nope_head_dim + cfg.v_head_dim)
    return w[..., :cfg.qk_nope_head_dim], w[..., cfg.qk_nope_head_dim:]


def mla_attn_half(x, p, cfg, positions, attn_fn=None, constrain=True,
                  local_flag=None):
    """The whole-sequence half in the EXPANDED form (`gpt._attn_half`'s
    signature and result: (attn_out, None, None) — no K/V to hand a cache):
    per-head keys and values rebuilt from the latent, dense causal
    attention with a float32 softmax."""
    del attn_fn, constrain, local_flag
    B, T, _ = x.shape
    H, dv = cfg.n_head, cfg.v_head_dim
    q_n, q_r, c, k_r = _projections(x, p, positions, cfg)
    w_k, w_v = _kv_b(p, cfg)
    with jax.named_scope("mla/expand"):
        k_n = jnp.einsum("btr,rhn->bthn", c, w_k)
        v = jnp.einsum("btr,rhv->bthv", c, w_v)
    s = (jnp.einsum("bthn,bshn->bhts", q_n, k_n)
         + jnp.einsum("bthd,bsd->bhts", q_r, k_r)).astype(jnp.float32) \
        * score_scale(cfg, cfg.head_dim)
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None, None], s, -1e30)
    probs = jax.nn.softmax(s, axis=-1).astype(x.dtype)
    o = jnp.einsum("bhts,bshv->bthv", probs, v).reshape(B, T, H * dv)
    with jax.named_scope("mla/out"):
        return o @ p["attn_out_w"], None, None


def paged_mla_half(x, p, pool_l, positions, block_tables, cfg,
                   local_flag=None, phase=None, block_base=None,
                   decode_work=None, attn_programs=None):
    """The attention half against one latent layer's paged pool, in the
    ABSORBED form (`gpt._paged_attn_half`'s signature and result).

    pool_l: `{"ckv": [N, 1, block, width]}` (in the in-place form the whole
    flat stack, this layer's blocks from `block_base`); a position's entry is
    `[c | k_r | 0]`. Writes the rows' entries through the tables, then
    attends each row over its table: a decode row by the walk over its live
    blocks, a chunk by the walk under its frontier, both reading entries
    only. A mixed call (`MixedTables`) runs the chunk's rows, then the
    slots', between ONE set of projections and one output matmul."""
    del local_flag, phase
    T = x.shape[1]
    q_n, q_r, c, k_r = _projections(x, p, positions, cfg)

    def group(rows, pool_l, positions, tables, site, work=None, record=None):
        # a group's rows are absorbed, written, attended and un-absorbed on
        # their own: the walks take their q whole, and a slice of one q made
        # for both groups is a copy of it
        return _write_attend(*(a[rows] for a in (q_n, q_r, c, k_r)), p,
                             pool_l, positions, tables, cfg, site,
                             block_base, work, attn_programs, record)

    if isinstance(block_tables, MixedTables):
        # the projections have readers on both sides of the barrier below;
        # left alone XLA frees the query's product in between and computes
        # it again for each (`fusion.N.remat`, `.remat2`; PERF.md section 6,
        # PR 42's lesson): held where they are made
        q_n, q_r, c, k_r = jax.lax.optimization_barrier((q_n, q_r, c, k_r))
        S = block_tables.decode.shape[0]
        C = T - S
        chunk, slots = (slice(None), slice(0, C)), (slice(None), slice(C, T))
        o_c, pool_l = group(chunk, pool_l, positions[:, :C],
                            block_tables.chunk, "prefill_chunk",
                            record="mixed/prefill_chunk")
        # the chunk's walk has read the pool before the slots' rows are
        # written into it in place (`gpt._paged_attn_half` has the story)
        o_c, pool_l = jax.lax.optimization_barrier((o_c, pool_l))
        o_d, pool_l = group(slots, pool_l, positions[:, C:].T,
                            block_tables.decode, "paged_decode",
                            work=decode_work, record="mixed/paged_decode")
        o = jnp.concatenate([o_c, o_d], axis=1)
    else:
        o, pool_l = group((slice(None), slice(None)), pool_l, positions,
                          block_tables,
                          "paged_decode" if T == 1 else "prefill_chunk",
                          work=decode_work)
    with jax.named_scope("mla/out"):
        return o @ p["attn_out_w"], pool_l


def _write_attend(q_n, q_r, c, k_r, p, pool_l, positions, block_tables, cfg,
                  phase, block_base, work, attn_programs, record):
    """Rows that share a dispatch site, handed to the site's program as
    `positions` [B, C] lays them out (a chunk's [1, C]; the slots' [S, 1],
    which a mixed call holds as [1, S, ...]): absorb the queries, write the
    rows' entries `[c | k_r | 0]` at `positions` through `block_tables`
    [B, nb], attend each row over its table, un-absorb -> (o, the rows'
    leading shape then H * d_v; pool_l)."""
    B, C = positions.shape
    H, r, dv = cfg.n_head, cfg.kv_lora_rank, cfg.v_head_dim
    pool = pool_l[LATENT_LEAF]
    block, width = pool.shape[2], pool.shape[3]
    nb = block_tables.shape[1]
    w_k, w_v = _kv_b(p, cfg)
    with jax.named_scope("mla/absorb"):
        pad = width - r - cfg.qk_rope_head_dim
        q = jnp.concatenate(
            [jnp.einsum("bthn,rhn->bthr", q_n, w_k), q_r,
             jnp.zeros(q_r.shape[:3] + (pad,), q_r.dtype)],
            axis=-1).reshape(B, C, H, width)
        entry = jnp.concatenate(
            [c, k_r, jnp.zeros(c.shape[:2] + (pad,), c.dtype)],
            axis=-1).reshape(B, C, 1, width)
    with jax.named_scope("kv_pool_write"):
        if block_base is not None:
            from deepspeed_tpu.ops.pallas.kv_pool import kv_pool_write
            block_tables = block_tables + block_base
            pool = kv_pool_write(pool, entry, positions[:, 0], block_tables)
        else:
            blk = jnp.take_along_axis(block_tables, positions // block,
                                      axis=1)
            pool = pool.at[blk, :, positions % block, :].set(
                entry.astype(pool.dtype))
    site = attn_dispatch.AttnSite(
        phase=phase, q_len=C, kv_len=nb * block, causal=True, latent=True,
        kv_dtype=str(jnp.dtype(pool.dtype)), block_size=block,
        pool_in_place=block_base is not None,
        mesh_axes=attn_dispatch.active_mesh_axes(),
        force_flash=cfg.use_flash_attention)
    program = attn_dispatch.select(site)
    if attn_programs is not None:
        attn_programs[record or phase] = program
    scale = score_scale(cfg, cfg.head_dim)
    runner = attn_dispatch.get_program(program).runner
    with jax.named_scope("walk"):
        if runner is not None:
            u = runner(q, {LATENT_LEAF: pool}, block_tables, positions[:, 0],
                       sm_scale=scale, window=None, work=work, rank=r)
        else:
            # the site's oracle: each row's whole table gathered, attended
            # densely in the absorbed form
            if block_base is not None:
                # reads of a carried pool are Mosaic calls too
                from deepspeed_tpu.ops.pallas.kv_pool import kv_pool_gather
                ctx = kv_pool_gather(pool, block_tables)[:, 0]
            else:
                ctx = gather_latent(pool, block_tables)
            u = mla_attend_gathered(q, ctx, positions, r, scale)
    with jax.named_scope("mla/absorb"):
        o = jnp.einsum("bthr,rhv->bthv", u.reshape(q_n.shape[:3] + (r,)), w_v)
    return o.reshape(q_n.shape[:2] + (H * dv,)), {LATENT_LEAF: pool}
