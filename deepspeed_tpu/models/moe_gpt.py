"""MoE-GPT — GPT with Mixture-of-Experts MLPs, training AND inference.

Reference: training MoE via `deepspeed/moe/layer.py:16` placed inside client
transformer MLPs, and MoE *inference* via the expert-parallel containers
(`ops/transformer/inference/moe_inference.py`, `inference/engine.py:260`
`_create_ep_parallel_group`).

TPU-native formulation: every `moe_freq`-th block's MLP is a GShard-style
expert layer. Training routes with masked static-capacity top-1 gating —
through the comm facade's instrumented all_to_all inside `shard_map` when a
mesh with expert parallelism is active (`parallel/moe.py`'s
`expert_parallel_moe`; dispatch bytes land in `comm/all_to_all_bytes`), and
through the dispatch-einsum + sharding-constraint fallback otherwise (XLA
emits the all-to-all pair). Capacity overflow masks tokens (no dynamic
shapes); drop/overflow counts surface as `moe/*` telemetry via the loss aux.

Inference routes **capacity-free**, top-k: every token goes to its `top_k`
most probable experts (`_routed_mlp`: `parallel/moe.py::topk_routing` and
`routed_experts`, a stable sort of the N*k assignments by expert and one
grouped matmul a projection, `ops/pallas/moe_gmm.py`). That choice is
deliberate — the routing decision and each token's result depend only on the
token itself, never on batch composition or chunk boundaries, which is
exactly the invariance the paged serving path needs for token-identical
continuous batching (a prompt chunked 3 ways routes identically to the same
prompt in one pass). Capacity is a training-throughput construct; at serving
granularity it only creates drops. The top-1 presets are `top_k` 1 on the
same path.

Two layouts of the expert weights, told apart by what the tree holds:

  * `moe_freq` >= 2 (MoE-GPT, the HF adapters): `params["moe"][str(layer)]`
    = `{gate_w, w_up, b_up, w_down, b_down}` beside a dense skeleton; the
    layers differ, so the serving programs loop over them in Python.
  * `moe_freq` 1 (every block routed: OLMoE): the experts are STACKED in
    `params["blocks"]` like every other block leaf — `moe_gate_w [L, D, E]`,
    `moe_w_gate_up [L, E, D, 2F]` (SwiGLU, gate and up side by side),
    `moe_w_down [L, E, F, D]` — there is no dense MLP, and the layer runs
    inside `gpt.py::scan_paged`, on the carried pool in its in-place form.
"""

import dataclasses
import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.comm.mesh import (BATCH_AXES, EXPERT_AXIS, SEQ_AXIS,
                                     TENSOR_AXIS, get_mesh, has_mesh,
                                     shard_constraint)
from deepspeed_tpu.models.gpt import (GPTConfig, _act, _attn_half, _block,
                                      _block_decode, _block_paged,
                                      _decode_attn_half, _embed, _last_rows,
                                      _lm_head, _norm, _residual_mlp,
                                      block_groups, gpt_cache_identity,
                                      gpt_init_fn,
                                      init_gpt_params, init_kv_cache,
                                      init_paged_kv_pool, gpt_param_specs,
                                      make_mixed_paged_fn,
                                      scan_paged)
from deepspeed_tpu.parallel.moe import (ROUTED_COUNTERS,
                                        can_use_expert_shard_map,
                                        expert_parallel_moe,
                                        gating_drop_stats, routed_experts,
                                        top1_gating, topk_routing)
from deepspeed_tpu.runtime.engine import ModelSpec


@dataclasses.dataclass
class MoEGPTConfig(GPTConfig):
    num_experts: int = 8
    moe_freq: int = 2                 # every moe_freq-th block is MoE (from
                                      # block 1); 1 = EVERY block, experts
                                      # stacked in params["blocks"], no dense MLP
    top_k: int = 1                    # experts a token at inference (no
                                      # capacity); training gates top-1
    norm_topk_prob: bool = False      # rescale the k probabilities to sum to 1
    capacity_factor: float = 1.25
    eval_capacity_factor: float = 2.0
    min_capacity: int = 4
    moe_aux_weight: float = 0.01
    moe_dispatch_wire: str = "none"   # WireTransform on the facade a2a pair

    def moe_layer_ids(self):
        if self.moe_freq == 1:
            return list(range(self.n_layer))
        return [i for i in range(self.n_layer) if i % self.moe_freq == 1]


def init_moe_gpt_params(cfg: MoEGPTConfig, seed: int = 0, dtype=jnp.float32):
    """Dense skeleton (stacked blocks, gpt.py layout) + per-MoE-layer expert
    weights {layer_id: {gate_w, w_up [E,D,F], w_down [E,F,D]}}."""
    params = init_gpt_params(cfg, seed=seed, dtype=dtype)
    if cfg.moe_freq == 1:
        return _stack_experts(params, cfg, jax.random.PRNGKey(seed + 7), dtype)
    rng = np.random.default_rng(seed + 7)
    D, F, E = cfg.d_model, cfg.d_ff, cfg.num_experts
    moe = {}
    for lid in cfg.moe_layer_ids():
        moe[str(lid)] = {
            "gate_w": jnp.asarray(rng.normal(0, 0.02, (D, E)), dtype),
            "w_up": jnp.asarray(rng.normal(0, 0.02, (E, D, F)), dtype),
            "b_up": jnp.zeros((E, F), dtype),
            "w_down": jnp.asarray(rng.normal(0, 0.02 / np.sqrt(2 * cfg.n_layer),
                                             (E, F, D)), dtype),
            "b_down": jnp.zeros((E, D), dtype),
        }
    params["moe"] = moe
    return params


_DENSE_MLP_LEAVES = ("mlp_gate_w", "mlp_up_w", "mlp_up_b", "mlp_down_w",
                     "mlp_out_b")


def _stack_experts(params, cfg: MoEGPTConfig, rng, dtype):
    """The `moe_freq` 1 layout: the dense MLP leaves leave `blocks`, the
    stacked experts join it (module docstring). jax-traceable."""
    D, F, E, L = cfg.d_model, cfg.d_ff, cfg.num_experts, cfg.n_layer
    k_gate, k_up, k_down = jax.random.split(rng, 3)
    normal = lambda key, shape, scale: (
        jax.random.normal(key, shape, dtype) * scale)
    blocks = {k: v for k, v in params["blocks"].items()
              if k not in _DENSE_MLP_LEAVES}
    blocks["moe_gate_w"] = normal(k_gate, (L, D, E), 0.02)
    if not cfg.use_swiglu:
        raise NotImplementedError(
            "the stacked (moe_freq=1) layout holds gated SwiGLU experts; "
            "plain experts with biases live in per-layer trees (moe_freq>=2)")
    down_scale = 0.02 / math.sqrt(2 * L)     # a Python float: stays weak-typed
    blocks["moe_w_gate_up"] = normal(k_up, (L, E, D, 2 * F), 0.02)
    blocks["moe_w_down"] = normal(k_down, (L, E, F, D), down_scale)
    return {**params, "blocks": blocks}


def moe_gpt_init_fn(cfg: MoEGPTConfig, dtype=jnp.float32,
                    embedding_std=None):
    """jax-traceable initializer (rng -> params) for the `moe_freq` 1 layout,
    the twin of `gpt.py::gpt_init_fn`: under one `jit` the whole tree is
    made on the device in the type it is served in (the dense MLP leaves it
    drops are never materialised). `embedding_std`: the token embedding's
    range where it is not the other matrices' 0.02 (a benchmark's way to a
    stream in which a token's own embedding is not swamped by what the first
    layers add to every token alike, as `exaone_moe_init_fn`'s)."""
    if cfg.moe_freq != 1:
        raise NotImplementedError(
            "moe_gpt_init_fn builds the stacked (moe_freq=1) layout only; "
            "use init_moe_gpt_params for per-layer expert trees")
    dense = gpt_init_fn(cfg, dtype=dtype)

    def init(rng):
        rng, sub = jax.random.split(rng)
        params = _stack_experts(dense(rng), cfg, sub, dtype)
        if embedding_std is not None:
            params["wte"] = (params["wte"].astype(jnp.float32)
                             * (float(embedding_std) / 0.02)).astype(dtype)
        return params

    return init


_EXPERT_STACKS = ("moe_w_gate_up", "moe_w_down")


def _flat_expert_stacks(blocks):
    """The stacked experts `[L, E, ...]` as `[L * E, ...]` (a bitcast), in
    `routed_experts`' names: layer l's experts begin at `l * E`. The WHOLE
    stack goes to the grouped matmul; slicing a layer out of it in front of
    a custom call would copy that layer's experts every step."""
    return {k[len("moe_"):]: v.reshape((-1,) + v.shape[2:])
            for k, v in blocks.items() if k in _EXPERT_STACKS}


def _layer_experts(params, p, lid):
    """Layer `lid`'s experts as `_routed_mlp` takes them — the tree `{gate_w,
    ...}` in `routed_experts`' names and where the layer's experts begin in
    it — from whichever layout `params` is in; None for a dense layer. `p`
    is the layer's slice of `params["blocks"]`; `lid` may be traced for the
    stacked layout."""
    if "moe_gate_w" in p:
        return ({"gate_w": p["moe_gate_w"],
                 **_flat_expert_stacks(params["blocks"])},
                lid * params["blocks"]["moe_gate_w"].shape[-1])
    mp = params.get("moe", {}).get(str(lid))
    return mp and (mp, 0)


def moe_gpt_param_specs(cfg: MoEGPTConfig):
    specs = gpt_param_specs(cfg)
    e, t = EXPERT_AXIS, TENSOR_AXIS
    if cfg.moe_freq == 1:
        blocks = {k: v for k, v in specs["blocks"].items()
                  if k not in _DENSE_MLP_LEAVES}
        blocks["moe_gate_w"] = P(None, None, None)
        blocks["moe_w_gate_up"] = P(None, e, None, t)
        blocks["moe_w_down"] = P(None, e, t, None)
        return {**specs, "blocks": blocks}
    moe_spec = {
        "gate_w": P(None, None),
        "w_up": P(e, None, t),
        "b_up": P(e, t),
        "w_down": P(e, t, None),
        "b_down": P(e, None),
    }
    specs["moe"] = {str(lid): dict(moe_spec) for lid in cfg.moe_layer_ids()}
    return specs


def _expert_ffn(xe, mp, cfg, constrain=True):
    """xe: [E, C, D] tokens per expert → [E, C, D]; batched expert FFN on the
    expert mesh axis. `constrain=False` for shard_map bodies (manual sharding
    forbids constraints — the expert dim is already local there)."""
    h = jnp.einsum("ecd,edf->ecf", xe, mp["w_up"]) + mp["b_up"][:, None, :]
    h = jax.nn.gelu(h) if cfg.activation == "gelu" else jax.nn.relu(h)
    if constrain:
        h = shard_constraint(h, EXPERT_AXIS, None, TENSOR_AXIS)
    return jnp.einsum("ecf,efd->ecd", h, mp["w_down"]) + mp["b_down"][:, None, :]


def _moe_mlp(x, mp, cfg: MoEGPTConfig, training=True, mesh=None):
    """x: [B, T, D] → (out, l_aux, drop_stats). Static-capacity top-1 routing.

    With a mesh that `can_use_expert_shard_map` accepts, dispatch/combine run
    inside shard_map with the facade's all_to_all pair (per-shard gating,
    local capacity); otherwise the GShard dispatch/combine einsums + expert
    sharding constraint (XLA inserts the a2a — invisible to facade stats).
    """
    B, T, D = x.shape
    E = cfg.num_experts
    cf = cfg.capacity_factor if training else cfg.eval_capacity_factor
    xf = x.reshape(B * T, D)

    if mesh is None and has_mesh():
        # lazy resolution: the engine builds the mesh after the ModelSpec, so
        # a loss traced under an active expert mesh picks up facade dispatch
        # automatically; can_use_expert_shard_map rejects unsuitable meshes
        mesh = get_mesh()
    if can_use_expert_shard_map(mesh, E, B * T):
        eparams = {k: mp[k] for k in ("w_up", "b_up", "w_down", "b_down")}
        out, l_aux, _counts, stats = expert_parallel_moe(
            xf, mp["gate_w"], eparams,
            lambda xe, p: _expert_ffn(xe, p, cfg, constrain=False), mesh,
            num_experts=E, capacity_factor=cf, min_capacity=cfg.min_capacity,
            dispatch_wire=cfg.moe_dispatch_wire)
        return out.reshape(B, T, D), l_aux, stats

    logits = (xf @ mp["gate_w"]).astype(jnp.float32)
    l_aux, dispatch, combine, counts = top1_gating(
        logits, capacity_factor=cf, min_capacity=cfg.min_capacity)
    stats = gating_drop_stats(dispatch, counts)
    # dispatch: [N, E, C] — einsum routes tokens to expert slots; the sharding
    # constraint on the expert dim makes XLA emit the a2a (reference _AllToAll)
    xe = jnp.einsum("nec,nd->ecd", dispatch.astype(x.dtype), xf)
    xe = shard_constraint(xe, EXPERT_AXIS, None, None)
    ye = _expert_ffn(xe, mp, cfg)
    out = jnp.einsum("nec,ecd->nd", combine.astype(x.dtype), ye)
    return out.reshape(B, T, D), l_aux, stats


def _routed_mlp(x, experts, cfg: MoEGPTConfig, groups=1):
    """Capacity-free inference routing: x [B, T, D] -> (out, counters
    int32[4] in `parallel.moe.ROUTED_COUNTERS` order, the chosen experts
    [B*T, top_k]). `groups` > 1 (`gpt.py::block_groups`, the count the
    attention half walks by): a row's T positions are that many groups, which
    go through the experts together and are combined a group at a time
    (`routed_experts`'s `groups`), so each leaves as a forward of its own
    would leave it, to the bit.

    Every token goes to its `cfg.top_k` most probable experts, weighted by
    the router's probabilities (rescaled under `norm_topk_prob`) — routing
    and result depend only on the token, so any batching/chunking of the
    same tokens produces identical outputs (the paged-serving parity
    invariant). `experts`: `_layer_experts`' (tree, base)."""
    mp, base = experts
    B, T, D = x.shape
    xf = x.reshape(B * T, D) if groups == 1 else jnp.swapaxes(
        x.reshape(B, groups, -1, D), 0, 1).reshape(B * T, D)   # a group a run
    top_p, top_e = topk_routing(xf, mp["gate_w"], cfg.top_k,
                                cfg.norm_topk_prob)
    out, counters = routed_experts(
        xf, top_p, top_e, {k: v for k, v in mp.items() if k != "gate_w"},
        activation=lambda h: _act(h, cfg), num_experts=cfg.num_experts,
        expert_base=base, groups=groups)
    if groups == 1:
        return out.reshape(B, T, D), counters, top_e
    # ... and back to a row's own order
    rows = lambda a: jnp.swapaxes(a.reshape(groups, B, T // groups, -1), 0, 1)
    return (rows(out).reshape(B, T, D), counters,
            rows(top_e).reshape(B * T, -1))


def _router_balance(x, gate_w, cfg: MoEGPTConfig):
    """The me.ce load-balance statistic (1 = even) of the inference router
    on x [B, T, D]: reported by the evaluation forward, never trained on."""
    E = cfg.num_experts
    xf = x.reshape(-1, x.shape[-1])
    probs = jax.nn.softmax(jnp.dot(xf, gate_w.astype(xf.dtype),
                                   preferred_element_type=jnp.float32), -1)
    _, top_e = jax.lax.top_k(probs, cfg.top_k)
    chosen = jnp.sum(jax.nn.one_hot(top_e, E, dtype=jnp.float32), axis=1)
    return jnp.sum(jnp.mean(probs, axis=0)
                   * jnp.mean(chosen, axis=0) / cfg.top_k) * E


def _routed_mlp_fn(experts, cfg, counters=None, routing=None, groups=1):
    """`_residual_mlp`'s `mlp_fn` for a routed layer (`experts`:
    `_layer_experts`' pair, None for a dense layer -> None); each call's
    counters and chosen experts are appended to the lists `counters` and
    `routing` where given. `groups`: `_routed_mlp`'s."""
    if experts is None:
        return None

    def mlp_fn(h):
        out, counted, top_e = _routed_mlp(h, experts, cfg, groups)
        if counters is not None:
            counters.append(counted)
        if routing is not None:
            routing.append(top_e)
        return out
    return mlp_fn


def _zero_drop_stats():
    z = jnp.asarray(0.0, jnp.float32)
    return {"routed": z, "kept": z, "overflow_tokens": z, "dropped_frac": z}


def _sum_drop_stats(acc, s):
    acc = {k: acc[k] + s[k] for k in ("routed", "kept", "overflow_tokens")}
    acc["dropped_frac"] = acc["overflow_tokens"] / jnp.maximum(acc["routed"], 1.0)
    return acc


def moe_gpt_forward(params, tokens, cfg: MoEGPTConfig, training=True, rng=None,
                    mesh=None, return_stats=False, routing=None):
    """[B, T] → (logits, total_l_aux[, drop_stats]). Python loop over layers
    (MoE layers break the homogeneous scan; L is moderate for MoE models)."""
    B, T = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (B, T))
    x = _embed(params, tokens, positions, cfg)
    x = shard_constraint(x, BATCH_AXES, SEQ_AXIS, None)

    if training and cfg.moe_freq == 1:
        raise NotImplementedError(
            "training a moe_freq=1 (stacked, top-k routed) MoE is not built "
            "yet: the capacity gating below is top-1 over per-layer trees "
            "(ROADMAP R6, training half)")
    l_aux_total = jnp.asarray(0.0, jnp.float32)
    stats_total = _zero_drop_stats()
    for lid in range(cfg.n_layer):
        p = jax.tree_util.tree_map(lambda a: a[lid], params["blocks"])
        mp = _layer_experts(params, p, lid)
        if mp is not None:
            # attention half from the dense block, MLP half replaced by MoE
            x, l_aux, stats = _moe_block(x, p, mp, cfg, positions, training,
                                         mesh, routing)
            l_aux_total = l_aux_total + l_aux
            stats_total = _sum_drop_stats(stats_total, stats)
        else:
            x = _block(x, p, cfg, positions)

    x = _norm(x, params["lnf_scale"], params.get("lnf_bias"), cfg.use_rmsnorm,
              cfg.norm_eps)
    head = params["lm_head"] if not cfg.tie_embeddings else params["wte"]
    logits = jnp.einsum("btd,vd->btv", x, head.astype(x.dtype))
    if return_stats:
        return logits, l_aux_total, stats_total
    return logits, l_aux_total


def moe_gpt_routing(params, tokens, cfg: MoEGPTConfig):
    """The experts the inference forward (`moe_gpt_forward(training=False)`,
    in the model's own dtype) routes every token to: tokens [B, T] -> int32
    [routed layers, B, T, top_k], each token's experts in ascending order.
    The reference check compares these SETS with the float32 reference's
    (near-ties can swap)."""
    chosen = []
    moe_gpt_forward(params, tokens, cfg, training=False, routing=chosen)
    return jnp.stack([jnp.sort(top_e, axis=-1).reshape(tokens.shape + (-1,))
                      for top_e in chosen])


def _moe_block(x, p, mp, cfg, positions, training, mesh=None, routing=None):
    """Transformer block with MoE MLP (attention half shared with gpt._block,
    so alibi/sliding-window/parallel-residual behave identically)."""
    aux = []

    def moe_fn(h):
        if training:
            out, l_aux, stats = _moe_mlp(h, mp[0], cfg, training=True,
                                         mesh=mesh)
        else:
            out = _routed_mlp_fn(mp, cfg, routing=routing)(h)
            l_aux, stats = _router_balance(h, mp[0]["gate_w"], cfg), \
                _zero_drop_stats()
        aux.append((l_aux, stats))
        return out

    attn_out, _, _ = _attn_half(x, p, cfg, positions)
    x = _residual_mlp(x, attn_out, p, cfg, mlp_fn=moe_fn)
    l_aux, stats = aux[0]
    return shard_constraint(x, BATCH_AXES, SEQ_AXIS, None), l_aux, stats


def moe_gpt_loss(params, batch, rng, cfg: MoEGPTConfig, mesh=None):
    tokens = batch.get("tokens", batch.get("input_ids"))
    labels = batch.get("labels")
    if labels is None:
        inputs, labels = tokens[:, :-1], tokens[:, 1:]
    else:
        inputs = tokens
    logits, l_aux, stats = moe_gpt_forward(params, inputs, cfg, training=True,
                                           rng=rng, mesh=mesh,
                                           return_stats=True)
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    safe = jnp.maximum(labels, 0)
    gold = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
    mask = (labels >= 0).astype(jnp.float32)
    nll = ((logz - gold) * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    # slash-keyed entries flow to telemetry gauges (runtime/engine.py threads
    # them through the grad path into `moe/*` — docs/profiling.md catalog)
    aux = {"lm_loss": nll, "l_aux": l_aux,
           "moe/aux_loss": l_aux,
           "moe/overflow_tokens": stats["overflow_tokens"],
           "moe/dropped_frac": stats["dropped_frac"]}
    return nll + cfg.moe_aux_weight * l_aux, aux


def make_moe_gpt_model(cfg: MoEGPTConfig, name="moe-gpt", seed=0,
                       mesh=None) -> ModelSpec:
    """Pass ``mesh=`` to route expert dispatch through the comm facade's
    all_to_all (shard_map over the expert axis) instead of the einsum path."""
    params = init_moe_gpt_params(cfg, seed=seed)
    return ModelSpec(loss_fn=partial(moe_gpt_loss, cfg=cfg, mesh=mesh),
                     params=params,
                     param_specs=moe_gpt_param_specs(cfg), has_aux=True,
                     apply_fn=partial(moe_gpt_forward, cfg=cfg, training=False),
                     name=name)


# ----------------------------------------------------------------------
# inference (expert-parallel decode — reference moe_inference.py)
# ----------------------------------------------------------------------


def moe_cache_identity(cfg: MoEGPTConfig, name: str = "") -> str:
    """`gpt_cache_identity` plus the MoE fields that change KV VALUES: expert
    count and placement change every MoE layer's output, hence every later
    layer's K/V. Capacity knobs are absent on purpose — inference routing is
    capacity-free, so they cannot change cached bytes."""
    blocks = f"blocks{cfg.block_length}|" if cfg.block_length > 1 else ""
    return (f"moe:{cfg.num_experts}|{cfg.moe_freq}|{cfg.top_k}|"
            f"{int(cfg.norm_topk_prob)}|" + blocks
            + gpt_cache_identity(cfg, name))


def make_moe_gpt_decode_model(cfg: MoEGPTConfig, params=None, name="moe-gpt",
                              seed=0, generator=None):
    """`generator` (`inference.engine.BlockDiffusion`; its block length is
    `cfg.block_length`): the model generates by diffusion over blocks, and
    the spec carries it and `denoise_paged_fn`."""
    from deepspeed_tpu.inference.engine import DecodeModelSpec
    if (generator.block_length if generator else 1) != cfg.block_length:
        raise ValueError(
            f"the generator's block length and the mask's must agree: "
            f"{generator} against cfg.block_length {cfg.block_length}")
    if params is None:
        params = init_moe_gpt_params(cfg, seed=seed)

    def prefill_fn(params, tokens, cache, pad_mask):
        B, T = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (B, T))
        x = _embed(params, tokens, positions, cfg)
        ks, vs = [], []
        for lid in range(cfg.n_layer):
            p = jax.tree_util.tree_map(lambda a: a[lid], params["blocks"])
            attn_out, k, v = _attn_half(x, p, cfg, positions)
            ks.append(jnp.moveaxis(k, 1, 2))
            vs.append(jnp.moveaxis(v, 1, 2))
            mp = _layer_experts(params, p, lid)
            x = _residual_mlp(x, attn_out, p, cfg,
                              mlp_fn=_routed_mlp_fn(mp, cfg))
        x = _norm(x, params["lnf_scale"], params.get("lnf_bias"), cfg.use_rmsnorm,
                  cfg.norm_eps)
        head = params["lm_head"] if not cfg.tie_embeddings else params["wte"]
        logits = jnp.einsum("btd,vd->btv", x, head.astype(x.dtype))
        new_cache = {
            "k": cache["k"].at[:, :, :, :T].set(jnp.stack(ks, 0).astype(cache["k"].dtype)),
            "v": cache["v"].at[:, :, :, :T].set(jnp.stack(vs, 0).astype(cache["v"].dtype)),
            "length": jnp.full((B,), T, jnp.int32),
        }
        return logits, new_cache

    def decode_fn(params, token, pos, cache):
        B = token.shape[0]
        x = _embed(params, token[:, None], pos[:, None], cfg)
        new_k, new_v = [], []
        for lid in range(cfg.n_layer):
            p = jax.tree_util.tree_map(lambda a: a[lid], params["blocks"])
            mp = _layer_experts(params, p, lid)
            if mp is not None:
                x, ck, cv = _moe_block_decode(x, p, mp,
                                              cache["k"][lid], cache["v"][lid],
                                              pos, cfg)
            else:
                x, ck, cv = _block_decode(x, p, cache["k"][lid], cache["v"][lid],
                                          pos, cfg)
            new_k.append(ck)
            new_v.append(cv)
        x = _norm(x, params["lnf_scale"], params.get("lnf_bias"), cfg.use_rmsnorm,
                  cfg.norm_eps)
        head = params["lm_head"] if not cfg.tie_embeddings else params["wte"]
        logits = jnp.einsum("bod,vd->bov", x, head.astype(x.dtype))[:, 0]
        cache_out = {"k": jnp.stack(new_k, 0), "v": jnp.stack(new_v, 0),
                     "length": cache["length"] + 1}
        return logits, cache_out

    def init_cache(batch_size, max_len, dtype=jnp.bfloat16):
        return init_kv_cache(cfg, batch_size, max_len, dtype)

    # paged-pool serving contract (see DecodeModelSpec): same pool layout and
    # attention machinery as gpt.py's paged path, and the routed experts keep
    # every chunking of a prompt token-identical, which is what continuous
    # batching relies on. Each program also returns the routed layers'
    # counters, summed over the layers (`step_counters` below). With a
    # `generator` (diffusion over blocks) the spec also carries
    # `denoise_paged_fn`, one forward of a block a slot, and a decode call
    # commits whole blocks instead of emitting `window` tokens a slot.
    #
    # Experts stacked in `blocks` (moe_freq 1): every block is alike, so the
    # layer — attention half and routed experts — runs inside
    # `gpt.py::scan_paged`, on the carried pool in its in-place form where
    # `kv_pool_writer` allows it. Per-layer expert trees (`params["moe"]`):
    # the layers differ, so the loop is Python's and the pool is sliced and
    # re-stacked a layer (`_loop_paged`, the form every MoE model had
    # before; no benchmark cell runs it).

    pool_writers = {}
    attn_programs = {}
    no_counts = jnp.zeros((len(ROUTED_COUNTERS),), jnp.int32)


    def _loop_paged(params, x, pool, block_tables, positions, phase=None):
        slices, counts = [], [no_counts]
        groups = block_groups(cfg, positions, phase)
        for lid in range(cfg.n_layer):
            p = jax.tree_util.tree_map(lambda a: a[lid], params["blocks"])
            pool_l = {k: v[lid] for k, v in pool.items()}
            mp = _layer_experts(params, p, lid)
            x, pool_l = _block_paged(
                x, p, pool_l, positions, block_tables, cfg, phase=phase,
                mlp_fn=_routed_mlp_fn(mp, cfg, counts, groups=groups),
                attn_programs=attn_programs)
            slices.append(pool_l)
        pool = {k: jnp.stack([s[k] for s in slices], 0) for k in pool}
        return x, pool, sum(counts)

    def _layers_paged(params, x, pool, block_tables, positions, phase=None,
                      routing=False):
        """`routing` (the stacked layout; a benchmark's check): a fourth
        result, the experts every row was routed to, int32 [L, rows, top_k]
        in the router's order — what the served forward chose on its own
        activations."""
        if "moe_gate_w" not in params["blocks"]:     # per-layer trees
            return _loop_paged(params, x, pool, block_tables, positions,
                               phase)
        # the scan slices the small leaves a layer; the expert stacks stay
        # whole (closed over, like the carried pool) and the layer finds
        # its experts by index
        blocks = params["blocks"]
        scanned = {k: v for k, v in blocks.items()
                   if k not in _EXPERT_STACKS}
        L, rows = cfg.n_layer, x.shape[0] * x.shape[1]
        # (the walk's groups, `gpt.py::_paged_write_attend`: the same rule)
        groups = block_groups(cfg, positions, phase)
        aux = no_counts
        if routing:
            # the scan SUMS a layer's third result: each layer's sets ride
            # the sum at the layer's own place in one flat vector
            aux = jnp.zeros((no_counts.size + L * rows * cfg.top_k,),
                            jnp.int32)

        def routed_block(x, p, pool_l, positions, block_tables, cfg, layer,
                         **kwargs):
            counted, chosen = [], [] if routing else None
            x, pool_l = _block_paged(
                x, p, pool_l, positions, block_tables, cfg,
                mlp_fn=_routed_mlp_fn(_layer_experts(params, p, layer), cfg,
                                      counted, chosen, groups),
                **kwargs)
            if not routing:
                return x, pool_l, counted[0]
            return x, pool_l, jax.lax.dynamic_update_slice(
                jnp.zeros_like(aux).at[:no_counts.size].set(counted[0]),
                chosen[0].reshape(-1),
                (no_counts.size + layer * rows * cfg.top_k,))

        x, pool, aux = scan_paged(
            cfg, scanned, x, pool, block_tables, positions, phase=phase,
            pool_writers=pool_writers, block_fn=routed_block, aux=aux,
            attn_programs=attn_programs)
        if not routing:
            return x, pool, aux
        return (x, pool, aux[:no_counts.size],
                aux[no_counts.size:].reshape(L, rows, cfg.top_k))

    def prefill_paged_fn(params, tokens, start_pos, last_idx, pool,
                         block_tables, **loop):
        B, C = tokens.shape
        positions = start_pos[:, None] + jnp.arange(C, dtype=jnp.int32)[None]
        x = _embed(params, tokens, positions, cfg)
        x, pool, *counts = _layers_paged(params, x, pool, block_tables,
                                         positions, **loop)
        logits = _lm_head(params, _last_rows(x, last_idx), cfg)[:, 0]
        return (logits, pool, *counts)

    def decode_paged_fn(params, token, pos, pool, block_tables):
        x = _embed(params, token[:, None], pos[:, None], cfg)
        x, pool, counts = _layers_paged(params, x, pool, block_tables,
                                        pos[:, None])
        logits = _lm_head(params, x, cfg)[:, 0]
        return logits, pool, counts

    def verify_paged_fn(params, tokens, pos, pool, block_tables):
        B, C = tokens.shape
        positions = pos[:, None] + jnp.arange(C, dtype=jnp.int32)[None]
        x = _embed(params, tokens, positions, cfg)
        x, pool, counts = _layers_paged(params, x, pool, block_tables,
                                        positions, phase="verify")
        logits = _lm_head(params, x, cfg)
        return logits, pool, counts

    def denoise_paged_fn(params, tokens, pos, pool, block_tables,
                         hidden=False, **loop):
        """One forward of a diffusion generator's block a slot (tokens
        [S, B] at pos .. pos + B - 1), denoise or commit alike: the rows'
        k/v written, [0, pos + B) attended, every row's logits [S * B, V],
        slot after slot. `tokens` [S, 2B]: a FUSED forward — a block's clean
        tokens and the next block's rows as ONE pass through every weight,
        both blocks' k/v written, each attended to its own end
        (`gpt.py::block_groups`); the logits are the second block's, the
        rows that sample. `hidden=True`: those rows as the layers leave them,
        [S * B, D], in the logits' place (`head_fn` makes the logits of
        them). Keywords go to the layer loop (`routing=True`: the sets, of
        every row, follow the counters)."""
        S, R = tokens.shape
        B = cfg.block_length
        positions = pos[:, None] + jnp.arange(R, dtype=jnp.int32)[None]
        x = _embed(params, tokens, positions, cfg)
        x, pool, *counts = _layers_paged(params, x, pool, block_tables,
                                         positions, phase="denoise", **loop)
        rows = x[:, R - B:].reshape(S * B, -1)
        return (rows if hidden else head_fn(params, rows), pool, *counts)

    def head_fn(params, rows):
        # (the head over the rows as ONE [S * B, D] matrix: B is no tile)
        return _lm_head(params, rows[None], cfg)[0]

    def init_paged_pool(num_blocks, block_size, dtype=jnp.bfloat16,
                        kv_group_size=0):
        return init_paged_kv_pool(cfg, num_blocks, block_size, dtype,
                                  kv_group_size)

    return DecodeModelSpec(prefill_fn=prefill_fn, decode_fn=decode_fn,
                           init_cache=init_cache, params=params,
                           param_specs=moe_gpt_param_specs(cfg), name=name,
                           generator=generator,
                           denoise_paged_fn=denoise_paged_fn if generator
                           else None,
                           head_fn=head_fn if generator else None,
                           prefill_paged_fn=prefill_paged_fn,
                           decode_paged_fn=decode_paged_fn,
                           mixed_paged_fn=make_mixed_paged_fn(cfg,
                                                              _layers_paged),
                           mixed_chunk_groups=True,
                           verify_paged_fn=verify_paged_fn,
                           init_paged_pool=init_paged_pool,
                           kv_pool_writers=pool_writers,
                           paged_attn_programs=attn_programs,
                           step_counters=ROUTED_COUNTERS,
                           cache_fingerprint=moe_cache_identity(cfg, name))


def _moe_block_decode(x, p, mp, cache_k, cache_v, pos, cfg):
    """_block_decode with the MLP replaced by single-token MoE routing."""
    attn_out, cache_k, cache_v = _decode_attn_half(x, p, cache_k, cache_v, pos, cfg)
    x = _residual_mlp(x, attn_out, p, cfg, constrain=False,
                      mlp_fn=_routed_mlp_fn(mp, cfg))
    return x, cache_k, cache_v


def moe_expert_store(params, layer_id):
    """One MoE layer's stacked expert tree as a `LayerParamStore` — experts
    play the role of layers, so `LayerStreamer(..., cyclic=True)` stages
    expert weights through a small HBM window exactly like PR 15's layer
    streaming (expert weights are the ideal streamed tier: each token's
    forward touches one expert, the rest are cold).

    Returns (store, expert_tree) — `store.layer_params(e)`-style access comes
    from the streamer; `expert_tree` is the [E, ...] source for parity checks.
    """
    from deepspeed_tpu.runtime.param_swap import LayerParamStore
    mp = params["moe"][str(layer_id)]
    expert_tree = {k: v for k, v in mp.items() if k != "gate_w"}
    return LayerParamStore(expert_tree), expert_tree
