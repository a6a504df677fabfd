"""EXAONE-MoE — the K-EXAONE family (`model_type: exaone_moe`) on the paged
serving path: window and full attention layers mixed, a leading dense layer,
then routed layers with a sigmoid router and a shared expert, served as ONE
CHIP'S SHARE of an expert-parallel deployment.

The layer, as `benchmark/references/exaone_moe.py` computes it in float32:

    q, k <- RMSNorm over each head's columns (one scale vector of head_dim)
    window layer: rotary at the absolute position, keys `i - j < window`
    full layer:   no rotary, every earlier key
    h = x + RMSNorm(attn(x) Wo)            (post-norm: nothing in front)
    y = h + RMSNorm(MLP(h))
    dense MLP:  SwiGLU of width `d_ff_dense`
    sparse MLP: sigmoid scores, the `top_k` largest `score + bias`, weights
                renormalised over the chosen and scaled; the routed experts'
                weighted sum + one shared SwiGLU expert

What is new here beside `models/moe_gpt.py`, and where each piece lives:

- THE LAYER PATTERN IS DATA (`layer_plan`): a prologue of listed layers (each
  with a parameter tree of its own) and then whole PERIODS of the pattern,
  scanned a period at a time with every position of the period traced for
  its own kind — no `lax.cond` over kinds inside the loop, so a carried pool
  is still touched by Mosaic calls only (`attention_dispatch.kv_pool_writer`'s
  rule). Kinds are compile-time: a window layer runs on a config whose
  `sliding_window` is set and `use_rotary` on, a full layer on one with
  neither, through the SAME `gpt.py::_paged_attn_half`.
- A POOL OF TWO KINDS (`inference/kv_cache.py::CacheKind`): full layers keep
  a sequence's whole context in allocator blocks (`k`/`v`
  `[Lf, N, Hkv, block, hd]`); window layers keep a per-slot ring
  (`wk`/`wv` `[Lw, 1 + slots*ring, Hkv, window_block, hd]`) that nobody
  allocates or frees. The paged programs take the tables as a PAIR
  `(full tables, ring tables)`. A KIND OWNS ITS ENTRY: its KV heads, its
  key and value widths (and with them its leaves: `AttnKind.entry`), its
  rotary base, its window and its sink are values of the configuration the
  kind's layers are traced with (`_kind_cfg`: the model's, with
  `ExaoneMoEConfig.kind_values[kind]` laid over it), so two kinds of one
  model may differ in any of them (`models/mimo_v2_flash.py`: 4 KV heads
  beside 8) and the parameter tree, the pool and both loops stay data over
  `ATTN_KINDS`.
- THE EXPERT SHARE: the router routes over all `num_experts`, this chip holds
  `experts_held = (first, count)` of them (`parallel/moe.py::routed_experts(
  held=)`), and what the others would add is left out, here and in the
  reference alike.

Not here: training, the contiguous-cache `generate()` path, the multi-token-
prediction module (it proposes tokens; the main model's logits do not depend
on it), the int8 pool, prefix caching and block transplant on a two-kind pool
(`ServingEngine` refuses them with the reason).
"""

import copy
import dataclasses
import math
from functools import partial
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.kv_cache import CacheKind
from deepspeed_tpu.models.gpt import (MixedTables, _attn_half, _embed,
                                      _last_rows, _lm_head, _paged_attn_half,
                                      _residual_mlp, decode_rows,
                                      make_mixed_paged_fn, offset_tables)
from deepspeed_tpu.models.mla import (LATENT_LEAF, entry_width, mla_attn_half,
                                      mla_shapes, paged_mla_half)
from deepspeed_tpu.models.moe_gpt import MoEGPTConfig
from deepspeed_tpu.models.sparse_attn import (INDEX_LEAF, index_shapes,
                                              paged_sparse_half,
                                              sparse_attn_half)
from deepspeed_tpu.ops import attention_dispatch as attn_dispatch
from deepspeed_tpu.ops.pallas.kv_pool import kv_leaf_shapes
from deepspeed_tpu.ops.pallas.sparse_index import SELECT_COUNTERS
from deepspeed_tpu.parallel.moe import (HELD_ROUTED_COUNTERS, routed_experts,
                                        topk_routing)

WINDOW, FULL = "sliding_attention", "full_attention"
LATENT = "latent_attention"     # MLA (`models/mla.py`): one entry a token
SELECTED = "sparse_attention"   # a learned indexer selects what a query
                                # attends (`models/sparse_attn.py`): the full
                                # kind's entry and an index key beside it
DENSE, SPARSE = "dense", "sparse"


@dataclasses.dataclass
class ExaoneMoEConfig(MoEGPTConfig):
    layer_types: Tuple[str, ...] = ()       # per layer: WINDOW | FULL
    mlp_layer_types: Tuple[str, ...] = ()   # per layer: DENSE | SPARSE
    d_ff_dense: int = 0                     # the dense layers' SwiGLU width
                                            # (`d_ff` is ONE expert's, and
                                            # the shared expert's a unit)
    num_shared_experts: int = 1             # 0: no shared expert (no leaves,
                                            # no call)
    experts_held: Optional[Tuple[int, int]] = None   # (first, count) of the
                                            # `num_experts` the router
                                            # chooses among; None = all
    router_scoring: str = "sigmoid"
    routed_scaling_factor: float = 1.0
    window_block: int = 128                 # the window kind's ring block
    pattern_period: int = 0                 # layers in a period of the
                                            # pattern (`layer_plan`); 0 =
                                            # found from the lists
    kind_values: Optional[dict] = None      # {kind: {field: value}}: what a
                                            # kind's layers are traced with
                                            # where it is not the model's
                                            # (`_kind_cfg`). None: the
                                            # family's — a full layer has
                                            # neither rotary nor a window

    def __post_init__(self):
        # what the family fixes (the published config has no key for them)
        self.use_rotary = self.use_rmsnorm = self.use_swiglu = True
        self.post_norm = self.qk_norm_per_head = True
        self.qk_norm = self.use_alibi = self.parallel_residual = False
        self.moe_freq = 1
        super().__post_init__()
        if len(self.layer_types) != self.n_layer \
                or len(self.mlp_layer_types) != self.n_layer:
            raise ValueError(
                f"layer_types and mlp_layer_types list {self.n_layer} "
                f"layers each (got {len(self.layer_types)} and "
                f"{len(self.mlp_layer_types)})")
        if WINDOW in self.layer_types and not self.sliding_window:
            raise ValueError("window layers need `sliding_window`")
        if self.kind_values is None:
            self.kind_values = {FULL: dict(use_rotary=False,
                                           sliding_window=None)}
        if self.experts_held is None:
            self.experts_held = (0, self.num_experts)
        first, count = self.experts_held
        if first < 0 or count < 1 or first + count > self.num_experts:
            raise ValueError(f"experts_held {self.experts_held} is not a "
                             f"range of the {self.num_experts} experts")


UNIT_NORMAL = "unit normal"     # an init "scale": drawn normal(0, 1), float32


def _gqa_shapes(cfg):
    """The attention leaves of a K/V kind, `cfg` the KIND's configuration."""
    D, hd = cfg.d_model, cfg.head_dim
    shapes = {
        "attn_qkv_w": ((D, cfg.qkv_dim), 0.02),
        "attn_qkv_b": ((cfg.qkv_dim,), 0.0),
        "attn_out_w": ((cfg.n_head * cfg.value_dim, D),
                       0.02 / math.sqrt(2 * cfg.n_layer)),
        "attn_out_b": ((D,), 0.0),
    }
    if cfg.qk_norm_per_head:
        shapes.update({"q_norm_scale": ((hd,), 1.0),
                       "k_norm_scale": ((hd,), 1.0)})
    if cfg.attn_sink:
        # drawn, not zero: a program that left the sink out would agree with
        # a zero sink's reference to within exp(0) in the denominator
        shapes["attn_sink"] = ((cfg.n_head,), UNIT_NORMAL)
    return shapes


def _kind_cfg(cfg, kind):
    """The configuration a kind's attention halves are traced with (and its
    leaves and its pool entry sized from): the model's with the kind's own
    values (`cfg.kind_values`) laid over it."""
    kcfg = copy.copy(cfg)                   # no `__post_init__`
    kcfg.attn_layer_types = None
    for field, value in cfg.kind_values.get(kind, {}).items():
        setattr(kcfg, field, value)
    return kcfg


def _kv_entry(cfg):
    return kv_leaf_shapes(cfg.n_kv_head, cfg.head_dim, cfg.value_dim)


@dataclasses.dataclass(frozen=True)
class AttnKind:
    """A kind of attention layer AS DATA — everything the parameter tree,
    the pool and the layer loops below need of it, so that a kind is an
    entry of `ATTN_KINDS` and not a branch in them. `shapes`, `entry` and
    `values` take the KIND's configuration (`_kind_cfg`), as `dense` and
    `paged` do: a kind owns its heads, its widths, its rotary base, its
    window and its sink."""
    prefix: str                 # its leaves of the pool pytree: this before
                                # each name of `entry` (`leaves`)
    shapes: Callable            # cfg -> its attention leaves' (shape, scale)
    dense: Callable             # the whole-sequence half (`gpt._attn_half`)
    paged: Callable             # the paged half (`gpt._paged_attn_half`)
    entry: Callable             # cfg -> {leaf name as the paged half reads
                                # it: (heads, width)} of a cached position
    values: Callable            # cfg -> the values the MODEL's entry has, a
                                # position a layer (what is stored may pad)
    name: str                   # its `CacheKind`'s name; the layers run
                                # under the `jax.named_scope` `attn_<name>`
    chunk_groups: bool = False  # its paged half runs a mixed call's GROUP
                                # of chunks (`gpt.MixedTables.count`)
    scope: str = ""             # the layers' `jax.named_scope` is
                                # `attn_<scope>` where that is not the name
    probes: bool = False        # its halves take `probe=` / `probed=`: what
                                # a check reads of the layer beside its
                                # result (`models/sparse_attn.py`)
    counters: tuple = ()        # the per-call counters its paged half
                                # books: it takes `counted=`, a list, and
                                # appends an int32 `[len(counters)]` a group
                                # of rows its kernels ran for

    def leaves(self, cfg):
        """{name as the paged half reads it: the pool's leaf}."""
        return {name: self.prefix + name for name in self.entry(cfg)}


def _kv_values(cfg):
    return cfg.n_kv_head * (cfg.head_dim + cfg.value_dim)


# window and full layers (rotary and the window belong to the window layers,
# a full layer has neither, unless the model's `kind_values` say otherwise);
# a latent layer (`models/mla.py`) rotates inside its half and caches one
# entry a token for all heads; a sparse layer (`models/sparse_attn.py`) is a
# full layer whose queries attend the positions its indexer selects, and its
# entry the full kind's with the index key a third leaf: the allocator's
# blocks, the tables and the host's books are the full kind's as they are
ATTN_KINDS = {
    FULL: AttnKind("", _gqa_shapes, _attn_half, _paged_attn_half, _kv_entry,
                   _kv_values, "full", chunk_groups=True),
    WINDOW: AttnKind("w", _gqa_shapes, _attn_half, _paged_attn_half,
                     _kv_entry, _kv_values, "window", chunk_groups=True),
    LATENT: AttnKind("", mla_shapes, mla_attn_half, paged_mla_half,
                     lambda cfg: {LATENT_LEAF: (1, entry_width(cfg))},
                     lambda cfg: cfg.kv_lora_rank + cfg.qk_rope_head_dim,
                     "latent"),
    SELECTED: AttnKind(
        "", lambda cfg: {**_gqa_shapes(cfg), **index_shapes(cfg)},
        sparse_attn_half, paged_sparse_half,
        lambda cfg: {**_kv_entry(cfg), INDEX_LEAF: (1, 128)},
        lambda cfg: _kv_values(cfg) + cfg.index_head_dim,
        "full", scope="sparse", probes=True, counters=SELECT_COUNTERS),
}


def pool_kinds(cfg):
    """The kinds of the model's POOL, the allocator's first: window and full
    layers keep a pool of two kinds (either may have no layer), latent
    layers a pool of one, sparse layers a pool of one (the full kind's
    blocks with one more leaf)."""
    for one in (LATENT, SELECTED):
        if one in cfg.layer_types:
            return (one,)
    return (FULL, WINDOW)


def layer_plan(cfg: ExaoneMoEConfig):
    """The layer pattern as data: (prologue, period, periods). `prologue`
    and `period` list `(attention kind, MLP kind)` pairs; the model is the
    prologue's layers, then `periods` repetitions of the period.

    The period's length is `cfg.pattern_period` (the published
    `sliding_window_pattern`, "LLLG": 4) and the prologue the shortest head
    that leaves whole periods (K-EXAONE as published: the dense layer and
    the three that complete its group of four, then eleven periods of
    window, window, window, full; the one-chip cut: the dense layer, then
    one). With no period given: the split that needs the fewest distinct
    layer bodies, the longer prologue among equals."""
    kinds = list(zip(cfg.layer_types, cfg.mlp_layer_types))
    n = len(kinds)

    def periodic(head, length):
        rest = kinds[head:]
        return len(rest) % length == 0 \
            and rest == rest[:length] * (len(rest) // length)

    lengths = [cfg.pattern_period] if cfg.pattern_period \
        else range(1, n + 1)
    plans = [(head + length, -head, head, length)
             for length in lengths for head in range(n - length + 1)
             if periodic(head, length)]
    if not plans:
        raise ValueError(
            f"layer_types/mlp_layer_types do not end in whole periods of "
            f"{cfg.pattern_period} layers")
    _, _, head, length = min(plans)
    return kinds[:head], kinds[head:head + length], (n - head) // length


def cache_kinds(cfg: ExaoneMoEConfig, block_size: int):
    """`CacheKind` a kind of the pool (`pool_kinds`), each with its own
    leaves and the values its entry has."""
    kcfg = _kind_cfgs(cfg)

    def kind(layer_type):
        window = layer_type == WINDOW
        attn = ATTN_KINDS[layer_type]
        return CacheKind(
            attn.name, cfg.layer_types.count(layer_type),
            cfg.window_block if window else block_size,
            int(kcfg[layer_type].sliding_window or 0) if window else 0,
            leaves=tuple(attn.leaves(kcfg[layer_type]).values()),
            entry_values=attn.values(kcfg[layer_type]),
            index_topk=kcfg[layer_type].index_topk
            if layer_type == SELECTED else 0)
    return tuple(kind(layer_type) for layer_type in pool_kinds(cfg))


def _kind_cfgs(cfg: ExaoneMoEConfig):
    """The configuration each kind's attention halves are traced with."""
    return {name: _kind_cfg(cfg, name) for name in pool_kinds(cfg)}


# ----------------------------------------------------------------------
# parameters
# ----------------------------------------------------------------------


def _layer_shapes(cfg: ExaoneMoEConfig, attn_kind, mlp_kind,
                  router_std=0.02):
    """One layer's leaves -> (shape, init scale; 1.0 = ones, 0.0 = zeros)."""
    D = cfg.d_model
    down = 0.02 / math.sqrt(2 * cfg.n_layer)
    shapes = {**ATTN_KINDS[attn_kind].shapes(_kind_cfg(cfg, attn_kind)),
              "ln1_scale": ((D,), 1.0), "ln2_scale": ((D,), 1.0)}
    if mlp_kind == DENSE:
        F = cfg.d_ff_dense
        shapes.update({"mlp_gate_w": ((D, F), 0.02), "mlp_up_w": ((D, F), 0.02),
                       "mlp_down_w": ((F, D), down),
                       "mlp_out_b": ((D,), 0.0)})
    else:
        F, Fs = cfg.d_ff, cfg.d_ff * cfg.num_shared_experts
        held = cfg.experts_held[1]
        shapes.update({
            "moe_gate_w": ((D, cfg.num_experts), router_std),
            "moe_gate_bias": ((cfg.num_experts,), 0.0),
            "moe_w_gate_up": ((held, D, 2 * F), 0.02),
            "moe_w_down": ((held, F, D), down)})
        if Fs:
            shapes.update({
                "shared_gate_w": ((D, Fs), 0.02),
                "shared_up_w": ((D, Fs), 0.02),
                "shared_down_w": ((Fs, D), down)})
    return shapes


def _make_layer(rng, cfg, kinds, dtype, lead=(), router_std=0.02):
    tree = {}
    shapes = _layer_shapes(cfg, *kinds, float(router_std))
    for name, (shape, scale) in sorted(shapes.items()):
        rng, sub = jax.random.split(rng)
        shape = tuple(lead) + shape
        if name == "moe_gate_bias":
            tree[name] = jnp.zeros(shape, jnp.float32)
        elif scale == UNIT_NORMAL:
            tree[name] = jax.random.normal(sub, shape, jnp.float32)
        elif scale in (0.0, 1.0):
            tree[name] = jnp.full(shape, scale, dtype)
        else:           # a Python float: the product stays in `dtype`
            tree[name] = jax.random.normal(sub, shape, dtype) * scale
    return tree


def exaone_moe_init_fn(cfg: ExaoneMoEConfig, dtype=jnp.float32,
                       embedding_std=0.02, router_std=0.02):
    """jax-traceable initializer (rng -> params): under one `jit` the whole
    tree is made on the device in the type it is served in. Layout:
    `prologue`: a list of layer trees; `period`: one tree a position of the
    period, every leaf with a leading `[periods]` axis; `wte`, `lm_head`,
    `lnf_scale`.

    `embedding_std`: every post-normed half adds a unit-RMS vector to the
    stream, so with the matrices' 0.02 a token's own embedding is a fiftieth
    of what its FIRST half-layer adds, the stream collapses onto what a
    sequence's tokens have in common, and a random router sends a whole
    sequence to the same few experts (at hidden 512: 46 of 128 experts idle
    over a 512-token chunk at 0.02, none at 2-4). A benchmark that wants the
    experts' loads of a trained model gives the embedding a few times the
    RMS of what the layers add — and the router's matrix `router_std` that
    much smaller, or its sigmoid saturates: at 0.02 under an embedding of 8 a
    tenth of the 128 scores round to exactly 1.0 in float32, `top_k` breaks
    the ties by index, and the low experts get twice their share."""
    prologue, period, periods = layer_plan(cfg)

    def init(rng):
        keys = jax.random.split(rng, 2 + len(prologue) + len(period))
        V, D = cfg.vocab_size, cfg.d_model
        params = {
            "wte": jax.random.normal(keys[0], (V, D), dtype)
            * float(embedding_std),
            "lm_head": jax.random.normal(keys[1], (V, D), dtype) * 0.02,
            "lnf_scale": jnp.ones((D,), dtype),
            "prologue": [_make_layer(k, cfg, kinds, dtype,
                                     router_std=router_std)
                         for k, kinds in zip(keys[2:], prologue)],
            "period": [_make_layer(k, cfg, kinds, dtype, lead=(periods,),
                                   router_std=router_std)
                       for k, kinds
                       in zip(keys[2 + len(prologue):], period)],
        }
        return params

    return init


_EXPERT_STACKS = ("moe_w_gate_up", "moe_w_down")


def _period_stacks(params):
    """(the scanned layers' small leaves, their expert stacks), a position
    of the period each: the small leaves keep their leading `[periods]` axis
    (a scan slices them a period), the experts are flat `[periods * held,
    ...]` in `routed_experts`' names and stay WHOLE — a layer finds its
    experts by index (`expert_base = period * held`), because a slice of a
    stack in front of the grouped matmul is a copy of a layer's experts."""
    small = [{k: v for k, v in tree.items() if k not in _EXPERT_STACKS}
             for tree in params["period"]]
    stacks = [{k[len("moe_"):]: v.reshape((-1,) + v.shape[2:])
               for k, v in tree.items() if k in _EXPERT_STACKS}
              for tree in params["period"]]
    return small, stacks


def _layers(params, cfg):
    """Every layer in model order as (its small leaves, `_mlp_fn`'s expert
    keywords): the unscanned forward's loop (the paged programs scan the
    periods instead)."""
    _, period, periods = layer_plan(cfg)
    held = cfg.experts_held[1]
    for tree in params["prologue"]:
        yield tree, {}
    small, stacks = _period_stacks(params)
    for n in range(periods):
        for i, (_, mlp_kind) in enumerate(period):
            yield (jax.tree_util.tree_map(lambda a: a[n], small[i]),
                   dict(stacks=stacks[i], expert_base=n * held)
                   if mlp_kind == SPARSE else {})


# ----------------------------------------------------------------------
# the MLP halves
# ----------------------------------------------------------------------


def _swiglu(h, gate_w, up_w, down_w):
    return (jax.nn.silu(h @ gate_w) * (h @ up_w)) @ down_w


def _sparse_mlp(h, p, cfg: ExaoneMoEConfig, stacks=None, expert_base=0):
    """The routed half of a sparse layer on h [B, T, D] -> (out, counters
    int32[5] in `HELD_ROUTED_COUNTERS` order, chosen experts [B*T, top_k]);
    the shared expert's result (where `p` has one) times `sigmoid(h w_s)`
    where `p` has a `shared_scale_w` [D].
    `stacks`: the experts' weights where they are not `p`'s own leaves —
    `{"w_gate_up": [n * held, D, 2F], "w_down": ...}`, a whole stack of the
    scanned layers' experts, with `expert_base` where this layer's begin."""
    B, T, D = h.shape
    xf = h.reshape(B * T, D)
    top_p, top_e = topk_routing(
        xf, p["moe_gate_w"], cfg.top_k, cfg.norm_topk_prob,
        scoring=cfg.router_scoring, bias=p.get("moe_gate_bias"),
        scale=cfg.routed_scaling_factor)
    if stacks is None:
        stacks = {"w_gate_up": p["moe_w_gate_up"], "w_down": p["moe_w_down"]}
    out, counters = routed_experts(xf, top_p, top_e, stacks,
                                   expert_base=expert_base,
                                   held=cfg.experts_held)
    if "shared_gate_w" not in p:        # `num_shared_experts` 0
        return out.reshape(B, T, D), counters, top_e
    with jax.named_scope("moe/shared_expert"):
        shared = _swiglu(xf, p["shared_gate_w"], p["shared_up_w"],
                         p["shared_down_w"])
        if "shared_scale_w" in p:       # Qwen3-Next: times a scalar a token
            shared = shared * jax.nn.sigmoid(
                (xf @ p["shared_scale_w"]).astype(jnp.float32)
            )[:, None].astype(shared.dtype)
        out = out + shared
    return out.reshape(B, T, D), counters, top_e


def _mlp_fn(p, cfg, mlp_kind, counts=None, routing=None, stacks=None,
            expert_base=0):
    """`_residual_mlp`'s `mlp_fn` for one layer; a sparse layer's counters
    and chosen experts are appended to `counts` / `routing` where given."""
    if mlp_kind == DENSE:
        return lambda h: _swiglu(h, p["mlp_gate_w"], p["mlp_up_w"],
                                 p["mlp_down_w"])

    def mlp_fn(h):
        out, counted, top_e = _sparse_mlp(h, p, cfg, stacks, expert_base)
        if counts is not None:
            counts.append(counted)
        if routing is not None:
            routing.append(top_e)
        return out
    return mlp_fn


# ----------------------------------------------------------------------
# the whole-sequence forward (no cache): what the tests and the reference
# check read the program's own routing from
# ----------------------------------------------------------------------


def exaone_moe_forward(params, tokens, cfg: ExaoneMoEConfig, routing=None,
                       probed=None):
    """tokens [B, T] -> logits [B, T, V]: dense masked attention, a Python
    loop over the layers. `routing`: a list that takes each sparse layer's
    chosen experts [B*T, top_k]; `probed`: a list that takes what each layer
    of a kind that `probes` hands out (`AttnKind.probes`)."""
    B, T = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (B, T))
    kcfg = _kind_cfgs(cfg)
    x = _embed(params, tokens, positions, cfg)
    kinds = zip(cfg.layer_types, cfg.mlp_layer_types)
    for (p, experts), (attn_kind, mlp_kind) in zip(_layers(params, cfg),
                                                   kinds):
        kind = ATTN_KINDS[attn_kind]
        attn_out, _, _ = kind.dense(
            x, p, kcfg[attn_kind], positions, constrain=False,
            **(dict(probed=probed)
               if kind.probes and probed is not None else {}))
        x = _residual_mlp(x, attn_out, p, cfg, constrain=False,
                          mlp_fn=_mlp_fn(p, cfg, mlp_kind, routing=routing,
                                         **experts))
    return _lm_head(params, x, cfg)


def exaone_moe_cache_identity(cfg: ExaoneMoEConfig, name: str = "") -> str:
    return (f"exaone_moe:{name}|{cfg.n_layer}|{cfg.d_model}|{cfg.n_head}|"
            f"{cfg.n_kv_head}|{cfg.head_dim}|{cfg.sliding_window}|"
            f"{','.join(t[0] for t in cfg.layer_types)}|"
            f"{','.join(t[0] for t in cfg.mlp_layer_types)}|"
            f"{cfg.num_experts}|{cfg.experts_held}|{cfg.top_k}|"
            f"{cfg.router_scoring}|{cfg.routed_scaling_factor}|"
            f"{cfg.rope_theta}|{cfg.norm_eps}")


# ----------------------------------------------------------------------
# the paged programs
# ----------------------------------------------------------------------

def make_exaone_moe_decode_model(cfg: ExaoneMoEConfig, params=None,
                                 name="exaone-moe", seed=0,
                                 family="exaone_moe", fingerprint=None):
    """The paged serving contract (`DecodeModelSpec`) of the family. The
    paged programs take `block_tables` as the PAIR `(full tables [B, nb],
    ring tables [B, nbw])` and a pool of two kinds (module docstring).

    `prefill_paged_fn` and `decode_paged_fn` take two keywords beside the
    contract's arguments: `routing=True` adds a FOURTH result, the experts
    the call routed every row to — int32 `[sparse layers, B, C, top_k]`
    (`C` 1 for decode), ascending in a token. The scheduler never passes it
    (it takes three results); a check that holds a reference to the served
    programs' own choices calls the served spec's functions with it.
    `probe=<a chunk row's index, int32 scalar>` (a model whose attention
    kind `probes`: `models/sparse_attn.py`) adds a LAST result, (index
    scores float32, selection bool), each `[layers, rows, nb * block]`: of
    that row of the chunk, then of every slot's row (a mixed call: 1 + S
    rows; a prefill call 1; a decode call S)."""
    from deepspeed_tpu.inference.engine import DecodeModelSpec
    if params is None:
        params = exaone_moe_init_fn(cfg)(jax.random.PRNGKey(seed))
    prologue, period, periods = layer_plan(cfg)
    kcfg = _kind_cfgs(cfg)
    kinds = {name: ATTN_KINDS[name] for name in pool_kinds(cfg)}
    # a kind's leaves: {name its paged half reads: the pool's leaf}
    leaves = {name: kind.leaves(kcfg[name]) for name, kind in kinds.items()}
    held = cfg.experts_held[1]
    no_counts = jnp.zeros((len(HELD_ROUTED_COUNTERS),), jnp.int32)
    # the counters a kind's kernels book (ONE kind of a pool may), after the
    # routed experts'
    attn_counters = tuple(c for kind in kinds.values() for c in kind.counters)
    assert sum(1 for kind in kinds.values() if kind.counters) <= 1
    pool_writers, attn_programs = {}, {}

    def total(counts, attn_counts):
        """A group of layers' counters as one vector, in `step_counters`'
        order."""
        acc = sum(counts, no_counts)
        if not attn_counters:
            return acc
        return jnp.concatenate([acc, sum(
            attn_counts, jnp.zeros((len(attn_counters),), jnp.int32))])

    def per_period(kind):
        return sum(1 for attn, _ in period if attn == kind)

    def _layers_paged(params, x, pool, block_tables, positions, routing,
                      probe=None):
        # a pool of one kind takes its tables bare, of two as the pair
        if len(kinds) == 1:
            block_tables = (block_tables,)
        tables = dict(zip(kinds, block_tables))
        # a mixed call (`gpt.py::MixedTables`): a chunk's rows, then a
        # decode row a slot, each kind's tables the pair of the two groups'
        mixed = isinstance(block_tables[0], MixedTables)
        site = "mixed" if mixed else \
            "paged_decode" if x.shape[1] == 1 else "prefill_chunk"
        in_place = all(
            attn_dispatch.kv_pool_writer(
                {n: pool[leaf] for n, leaf in leaves[name].items()})
            == attn_dispatch.KV_POOL_WRITE_KERNEL
            for name in kinds)
        pool_writers[site] = attn_dispatch.KV_POOL_WRITE_KERNEL if in_place \
            else attn_dispatch.KV_POOL_WRITE_SCATTER
        first = {name: pool[next(iter(leaves[name].values()))]
                 for name in kinds}
        blocks_of = {name: leaf.shape[1] for name, leaf in first.items()}
        # one work list a KIND, built once a token, outside the layer loop
        work = dict.fromkeys(kinds)
        if site != "prefill_chunk":
            from deepspeed_tpu.ops.pallas.decode_attention import \
                paged_decode_work
            work = {name: paged_decode_work(
                *decode_rows(tables[name], positions), first[name].shape[3],
                window=kcfg[name].sliding_window)
                for name in kinds}
        # every kind's leaves flat and CARRIED: layer i of a kind addresses
        # its blocks as `table + i * N`
        flat = {k: v.reshape((-1,) + v.shape[2:]) for k, v in pool.items()}

        def layer(x, flat, p, layer_kinds, kind_index, counts, attn_counts,
                  probed=None, **experts):
            # `experts`: `_mlp_fn`'s keywords (routing=, stacks=, expert_base=)
            attn_kind, mlp_kind = layer_kinds
            kind = kinds[attn_kind]
            base = kind_index * blocks_of[attn_kind]
            # the kernels take the layer's offset; the scatter and the
            # gather of the other form take tables already offset
            where = dict(block_base=base) if in_place else {}
            if kind.counters:
                where["counted"] = attn_counts
            if probed is not None and kind.probes:
                # the layer's groups of rows, in order, as ONE entry
                mine = []
                where["probe"] = (probe, mine)
                probed.append(mine)
            table = tables[attn_kind] if in_place \
                else offset_tables(tables[attn_kind], base)
            with jax.named_scope(f"attn_{kind.scope or kind.name}"):
                attn_out, pool_l = kind.paged(
                    x, p, {n: flat[leaf]
                           for n, leaf in leaves[attn_kind].items()},
                    positions, table, kcfg[attn_kind],
                    decode_work=work[attn_kind],
                    attn_programs=attn_programs, **where)
            flat = {**flat, **{leaf: pool_l[n]
                               for n, leaf in leaves[attn_kind].items()}}
            with jax.named_scope("mlp"):
                x = _residual_mlp(x, attn_out, p, cfg, constrain=False,
                                  mlp_fn=_mlp_fn(p, cfg, mlp_kind, counts,
                                                 **experts))
            return x, flat

        counts, attn_counts = [], []
        chosen = [] if routing else None     # a layer's [B*C, top_k]
        probes = [] if probe is not None else None   # a layer's groups
        seen = dict.fromkeys(kinds, 0)
        for p, layer_kinds in zip(params["prologue"], prologue):
            x, flat = layer(x, flat, p, layer_kinds, seen[layer_kinds[0]],
                            counts, attn_counts, probes, routing=chosen)
            seen[layer_kinds[0]] += 1
        acc = total(counts, attn_counts)

        if periods:
            # the scan slices the small leaves a period; the expert stacks
            # stay whole (closed over, like the carried pool) and a layer
            # finds its experts by index
            scanned, stacks = _period_stacks(params)

            def body(carry, inputs):
                x, flat, acc = carry
                trees, n = inputs
                counts, attn_counts = [], []
                routed = [] if routing else None
                probed = [] if probe is not None else None
                rank = dict.fromkeys(kinds, 0)
                for i, layer_kinds in enumerate(period):
                    kind = layer_kinds[0]
                    index = seen[kind] + n * per_period(kind) + rank[kind]
                    rank[kind] += 1
                    experts = dict(stacks=stacks[i], expert_base=n * held) \
                        if layer_kinds[1] == SPARSE else {}
                    x, flat = layer(x, flat, trees[i], layer_kinds, index,
                                    counts, attn_counts, probed,
                                    routing=routed, **experts)
                return (x, flat, acc + total(counts, attn_counts)), \
                    (routed, probed)

            (x, flat, acc), (routed, probed) = jax.lax.scan(
                body, (x, flat, acc),
                (scanned, jnp.arange(periods, dtype=jnp.int32)))
            if routing and routed:
                # [periods, B*C, k] a sparse position -> model order
                chosen += [r[n] for n in range(periods) for r in routed]
            if probed:
                probes += [jax.tree_util.tree_map(lambda a: a[n], groups)
                           for n in range(periods) for groups in probed]
        pool = {k: v.reshape(pool[k].shape) for k, v in flat.items()}
        out = (x, pool, acc)
        if routing:
            B, C = positions.shape
            out += (jnp.stack(
                [jnp.sort(e, axis=-1).reshape(B, C, -1) for e in chosen]),)
        if probes is not None:
            # a layer's groups of rows one after another: [layers, rows, S]
            out += (tuple(
                jnp.stack([jnp.concatenate([g[i] for g in groups])
                           for groups in probes]) for i in range(2)),)
        return out

    def prefill_paged_fn(params, tokens, start_pos, last_idx, pool,
                         block_tables, routing=False, probe=None):
        B, C = tokens.shape
        positions = start_pos[:, None] + jnp.arange(C, dtype=jnp.int32)[None]
        x = _embed(params, tokens, positions, cfg)
        x, pool, *counted = _layers_paged(params, x, pool, block_tables,
                                          positions, routing, probe)
        return (_lm_head(params, _last_rows(x, last_idx), cfg)[:, 0], pool,
                *counted)

    def decode_paged_fn(params, token, pos, pool, block_tables,
                        routing=False, probe=None):
        x = _embed(params, token[:, None], pos[:, None], cfg)
        x, pool, *counted = _layers_paged(params, x, pool, block_tables,
                                          pos[:, None], routing, probe)
        return (_lm_head(params, x, cfg)[:, 0], pool, *counted)

    def init_paged_pool(num_blocks, block_size, dtype=jnp.bfloat16,
                        kv_group_size=0, window_blocks=None):
        if jnp.dtype(dtype) == jnp.int8:
            raise ValueError(
                f"model spec '{name}': the int8 pool is not built for a pool "
                f"of kinds {'/'.join(k.name for k in kinds.values())} (a "
                f"window kind's rings, a latent kind's entries and a sparse "
                f"layer's index keys have no scale leaves, their walks no "
                f"dequantizing twin)")
        if WINDOW in kinds and window_blocks is None:
            raise ValueError(
                f"model spec '{name}' keeps a pool of two kinds: "
                f"init_paged_pool needs `window_blocks` (1 + slots * "
                f"kv_cache.ring_blocks(...)), as ServingEngine passes it")
        pool = {}
        for attn, kind in zip(kinds, cache_kinds(cfg, block_size)):
            blocks = window_blocks if kind.window else num_blocks
            for n, (heads, width) in kinds[attn].entry(kcfg[attn]).items():
                pool[leaves[attn][n]] = jnp.zeros(
                    (kind.layers, blocks, heads, kind.block, width), dtype)
        return pool

    def unserved(*_args, **_kwargs):
        raise NotImplementedError(
            f"model spec '{name}' ({family}) is served through the paged "
            f"scheduler only (`engine.serving(...)`): the contiguous-cache "
            f"generate() path is not built for a pool of kinds")

    return DecodeModelSpec(prefill_fn=unserved, decode_fn=unserved,
                           init_cache=unserved, params=params, name=name,
                           prefill_paged_fn=prefill_paged_fn,
                           decode_paged_fn=decode_paged_fn,
                           mixed_paged_fn=make_mixed_paged_fn(
                               cfg, partial(_layers_paged, routing=False)),
                           mixed_chunk_groups=all(
                               kind.chunk_groups for kind in kinds.values()),
                           init_paged_pool=init_paged_pool,
                           paged_cache_kinds=lambda block_size: cache_kinds(
                               cfg, block_size),
                           kv_pool_writers=pool_writers,
                           paged_attn_programs=attn_programs,
                           step_counters=HELD_ROUTED_COUNTERS
                           + attn_counters,
                           cache_fingerprint=fingerprint
                           or exaone_moe_cache_identity(cfg, name))
