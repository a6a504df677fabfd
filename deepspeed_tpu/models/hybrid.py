"""The hybrid layer loop — ONE loop for the families whose stack mixes
recurrent layers (Mamba-2, or Gated DeltaNet), a few attention layers and
routed experts, on the paged serving path: `models/nemotron_h.py` (a layer
is a mixer OR a feed-forward part alone), `models/granite_moe_hybrid.py` and
`models/qwen3_next.py` (a layer is a mixer AND the routed experts) give it
their data and run the same code.

A stack is a list of HALVES, each `x <- x + r * f(RMSNorm(x))` with `r` the
family's `residual_multiplier`, `f` by the half's letter:

    M  Mamba-2:   [z | xBC | dt] = u W_in; xBC = silu(conv1d(xBC)) (causal,
                  depthwise, width 4) = [x | B | C]; dt = softplus(dt + bias);
                  S_t = exp(dt_t A_h) S_(t-1) + dt_t x_t (outer) B_t;
                  y_t = S_t C_t + D_h x_t; out = RMSNorm_group(y * silu(z)) W_out
    D  Gated DeltaNet: [q | k | v | z] = u W_qkvz, [b | a] = u W_ba;
                  [q | k | v] = silu(conv1d(.)) (causal, depthwise, no bias);
                  q = q / |q| / sqrt(K), k = k / |k| a key head, each serving
                  H / G value heads; beta = sigmoid(b); g = -exp(A_log)
                  softplus(a + dt_bias); S_t = exp(g_t) S_(t-1) + k_t (outer)
                  beta_t (v_t - exp(g_t) S_(t-1)^T k_t); o_t = S_t^T q_t;
                  out = (RMSNorm_V(o) * w * silu(z)) W_out
    *  attention: grouped-query, causal, no bias, the scores scaled by
                  `scale_attn`; without positions, or (`rotary_attention`)
                  rotated over `rotary_pct` of a head, with `gpt.py`'s
                  per-head q/k norm and output gate where the family has them
    E  the family's expert half (`expert_half`): Nemotron-H's LatentMoE,
       Granite's gated experts beside a shared one
    F  a dense gated feed-forward, (silu(u W_g) * (u W_u)) W_d: `gpt.py::_mlp`

Under `cfg.post_norm` (the Olmo-2 order) a half reads the stream as it is
and its OUTPUT is normed: `x <- x + r * RMSNorm(f(x))`, the half's one norm
scale on the other side of `f`.

What a family gives: `HybridConfig.pattern`, a BLOCK a layer, each block a
string of its halves' letters ("EMEM*": a half a layer; ("ME", "ME", "*E"):
two); its `expert_half` and the names of that half's expert stacks; the
scalars (`residual_multiplier` here, `scale_attn`, `embedding_multiplier`,
`logits_scaling` and `tie_embeddings` in `gpt.py`).

- THE STATE KIND (`inference/kv_cache.py::CacheKind(state=True)`): a
  recurrent half keeps, per slot and not per token, its state `ssm`
  `[Lm, 1 + slots, H, P, N]` float32 (Gated DeltaNet: `[.., H, K, V]`) and
  the last `conv_kernel - 1` inputs of its convolution `conv`
  `[Lm, 1 + slots, K - 1, W]` (row 0 the trash row): `state_leaves` has a
  family's shapes, and a stack has ONE recurrent kind.
  Nobody allocates, frees or walks it. A prefill chunk reads its slot's row
  (zeros where the chunk starts at position 0: a slot newly admitted), runs
  the recurrence in its chunked form from there (`ops/pallas/ssm.py::
  ssm_chunk_scan`) and writes the row back; a decode token reads and
  rewrites every row whole, in place (`dstpu_ssm_update`; the delta rule's
  `dstpu_gdn_update` and `ops/pallas/gdn.py::gdn_chunk_scan`). A chunk's
  padded tail leaves the state alone (`dt = 0` past the last real position —
  the delta rule: `g = 0` and `beta = 0` — and the
  convolution's tail taken from the last REAL inputs); a dead slot's row is
  the trash row. The paged programs take `block_tables` as the PAIR (KV
  tables [B, nb], state rows [B, 1]).
- THE PATTERN IS DATA (`models/layer_pattern.py::repeated_runs`, over the
  blocks): the stack is a list of runs, each a unit of halves and how often
  it repeats ("EMEMEMEMEM*": ("EM", 5), ("*", 1); nine `ME` blocks around
  one `*E`: ("ME", 5), ("*E", 1), ("ME", 4)); a repeated unit is scanned,
  every position of the unit traced for its own kind.
- THE EXPERT SHARE, as K-EXAONE's: the router routes over all `num_experts`,
  this chip holds `experts_held = (first, count)`, and what the others would
  add is left out, here and in the references alike.

TRAINING (`hybrid_loss`, `hybrid_param_specs`; `models/olmo_hybrid.py` is
the family that trains): the same halves over whole sequences from a zero
state, a `jax.checkpoint` a HALF (a recurrent half: a segment of 4096
positions of it, on the carried state) that holds what fits of the results
its backward reads (`held_candidates`: the flash kernel's residuals first,
then the attention half's and the feed-forwards' products, then the delta
rule's scan output a segment; nothing where no budget is installed), a
run's repeats scanned, the head through `ops/chunked_ce.py`;
the delta rule's chunked scan brings its own backward
(`ops/pallas/gdn.py`). What does not train: the
expert half `E` (its halves count what they route, which has no gradient
path here), packed documents (a state reset and a block-diagonal mask at a
boundary), the recurrent halves under sequence parallelism (a chunk's state
would cross devices).

Not here: the contiguous-cache `generate()` path, a decode spec for a stack
with `F` halves or `post_norm`, and on a pool with a state kind the int8 pool,
prefix caching, speculative verify and block transplant (`ServingEngine`
refuses them with the reason).
"""

import copy
import dataclasses
import math
from functools import partial
from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.kv_cache import CacheKind
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.comm.mesh import (BATCH_AXES, SEQ_AXIS, TENSOR_AXIS,
                                     shard_constraint)
from deepspeed_tpu.models.gpt import (MLP_PRODUCT, QKV_PRODUCT, MixedTables,
                                      _attn_half, _embed, _half_input,
                                      _head_table, _last_rows, _lm_head,
                                      _mlp, _norm, _paged_attn_half,
                                      _train_attn_site, decode_rows,
                                      make_mixed_paged_fn, offset_tables,
                                      on_one_device, over_chunk_group)
from deepspeed_tpu.models.layer_pattern import repeated_runs
from deepspeed_tpu.models.moe_gpt import MoEGPTConfig
from deepspeed_tpu.ops import attention_dispatch as attn_dispatch
from deepspeed_tpu.ops.pallas import gdn, ssm
from deepspeed_tpu.ops.pallas.flash_attention import FLASH_RESIDUALS
from deepspeed_tpu.parallel.moe import HELD_ROUTED_COUNTERS

MAMBA, DELTANET, ATTENTION, MOE, DENSE = "M", "D", "*", "E", "F"


@dataclasses.dataclass
class HybridConfig(MoEGPTConfig):
    pattern: Any = ""                   # a block a layer: a string (a letter
                                        # a layer) or a tuple of strings
    mamba_num_heads: int = 128
    mamba_head_dim: int = 64
    ssm_state_size: int = 128
    n_groups: int = 8                   # groups that share B and C
    conv_kernel: int = 4
    chunk_size: int = 128               # positions a chunk of the scan
    gdn_key_heads: int = 16             # Gated DeltaNet: G key heads (q, k)
    gdn_value_heads: int = 32           # serve H value heads, of widths
    gdn_key_dim: int = 128              # K and
    gdn_value_dim: int = 128            # V
    gdn_beta_scale: float = 1.0         # beta = this * sigmoid(b): 2.0 lets
                                        # I - beta k k^T take a NEGATIVE
                                        # eigenvalue, 1 - beta in (-1, 1)
    rotary_attention: bool = False      # the attention halves rotate q and k
    shared_d_ff: int = 0                # the shared expert's, at full width
    experts_held: Optional[Tuple[int, int]] = None  # (first, count); None=all
    residual_multiplier: float = 1.0    # `r`: what a half adds, times this
    time_step_min: float = 0.001        # `dt_bias` is drawn so that
    time_step_max: float = 0.1          # softplus(dt_bias) is log-uniform
    time_step_floor: float = 1e-4       # between these, floored

    def __post_init__(self):
        # what the families share. `use_rotary` is how `gpt.py::_embed` knows
        # a model WITHOUT learned positions; the attention halves are traced
        # on a copy with it off (`_attention_cfg`): they rotate nothing
        self.use_rotary = self.use_rmsnorm = True
        self.use_alibi, self.sliding_window = False, None
        self.moe_freq, self.n_layer = 1, len(self.pattern)
        super().__post_init__()
        if not self.pattern \
                or set(self.halves) - {MAMBA, DELTANET, ATTENTION, MOE,
                                       DENSE} \
                or {MAMBA, DELTANET} <= set(self.halves):
            raise ValueError(f"pattern {self.pattern!r}: a letter a layer, "
                             f"or a block of them, of {MAMBA!r} or "
                             f"{DELTANET!r} (one recurrent kind a stack), "
                             f"{ATTENTION!r}, {MOE!r}, {DENSE!r}")
        if self.mamba_num_heads % self.n_groups \
                or self.ssm_inner % self.n_groups \
                or self.gdn_value_heads % self.gdn_key_heads:
            raise ValueError("mamba_num_heads and the inner width divide "
                             "into n_groups, gdn_value_heads into "
                             "gdn_key_heads")
        if MOE not in self.halves:      # no expert half: no experts asked for
            return
        if self.experts_held is None:
            self.experts_held = (0, self.num_experts)
        first, count = self.experts_held
        if first < 0 or count < 1 or first + count > self.num_experts:
            raise ValueError(f"experts_held {self.experts_held} is not a "
                             f"range of the {self.num_experts} experts")

    @property
    def halves(self):
        """Every half's letter, in model order."""
        return "".join(self.pattern)

    @property
    def ssm_inner(self):
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_width(self):
        return self.ssm_inner + 2 * self.n_groups * self.ssm_state_size

    @property
    def gdn_widths(self):
        """(q, k, v) columns of a Gated DeltaNet half; z is v's again."""
        qk = self.gdn_key_heads * self.gdn_key_dim
        return qk, qk, self.gdn_value_heads * self.gdn_value_dim


def _attention_cfg(cfg: HybridConfig):
    acfg = copy.copy(cfg)                       # no `__post_init__`
    acfg.use_rotary = cfg.rotary_attention
    return acfg


def state_leaves(cfg: HybridConfig):
    """The state kind's leaves, a slot's row of a layer -> shape: the
    recurrent state (float32) and the convolution's tail, by the stack's
    recurrent kind."""
    if DELTANET in cfg.halves:
        return {"ssm": (cfg.gdn_value_heads, cfg.gdn_key_dim,
                        cfg.gdn_value_dim),
                "conv": (cfg.conv_kernel - 1, sum(cfg.gdn_widths))}
    return {"ssm": (cfg.mamba_num_heads, cfg.mamba_head_dim,
                    cfg.ssm_state_size),
            "conv": (cfg.conv_kernel - 1, cfg.conv_width)}


def cache_kinds(cfg: HybridConfig, block_size: int):
    """`CacheKind` a kind of cache: the attention halves' blocks, then the
    recurrent halves' per-slot state."""
    return (CacheKind("full", cfg.halves.count(ATTENTION), block_size,
                      leaves=("k", "v")),
            CacheKind("state",
                      cfg.halves.count(MAMBA) + cfg.halves.count(DELTANET),
                      0, leaves=tuple(state_leaves(cfg)), state=True))


def layer_runs(cfg: HybridConfig):
    """The pattern as data: [(unit: its halves' letters, repeats), ...]."""
    return [("".join(unit), repeats)
            for unit, repeats in repeated_runs(cfg.pattern)]


# ----------------------------------------------------------------------
# parameters
# ----------------------------------------------------------------------

# leaves that stay float32 whatever the tree is served in
_FLOAT32_LEAVES = ("dt_bias", "A_log", "ssm_D", "moe_gate_bias")


def stream_range(cfg: HybridConfig):
    """The range every projection back into the stream is drawn at
    (`rescale_prenorm_residual`)."""
    return 0.02 / math.sqrt(2 * cfg.n_layer)


def mixer_shapes(cfg: HybridConfig, kind):
    """A recurrent, attention or dense half's leaves -> (shape, init: a float =
    normal of that range, 1.0 = ones, 0.0 = zeros, a name = `make_layer`'s
    own rule). A family's `layer_shapes(cfg, kind, router_std)` adds its
    expert half's."""
    D, hd = cfg.d_model, cfg.head_dim
    down = stream_range(cfg)
    shapes = {"ln1_scale": ((D,), 1.0)}
    if kind == ATTENTION:
        shapes.update({
            "attn_qkv_w": ((D, cfg.qkv_dim), 0.02),
            "attn_qkv_b": ((cfg.qkv_dim,), 0.0),
            "attn_out_w": ((cfg.n_head * hd, D), down),
            "attn_out_b": ((D,), 0.0)})
        if cfg.qk_norm_per_head:
            shapes.update({"q_norm_scale": ((hd,), 1.0),
                           "k_norm_scale": ((hd,), 1.0)})
        if cfg.qk_norm:                 # over the whole projection
            shapes.update({"q_norm_scale": ((cfg.n_head * hd,), 1.0),
                           "k_norm_scale": ((cfg.n_kv_head * hd,), 1.0)})
    elif kind == DENSE:
        shapes.update({
            "mlp_gate_w": ((D, cfg.d_ff), 0.02),
            "mlp_up_w": ((D, cfg.d_ff), 0.02),
            "mlp_down_w": ((cfg.d_ff, D), down),
            "mlp_out_b": ((D,), 0.0)})
    elif kind == MAMBA:
        H, inner, W = cfg.mamba_num_heads, cfg.ssm_inner, cfg.conv_width
        shapes.update({
            "ssm_in_w": ((D, inner + W + H), 0.02),
            "conv_w": ((cfg.conv_kernel, W), "conv"),
            "conv_b": ((W,), "conv"),
            "dt_bias": ((H,), "dt_bias"), "A_log": ((H,), "A_log"),
            "ssm_D": ((H,), 1.0),
            "gate_norm_scale": ((inner,), 1.0),
            "ssm_out_w": ((inner, D), down)})
    elif kind == DELTANET:
        H, (Wq, Wk, Wv) = cfg.gdn_value_heads, cfg.gdn_widths
        shapes.update({
            "gdn_qkvz_w": ((D, Wq + Wk + 2 * Wv), 0.02),
            "gdn_ba_w": ((D, 2 * H), 0.02),
            "conv_w": ((cfg.conv_kernel, Wq + Wk + Wv), "conv"),
            "dt_bias": ((H,), "dt_bias"), "A_log": ((H,), "A_log"),
            "gate_norm_scale": ((cfg.gdn_value_dim,), 1.0),
            "gdn_out_w": ((Wv, D), down)})
    return shapes


def make_layer(rng, cfg, shapes, dtype, lead):
    """One half's tree from `shapes` (`mixer_shapes`' form), every leaf with
    the leading axes `lead`."""
    tree = {}
    for name, (shape, how) in sorted(shapes.items()):
        rng, sub = jax.random.split(rng)
        shape = tuple(lead) + shape
        leaf_dtype = jnp.float32 if name in _FLOAT32_LEAVES else dtype
        if how == "dt_bias":
            # softplus(dt_bias) log-uniform in [time_step_min, time_step_max]
            lo, hi = math.log(cfg.time_step_min), math.log(cfg.time_step_max)
            dt = jnp.maximum(jnp.exp(jax.random.uniform(
                sub, shape, jnp.float32, lo, hi)), cfg.time_step_floor)
            tree[name] = dt + jnp.log(-jnp.expm1(-dt))
        elif how == "conv":
            # a depthwise convolution is left at its framework default in
            # the published initialiser: uniform within 1 / sqrt(kernel)
            bound = 1.0 / math.sqrt(cfg.conv_kernel)
            tree[name] = jax.random.uniform(sub, shape, jnp.float32, -bound,
                                            bound).astype(leaf_dtype)
        elif how == "A_log":
            tree[name] = jnp.log(jax.random.uniform(sub, shape, jnp.float32,
                                                    1.0, 16.0))
        elif how in (0.0, 1.0):
            tree[name] = jnp.full(shape, how, leaf_dtype)
        else:           # a Python float: the product stays in `dtype`
            tree[name] = jax.random.normal(sub, shape, leaf_dtype) * how
    return tree


def hybrid_init_fn(cfg: HybridConfig, layer_shapes, dtype=jnp.float32,
                   embedding_std=0.02, router_std=0.02):
    """jax-traceable initializer (rng -> params): under one `jit` the whole
    tree is made on the device in the type it is served in. Layout: `runs`:
    a list, a run of `layer_runs(cfg)`, of one tree a position of the run's
    unit, every leaf with a leading `[repeats]` axis; `wte`, `lnf_scale`
    and, where the head is not tied, `lm_head`. `layer_shapes(cfg, kind,
    router_std)`: the family's leaves of a half. `embedding_std` /
    `router_std`: as `exaone_moe_init_fn`'s (a benchmark's way to the loads
    of a trained router)."""
    runs = layer_runs(cfg)

    def init(rng):
        keys = iter(jax.random.split(
            rng, 2 + sum(len(unit) for unit, _ in runs)))
        V, D = cfg.vocab_size, cfg.d_model
        wte_key, head_key = next(keys), next(keys)
        tree = {"wte": jax.random.normal(wte_key, (V, D), dtype)
                * float(embedding_std),
                "lnf_scale": jnp.ones((D,), dtype)}
        if not cfg.tie_embeddings:
            tree["lm_head"] = jax.random.normal(head_key, (V, D), dtype) * 0.02
        tree["runs"] = [
            [make_layer(next(keys), cfg,
                        layer_shapes(cfg, kind, float(router_std)), dtype,
                        (repeats,)) for kind in unit]
            for unit, repeats in runs]
        return tree

    return init


def _split_stacks(tree, names):
    """(a scanned half's small leaves, its expert stacks `names`): the small
    leaves keep their leading `[repeats]` axis (a scan slices them), the
    experts are flat `[repeats * held, ...]` in `routed_experts`' names and
    stay WHOLE — a layer finds its experts by index (`expert_base`), because
    a slice of a stack in front of the grouped matmul is a copy of them."""
    small = {k: v for k, v in tree.items() if k not in names}
    stacks = {k[len("moe_"):]: v.reshape((-1,) + v.shape[2:])
              for k, v in tree.items() if k in names}
    return small, stacks


# ----------------------------------------------------------------------
# the Mamba-2 half
# ----------------------------------------------------------------------


def _conv(seq, p, T):
    """The causal depthwise convolution and its SiLU: seq [b, T + K - 1, W]
    (the K - 1 inputs before the first position, then the T positions')
    -> [b, T, W] in `seq.dtype`."""
    w = p["conv_w"].astype(jnp.float32)
    out = sum(w[k] * seq[:, k:k + T].astype(jnp.float32)
              for k in range(w.shape[0]))
    if "conv_b" in p:                   # Gated DeltaNet's has none
        out = out + p["conv_b"].astype(jnp.float32)
    return jax.nn.silu(out).astype(seq.dtype)


def _ssm_inputs(xBC, dt, p, cfg):
    """The convolved `xBC` [.., W] and raw `dt` [.., H] -> (x [.., H, P],
    B, C [.., G, N], dt float32 after its bias and softplus, A [H])."""
    H, P = cfg.mamba_num_heads, cfg.mamba_head_dim
    G, N = cfg.n_groups, cfg.ssm_state_size
    lead = xBC.shape[:-1]
    x, B, C = jnp.split(xBC, [H * P, H * P + G * N], axis=-1)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])
    return (x.reshape(lead + (H, P)), B.reshape(lead + (G, N)),
            C.reshape(lead + (G, N)), dt, -jnp.exp(p["A_log"]))


def _skip(y, x, p):
    return y + p["ssm_D"][:, None] * x.astype(jnp.float32)


class Carried(NamedTuple):
    """A recurrent half's cache as the two arrays of ONE call's sequences,
    carried by the caller itself (a training step walking a long sequence a
    segment at a time) and not rows of a pool: `state` [b, ...] float32,
    `state_leaves`' `ssm` shape; `tail` [b, K - 1, W], the convolution's
    last inputs. Every position of a chunk on it is real."""
    state: Any
    tail: Any


def _chunk_start(conv_in, cfg, cache, rows, start):
    """What a chunk of b sequences starts from: conv_in [b, T, W], the
    convolution's inputs at its positions -> (seq [b, K - 1 + T, W]: the
    K - 1 inputs before the first position, then the chunk's; S [b, ...]
    float32, the state before it, `state_leaves`' shape). `cache`: the
    carried `(ssm, conv)` pair, rows `rows` [b] of it this call's (None: no
    cache, every sequence from zero — the whole-sequence forward)."""
    if isinstance(cache, Carried):
        return jnp.concatenate([cache.tail.astype(conv_in.dtype), conv_in],
                               axis=1), cache.state
    b, _, W = conv_in.shape
    tail = jnp.zeros((b, cfg.conv_kernel - 1, W), conv_in.dtype)
    S = jnp.zeros((b,) + state_leaves(cfg)["ssm"], jnp.float32)
    if cache is not None:
        # a chunk at position 0 is a slot newly admitted: nothing carried
        fresh = (start == 0)[:, None, None]
        tail = jnp.where(fresh, 0, ssm.state_read(cache[1], rows))
        S = jnp.where(fresh[..., None], 0,
                      ssm.state_read(cache[0], rows).astype(jnp.float32))
    return jnp.concatenate([tail.astype(conv_in.dtype), conv_in], axis=1), S


def _chunk_end(seq, S, cfg, cache, rows, valid):
    """The cache after a chunk: the state `S` after its last REAL position
    and the convolution's inputs of the last K - 1 real positions (earlier
    chunks' too) of `seq`, where the first `valid` [b] positions are real."""
    if cache is None:
        return None
    if isinstance(cache, Carried):
        return Carried(S, seq[:, seq.shape[1] - (cfg.conv_kernel - 1):])
    tail = jax.vmap(lambda s, n: jax.lax.dynamic_slice_in_dim(
        s, n, cfg.conv_kernel - 1))(seq, valid)
    return (ssm.state_write(cache[0], rows, S),
            ssm.state_write(cache[1], rows, tail))


def _token_start(conv_in, cache, rows):
    """A decode token of every row: conv_in [S, W] -> (seq [S, K, W], the
    convolution's K inputs that end at it; the tails' buffer after it)."""
    seq = jnp.concatenate([ssm.state_read(cache[1], rows).astype(
        conv_in.dtype), conv_in[:, None]], axis=1)
    return seq, ssm.state_write(cache[1], rows, seq[:, 1:])


def _real(valid, T):
    """[b, T, 1]: the chunk's positions that are not its padded tail."""
    return jnp.arange(T)[None, :, None] < valid[:, None, None]


def _mamba_chunk(p, cfg, proj, cache, rows, start, valid):
    """Positions `start .. start + T - 1` of b sequences, of which the first
    `valid` [b] are real: (y [b, T, H, P] float32, cache)."""
    zxbcdt, = proj
    T = zxbcdt.shape[1]
    _, xBC, dt = jnp.split(
        zxbcdt, [cfg.ssm_inner, cfg.ssm_inner + cfg.conv_width], -1)
    with jax.named_scope("conv"):
        seq, S = _chunk_start(xBC, cfg, cache, rows, start)
        x, B, C, dt, A = _ssm_inputs(_conv(seq, p, T), dt, p, cfg)
    with jax.named_scope("scan"):
        # a padded tail leaves the state alone: decay 1, no input
        y, S = ssm.ssm_chunk_scan(x, jnp.where(_real(valid, T), dt, 0.0), A,
                                  B, C, S, cfg.chunk_size)
    return _skip(y, x, p), _chunk_end(seq, S, cfg, cache, rows, valid)


def _mamba_token(p, cfg, proj, cache, rows):
    """One decode token of every row: zxbcdt [S, .] -> (y [S, H, P] float32,
    cache), each row's state read and rewritten whole, in place."""
    zxbcdt, = proj
    _, xBC, dt = jnp.split(
        zxbcdt, [cfg.ssm_inner, cfg.ssm_inner + cfg.conv_width], -1)
    with jax.named_scope("conv"):
        seq, conv = _token_start(xBC, cache, rows)
        x, B, C, dt, A = _ssm_inputs(_conv(seq, p, 1)[:, 0], dt, p, cfg)
    with jax.named_scope("update"):
        y, state = ssm.ssm_update(
            cache[0], rows, jnp.exp(dt * A),
            dt[..., None] * x.astype(jnp.float32), B, C)
    return _skip(y, x, p), (state, conv)


def _recurrent(proj, chunk, token, cache, rows, positions, valid):
    """A recurrent half between its projections, the three ways a program
    runs it: `proj`, the in-projections' products [B, T, .] -> (y [B, T, H,
    .] float32, cache) by `chunk(proj, cache, rows, start, valid)` and
    `token(proj, cache, rows)`. `rows`: each sequence's row of the cache,
    [B, 1] — or, of a mixed call, a `MixedTables` of the chunk's and the
    slots'. `valid` [chunks]: a chunk's real positions (default: all)."""
    B, T = proj[0].shape[:2]
    part = lambda *at: tuple(a[at] for a in proj)
    if isinstance(cache, Carried):      # the caller's own state: a chunk
        return chunk(proj, cache, None, None, jnp.full((B,), T, jnp.int32))
    if isinstance(rows, MixedTables):
        # a chunk's rows [1, C, .], then a row a slot: the chunk first, whole
        # (its state read, scanned and written back), then the slots' token
        # on the buffer it returned — one chain, nothing for XLA to reorder
        G = rows.chunk.shape[0]
        R = T - rows.decode.shape[0]    # the chunks' rows, C a chunk
        if G == 1:
            y_c, cache = chunk(part(slice(None), slice(R)), cache,
                               rows.chunk[:, 0], positions[:, 0], valid)
        else:
            # a GROUP of chunks: each in turn, whole, on the state the one
            # before it wrote back (an absent chunk's rows stay zeros)
            C = R // G

            def nth(at, i, cache):
                one = jax.lax.dynamic_slice_in_dim
                return chunk(tuple(at(a) for a in proj), cache,
                             one(rows.chunk[:, 0], i, 1), at(positions)[:, 0],
                             one(valid, i, 1))

            y = jax.eval_shape(
                lambda cache: nth(lambda a: a[:, :C], 0, cache)[0], cache)
            y_c, cache = over_chunk_group(
                rows.count, C, jnp.zeros((1, R) + y.shape[2:], y.dtype),
                cache, nth)
        y_d, cache = token(part(0, slice(R, None)), cache, rows.decode[:, 0])
        return jnp.concatenate([y_c, y_d[None]], axis=1), cache
    if cache is not None and valid is None:
        y, cache = token(part(slice(None), 0), cache, rows[:, 0])
        return y[:, None], cache
    if valid is None:
        valid = jnp.full((B,), T, jnp.int32)
    return chunk(proj, cache, None if rows is None else rows[:, 0],
                 None if positions is None else positions[:, 0], valid)


def _mamba_half(x, p, cfg, cache=None, rows=None, positions=None, valid=None):
    """`f` of a Mamba-2 half on x [B, T, D] -> (f(RMSNorm(x)), cache);
    `cache`, `rows`, `positions`, `valid`: `_recurrent`'s."""
    B, T, _ = x.shape
    u = _half_input(x, p, cfg)
    with jax.named_scope("in_proj"):
        # xBC and dt are read at the half's start, z at its end: the barrier
        # HOLDS the product between them — left alone, XLA frees it after the
        # convolution and computes it again for the gate (`fusion.N.remat`)
        zxbcdt = jax.lax.optimization_barrier(u @ p["ssm_in_w"])
    y, cache = _recurrent(
        (zxbcdt,), partial(_mamba_chunk, p, cfg),
        partial(_mamba_token, p, cfg), cache, rows, positions, valid)
    with jax.named_scope("out_proj"):
        z = zxbcdt[..., :cfg.ssm_inner].astype(jnp.float32)
        gated = (y.reshape(B, T, cfg.n_groups, -1)
                 * jax.nn.silu(z).reshape(B, T, cfg.n_groups, -1))
        gated = gated * jax.lax.rsqrt(
            jnp.mean(jnp.square(gated), -1, keepdims=True) + cfg.norm_eps)
        out = (gated.reshape(B, T, -1).astype(x.dtype)
               * p["gate_norm_scale"]) @ p["ssm_out_w"]
    return out, cache


# ----------------------------------------------------------------------
# the Gated DeltaNet half
# ----------------------------------------------------------------------

_L2_EPS = 1e-6          # q / |q|, k / |k|: x rsqrt(sum x^2 + eps)


def _gdn_inputs(qkv, ba, p, cfg):
    """The convolved `[q | k | v]` [.., W] and raw `[b | a]` [.., 2 H] -> (q,
    k [.., G, K] float32, each key head's of unit length, q over sqrt(K)
    besides; v [.., H, V]; g [.., H] float32 log-decay; beta [.., H]
    float32, `cfg.gdn_beta_scale` times a sigmoid)."""
    G, H = cfg.gdn_key_heads, cfg.gdn_value_heads
    K, V = cfg.gdn_key_dim, cfg.gdn_value_dim
    lead = qkv.shape[:-1]
    q, k, v = jnp.split(qkv, [G * K, 2 * G * K], axis=-1)

    def unit(x):
        x = x.astype(jnp.float32).reshape(lead + (G, K))
        return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + _L2_EPS)

    b, a = jnp.split(ba.astype(jnp.float32), 2, axis=-1)
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(a + p["dt_bias"])
    q, k, v = unit(q) * K ** -0.5, unit(k), v.reshape(lead + (H, V))
    beta = jax.nn.sigmoid(b)
    if cfg.gdn_beta_scale != 1.0:
        beta = beta * cfg.gdn_beta_scale
    return q, k, v, g, beta


def _gdn_chunk(p, cfg, proj, cache, rows, start, valid):
    """`_mamba_chunk` of the delta rule: (o [b, T, H, V] float32, cache)."""
    qkvz, ba = proj
    T = qkvz.shape[1]
    with jax.named_scope("conv"):
        seq, S = _chunk_start(qkvz[..., :sum(cfg.gdn_widths)], cfg, cache,
                              rows, start)
        q, k, v, g, beta = _gdn_inputs(_conv(seq, p, T), ba, p, cfg)
    with jax.named_scope("scan"):
        # a padded tail leaves the state alone: decay 1 AND nothing written
        real = _real(valid, T)
        o, S = gdn.gdn_chunk_scan(
            q.astype(v.dtype), k.astype(v.dtype), v, jnp.where(real, g, 0.0),
            jnp.where(real, beta, 0.0), S, cfg.chunk_size)
    return o, _chunk_end(seq, S, cfg, cache, rows, valid)


def _gdn_token(p, cfg, proj, cache, rows):
    """`_mamba_token` of the delta rule: (o [S, H, V] float32, cache)."""
    qkvz, ba = proj
    with jax.named_scope("conv"):
        seq, conv = _token_start(qkvz[..., :sum(cfg.gdn_widths)], cache, rows)
        q, k, v, g, beta = _gdn_inputs(_conv(seq, p, 1)[:, 0], ba, p, cfg)
    with jax.named_scope("update"):
        o, state = gdn.gdn_update(cache[0], rows, jnp.exp(g), beta, q, k, v)
    return o, (state, conv)


def _gdn_half(x, p, cfg, cache=None, rows=None, positions=None, valid=None):
    """`f` of a Gated DeltaNet half on x [B, T, D] -> (f(RMSNorm(x)),
    cache); `cache`, `rows`, `positions`, `valid`: `_recurrent`'s."""
    B, T, _ = x.shape
    H, V = cfg.gdn_value_heads, cfg.gdn_value_dim
    u = _half_input(x, p, cfg)
    with jax.named_scope("in_proj"):
        # q, k and v are read at the half's start, z at its end: held, as
        # `_mamba_half` holds its product and for its reason
        qkvz = jax.lax.optimization_barrier(u @ p["gdn_qkvz_w"])
        ba = u @ p["gdn_ba_w"]
    o, cache = _recurrent(
        (qkvz, ba), partial(_gdn_chunk, p, cfg),
        partial(_gdn_token, p, cfg), cache, rows, positions, valid)
    with jax.named_scope("out_proj"):
        # the norm FIRST (a head's V columns, one scale for all heads), then
        # the gate
        z = qkvz[..., -H * V:].astype(jnp.float32).reshape(B, T, H, V)
        o = o * jax.lax.rsqrt(
            jnp.mean(jnp.square(o), -1, keepdims=True) + cfg.norm_eps)
        gated = o * p["gate_norm_scale"].astype(jnp.float32) * jax.nn.silu(z)
        out = gated.reshape(B, T, H * V).astype(x.dtype) @ p["gdn_out_w"]
    return out, cache


# a recurrent half's letter -> (the scope a program runs it under, its `f`):
# the half's pieces are named inside it (`in_proj`, `conv`, `scan` or `update`,
# `out_proj`), so a program's instructions read `ssm/in_proj`, `gdn/scan`
RECURRENT = {MAMBA: ("ssm", _mamba_half), DELTANET: ("gdn", _gdn_half)}


def _residual(x, out, cfg, p):
    """`x + r * out`; a family without the multiplier adds `out` as it is.
    Under `cfg.post_norm` the half `p` read the stream un-normed
    (`gpt.py::_half_input`) and its norm is taken HERE, of what it gives."""
    if cfg.post_norm:
        out = _norm(out, p["ln1_scale"], None, True, cfg.norm_eps)
    if cfg.residual_multiplier != 1.0:
        out = out * cfg.residual_multiplier
    return x + out


# ----------------------------------------------------------------------
# the whole-sequence forward (no cache): what the tests and the reference
# check read the program's own routing from
# ----------------------------------------------------------------------


def _layers(params, cfg):
    """Every half in model order as (kind, its leaves)."""
    for (unit, repeats), trees in zip(layer_runs(cfg), params["runs"]):
        for n in range(repeats):
            for kind, tree in zip(unit, trees):
                yield kind, jax.tree_util.tree_map(lambda a: a[n], tree)


def _half(x, p, kind, cfg, acfg, positions, expert_half=None, routing=None,
          constrain=False):
    """One half of a whole-sequence forward from a zero state: x [B, T, D]
    -> x after it."""
    if kind == ATTENTION:
        with jax.named_scope("attn"):
            out, _, _ = _attn_half(x, p, acfg, positions,
                                   constrain=constrain)
            return _residual(x, out, cfg, p)
    if kind in RECURRENT:
        scope, mixer = RECURRENT[kind]
        with jax.named_scope(scope):
            return _residual(x, mixer(x, p, cfg)[0], cfg, p)
    with jax.named_scope("mlp"):
        if kind == DENSE:
            out = _mlp(_half_input(x, p, cfg), p, cfg, constrain)
            return _residual(x, out, cfg, p)
        out, _, top_e = expert_half(x, p, cfg)
        if routing is not None:
            routing.append(top_e)
        return _residual(x, out, cfg, p)


def _positions(tokens):
    B, T = tokens.shape
    return jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (B, T))


def hybrid_forward(params, tokens, cfg: HybridConfig, expert_half=None,
                   routing=None):
    """tokens [B, T] -> logits [B, T, V]: dense masked attention, the scan
    from a zero state, a Python loop over the halves. `routing`: a list that
    takes each expert half's chosen experts [B*T, top_k]."""
    positions = _positions(tokens)
    acfg = _attention_cfg(cfg)
    x = _embed(params, tokens, positions, cfg)
    for kind, p in _layers(params, cfg):
        x = _half(x, p, kind, cfg, acfg, positions, expert_half, routing)
    return _lm_head(params, x, cfg)


# ----------------------------------------------------------------------
# training: the same halves, a `jax.checkpoint` a half, a run scanned
# ----------------------------------------------------------------------

# A block of the training step is a HALF — of a recurrent half, a SEGMENT of
# it — under a `jax.checkpoint`: at 32768 positions a sequence the one block
# whose backward is running takes most of what the step's state leaves of a
# 16 GB chip (a layer's two halves together do not fit, nor does a Gated
# DeltaNet half whole: PERF.md section 6, PR 56). What is left beside it is
# spent on the results a block's backward reads of its forward, block by
# block (`held_candidates`); a block that holds nothing is made again from
# its input.

# positions a block of a recurrent half: a longer sequence runs the half a
# segment at a time on the carried state and convolution tail (`Carried`), as
# the serving path runs a prompt a chunk at a time
SEGMENT = 4096

# a chunk of the head's logits, float32 `[tokens, V / chunks]`, is held to
# about this many bytes (`ops/chunked_ce.py`)
_LOSS_CHUNK_BYTES = 1 << 28

# a half's letter -> the named results its backward reads of its forward
_BACKWARD_READS = {ATTENTION: (FLASH_RESIDUALS, QKV_PRODUCT),
                   DELTANET: (gdn.SCAN_OUTPUT,), DENSE: (MLP_PRODUCT,)}


def _blocks(kind, T):
    """The `jax.checkpoint` blocks a half of `kind` runs T positions in."""
    return -(-T // SEGMENT) if kind in RECURRENT else 1


def held_candidates(cfg: HybridConfig, B, T):
    """({name: bytes a BLOCK on ONE device}, {name: the blocks that carry
    it from the stack's END, a group a scanned block: `fit_held`'s
    `layers`}, the step's working sets with nothing named: `held_plan`'s)
    for a `[B, T]` batch as the traced program sees it —
    `gpt.py::held_candidates` for a stack of halves, where a block is a half
    and, of a recurrent half, a `SEGMENT` of it, so a name may be held by
    its last few blocks alone (what is held there lives shortest: at this
    cell's size the last run's residuals are gone before the step's memory
    peaks).

    The names are in the order `fit_held` takes them, by the milliseconds a
    held GiB takes off a step on the v5e at Olmo-Hybrid-7B's widths, each
    name forced in turn (PERF.md section 6, PR 57): the flash forward 211
    (a kernel at four fifths of its roofline whose results are an eighth of
    its inputs); the attention half's QKV product 22 and a feed-forward's
    gate and up products 26 (matmuls near the peak; the narrower first: it
    strands less of the room); the delta rule's scan output 4.5 (float32
    `[b, SEGMENT, H, V]`, `ops/pallas/gdn.py::SCAN_OUTPUT`: held, the scan
    runs once forward and never again, but of the forward only the loop over
    the chunks was run again — XLA shares a chunk's systems between the
    block made again and the scan's own backward — and stacking a result
    through the two scans around a segment costs four fifths of what that
    saves). The delta rule's in-projection is NOT a candidate: held in
    every segment it lengthened the step by 6%.

    A working set a RUN of the stack (`layer_runs`), at its backward: the
    inputs of every block up to the run's last (a half's; a recurrent
    half's a segment at a time, beside the state and the convolution tail
    it starts from — the runs behind have run their backward and freed
    theirs); the largest backward of one of its blocks — a feed-forward's
    (both products, the activation's result, two of the three gradients),
    the attention half's (the QKV product and its gradient, q, k, v, the
    kernel's output and their four gradients), a recurrent segment's —
    beside the gradients of this run and the runs behind it, by their share
    of the weights (a scan's gradient stack is allocated where its backward
    begins). The last run's has the loss's too: a chunk of the logits in
    float32, its exponentials and its gradient, beside the stream's."""
    divide = on_one_device
    b, t = divide(B, BATCH_AXES), divide(T, SEQ_AXIS)
    tokens, segment = b * t, b * min(t, SEGMENT)
    item = jnp.dtype(cfg.dtype).itemsize
    stream = tokens * cfg.d_model * item
    hd, heads = cfg.head_dim, divide(cfg.n_head, TENSOR_AXIS)
    qkv = tokens * hd * item * divide(
        cfg.n_head * (2 if cfg.attn_output_gate else 1) + 2 * cfg.n_kv_head,
        TENSOR_AXIS)
    up = tokens * divide(cfg.d_ff, TENSOR_AXIS) * item
    Wq, Wk, Wv = cfg.gdn_widths
    in_proj = segment * (Wq + Wk + 2 * Wv) * item
    flash = attn_dispatch.select(_train_attn_site(
        _attention_cfg(cfg), T, T, False, None)) == "flash"
    held = {
        # the output, and a float32 log-sum-exp a row in the kernels' tile
        # (`gpt.py::held_candidates`)
        FLASH_RESIDUALS: tokens * heads * (hd * item + 8 * 4) if flash else 0,
        QKV_PRODUCT: qkv, MLP_PRODUCT: 2 * up,
        gdn.SCAN_OUTPUT: segment * Wv * 4}
    runs = layer_runs(cfg)
    # a name's blocks from the stack's END, where what is held lives
    # shortest (a block's forward comes last and its backward first), a
    # GROUP a scanned block: its repeats are one program and hold a name
    # together
    carriers = {name: tuple(
        repeats for unit, repeats in reversed(runs) for kind in unit
        if name in _BACKWARD_READS.get(kind, ())
        for _ in range(_blocks(kind, T))) for name in held}
    held = {name: nbytes for name, nbytes in held.items()
            if nbytes and carriers[name]}

    leaves = state_leaves(cfg)
    carried = b * (math.prod(leaves["ssm"]) * 4
                   + math.prod(leaves["conv"]) * item)
    inputs = lambda kind: stream + \
        (_blocks(kind, T) * carried if kind in RECURRENT else 0)
    backward = {
        DENSE: 5 * up, ATTENTION: 2 * qkv + 8 * tokens * heads * hd * item,
        DELTANET: 8 * in_proj + 4 * segment * Wv * 4,
        MAMBA: 8 * segment * (cfg.ssm_inner + cfg.conv_width
                              + cfg.mamba_num_heads) * item}
    weights = lambda kind: sum(
        math.prod(shape) for shape, _ in mixer_shapes(cfg, kind).values())
    of_run = [repeats * sum(weights(kind) for kind in unit)
              for unit, repeats in runs]
    ends = (1 if cfg.tie_embeddings else 2) * cfg.vocab_size * cfg.d_model
    working_sets, before = [], 0
    for r, (unit, repeats) in enumerate(runs):
        before += repeats * sum(inputs(kind) for kind in unit)
        working_sets.append(dict(
            carried_bytes=before,
            grads_share=(sum(of_run[r:]) + ends) / (sum(of_run) + ends),
            backward_bytes=max(backward[kind] for kind in unit)))
    chunks = cfg.loss_chunks or max(
        1, -(-tokens * cfg.vocab_size * 4 // _LOSS_CHUNK_BYTES))
    working_sets[-1]["loss_bytes"] = \
        3 * tokens * 4 * (cfg.vocab_size // chunks) + 4 * stream
    return held, {name: carriers[name] for name in held}, working_sets


def _in_segments(x, p, kind, cfg, holds, remat):
    """A recurrent half AND its residual on x [B, T, D] from a zero state,
    `SEGMENT` positions a block: the whole segments scanned, then what is
    left of T as a last, shorter block on the same carried state (a block's
    size never follows T's remainder). `holds`: the names each block holds
    in turn, `remat(names)` its `jax.checkpoint`; the whole segments that
    hold the same names are one scan."""
    scope, mixer = RECURRENT[kind]
    B, T, D = x.shape
    whole = T // SEGMENT
    cut = lambda first, last: x if (first, last) == (0, T) else \
        x[:, first:last]

    def segment(carry, x):
        with jax.named_scope(scope):
            out, carry = mixer(x, p, cfg, carry)
            return carry, _residual(x, out, cfg, p)

    leaves = state_leaves(cfg)
    carry = Carried(jnp.zeros((B,) + leaves["ssm"], jnp.float32),
                    jnp.zeros((B,) + leaves["conv"], x.dtype))
    outs, at = [], 0
    while at < whole:
        upto = at + 1
        while upto < whole and holds[upto] == holds[at]:
            upto += 1
        carry, out = jax.lax.scan(
            remat(holds[at])(segment), carry,
            jnp.moveaxis(cut(at * SEGMENT, upto * SEGMENT).reshape(
                B, -1, SEGMENT, D), 1, 0))
        outs.append(jnp.moveaxis(out, 0, 1).reshape(B, -1, D))
        at = upto
    if whole * SEGMENT < T:
        outs.append(remat(holds[whole])(segment)(
            carry, cut(whole * SEGMENT, T))[1])
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=1)


def hybrid_hidden(params, tokens, cfg: HybridConfig):
    """tokens [B, T] -> the stream after the last block [B, T, D], every
    sequence from a zero state, as the training step runs it: each half (of
    a recurrent half, each segment: `_in_segments`) under a `jax.checkpoint`
    whose policy holds the names the installed budget has room for
    (`held_candidates`, `activation_checkpointing.held_plan`: a name's last
    blocks in model order; no budget, nothing held), the repeats of a run
    (`layer_runs`) scanned."""
    from deepspeed_tpu.runtime.activation_checkpointing import (
        held_plan, policy_holding)
    if MOE in cfg.halves:
        raise NotImplementedError(
            "the expert half `E` does not train: its halves count what they "
            "route and dispatch by sorted indices, and neither has a "
            "gradient path here (ROADMAP, Reach)")
    positions = _positions(tokens)
    acfg = _attention_cfg(cfg)
    x = _embed(params, tokens, positions, cfg)
    x = shard_constraint(x, BATCH_AXES, SEQ_AXIS, None)
    T = tokens.shape[1]
    held, carriers, working_sets = held_candidates(cfg, *tokens.shape)
    left = dict(held_plan(held, carriers, working_sets).blocks)
    runs = layer_runs(cfg)
    # the names every block holds, a name's blocks dealt from the stack's
    # END (`held_candidates`' order). A scanned body is one program for all
    # its repeats: a block of it holds a name in every repeat or in none
    holds = [[[()] * _blocks(kind, T) for kind in unit] for unit, _ in runs]
    for of_run, (unit, repeats) in reversed(list(zip(holds, runs))):
        for of_half, kind in reversed(list(zip(of_run, unit))):
            for block in reversed(range(len(of_half))):
                names = tuple(name for name in _BACKWARD_READS.get(kind, ())
                              if left.get(name, 0) >= repeats)
                left.update({name: left[name] - repeats for name in names})
                of_half[block] = names

    # `prevent_cse`: a scan's body holds SEVERAL blocks, and without the
    # barrier XLA starts the next block's recomputation before this block's
    # backward is done, both blocks' forwards alive at once
    remat = lambda names: partial(jax.checkpoint, prevent_cse=True,
                                  policy=policy_holding(names))

    def half(x, p, kind, holds):
        if kind in RECURRENT:
            x = _in_segments(x, p, kind, cfg, holds, remat)
        else:
            x = remat(holds[0])(
                lambda x, p: _half(x, p, kind, cfg, acfg, positions,
                                   constrain=True))(x, p)
        return shard_constraint(x, BATCH_AXES, SEQ_AXIS, None)

    for (unit, _), of_run, trees in zip(runs, holds, params["runs"]):
        def body(x, trees, unit=unit, of_run=of_run):
            for kind, p, names in zip(unit, trees, of_run):
                x = half(x, p, kind, names)
            return x, None

        x, _ = jax.lax.scan(body, x, trees)     # a run of one repeat too
    return x


def hybrid_loss(params, batch, rng, cfg: HybridConfig):
    """Causal-LM cross entropy of a hybrid stack, as `gpt.py::gpt_loss`
    takes its batch: {"tokens": [B, T]} (the labels are the tokens shifted
    by one) or {"tokens" | "input_ids", "labels"} (a negative label is
    ignored). The head never materialises `[B * T, V]`: chunks of the
    vocabulary through `ops/chunked_ce.py` (`cfg.loss_chunks`, or as many as
    hold a chunk's logits to `_LOSS_CHUNK_BYTES`)."""
    from deepspeed_tpu.ops.chunked_ce import chunked_softmax_xent
    if cfg.logits_scaling != 1.0:
        raise NotImplementedError("`logits_scaling` in the chunked loss")
    tokens = batch.get("tokens", batch.get("input_ids"))
    labels = batch.get("labels")
    if labels is None:
        tokens, labels = tokens[:, :-1], tokens[:, 1:]
    x = hybrid_hidden(params, tokens, cfg)
    rows = labels.size
    chunks = cfg.loss_chunks or max(
        1, -(-rows * cfg.vocab_size * 4 // _LOSS_CHUNK_BYTES))
    with jax.named_scope("head"):
        x = _norm(x, params["lnf_scale"], None, True, cfg.norm_eps)
    with jax.named_scope("head_loss"):
        nll = chunked_softmax_xent(
            x.reshape(rows, -1), _head_table(params, cfg).astype(x.dtype),
            labels.reshape(rows), chunks)
        mask = (labels.reshape(rows) >= 0).astype(jnp.float32)
        return (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)


# a half's leaf -> where the tensor axis divides it (Megatron: column-parallel
# in, row-parallel out), after the leading `[repeats]` axis; every other leaf
# of a half, the recurrent halves' among them, is whole on every tensor rank
_TENSOR_PARALLEL = {"attn_qkv_w": P(None, None, TENSOR_AXIS),
                    "attn_qkv_b": P(None, TENSOR_AXIS),
                    "attn_out_w": P(None, TENSOR_AXIS, None),
                    "mlp_gate_w": P(None, None, TENSOR_AXIS),
                    "mlp_up_w": P(None, None, TENSOR_AXIS),
                    "mlp_down_w": P(None, TENSOR_AXIS, None)}


def hybrid_param_specs(cfg: HybridConfig, layer_shapes):
    """PartitionSpecs of `hybrid_init_fn`'s tree (`layer_shapes`: the
    family's), for the engine to place the leaves by — ZeRO adds its axes
    orthogonally, the abstract init makes each leaf in its shard."""
    whole = lambda shape: P(*([None] * (1 + len(shape))))
    specs = {"wte": P(TENSOR_AXIS, None), "lnf_scale": P(None),
             "runs": [[{name: _TENSOR_PARALLEL.get(name, whole(shape))
                        for name, (shape, _) in
                        layer_shapes(cfg, kind, 0.02).items()}
                       for kind in unit] for unit, _ in layer_runs(cfg)]}
    if not cfg.tie_embeddings:
        specs["lm_head"] = P(TENSOR_AXIS, None)
    return specs


# ----------------------------------------------------------------------
# the paged programs
# ----------------------------------------------------------------------

def make_hybrid_decode_model(cfg: HybridConfig, params, name, expert_half,
                             expert_stacks, cache_fingerprint):
    """The paged serving contract (`DecodeModelSpec`) of a hybrid family.
    `expert_half(x, p, cfg, stacks=None, expert_base=0) -> (f(RMSNorm(x)),
    counters int32[5] in `HELD_ROUTED_COUNTERS` order, chosen experts [B*T,
    top_k])` is the family's `E` half, `expert_stacks` the leaves of it that
    hold a weight an expert (`moe_w_*`). The paged programs take
    `block_tables` as the PAIR (KV tables [B, nb], state rows [B, 1]) and a
    pool of `cache_kinds`' leaves (module docstring).

    `prefill_paged_fn` and `decode_paged_fn` take one keyword beside the
    contract's arguments, as K-EXAONE's do: `routing=True` adds a FOURTH
    result, the experts the call routed every row to — int32 `[expert
    halves, B, C, top_k]` (`C` 1 for decode), ascending in a token."""
    from deepspeed_tpu.inference.engine import DecodeModelSpec
    if DENSE in cfg.halves or cfg.post_norm:
        raise NotImplementedError(
            f"model spec '{name}': the paged programs are not built for a "
            f"stack with dense feed-forward halves or `post_norm` (no cell "
            f"would guard them; ROADMAP, Reach)")
    runs = layer_runs(cfg)
    acfg = _attention_cfg(cfg)
    held = cfg.experts_held[1]
    no_counts = jnp.zeros((len(HELD_ROUTED_COUNTERS),), jnp.int32)
    pool_writers, attn_programs = {}, {}

    def _layers_paged(params, x, pool, block_tables, positions, valid=None,
                      routing=False):
        tables, state_rows = block_tables
        mixed = isinstance(tables, MixedTables)
        decode = not mixed and valid is None
        site = "mixed" if mixed else \
            "paged_decode" if decode else "prefill_chunk"
        in_place = attn_dispatch.kv_pool_writer(
            {"k": pool["k"], "v": pool["v"]}) \
            == attn_dispatch.KV_POOL_WRITE_KERNEL
        pool_writers[site] = attn_dispatch.KV_POOL_WRITE_KERNEL if in_place \
            else attn_dispatch.KV_POOL_WRITE_SCATTER
        # rows of a layer's leaves: KV blocks, and 1 + slots state rows
        kv_rows, state_n = pool["k"].shape[1], pool["ssm"].shape[1]
        work = None
        if site != "prefill_chunk":     # once a token, outside the layers
            from deepspeed_tpu.ops.pallas.decode_attention import \
                paged_decode_work
            work = paged_decode_work(*decode_rows(tables, positions),
                                     pool["k"].shape[3])
        # every leaf flat and CARRIED: layer i of a kind addresses its rows
        # as `row + i * rows a layer`
        flat = {k: v.reshape((-1,) + v.shape[2:]) for k, v in pool.items()}
        offset = offset_tables

        def half(x, flat, p, kind, index, counts, chosen, **experts):
            if kind == ATTENTION:
                base = index * kv_rows
                # the kernels take the layer's offset; the scatter and the
                # gather of the other form take tables already offset
                where = dict(block_base=base) if in_place else {}
                with jax.named_scope("attn_full"):
                    out, kv = _paged_attn_half(
                        x, p, {"k": flat["k"], "v": flat["v"]}, positions,
                        tables if in_place else offset(tables, base), acfg,
                        decode_work=work, attn_programs=attn_programs,
                        phase=None if mixed else site, **where)
                    x = _residual(x, out, cfg, p)
                flat = {**flat, **kv}
            elif kind in RECURRENT:
                scope, mixer = RECURRENT[kind]
                with jax.named_scope(scope):
                    out, (state, conv) = mixer(
                        x, p, cfg, (flat["ssm"], flat["conv"]),
                        offset(state_rows, index * state_n), positions, valid)
                    x = _residual(x, out, cfg, p)
                flat = {**flat, "ssm": state, "conv": conv}
            else:
                with jax.named_scope("mlp"):
                    out, counted, top_e = expert_half(x, p, cfg, **experts)
                    x = _residual(x, out, cfg, p)
                counts.append(counted)
                if chosen is not None:
                    chosen.append(top_e)
            # (a half's residual stands under the half's scope)
            return x, flat

        acc = no_counts
        chosen = [] if routing else None    # an expert half's [B*C, top_k]
        seen = dict.fromkeys(RECURRENT, 0) | {ATTENTION: 0, MOE: 0}
        for (unit, repeats), trees in zip(runs, params["runs"]):
            split = [_split_stacks(tree, expert_stacks) for tree in trees]
            small = [s for s, _ in split]

            def body(carry, inputs, unit=unit, split=split, seen=dict(seen)):
                x, flat, acc = carry
                trees, n = inputs
                counts, routed = [], [] if routing else None
                rank = dict.fromkeys(seen, 0)
                for i, kind in enumerate(unit):
                    index = seen[kind] + n * unit.count(kind) + rank[kind]
                    rank[kind] += 1
                    experts = dict(stacks=split[i][1], expert_base=n * held) \
                        if kind == MOE else {}
                    x, flat = half(x, flat, trees[i], kind, index, counts,
                                   routed, **experts)
                return (x, flat, acc + sum(counts, no_counts)), routed

            if repeats == 1:
                (x, flat, acc), routed = body(
                    (x, flat, acc),
                    (jax.tree_util.tree_map(lambda a: a[0], small),
                     jnp.int32(0)))
                if routing:
                    chosen += routed
            else:
                # the scan slices the small leaves a repeat; the expert
                # stacks stay whole (closed over, like the carried pool)
                (x, flat, acc), routed = jax.lax.scan(
                    body, (x, flat, acc),
                    (small, jnp.arange(repeats, dtype=jnp.int32)))
                if routing and routed:
                    # [repeats, B*C, k] a position -> model order
                    chosen += [r[n] for n in range(repeats) for r in routed]
            for kind in seen:
                seen[kind] += repeats * unit.count(kind)
        pool = {k: v.reshape(pool[k].shape) for k, v in flat.items()}
        if routing:
            B, C = positions.shape
            return x, pool, acc, jnp.stack(
                [jnp.sort(e, axis=-1).reshape(B, C, -1) for e in chosen])
        return x, pool, acc

    def prefill_paged_fn(params, tokens, start_pos, last_idx, pool,
                         block_tables, routing=False):
        B, C = tokens.shape
        positions = start_pos[:, None] + jnp.arange(C, dtype=jnp.int32)[None]
        x = _embed(params, tokens, positions, cfg)
        x, pool, *counted = _layers_paged(params, x, pool, block_tables,
                                          positions, valid=last_idx + 1,
                                          routing=routing)
        return (_lm_head(params, _last_rows(x, last_idx), cfg)[:, 0], pool,
                *counted)

    def decode_paged_fn(params, token, pos, pool, block_tables,
                        routing=False):
        x = _embed(params, token[:, None], pos[:, None], cfg)
        x, pool, *counted = _layers_paged(params, x, pool, block_tables,
                                          pos[:, None], routing=routing)
        return (_lm_head(params, x, cfg)[:, 0], pool, *counted)

    def init_paged_pool(num_blocks, block_size, dtype=jnp.bfloat16,
                        kv_group_size=0, state_rows=None):
        if jnp.dtype(dtype) == jnp.int8:
            raise ValueError(
                f"model spec '{name}': the int8 pool is not built for a pool "
                f"with a state kind (a recurrent state has no scale leaves)")
        if state_rows is None:
            raise ValueError(
                f"model spec '{name}' keeps per-slot recurrent state: "
                f"init_paged_pool needs `state_rows` (1 + slots), as "
                f"ServingEngine passes it")
        full, state = cache_kinds(cfg, block_size)
        kv = (full.layers, num_blocks, cfg.n_kv_head, block_size,
              cfg.head_dim)
        row = state_leaves(cfg)
        return {
            "k": jnp.zeros(kv, dtype), "v": jnp.zeros(kv, dtype),
            "ssm": jnp.zeros((state.layers, state_rows) + row["ssm"],
                             jnp.float32),
            "conv": jnp.zeros((state.layers, state_rows) + row["conv"],
                              dtype)}

    def unserved(*_args, **_kwargs):
        raise NotImplementedError(
            f"model spec '{name}' is served through the paged scheduler only "
            f"(`engine.serving(...)`): the contiguous-cache generate() path "
            f"is not built for layers with recurrent state")

    return DecodeModelSpec(prefill_fn=unserved, decode_fn=unserved,
                           init_cache=unserved, params=params, name=name,
                           prefill_paged_fn=prefill_paged_fn,
                           decode_paged_fn=decode_paged_fn,
                           mixed_paged_fn=make_mixed_paged_fn(
                               cfg, _layers_paged, chunk_valid=True),
                           mixed_chunk_groups=True,
                           init_paged_pool=init_paged_pool,
                           paged_cache_kinds=partial(cache_kinds, cfg),
                           kv_pool_writers=pool_writers,
                           paged_attn_programs=attn_programs,
                           step_counters=HELD_ROUTED_COUNTERS,
                           cache_fingerprint=cache_fingerprint)
