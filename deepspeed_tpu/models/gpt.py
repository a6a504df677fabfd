"""GPT family — the flagship model, TPU-first.

The reference trains GPT through client Megatron models and serves it through
injected containers (`module_inject/containers/gpt2.py`, `megatron_gpt.py`); its
flagship benchmark is GPT ZeRO-3 (BASELINE.md). Here the model itself is part of
the framework's zoo, written the TPU way:

  * stacked block parameters + `lax.scan` over layers — one compiled block program,
    O(1) compile time in depth;
  * logical sharding via PartitionSpecs: batch on `data`, heads/ffn on `tensor`
    (Megatron TP), sequence on `sequence` (Ulysses — see parallel/ulysses.py);
  * `jax.checkpoint` (remat) policy per block for activation-memory control
    (analog of `runtime/activation_checkpointing/`);
  * bf16 activations, fp32 softmax/layernorm accumulation;
  * a static-shape KV-cache decode path for the inference engine.

Architecture: pre-LN GPT-2 (learned positions) with optional GPT-NeoX/LLaMA-style
rotary embeddings and (Sw)iGLU — enough surface to cover the reference's
gpt2/gptj/neox/llama containers with one implementation.
"""

import dataclasses
import math
from functools import partial
from typing import Any, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.comm import mesh as mesh_mod
from deepspeed_tpu.comm.mesh import BATCH_AXES, SEQ_AXIS, TENSOR_AXIS, shard_constraint
from deepspeed_tpu.ops import attention_dispatch as attn_dispatch
from deepspeed_tpu.runtime.engine import ModelSpec


@dataclasses.dataclass
class GPTConfig:
    vocab_size: int = 50304          # padded to 128 multiple (MXU-friendly)
    n_layer: int = 12
    n_head: int = 12
    n_kv_head: Optional[int] = None  # grouped-query attention; None = n_head (MHA)
    d_model: int = 768
    attn_head_dim: Optional[int] = None  # a head's width where it is not
                                     # d_model // n_head (K-EXAONE: 64 heads
                                     # of 128 on a 6144 stream); the
                                     # projections are then [D, H*hd] / [H*hd, D]
    attn_value_dim: Optional[int] = None  # a head's VALUE width where it is
                                     # not its query-key width `head_dim`
                                     # (192-wide keys beside 128-wide values):
                                     # the out-projection is then
                                     # [H*value_dim, D]
    attn_value_scale: float = 1.0    # the values times this, where they are
                                     # projected (so the cache holds them
                                     # scaled)
    attn_sink: bool = False          # a learned logit a head (`attn_sink` [H],
                                     # float32) joins the softmax's
                                     # denominator and has no value: a row's
                                     # weights sum to less than 1. Dense
                                     # forms: one more column; the streaming
                                     # kernels: the online softmax's INITIAL
                                     # state (m = sink, l = 1, acc = 0)
    d_ff: Optional[int] = None       # default 4*d_model (or 8/3 for swiglu)
    max_seq_len: int = 1024
    dropout: float = 0.0
    use_rotary: bool = False         # False: learned positions (GPT-2); True: RoPE
    rotary_pct: float = 1.0
    rope_theta: float = 10000.0      # RoPE base (LLaMA-3 uses 500000)
    norm_eps: float = 1e-5           # LayerNorm/RMSNorm epsilon (HF LLaMA: 1e-6)
    use_swiglu: bool = False         # LLaMA-style gated MLP
    use_rmsnorm: bool = False        # LLaMA-style RMSNorm
    activation: str = "gelu"         # "gelu" (tanh approx = HF gelu_new), "relu" (OPT)
    use_alibi: bool = False          # BLOOM attention bias instead of positions
    use_emb_ln: bool = False         # BLOOM LayerNorm after word embedding
    parallel_residual: bool = False  # NeoX/GPT-J: x + attn(ln1 x) + mlp(ln2 x)
    sliding_window: Optional[int] = None  # Mistral local attention window
    attn_layer_types: Optional[tuple] = None  # GPT-Neo per-layer ("global",
                                     # "local", ...): "local" layers apply the
                                     # sliding_window mask, "global" full causal
    scale_attn: Any = True           # the score scale: True = 1/sqrt(hd),
                                     # False = 1 (GPT-Neo scores are NOT
                                     # scaled), a float = that VALUE (the
                                     # Granite family's `attention_multiplier`)
    embedding_multiplier: float = 1.0  # the token embedding times this
    logits_scaling: float = 1.0      # the logits DIVIDED by this
    qk_norm: bool = False            # OLMoE/OLMo-2: RMSNorm over the WHOLE
                                     # projected query and key (all heads'
                                     # columns together) before the heads
                                     # are split and rotated
    qk_norm_per_head: bool = False   # EXAONE-4 family: RMSNorm over EACH
                                     # head's columns of q and k (one scale
                                     # vector of head_dim shared by the
                                     # heads), after the split, before rotary
    attn_output_gate: bool = False   # Qwen3-Next: the query projection is twice
                                     # as wide and its second half GATES the
                                     # attention's result, `attn *
                                     # sigmoid(gate)` before the out-projection
                                     # (`attn_qkv_w`'s columns: q | k | v | gate)
    post_norm: bool = False          # EXAONE-4 family: no norm in front of a
                                     # half; `x + norm(attn(x))`, then
                                     # `h + norm(mlp(h))` (ln1/ln2 scale the
                                     # halves' OUTPUTS)
    block_length: int = 1            # generation by diffusion over blocks
                                     # (SDAR): position i attends j iff
                                     # j // B <= i // B, causal over blocks of
                                     # B positions and BIDIRECTIONAL inside
                                     # one, on the paged serving path (a row's
                                     # frontier is the END of its block, `pos
                                     # | (B - 1)`; B a power of two). 1: the
                                     # causal mask
    tie_embeddings: bool = True
    remat: bool = True               # jax.checkpoint each block
    remat_policy: Any = None         # None: the block HOLDS what its backward
                                     # reads of the forward (the flash
                                     # kernel's output and log-sum-exp, the
                                     # MLP's product before its activation,
                                     # the QKV product; the out-projection's
                                     # result where the residual is
                                     # sequential), as many of them, in that
                                     # order, as the device's free memory
                                     # allows: `held_candidates`, chosen where
                                     # the engine traces its step
                                     # (docs/activation_checkpointing.md);
                                     # nothing fits or nobody gave a budget:
                                     # every block is recomputed. A
                                     # `jax.checkpoint_policies` name, or a
                                     # policy itself, overrides the choice
    use_flash_attention: Optional[bool] = None  # None = AUTO by sequence
                                     # length: the Pallas kernel engages at
                                     # T >= FLASH_MIN_SEQ (measured r4, bf16
                                     # dots + 512-blocks: XLA wins <=512
                                     # (0.78 vs 1.22ms), flash wins 1.6x at
                                     # 1k, 2.3x at 2k, 3.4x at 4k fwd+bwd)
                                     # and, since it streams K/V from HBM,
                                     # carries EVERY longer T (no VMEM cap).
                                     # True/False force the choice. The
                                     # DECODE kernel auto-engages from
                                     # M >= DECODE_KERNEL_MIN_CTX: at short
                                     # contexts XLA's einsum sits at the
                                     # bandwidth floor (r5: 174-204us vs
                                     # kernel 189us vs floor 164us at ctx
                                     # 8k), but the blocked kernel reads
                                     # only the live prefix of the cache
                                     # while XLA always reads all M — at
                                     # serving-scale caches that asymmetry,
                                     # not the matmul, decides; see
                                     # docs/kernels.md
    attention_backend: Optional[str] = None  # explicit attention-program
                                     # request for the dispatch layer
                                     # (ops/attention_dispatch.py): "ring" /
                                     # "ring_ulysses" engage context
                                     # parallelism over the `sequence` mesh
                                     # axis (K/V shards rotate via ppermute;
                                     # the hybrid adds the Ulysses head
                                     # all-to-all, sp = ulysses x ring).
                                     # None = auto (flash/dense by
                                     # the measured crossovers). Ignored
                                     # when no `sequence` axis > 1 is
                                     # installed — the request falls through
                                     # to the auto programs
    act_quant: Any = None            # ActQuantGate (compression/pruners.py):
                                     # when .active, each block linear's INPUT
                                     # is fake-quantized to .bits with STE
                                     # (reference basic_layer QuantAct role)
    loss_chunks: int = 0             # >0: chunked-vocab CE (ops/chunked_ce.py)
                                     # — never materializes [B,T,V] logits;
                                     # frees ~1.2G peak HBM at 50k vocab for
                                     # one extra head-matmul pass in the bwd
    softmax_dtype: Any = jnp.float32  # attention softmax accumulation dtype;
                                     # bf16 halves the dominant HBM traffic of
                                     # materialized attention (max-subtracted,
                                     # exp still in fp32) — the bench uses it
    scan_unroll: int = 1             # lax.scan unroll over layers (measured r4:
                                     # unroll=2 LOSES 14% at the bench shape —
                                     # bigger program, no slice saved; keep 1)
    remat_prevent_cse: bool = False  # jax.checkpoint prevent_cse. False is the
                                     # documented-efficient form inside scan
                                     # (the scan boundary already stops the CSE
                                     # that prevent_cse guards against) and
                                     # measured +6.4%/+6.7% MFU on the
                                     # 760m/1.3b bench lanes (0.597->0.633,
                                     # 0.588->0.628 at gas 8)
    dtype: Any = jnp.bfloat16        # activation dtype

    def __post_init__(self):
        if self.d_ff is None:
            self.d_ff = int(8 * self.d_model / 3) if self.use_swiglu else 4 * self.d_model
        if self.n_kv_head is None:
            self.n_kv_head = self.n_head
        # a head width given apart (`attn_head_dim`) need not divide d_model
        assert self.attn_head_dim or self.d_model % self.n_head == 0
        assert self.n_head % self.n_kv_head == 0
        assert self.block_length >= 1 \
            and self.block_length & (self.block_length - 1) == 0

    @property
    def head_dim(self):
        return self.attn_head_dim or self.d_model // self.n_head

    @property
    def value_dim(self):
        return self.attn_value_dim or self.head_dim

    @property
    def qkv_dim(self):
        """Fused qkv output width: H*hd for q + Hkv*hd for k + Hkv*value_dim
        for v (GQA-aware), and H*value_dim more where the output is gated
        (`attn_output_gate`)."""
        return (self.n_head + self.n_kv_head) * self.head_dim \
            + (self.attn_output_gate * self.n_head
               + self.n_kv_head) * self.value_dim

    def num_params(self):
        wpe = 0 if self.use_rotary else self.max_seq_len * self.d_model
        per_block = (self.d_model * (self.qkv_dim + self.d_model)  # qkv + proj
                     + (3 if self.use_swiglu else 2) * self.d_model * self.d_ff
                     + 4 * self.d_model)                       # norms/biases approx
        emb = self.vocab_size * self.d_model
        head = 0 if self.tie_embeddings else self.vocab_size * self.d_model
        return emb + head + wpe + self.n_layer * per_block


# Reference model sizes used in the baseline ladder (BASELINE.md). Head counts
# for the training-bench sizes are chosen so head_dim == 128, the MXU lane
# width (head_dim 64/96 leaves 25-50% of every attention dot's lanes padded —
# measured +14% MFU on the 1.3B lane, +3.5% on 760m). Param count is
# head-count invariant, and the reference's own ZeRO tutorial picks 16 heads
# for its 1.5B GPT-2 (`docs/_tutorials/zero.md:35`); HF-checkpoint adapters
# (`inference/adapters.py`) carry each checkpoint's true head count instead.
GPT2_CONFIGS = {
    "gpt2-tiny": GPTConfig(n_layer=2, n_head=4, d_model=128, max_seq_len=256, vocab_size=1024),
    "gpt2-125m": GPTConfig(n_layer=12, n_head=12, d_model=768, max_seq_len=1024),
    "gpt2-350m": GPTConfig(n_layer=24, n_head=8, d_model=1024, max_seq_len=1024),
    "gpt2-760m": GPTConfig(n_layer=24, n_head=12, d_model=1536, max_seq_len=1024),
    "gpt2-1.3b": GPTConfig(n_layer=24, n_head=16, d_model=2048, max_seq_len=1024),
    "gpt2-2.7b": GPTConfig(n_layer=32, n_head=20, d_model=2560, max_seq_len=1024),
    "gpt2-6.7b": GPTConfig(n_layer=32, n_head=32, d_model=4096, max_seq_len=1024),
}


# ----------------------------------------------------------------------
# init
# ----------------------------------------------------------------------


def init_gpt_params(cfg: GPTConfig, seed: int = 0, dtype=jnp.float32):
    """Stacked-block parameter pytree. Block leaves have leading dim n_layer."""
    rng = np.random.default_rng(seed)
    D, F, L, H = cfg.d_model, cfg.d_ff, cfg.n_layer, cfg.n_head

    def norm(*shape, scale=0.02):
        return jnp.asarray(rng.normal(0.0, scale, shape), dtype)

    def zeros(*shape):
        return jnp.zeros(shape, dtype)

    def ones(*shape):
        return jnp.ones(shape, dtype)

    proj_scale = 0.02 / math.sqrt(2 * L)  # GPT-2 residual-proj init
    QKV = cfg.qkv_dim
    block = {
        "ln1_scale": ones(L, D),
        "ln2_scale": ones(L, D),
        "attn_qkv_w": norm(L, D, QKV),
        "attn_qkv_b": zeros(L, QKV),
        "attn_out_w": jnp.asarray(rng.normal(0.0, proj_scale, (L, D, D)), dtype),
        "attn_out_b": zeros(L, D),
        "mlp_out_b": zeros(L, D),
    }
    if not cfg.use_rmsnorm:
        block["ln1_bias"] = zeros(L, D)
        block["ln2_bias"] = zeros(L, D)
    if cfg.qk_norm:
        block["q_norm_scale"] = ones(L, H * cfg.head_dim)
        block["k_norm_scale"] = ones(L, cfg.n_kv_head * cfg.head_dim)
    if cfg.use_swiglu:
        block["mlp_gate_w"] = norm(L, D, F)
        block["mlp_up_w"] = norm(L, D, F)
        block["mlp_down_w"] = jnp.asarray(rng.normal(0.0, proj_scale, (L, F, D)), dtype)
    else:
        block["mlp_up_w"] = norm(L, D, F)
        block["mlp_up_b"] = zeros(L, F)
        block["mlp_down_w"] = jnp.asarray(rng.normal(0.0, proj_scale, (L, F, D)), dtype)

    params = {
        "wte": norm(cfg.vocab_size, D, scale=0.02),
        "blocks": block,
        "lnf_scale": ones(D),
    }
    if not cfg.use_rmsnorm:
        params["lnf_bias"] = zeros(D)
    if not cfg.use_rotary and not cfg.use_alibi:
        params["wpe"] = norm(cfg.max_seq_len, D, scale=0.01)
    if cfg.use_emb_ln:
        params["emb_ln_scale"] = ones(D)
        params["emb_ln_bias"] = zeros(D)
    if not cfg.tie_embeddings:
        params["lm_head"] = norm(cfg.vocab_size, D, scale=0.02)
    return params


def gpt_init_fn(cfg: GPTConfig, dtype=jnp.float32):
    """jax-traceable initializer (rng -> params) mirroring `init_gpt_params`.

    For the engine's zero.Init path (ModelSpec.init_fn): the returned function
    runs under jit with stage-3 out_shardings, so each leaf is created directly
    in its shard and a model larger than host RAM / one-chip HBM never
    materializes whole (reference `zero/partition_parameters.py:723`)."""
    D, F, L = cfg.d_model, cfg.d_ff, cfg.n_layer
    proj_scale = 0.02 / math.sqrt(2 * L)
    QKV = cfg.qkv_dim

    def init(rng):
        keys = iter(jax.random.split(rng, 16))
        norm = lambda *shape, scale=0.02: (
            jax.random.normal(next(keys), shape, dtype) * scale)
        zeros = lambda *shape: jnp.zeros(shape, dtype)
        ones = lambda *shape: jnp.ones(shape, dtype)
        block = {
            "ln1_scale": ones(L, D),
            "ln2_scale": ones(L, D),
            "attn_qkv_w": norm(L, D, QKV),
            "attn_qkv_b": zeros(L, QKV),
            # ([L, D, D] wherever the heads' values tile the stream)
            "attn_out_w": norm(L, cfg.n_head * cfg.value_dim, D,
                               scale=proj_scale),
            "attn_out_b": zeros(L, D),
            "mlp_out_b": zeros(L, D),
        }
        if not cfg.use_rmsnorm:
            block["ln1_bias"] = zeros(L, D)
            block["ln2_bias"] = zeros(L, D)
        if cfg.qk_norm:
            block["q_norm_scale"] = ones(L, cfg.n_head * cfg.head_dim)
            block["k_norm_scale"] = ones(L, cfg.n_kv_head * cfg.head_dim)
        if cfg.qk_norm_per_head:        # one scale vector the heads share
            block["q_norm_scale"] = ones(L, cfg.head_dim)
            block["k_norm_scale"] = ones(L, cfg.head_dim)
        if cfg.use_swiglu:
            block["mlp_gate_w"] = norm(L, D, F)
            block["mlp_up_w"] = norm(L, D, F)
            block["mlp_down_w"] = norm(L, F, D, scale=proj_scale)
        else:
            block["mlp_up_w"] = norm(L, D, F)
            block["mlp_up_b"] = zeros(L, F)
            block["mlp_down_w"] = norm(L, F, D, scale=proj_scale)
        params = {
            "wte": norm(cfg.vocab_size, D, scale=0.02),
            "blocks": block,
            "lnf_scale": ones(D),
        }
        if not cfg.use_rmsnorm:
            params["lnf_bias"] = zeros(D)
        if not cfg.use_rotary and not cfg.use_alibi:
            params["wpe"] = norm(cfg.max_seq_len, D, scale=0.01)
        if cfg.use_emb_ln:
            params["emb_ln_scale"] = ones(D)
            params["emb_ln_bias"] = zeros(D)
        if not cfg.tie_embeddings:
            params["lm_head"] = norm(cfg.vocab_size, D, scale=0.02)
        return params

    return init


def gpt_param_specs(cfg: GPTConfig):
    """Megatron-style TP PartitionSpecs (reference: AutoTP's shard plan,
    `module_inject/auto_tp.py` — column-parallel qkv/up, row-parallel out/down).
    ZeRO adds its axes orthogonally (runtime/zero.py)."""
    t = TENSOR_AXIS
    block = {
        "ln1_scale": P(None, None),
        "ln2_scale": P(None, None),
        "attn_qkv_w": P(None, None, t),      # column parallel
        "attn_qkv_b": P(None, t),
        "attn_out_w": P(None, t, None),      # row parallel
        "attn_out_b": P(None, None),
        "mlp_out_b": P(None, None),
    }
    if not cfg.use_rmsnorm:
        block["ln1_bias"] = P(None, None)
        block["ln2_bias"] = P(None, None)
    if cfg.qk_norm or cfg.qk_norm_per_head:
        # the norm reduces over all heads' columns (or the heads share one
        # scale vector): replicated, like ln1
        block["q_norm_scale"] = P(None, None)
        block["k_norm_scale"] = P(None, None)
    if cfg.use_swiglu:
        block["mlp_gate_w"] = P(None, None, t)
        block["mlp_up_w"] = P(None, None, t)
        block["mlp_down_w"] = P(None, t, None)
    else:
        block["mlp_up_w"] = P(None, None, t)
        block["mlp_up_b"] = P(None, t)
        block["mlp_down_w"] = P(None, t, None)
    specs = {
        "wte": P(t, None),                   # vocab-parallel embedding
        "blocks": block,
        "lnf_scale": P(None),
    }
    if not cfg.use_rmsnorm:
        specs["lnf_bias"] = P(None)
    if not cfg.use_rotary and not cfg.use_alibi:
        specs["wpe"] = P(None, None)
    if cfg.use_emb_ln:
        specs["emb_ln_scale"] = P(None)
        specs["emb_ln_bias"] = P(None)
    if not cfg.tie_embeddings:
        specs["lm_head"] = P(t, None)
    return specs


# ----------------------------------------------------------------------
# forward
# ----------------------------------------------------------------------


def _norm(x, scale, bias, use_rms, eps=1e-5):
    xf = x.astype(jnp.float32)
    if use_rms:
        xf = xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True) + eps)
        return (xf * scale.astype(jnp.float32)).astype(x.dtype)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    xf = (xf - mean) * jax.lax.rsqrt(var + eps)
    return (xf * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(x.dtype)


def _act(x, cfg):
    if cfg.activation == "relu":
        return jax.nn.relu(x)
    if cfg.activation == "quick_gelu":  # CLIP text encoder (x * sigmoid(1.702x))
        return x * jax.nn.sigmoid(1.702 * x)
    return jax.nn.gelu(x)


def _alibi_slopes(n_heads):
    """BLOOM/press-et-al alibi head slopes (geometric in 2^(-8/n); odd head
    counts get the interleaved extension)."""
    def pow2_slopes(n):
        start = 2.0 ** (-(2.0 ** -(math.log2(n) - 3)))
        return [start * (start ** i) for i in range(n)]

    if math.log2(n_heads).is_integer():
        slopes = pow2_slopes(n_heads)
    else:
        base = 2 ** math.floor(math.log2(n_heads))
        slopes = pow2_slopes(base)
        extra = pow2_slopes(2 * base)[0::2][: n_heads - base]
        slopes += extra
    return jnp.asarray(slopes, jnp.float32)


def _alibi_bias(cfg, q_positions, k_positions):
    """[H, Tq, S] additive attention bias: -slope_h * (t - s)."""
    dist = (q_positions[:, None] - k_positions[None, :]).astype(jnp.float32)
    return -_alibi_slopes(cfg.n_head)[:, None, None] * dist


def _window_mask(q_positions, k_positions, window):
    """Sliding-window validity [Tq, S]: key within `window` of the query."""
    dist = q_positions[:, None] - k_positions[None, :]
    return dist < window


@partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _rope(x, positions, rotary_dims, theta=10000.0):
    """Rotary position embedding over the first `rotary_dims` of the head dim.
    x: [B, T, H, hd]; positions: [B, T].

    The pairing is (even, odd) INTERLEAVED: columns (2i, 2i+1) turn by
    `positions * theta**(-2i / rotary_dims)` (HF's rotate-half order is
    re-ordered to it at import, `inference/adapters.py::_unpermute_rope_rows`;
    `benchmark/references/decoder.py::_rope` pairs the same way). It is ONE
    multiply-add over the whole head:

        rope(x) = x * C + swap(x) * S
        C[j] = cos(angle[j // 2]) for j < rd, 1 beyond
        S[j] = -sin(..) for even j < rd, +sin(..) for odd j < rd, 0 beyond
        swap(x)[j] = x[j ^ 1] for j < rd

    `swap` is a PRODUCT with a constant 0/1 matrix in x's dtype with a float32
    result: exact for finite x (an output is one input times 1, plus zeros).
    The products and the sum are float32 and round to x's dtype once. An
    `inf` or `nan` in one column of a head reaches every column of that head
    (of that row alone) as `nan` (`inf * 0`); both are non-finite to whoever
    checks (the loss scaler's overflow test reads `isfinite`).

    The transpose of a rotation turns the other way, so the backward pass IS
    this function at `-positions` (a `custom_vjp`), rounded once like the
    forward. Reverse mode passes it any number of times; forward mode
    (`jax.jvp`) does not pass a `custom_vjp`. The backward's result stands
    behind an `optimization_barrier`: the gradient goes on to a reshape
    `[.., H, hd] -> [.., H*hd]`, which on the TPU is a relayout, and left to
    itself XLA makes that reshape a bitcast by running THIS product T-minor,
    with a transposing copy in front of it and one behind (PERF.md section
    7); held, the product runs in the layout its operand has and the one
    relayout rides in the update that places the gradient in the fused
    projection's."""
    return _rotate(x, positions, rotary_dims, theta)


def _rotate(x, positions, rd, theta):
    """`_rope`'s multiply-add (its docstring has the formula)."""
    hd = x.shape[-1]
    lane = jnp.arange(hd, dtype=jnp.int32)
    freqs = 1.0 / (theta**((lane // 2 * 2).astype(jnp.float32) / rd))
    # a lane beyond rd turns by the angle 0: C = 1 and S = 0 there, exactly
    angles = positions[..., None].astype(jnp.float32) * jnp.where(
        lane < rd, freqs, 0.0)                                  # [B,T,hd]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = (jnp.where(lane % 2 == 0, -1.0, 1.0) * jnp.sin(angles))[:, :, None, :]
    pair = np.arange(rd)
    swap = np.zeros((hd, hd), np.float32)
    swap[pair ^ 1, pair] = 1.0
    # bfloat16 goes through the MXU as it is, in one pass; a wider mantissa
    # is kept whole only at the highest precision
    wide = x.dtype != jnp.bfloat16
    swapped = jnp.matmul(
        x, jnp.asarray(swap, x.dtype), preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST if wide else None)
    kept, turned = x * cos, swapped * sin
    if wide:
        # float32's last bit shows whether the compiler contracted a product
        # into the sum, which XLA's CPU backend does or not by what surrounds
        # the call: each product is rounded where it is made, so every
        # program that rotates the same rows writes the same bits
        kept, turned = (jax.lax.reduce_precision(t, 8, 23)
                        for t in (kept, turned))
    return (kept + turned).astype(x.dtype)


_rope.defvjp(
    lambda x, positions, rd, theta: (_rotate(x, positions, rd, theta), positions),
    lambda rd, theta, positions, g: (
        jax.lax.optimization_barrier(_rotate(g, -positions, rd, theta)), None))


# What a block's backward reads of its forward is named where it is made
# (`jax.ad_checkpoint.checkpoint_name`; the flash kernel names its own two
# residuals, `ops/pallas/flash_attention.py::FLASH_RESIDUALS`). Outside a
# `jax.checkpoint` a name lowers to nothing: the serving programs run the same
# halves and do not change by it.
MLP_PRODUCT = "mlp_product"     # h @ w_up (+ b) BEFORE the activation; SwiGLU:
                                # both products
QKV_PRODUCT = "qkv_product"     # the fused q | k | v (| gate) projection
ATTN_OUT = "attn_out"           # the out-projection's result: the second
                                # half's input reads it where the residual is
                                # sequential, nothing does where it is parallel


def on_one_device(n, axis):
    """`n` of a dimension the mesh's `axis` divides, as one device holds it
    (`shard_constraint` leaves a dimension whole where the axis does not
    divide it; no mesh: whole)."""
    if mesh_mod.has_mesh() and n % mesh_mod.axis_size(axis) == 0:
        return n // mesh_mod.axis_size(axis)
    return n


def held_candidates(cfg: GPTConfig, B, T, attn_fn=None):
    """({name: bytes a layer on ONE device}, the step's working set with
    nothing named) for a `[B, T]` batch as the traced program sees it
    (global under the engine's partitioned `jit`: the mesh's batch, sequence
    and tensor axes divide it here, as `shard_constraint` lays the
    activations out).

    The names are in the order `fit_held` takes them, by the milliseconds of
    recompute a held GiB saves on the v5e (PERF.md section 6, PR 49): the
    flash forward 14 on one chip and 28 on four (a kernel at half its
    roofline), the MLP's and the QKV products 12 and 13 (matmuls near the
    peak at the same FLOPs a byte: the wider one first, it strands less of
    the room), then the out-projection's, which has a reader only in a
    sequential block. The second result is the step's working set whatever
    is named, as `held_policy` takes it: every layer's input; the loss's
    (the logits and a quarter again); one block's backward (the MLP's
    product, its gradient and the QKV product)."""
    divide = on_one_device
    tokens = divide(B, BATCH_AXES) * divide(T, SEQ_AXIS)
    item = jnp.dtype(cfg.dtype).itemsize
    H, Hkv, hd = cfg.n_head, cfg.n_kv_head, cfg.head_dim
    heads = divide(H, TENSOR_AXIS)
    held = {}
    site = _train_attn_site(cfg, T, T, cfg.use_alibi, attn_fn)
    if attn_dispatch.select(site) == "flash":
        from deepspeed_tpu.ops.pallas.flash_attention import FLASH_RESIDUALS
        # the output, and a float32 log-sum-exp a row in the kernels' own
        # `[BH, T / bq, 1, bq]` tile, whose unit sublane the device pads to 8
        held[FLASH_RESIDUALS] = tokens * heads * (hd * item + 8 * 4)
    up = tokens * divide(cfg.d_ff, TENSOR_AXIS) * item
    held[MLP_PRODUCT] = up * (2 if cfg.use_swiglu else 1)
    qkv_heads = H + 2 * Hkv + (H if cfg.attn_output_gate else 0)
    held[QKV_PRODUCT] = tokens * divide(qkv_heads, TENSOR_AXIS) * hd * item
    if cfg.post_norm or not cfg.parallel_residual:
        held[ATTN_OUT] = tokens * cfg.d_model * item
    logits = 0 if cfg.loss_chunks else \
        tokens * divide(cfg.vocab_size, TENSOR_AXIS) * item * 5 // 4
    return held, dict(
        carried_bytes=cfg.n_layer * tokens * cfg.d_model * item,
        loss_bytes=logits,
        backward_bytes=2 * up + held[QKV_PRODUCT])


def _remat_policy(cfg: GPTConfig, B, T, attn_fn):
    """The `jax.checkpoint` policy of the scanned block: the held set that
    fits (`cfg.remat_policy` None), else the `jax.checkpoint_policies` name
    or the policy that field carries."""
    policy = cfg.remat_policy
    if policy is None:
        from deepspeed_tpu.runtime.activation_checkpointing import held_policy
        held, working_set = held_candidates(cfg, B, T, attn_fn)
        return held_policy(held, cfg.n_layer, **working_set)
    if callable(policy):
        return policy
    try:
        return getattr(jax.checkpoint_policies, policy)
    except (AttributeError, TypeError):
        raise ValueError(
            f"remat_policy {policy!r} is no jax.checkpoint_policies name; "
            f"leave it None for the held set the engine derives") from None


# Dispatch crossovers live in ops/attention_dispatch.py (ONE home for the
# predicates every attention call site shares); re-exported here for the
# callers that read the constants (tests, bench).
FLASH_MIN_SEQ = attn_dispatch.FLASH_MIN_SEQ
DECODE_KERNEL_MIN_CTX = attn_dispatch.DECODE_KERNEL_MIN_CTX


def sm_scale(cfg):
    """`cfg.scale_attn` as the kernels' `sm_scale`: None is their own default,
    1 / sqrt(head_dim)."""
    if cfg.scale_attn is True:
        return None
    return 1.0 if cfg.scale_attn is False else float(cfg.scale_attn)


def score_scale(cfg, hd):
    """... and as the number a dense path multiplies the scores by."""
    scale = sm_scale(cfg)
    return 1.0 / math.sqrt(hd) if scale is None else scale


def _train_attn_site(cfg, T, S, has_bias, attn_fn):
    """Dispatch key for the training/prefill attention call sites."""
    return attn_dispatch.AttnSite(
        phase="train", q_len=T, kv_len=S, causal=True,
        has_bias=has_bias, has_window=bool(cfg.sliding_window),
        scale_attn=bool(cfg.scale_attn),
        sink=cfg.attn_sink, head_dim=cfg.head_dim, value_dim=cfg.value_dim,
        mesh_axes=attn_dispatch.active_mesh_axes(),
        force_flash=cfg.use_flash_attention,
        backend=getattr(cfg, "attention_backend", None),
        external_fn=attn_fn is not None)


def _softmax_with_sink(logits, sink):
    """float32 softmax over the keys of grouped scores `logits` [B, Hkv, G,
    rows, S] with one more column, the logit `sink` [H] a head, that joins
    the denominator and is dropped: the rows sum to less than 1. `sink`
    None: the plain softmax."""
    if sink is None:
        return jax.nn.softmax(logits, axis=-1)
    column = jnp.broadcast_to(
        sink.astype(logits.dtype).reshape(logits.shape[1:3])[:, :, None,
                                                             None],
        logits.shape[:-1] + (1,))
    return jax.nn.softmax(jnp.concatenate([logits, column], axis=-1),
                          axis=-1)[..., :-1]


def _attention(q, k, v, causal_mask, cfg, attn_fn=None, bias=None,
               sink=None):
    """q: [B, T, H, hd]; k: [B, S, Hkv, hd]; v: [B, S, Hkv, vd] → [B, T, H,
    vd]. fp32 softmax; `sink` [H]: `cfg.attn_sink`'s logit a head.

    GQA (Hkv < H): query heads are grouped per kv head and contracted without
    materializing repeated k/v (reference serves GQA models like llama2-70b via
    `module_inject/containers/llama2.py`). `bias`: additive [H, T, S] (alibi).

    Program selection goes through the unified dispatch layer
    (`ops/attention_dispatch.py`): flash at the measured crossover, ring /
    ring∘Ulysses context parallelism on request
    (`GPTConfig.attention_backend`), dense XLA otherwise — every
    registered program's runner is invoked through the same matched-heads
    external-fn path, so a new variant plugs in at the registry, not here
    (a program without a runner: the caller's `attn_fn`, or the dense form
    below)."""
    runner = attn_dispatch.get_program(attn_dispatch.select(
        _train_attn_site(cfg, q.shape[1], k.shape[1], bias is not None,
                         attn_fn))).runner
    if runner is not None:
        attn_fn = partial(runner, causal=True,
                          sm_scale=sm_scale(cfg))
    if attn_fn is not None:
        if k.shape[2] != q.shape[2]:  # external kernels expect matched heads
            rep = q.shape[2] // k.shape[2]
            k = jnp.repeat(k, rep, axis=2)
            v = jnp.repeat(v, rep, axis=2)
        return attn_fn(q, k, v)
    scale = score_scale(cfg, q.shape[-1])
    B, T, H, hd = q.shape
    Hkv = k.shape[2]
    G = H // Hkv  # grouped einsum; G == 1 is plain MHA
    sm_dtype = jnp.dtype(getattr(cfg, "softmax_dtype", jnp.float32))
    qg = q.reshape(B, T, Hkv, G, hd)
    logits = jnp.einsum("btkgd,bskd->bkgts", qg, k).astype(sm_dtype) * scale
    if bias is not None:
        S = k.shape[1]
        logits = logits + bias.reshape(Hkv, G, T, S)[None].astype(sm_dtype)
    neg = jnp.asarray(-1e30 if sm_dtype == jnp.float32 else -3e38, sm_dtype)
    logits = jnp.where(causal_mask[:, None], logits, neg)
    if sm_dtype == jnp.float32:
        probs = _softmax_with_sink(logits, sink).astype(q.dtype)
    else:
        assert sink is None, "a sink logit needs the float32 softmax"
        # reduced-precision softmax: the [T,S] score tensor stays bf16 (the
        # HBM-traffic hot spot); max-subtraction keeps exp well-conditioned
        # and the exp itself runs in fp32 before narrowing back
        m = jax.lax.stop_gradient(jnp.max(logits, axis=-1, keepdims=True))
        e = jnp.exp((logits - m).astype(jnp.float32)).astype(q.dtype)
        denom = jnp.sum(e, axis=-1, keepdims=True, dtype=jnp.float32)
        probs = (e.astype(jnp.float32) / denom).astype(q.dtype)
    out = jnp.einsum("bkgts,bskd->btkgd", probs, v)
    return out.reshape(B, T, H, v.shape[-1])


def _act_quant(x, cfg):
    """Activation fake-quant at a linear input, gated by the compression
    schedule (trace-time read; the engine retraces when the gate flips)."""
    gate = getattr(cfg, "act_quant", None)
    if gate is None or not gate.active:
        return x
    from deepspeed_tpu.compression.basic_layer import quantize_activation
    return quantize_activation(x, gate.bits, symmetric=gate.symmetric)


def _mlp(h, p, cfg, constrain=True):
    """MLP half-block: gated (swiglu) or plain with configurable activation.
    `constrain=False` on the decode path ([B, 1, F] can't shard on sequence)."""
    h = _act_quant(h, cfg)
    # the products are named BEFORE the activation: its backward reads them,
    # so a block that held the activation's result would still make them again
    if cfg.use_swiglu:
        up = jax.nn.silu(checkpoint_name(h @ p["mlp_gate_w"], MLP_PRODUCT)) \
            * checkpoint_name(h @ p["mlp_up_w"], MLP_PRODUCT)
    else:
        up = _act(checkpoint_name(h @ p["mlp_up_w"] + p["mlp_up_b"],
                                  MLP_PRODUCT), cfg)
    if constrain:
        up = shard_constraint(up, BATCH_AXES, SEQ_AXIS, TENSOR_AXIS)
    up = _act_quant(up, cfg)
    return up @ p["mlp_down_w"] + p["mlp_out_b"]


def _qk_norm(q, k, p, cfg: GPTConfig, heads_split=False):
    """RMSNorm of the query and the key over their last axis, at one of two
    places (one definition for the training, prefill and paged halves):
    `cfg.qk_norm` BEFORE the heads are split, over the whole projected query
    [.., H*hd] and key [.., Hkv*hd]; `cfg.qk_norm_per_head` AFTER
    (`heads_split`), over each head's columns of [.., H, hd] / [.., Hkv, hd]
    with one scale vector [hd] for all heads."""
    if not (cfg.qk_norm_per_head if heads_split else cfg.qk_norm):
        return q, k
    return (_norm(q, p["q_norm_scale"], None, True, cfg.norm_eps),
            _norm(k, p["k_norm_scale"], None, True, cfg.norm_eps))


def _split_qkv(qkv, cfg: GPTConfig):
    """The fused projection's columns -> (q, k, v, gate): `gate` [.., H*hd]
    under `cfg.attn_output_gate`, else None."""
    H, Hkv, hd = cfg.n_head, cfg.n_kv_head, cfg.head_dim
    keys = (H + Hkv) * hd
    q, k, v, gate = jnp.split(
        qkv, [H * hd, keys, keys + Hkv * cfg.value_dim], axis=-1)
    return q, k, v, (gate if cfg.attn_output_gate else None)


def _scale_values(v, cfg: GPTConfig):
    """`cfg.attn_value_scale` on the projected values."""
    if cfg.attn_value_scale == 1.0:
        return v
    return (v * cfg.attn_value_scale).astype(v.dtype)


def _gate_output(attn, gate):
    """`attn * sigmoid(gate)` on the heads' results [.., H*hd] in front of
    the out-projection; `gate` None (no `attn_output_gate`): `attn`."""
    if gate is None:
        return attn
    with jax.named_scope("gate"):
        return attn * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(
            attn.dtype)


def _half_input(x, p, cfg: GPTConfig):
    """What the attention half reads: ln1(x), or x itself under
    `cfg.post_norm` (the norm then follows the half, `_residual_mlp`)."""
    if cfg.post_norm:
        return x
    return _norm(x, p["ln1_scale"], p.get("ln1_bias"), cfg.use_rmsnorm,
                 cfg.norm_eps)


def _layer_local_flags(cfg: GPTConfig):
    """attn_layer_types → bool[L] scan data (None when uniform attention)."""
    if cfg.attn_layer_types is None:
        return None
    assert cfg.sliding_window, "attn_layer_types needs sliding_window set"
    return jnp.asarray([t == "local" for t in cfg.attn_layer_types], bool)


def _attn_half(x, p, cfg: GPTConfig, positions, attn_fn=None, constrain=True,
               local_flag=None):
    """Attention half-block: ln1 → qkv → rope → masked attention → out-proj.

    Returns (attn_out, k, v) with k/v [B, T, Hkv, hd] so decode-model prefill
    can write them into the KV cache. Every architecture flag (rotary, alibi,
    sliding window, GQA) is honored here, in ONE place, for the training
    forward, the MoE blocks, and the inference prefill alike."""
    B, T, D = x.shape
    H, Hkv, hd = cfg.n_head, cfg.n_kv_head, cfg.head_dim

    with jax.named_scope("qkv"):
        h = _half_input(x, p, cfg)
        h = _act_quant(h, cfg)
        qkv = checkpoint_name(h @ p["attn_qkv_w"] + p["attn_qkv_b"],
                              QKV_PRODUCT)
        q, k, v, gate = _split_qkv(qkv, cfg)
        q, k = _qk_norm(q, k, p, cfg)
        q = q.reshape(B, T, H, hd)
        k = k.reshape(B, T, Hkv, hd)
        v = _scale_values(v.reshape(B, T, Hkv, cfg.value_dim), cfg)
        q, k = _qk_norm(q, k, p, cfg, heads_split=True)
        if constrain:
            # activations: heads on tensor axis (Megatron), seq on sequence
            # axis
            q = shard_constraint(q, BATCH_AXES, SEQ_AXIS, TENSOR_AXIS, None)
            k = shard_constraint(k, BATCH_AXES, SEQ_AXIS, TENSOR_AXIS, None)
            v = shard_constraint(v, BATCH_AXES, SEQ_AXIS, TENSOR_AXIS, None)
        if cfg.use_rotary:
            rd = int(cfg.rotary_pct * hd) // 2 * 2
            q = _rope(q, positions, rd, cfg.rope_theta)
            k = _rope(k, positions, rd, cfg.rope_theta)
    t_pos = jnp.arange(T, dtype=jnp.int32)
    causal = jnp.tril(jnp.ones((T, T), bool))
    if cfg.sliding_window:
        win = causal & _window_mask(t_pos, t_pos, cfg.sliding_window)
        if local_flag is None:
            causal = win
        else:  # GPT-Neo alternating global/local: flag is per-layer scan data
            causal = jnp.where(local_flag, win, causal)
    causal = causal[None, None, :, :]
    # alibi uses in-sequence distances (standard unpadded formulation)
    bias = _alibi_bias(cfg, t_pos, t_pos) if cfg.use_alibi else None
    attn = _attention(q, k, v, causal, cfg, attn_fn=attn_fn, bias=bias,
                      sink=p["attn_sink"] if cfg.attn_sink else None)
    attn_flat = _act_quant(
        _gate_output(attn.reshape(B, T, H * cfg.value_dim), gate), cfg)
    with jax.named_scope("out"):
        attn_out = checkpoint_name(
            attn_flat @ p["attn_out_w"] + p["attn_out_b"], ATTN_OUT)
    return attn_out, k, v


def _residual_mlp(x, attn_out, p, cfg: GPTConfig, constrain=True, mlp_fn=None):
    """Residual second half of a block; `mlp_fn` lets MoE swap the dense MLP."""
    if mlp_fn is None:
        mlp_fn = lambda h: _mlp(h, p, cfg, constrain)
    use_rms = cfg.use_rmsnorm
    if cfg.post_norm:
        x = x + _norm(attn_out, p["ln1_scale"], p.get("ln1_bias"), use_rms,
                      cfg.norm_eps)
        return x + _norm(mlp_fn(x), p["ln2_scale"], p.get("ln2_bias"),
                         use_rms, cfg.norm_eps)
    if cfg.parallel_residual:
        # NeoX/GPT-J: both halves read the block INPUT (GPT-J ties ln2 == ln1)
        h2 = _norm(x, p["ln2_scale"], p.get("ln2_bias"), use_rms, cfg.norm_eps)
        return x + attn_out + mlp_fn(h2)
    x = x + attn_out
    h2 = _norm(x, p["ln2_scale"], p.get("ln2_bias"), use_rms, cfg.norm_eps)
    return x + mlp_fn(h2)


def _head_table(params, cfg: GPTConfig):
    """The (tied) LM-head weight table [V, D] — single source of truth."""
    return params["lm_head"] if not cfg.tie_embeddings else params["wte"]


def _head_logits(params, x, cfg: GPTConfig):
    """LM-head matmul (+ GPT-J's tied bias). x: [B, T, D] -> [B, T, V]."""
    logits = jnp.einsum("btd,vd->btv", x, _head_table(params, cfg).astype(x.dtype))
    if "lm_head_bias" in params:  # GPT-J ties a bias to the LM head
        logits = logits + params["lm_head_bias"].astype(logits.dtype)
    return logits


def _last_rows(x, last_idx):
    """x [B, C, D] -> [B, 1, D], each row's position `last_idx` [B]: what
    the head reads of a prefill chunk (named with the head)."""
    with jax.named_scope("head"):
        return jnp.take_along_axis(x, last_idx[:, None, None], axis=1)


def _lm_head(params, x, cfg: GPTConfig):
    """Final norm + (tied) LM head. x: [B, T, D] -> logits [B, T, V]."""
    with jax.named_scope("head"):
        x = _norm(x, params["lnf_scale"], params.get("lnf_bias"),
                  cfg.use_rmsnorm, cfg.norm_eps)
        logits = _head_logits(params, x, cfg)
        if cfg.logits_scaling != 1.0:
            logits = logits / cfg.logits_scaling
    return logits


def _embed(params, tokens, positions, cfg: GPTConfig):
    """Token embedding + (absolute) position embedding + BLOOM emb LayerNorm.

    The tables are constrained to their gathered (TP-only) layout before the
    lookup: under ZeRO-3 the policy shards their feature dim over the zero
    domain, and XLA cannot reshard a gather whose operand is feature-sharded
    without a full replicate-then-partition of the output (SPMD partitioner
    warning). Constraining the *table* instead makes the all-gather explicit —
    exactly ZeRO-3's gather-before-use (reference
    `zero/partitioned_param_coordinator.py:256`) — after which the output
    transition to batch/seq sharding is a cheap slice."""
    with jax.named_scope("embed"):
        wte = shard_constraint(params["wte"], TENSOR_AXIS, None)
        x = jnp.take(wte, tokens, axis=0).astype(cfg.dtype)
        if cfg.embedding_multiplier != 1.0:
            x = x * cfg.embedding_multiplier
        if not cfg.use_rotary and not cfg.use_alibi:
            wpe = shard_constraint(params["wpe"], None, None)
            x = x + jnp.take(wpe, positions, axis=0).astype(cfg.dtype)
        if cfg.use_emb_ln:  # BLOOM word-embedding LayerNorm
            x = _norm(x, params["emb_ln_scale"], params.get("emb_ln_bias"),
                      use_rms=False, eps=cfg.norm_eps)
    return x


def _block(x, p, cfg: GPTConfig, positions, dropout_rng=None, attn_fn=None,
           local_flag=None):
    """One transformer block. x: [B, T, D]. The halves stand under the
    scopes the paged path gives them (`_block_paged`): a device trace of the
    training step reads `attn` and `mlp` too, its backward `transpose(...)`
    of the same names (`telemetry/device_scopes.py`)."""
    with jax.named_scope("attn"):
        attn_out, _, _ = _attn_half(x, p, cfg, positions, attn_fn=attn_fn,
                                    local_flag=local_flag)
    with jax.named_scope("mlp"):
        x = _residual_mlp(x, attn_out, p, cfg)
        return shard_constraint(x, BATCH_AXES, SEQ_AXIS, None)


def gpt_hidden(params, tokens, cfg: GPTConfig, positions=None, attn_fn=None,
               pld=None, ltd=None):
    """tokens: [B, T] int32 → final-norm'd hidden states [B, T, D].

    `pld`: (keep_idx [n_keep] int32, theta scalar) — progressive layer drop
    (reference `runtime/progressive_layer_drop.py`): only the kept layers'
    params are gathered and scanned (real flop savings — one compiled
    program per kept count), each kept layer's residual delta rescaled by
    1/theta (inverted stochastic depth, expectation-preserving).

    `ltd`: (start_layer int, keep_idx [B, n_ltd, K] int32) — random-LTD
    (reference `data_routing/basic_layer.py`): layers [start, start+n_ltd)
    process only each sample's K kept token positions (gather → block →
    scatter); dropped tokens bypass those layers unchanged. Attention inside
    the subset stays causal in ORIGINAL positions (indices arrive sorted);
    rotary embeddings read the true positions.
    """
    B, T = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (B, T))
    x = _embed(params, tokens, positions, cfg)
    x = shard_constraint(x, BATCH_AXES, SEQ_AXIS, None)

    flags = _layer_local_flags(cfg)
    if flags is None:
        block_fn = partial(_block, cfg=cfg, positions=positions, attn_fn=attn_fn)
    else:
        def block_fn(x, layer_params, flag):
            return _block(x, layer_params, cfg=cfg, positions=positions,
                          attn_fn=attn_fn, local_flag=flag)
    if cfg.remat:
        remat = partial(jax.checkpoint,
                        policy=_remat_policy(cfg, B, T, attn_fn),
                        prevent_cse=cfg.remat_prevent_cse)
        block_fn = remat(block_fn)

    if pld is not None:
        assert flags is None and ltd is None, \
            "progressive_layer_drop composes with neither per-layer attention "\
            "flags nor random-LTD"
        keep_idx, theta = pld
        kept = jax.tree_util.tree_map(
            lambda l: jnp.take(l, keep_idx, axis=0), params["blocks"])
        inv = (1.0 / jnp.maximum(theta, 1e-6)).astype(x.dtype)

        def pld_body(x, layer_params):
            return x + (block_fn(x, layer_params) - x) * inv, None

        x, _ = jax.lax.scan(pld_body, x, kept, unroll=cfg.scan_unroll)
    elif ltd is not None:
        assert flags is None, "random-LTD needs uniform attention layers"
        assert attn_fn is None, \
            "random-LTD gathers token subsets; a custom attn_fn with a " \
            "T-static layout cannot run on them"
        assert not cfg.use_alibi and not cfg.sliding_window, \
            "random-LTD subset attention does not carry alibi/window masks yet"
        start, kidx = ltd
        n_ltd = kidx.shape[1]
        blocks = params["blocks"]
        pre = jax.tree_util.tree_map(lambda l: l[:start], blocks)
        mid = jax.tree_util.tree_map(lambda l: l[start:start + n_ltd], blocks)
        post = jax.tree_util.tree_map(lambda l: l[start + n_ltd:], blocks)

        def sub_block(sx, lp, pos):
            return _block(sx, lp, cfg=cfg, positions=pos, attn_fn=None)
        if cfg.remat:
            sub_block = remat(sub_block)

        def plain_body(x, layer_params):
            return block_fn(x, layer_params), None

        def mid_body(carry, inp):
            lp, kx = inp                                  # kx: [B, K]
            sub = jnp.take_along_axis(carry, kx[..., None], axis=1)
            sub_out = sub_block(sub, lp, kx)
            carry = carry.at[jnp.arange(carry.shape[0])[:, None], kx].set(
                sub_out.astype(carry.dtype))
            return carry, None

        x, _ = jax.lax.scan(plain_body, x, pre, unroll=cfg.scan_unroll)
        x, _ = jax.lax.scan(mid_body, x, (mid, jnp.moveaxis(kidx, 0, 1)),
                            unroll=cfg.scan_unroll)
        x, _ = jax.lax.scan(plain_body, x, post, unroll=cfg.scan_unroll)
    elif flags is None:
        def scan_body(x, layer_params):
            return block_fn(x, layer_params), None
        x, _ = jax.lax.scan(scan_body, x, params["blocks"],
                            unroll=cfg.scan_unroll)
    else:
        def scan_body(x, inputs):
            layer_params, flag = inputs
            return block_fn(x, layer_params, flag), None
        x, _ = jax.lax.scan(scan_body, x, (params["blocks"], flags),
                            unroll=cfg.scan_unroll)

    with jax.named_scope("head"):
        return _norm(x, params["lnf_scale"], params.get("lnf_bias"),
                     cfg.use_rmsnorm, cfg.norm_eps)


def gpt_forward(params, tokens, cfg: GPTConfig, positions=None, attn_fn=None):
    """tokens: [B, T] int32 → logits [B, T, vocab]."""
    x = gpt_hidden(params, tokens, cfg, positions=positions, attn_fn=attn_fn)
    return _head_logits(params, x, cfg)


def gpt_loss(params, batch, rng, cfg: GPTConfig, attn_fn=None):
    """Causal-LM cross entropy. batch: {"tokens": [B,T]} or {"input_ids", "labels"}."""
    tokens = batch.get("tokens", batch.get("input_ids"))
    labels = batch.get("labels")
    if labels is None:
        inputs, labels = tokens[:, :-1], tokens[:, 1:]
    else:
        inputs = tokens
    # engine-injected routing directives (engine._inject_routing_directives):
    # broadcast over the batch dim; counts ride in the SHAPES (static)
    pld = ltd = None
    if "pld_keep_idx" in batch:
        pld = (batch["pld_keep_idx"][0], batch["pld_theta"][0])
    if "ltd_keep_idx" in batch:
        ltd = (batch["ltd_start"].shape[1], batch["ltd_keep_idx"])
    if cfg.loss_chunks:
        from deepspeed_tpu.ops.chunked_ce import chunked_softmax_xent
        B, T = inputs.shape
        x = gpt_hidden(params, inputs, cfg, attn_fn=attn_fn, pld=pld, ltd=ltd)
        assert "lm_head_bias" not in params, \
            "chunked CE does not support a tied LM-head bias"
        head = _head_table(params, cfg)
        with jax.named_scope("head_loss"):
            nll = chunked_softmax_xent(
                x.reshape(B * T, -1), head.astype(x.dtype),
                labels.reshape(B * T), cfg.loss_chunks)
            mask = (labels.reshape(B * T) >= 0).astype(jnp.float32)
            return (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    x = gpt_hidden(params, inputs, cfg, attn_fn=attn_fn, pld=pld, ltd=ltd)
    with jax.named_scope("head_loss"):
        return _xent(_head_logits(params, x, cfg), labels)


def _xent(logits, labels):
    """Mean causal-LM cross entropy of logits [B, T, V] against labels
    [B, T] (negative = ignored)."""
    # cross entropy WITHOUT materializing an fp32 [B,T,V] buffer (1.65G at
    # mbs16/seq512/50k vocab): logits stay in compute dtype, the exp/sum runs
    # with an fp32 accumulator fused into the reduction, and only [B,T]
    # tensors ever exist in fp32.
    m = jax.lax.stop_gradient(jnp.max(logits, axis=-1, keepdims=True))
    sumexp = jnp.sum(jnp.exp((logits - m).astype(jnp.float32)), axis=-1)
    logz = m[..., 0].astype(jnp.float32) + jnp.log(sumexp)
    safe_labels = jnp.maximum(labels, 0)  # ignore-index (<0) must not wrap
    gold = jnp.take_along_axis(logits, safe_labels[..., None],
                               axis=-1)[..., 0].astype(jnp.float32)
    mask = (labels >= 0).astype(jnp.float32)
    nll = (logz - gold) * mask
    return nll.sum() / jnp.maximum(mask.sum(), 1.0)


def make_gpt_model(cfg: GPTConfig = None, name="gpt2-125m", seed=0, attn_fn=None,
                   abstract=False) -> ModelSpec:
    """ModelSpec for the training engine.

    `abstract=True` returns a spec with init_fn instead of concrete params —
    the engine then materializes each leaf directly into its ZeRO/TP shard
    (zero.Init, `zero/partition_parameters.py:723`)."""
    cfg = cfg or GPT2_CONFIGS[name]
    return ModelSpec(
        loss_fn=partial(gpt_loss, cfg=cfg, attn_fn=attn_fn),
        params=None if abstract else init_gpt_params(cfg, seed=seed),
        init_fn=gpt_init_fn(cfg) if abstract else None,
        arch_cfg=cfg,
        # same attention on the eval/inference forward as in training (a
        # sparse/custom attn_fn must not silently fall back to dense here)
        apply_fn=partial(gpt_forward, cfg=cfg, attn_fn=attn_fn),
        param_specs=gpt_param_specs(cfg),
        name=name,
    )


# ----------------------------------------------------------------------
# decode path (KV cache) — for the inference engine
# ----------------------------------------------------------------------


def init_kv_cache(cfg: GPTConfig, batch_size, max_len, dtype=jnp.bfloat16):
    """[L, B, Hkv, max_len, hd] stacked cache (reference: InferenceContext
    workspace, `csrc/transformer/inference/includes/inference_context.h:49`).
    Head-major layout so the decode kernel streams one head's K/V contiguously.

    Blocked layout: when max_len is a whole number of KV blocks the
    streaming decode kernel addresses the contiguous M axis as
    [num_blocks, block, hd] tiles (a free reshape). The inference engine
    rounds max_len up via `TpuInferenceConfig.kv_block_size`
    (`InferenceEngine._cache_len`) so decode steps never pay a runtime
    pad-to-block copy of the whole cache."""
    shape = (cfg.n_layer, batch_size, cfg.n_kv_head, max_len, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype),
            "length": jnp.zeros((batch_size,), jnp.int32)}


def _decode_qkv(x, p, positions, cfg: GPTConfig, hold=False):
    """Shared decode-path preamble: ln1 -> fused qkv -> split/reshape ->
    rope at absolute positions. One definition for the contiguous-cache
    half AND the paged half — a rope/GQA change cannot diverge them.
    x: [B, C, D]; positions: [B, C]. Returns q [B,C,H,hd], k/v [B,C,Hkv,hd]
    and the output's gate [B,C,H*hd] (`_gate_output`'s; None without one).
    `hold`: the fused product is HELD where it is made (a caller whose
    readers of q, k and v lie far apart: see `_paged_attn_half`).
    (The training `_attn_half` stays separate: it additionally threads
    act-quant gates, remat checkpoint names, and shard constraints.)"""
    B, C, _ = x.shape
    H, Hkv, hd = cfg.n_head, cfg.n_kv_head, cfg.head_dim
    with jax.named_scope("qkv"):
        h = _half_input(x, p, cfg)
        qkv = h @ p["attn_qkv_w"] + p["attn_qkv_b"]
        if hold:
            qkv = jax.lax.optimization_barrier(qkv)
        q, k, v, gate = _split_qkv(qkv, cfg)
        q, k = _qk_norm(q, k, p, cfg)
        q = q.reshape(B, C, H, hd)
        k = k.reshape(B, C, Hkv, hd)
        v = _scale_values(v.reshape(B, C, Hkv, cfg.value_dim), cfg)
        q, k = _qk_norm(q, k, p, cfg, heads_split=True)
        if cfg.use_rotary:
            rd = int(cfg.rotary_pct * hd) // 2 * 2
            q = _rope(q, positions, rd, cfg.rope_theta)
            k = _rope(k, positions, rd, cfg.rope_theta)
    return q, k, v, gate


def _static_window(cfg: GPTConfig):
    """The sliding window EVERY layer of `cfg` has, which the paged walks
    take as a lower bound and a mask (None: no window, or a per-layer local
    flag, which is traced and stays with the gather path)."""
    if cfg.sliding_window and cfg.attn_layer_types is None:
        return int(cfg.sliding_window)
    return None


def _decode_attn_site(cfg: GPTConfig, phase, C, M, kv_dtype="bfloat16",
                      block_size=0, pool_in_place=False):
    """Dispatch key for the decode/paged call sites. The engage rule itself
    (`attn_dispatch.decode_kernel_wanted`) has ONE definition shared by the
    contiguous path (M = allocated cache length) and the paged path
    (M = table_width * block = the effective context)."""
    # a paged walk takes a window that every call of the site has; the
    # contiguous decode kernel takes none
    window = _static_window(cfg) if phase != "decode" else None
    return attn_dispatch.AttnSite(
        phase=phase, q_len=C, kv_len=M, causal=True,
        has_bias=cfg.use_alibi,
        has_window=bool(cfg.sliding_window) and window is None,
        window=window or 0,
        scale_attn=bool(cfg.scale_attn), kv_dtype=kv_dtype,
        sink=cfg.attn_sink, head_dim=cfg.head_dim, value_dim=cfg.value_dim,
        block_size=block_size,
        pool_in_place=pool_in_place,
        mesh_axes=attn_dispatch.active_mesh_axes(),
        force_flash=cfg.use_flash_attention)


def _decode_attn_half(x, p, cache_k, cache_v, pos, cfg: GPTConfig,
                      local_flag=None):
    """Single-token attention half: writes k/v at `pos` into the head-major
    cache and attends over it. x: [B, 1, D]; cache_[kv]: [B, Hkv, M, hd];
    pos: [B]. Returns (attn_out, cache_k, cache_v)."""
    B, _, D = x.shape
    H, Hkv, hd = cfg.n_head, cfg.n_kv_head, cfg.head_dim
    M = cache_k.shape[2]
    q, k, v, gate = _decode_qkv(x, p, pos[:, None], cfg)

    # write k,v at pos via one-hot masked rewrite. Counterintuitive but
    # measured: streaming the whole [B,Hkv,M,hd] cache through fused
    # elementwise ops beats a batched scatter inside the decode scan on TPU
    # (3.2 vs 3.9 ms/token, gpt2-125m bs8 M=576 — scatter breaks the carry's
    # in-place update); revisit if XLA's scatter lowering improves
    # cache dtype wins (mirrors prefill's .astype(ck.dtype)): without the
    # casts, a model whose compute dtype is wider than kv_cache_dtype (e.g.
    # fp32-adapted HF weights + bf16 cache) promotes the rewrite to fp32 and
    # the decode scan carry dtype flips
    onehot = jax.nn.one_hot(pos, M, dtype=cache_k.dtype)      # [B, M]
    k_new = jnp.moveaxis(k, 1, 2).astype(cache_k.dtype)       # [B, Hkv, 1, hd]
    v_new = jnp.moveaxis(v, 1, 2).astype(cache_v.dtype)
    cache_k = cache_k * (1 - onehot)[:, None, :, None] + onehot[:, None, :, None] * k_new
    cache_v = cache_v * (1 - onehot)[:, None, :, None] + onehot[:, None, :, None] * v_new

    # decode kernel: explicit True forces it; auto engages from
    # DECODE_KERNEL_MIN_CTX — the blocked streaming kernel reads only the
    # live prefix of the cache while the XLA einsum reads the whole
    # allocated M every step (at short contexts XLA already sits at the
    # bandwidth floor: r5 174-204us vs kernel 189us at ctx 8k)
    # auto additionally requires a block-tileable M (128-multiple): an
    # unrounded cache would otherwise pay a whole-cache pad-to-block copy
    # INSIDE every jitted decode step (the engine's kv_block_size rounding
    # guarantees this; direct callers with odd M stay on XLA). Alibi/window
    # archs disqualify the kernel — all through the dispatch registry: a
    # program with a runner is run by it, one without is the einsum below.
    runner = attn_dispatch.get_program(attn_dispatch.select(
        _decode_attn_site(cfg, "decode", 1, M))).runner
    if runner is not None:
        # honor scale_attn=False (GPT-Neo): the kernel defaults to
        # 1/sqrt(hd) when sm_scale is None
        attn = runner(q[:, 0], cache_k, cache_v, pos,
                      sm_scale=sm_scale(cfg)).reshape(B, 1, D)
    else:
        scale = score_scale(cfg, hd)
        m_pos = jnp.arange(M)
        valid = (m_pos[None, :] <= pos[:, None])              # [B, M]
        if cfg.sliding_window:
            win = valid & (pos[:, None] - m_pos[None, :] < cfg.sliding_window)
            valid = win if local_flag is None else jnp.where(local_flag, win, valid)
        G = H // Hkv  # grouped einsum; G == 1 is plain MHA
        qg = q.reshape(B, Hkv, G, hd)
        logits = jnp.einsum("bkgd,bkmd->bkgm", qg, cache_k).astype(jnp.float32) * scale
        if cfg.use_alibi:
            dist = (pos[:, None] - m_pos[None, :]).astype(jnp.float32)  # [B, M]
            bias = -_alibi_slopes(H).reshape(Hkv, G)[None, :, :, None] * \
                dist[:, None, None, :]
            logits = logits + bias
        logits = jnp.where(valid[:, None, None, :], logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
        attn = jnp.einsum("bkgm,bkmd->bkgd", probs, cache_v).reshape(B, 1, D)
    attn_out = _gate_output(attn, gate) @ p["attn_out_w"] + p["attn_out_b"]
    return attn_out, cache_k, cache_v


def _block_decode(x, p, cache_k, cache_v, pos, cfg: GPTConfig, local_flag=None):
    """Single-token decode for one block."""
    attn_out, cache_k, cache_v = _decode_attn_half(x, p, cache_k, cache_v, pos,
                                                   cfg, local_flag=local_flag)
    x = _residual_mlp(x, attn_out, p, cfg, constrain=False)
    return x, cache_k, cache_v


def gpt_cache_identity(cfg: GPTConfig, name: str = "") -> str:
    """Cache-identity fingerprint for the prefix cache's hash chain
    (`DecodeModelSpec.cache_fingerprint`): every arch field that changes the
    KV VALUES a prompt writes into the paged pool — layer/head geometry,
    position encoding (learned wpe vs rotary incl. theta/pct, alibi),
    normalization, embedding LayerNorm — plus the spec name. Two configs
    differing in any of these can never serve each other's cached blocks
    even on identical token streams. Weights are engine-local (the cache
    lives inside one ServingEngine), so parameters are deliberately not
    hashed."""
    fields = (name, cfg.vocab_size, cfg.n_layer, cfg.n_head, cfg.n_kv_head,
              cfg.d_model, cfg.d_ff, cfg.max_seq_len, cfg.use_rotary,
              cfg.rotary_pct, cfg.rope_theta, cfg.use_alibi, cfg.use_emb_ln,
              cfg.use_rmsnorm, cfg.norm_eps, cfg.sliding_window,
              cfg.attn_layer_types, cfg.scale_attn, cfg.parallel_residual,
              cfg.use_swiglu, cfg.activation, jnp.dtype(cfg.dtype).name,
              jnp.dtype(cfg.softmax_dtype).name)
    if cfg.qk_norm:     # appended only when set: older fingerprints keep
        fields += ("qk_norm",)
    return "gpt:" + "|".join(map(str, fields))


def make_gpt_decode_model(cfg: GPTConfig = None, name="gpt2-125m", params=None, seed=0):
    """DecodeModelSpec for the inference engine (prefill + per-token decode)."""
    from deepspeed_tpu.inference.engine import DecodeModelSpec
    cfg = cfg or GPT2_CONFIGS[name]
    if params is None:
        params = init_gpt_params(cfg, seed=seed)

    def prefill_fn(params, tokens, cache, pad_mask):
        B, T = tokens.shape
        # single pass: compute activations AND populate the KV cache in one scan
        positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (B, T))
        x = _embed(params, tokens, positions, cfg)

        flags = _layer_local_flags(cfg)

        def body(x, inputs, flag=None):
            p, ck, cv = inputs
            attn_out, k, v = _attn_half(x, p, cfg, positions, local_flag=flag)
            ck = ck.at[:, :, :T].set(jnp.moveaxis(k, 1, 2).astype(ck.dtype))
            cv = cv.at[:, :, :T].set(jnp.moveaxis(v, 1, 2).astype(cv.dtype))
            x = _residual_mlp(x, attn_out, p, cfg)
            return x, (ck, cv)

        layers = (params["blocks"], cache["k"], cache["v"])
        if flags is None:
            x, (ks, vs) = jax.lax.scan(body, x, layers)
        else:
            x, (ks, vs) = jax.lax.scan(
                lambda c, inp: body(c, inp[0], flag=inp[1]), x, (layers, flags))
        logits = _lm_head(params, x, cfg)
        cache = {"k": ks, "v": vs, "length": jnp.full((B,), T, jnp.int32)}
        return logits, cache

    def decode_fn(params, token, pos, cache):
        B = token.shape[0]
        x = _embed(params, token[:, None], pos[:, None], cfg)

        flags = _layer_local_flags(cfg)

        def body(x, inputs, flag=None):
            p, ck, cv = inputs
            x, ck, cv = _block_decode(x, p, ck, cv, pos, cfg, local_flag=flag)
            return x, (ck, cv)

        layers = (params["blocks"], cache["k"], cache["v"])
        if flags is None:
            x, (ks, vs) = jax.lax.scan(body, x, layers)
        else:
            x, (ks, vs) = jax.lax.scan(
                lambda c, inp: body(c, inp[0], flag=inp[1]), x, (layers, flags))
        logits = _lm_head(params, x, cfg)[:, 0]
        cache = {"k": ks, "v": vs, "length": cache["length"] + 1}
        return logits, cache

    def init_cache(batch_size, max_len, dtype=jnp.bfloat16):
        return init_kv_cache(cfg, batch_size, max_len, dtype)

    # paged-pool serving contract (see DecodeModelSpec): both fns scan the
    # stacked blocks with the pool's layer axis as scan data, exactly like
    # the contiguous cache path, so layer count stays out of compile time

    # which writer each paged program was traced with, by dispatch phase
    # (`ServingEngine.stats()["kv_pool_writer"]` reads it)
    pool_writers = {}
    # ... and which attention program (`attention_dispatch`'s registry name;
    # `stats()["attention_program"]`)
    attn_programs = {}

    def _scan_paged(params, x, pool, block_tables, positions, phase=None):
        return scan_paged(cfg, params["blocks"], x, pool, block_tables,
                          positions, phase=phase, pool_writers=pool_writers,
                          attn_programs=attn_programs)

    def prefill_paged_fn(params, tokens, start_pos, last_idx, pool,
                         block_tables):
        B, C = tokens.shape
        positions = start_pos[:, None] + jnp.arange(C, dtype=jnp.int32)[None]
        x = _embed(params, tokens, positions, cfg)
        x, pool = _scan_paged(params, x, pool, block_tables, positions)
        logits = _lm_head(params, _last_rows(x, last_idx), cfg)[:, 0]
        return logits, pool

    def decode_paged_fn(params, token, pos, pool, block_tables):
        x = _embed(params, token[:, None], pos[:, None], cfg)
        x, pool = _scan_paged(params, x, pool, block_tables, pos[:, None])
        logits = _lm_head(params, x, cfg)[:, 0]
        return logits, pool

    def verify_paged_fn(params, tokens, pos, pool, block_tables):
        """Speculative-decoding verify: score C tokens per row in ONE pass
        at an arbitrary cursor. Identical machinery to a prefill chunk —
        `_paged_attend`'s absolute-position causal mask already lets row b's
        positions start anywhere — but the logits of EVERY position come
        back, not just the last: row i's argmax is the greedy ground truth
        for draft i+1 and the bonus token at the first disagreement."""
        B, C = tokens.shape
        positions = pos[:, None] + jnp.arange(C, dtype=jnp.int32)[None]
        x = _embed(params, tokens, positions, cfg)
        x, pool = _scan_paged(params, x, pool, block_tables, positions,
                              phase="verify")
        logits = _lm_head(params, x, cfg)
        return logits, pool

    def init_paged_pool(num_blocks, block_size, dtype=jnp.bfloat16,
                        kv_group_size=0):
        return init_paged_kv_pool(cfg, num_blocks, block_size, dtype,
                                  kv_group_size)

    return DecodeModelSpec(prefill_fn=prefill_fn, decode_fn=decode_fn,
                           init_cache=init_cache, params=params, name=name,
                           prefill_paged_fn=prefill_paged_fn,
                           decode_paged_fn=decode_paged_fn,
                           mixed_paged_fn=make_mixed_paged_fn(cfg,
                                                              _scan_paged),
                           mixed_chunk_groups=True,
                           verify_paged_fn=verify_paged_fn,
                           init_paged_pool=init_paged_pool,
                           kv_pool_writers=pool_writers,
                           paged_attn_programs=attn_programs,
                           cache_fingerprint=gpt_cache_identity(cfg, name))


# ----------------------------------------------------------------------
# paged decode path — for the continuous-batching serving engine
# (inference/scheduler.py): KV lives in a shared pool of physical blocks,
# each slot addresses it through a block table
# ----------------------------------------------------------------------


def init_paged_kv_pool(cfg: GPTConfig, num_blocks, block_size,
                       dtype=jnp.bfloat16, kv_group_size=0):
    """[L, num_blocks, Hkv, block, hd] physical-block pool, allocated ONCE at
    serving-engine init (vLLM's PagedAttention layout on the blocked cache
    unit). Block 0 is the trash block (inference/kv_cache.py): inactive
    slots' writes land there so the fixed-shape decode step never branches
    on liveness.

    `dtype=int8` selects the QUANTIZED pool (`ServingConfig.quantization.
    kv_cache_dtype`): the k/v payload is symmetric per-group int8 and the
    pool grows `k_scale`/`v_scale` f32 leaves [L, N, Hkv, block, hd//g]
    (`kv_group_size` g, 0 = head_dim — one scale per written K/V vector per
    head). Scales share the physical-block axis with the payload, so every
    block-indexed operation — transplant handoff, the prefix cache's
    content-immutable sharing, the pool auditor — carries a block's scales
    with its bytes automatically. Zero-init is safe: a trash-block read
    dequantizes to exact zeros, garbage rows callers already ignore."""
    shape = (cfg.n_layer, num_blocks, cfg.n_kv_head, block_size, cfg.head_dim)
    pool = {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
    if jnp.dtype(dtype) == jnp.int8:
        g = int(kv_group_size) or cfg.head_dim
        if g < 1 or cfg.head_dim % g != 0:
            raise ValueError(
                f"init_paged_kv_pool: kv_group_size {g} does not tile "
                f"head_dim {cfg.head_dim} (one scale per {g}-element group "
                f"of each K/V vector)")
        sshape = shape[:-1] + (cfg.head_dim // g,)
        pool["k_scale"] = jnp.zeros(sshape, jnp.float32)
        pool["v_scale"] = jnp.zeros(sshape, jnp.float32)
    return pool


class MixedTables(NamedTuple):
    """The block tables of a MIXED call (`make_mixed_paged_fn`): a GROUP of up
    to G prefill chunks and a decode token of every slot as the rows of ONE
    tensor, x [1, G*C + S, D] with positions [1, G*C + S] — the chunks' C
    rows each first, in the order they run (one sequence a chunk, its table
    a row of `chunk` [G, nb]), then one row a slot (tables `decode`
    [S, nb]). Every weight is applied to all the rows at once; only
    `_paged_attn_half` tells the groups apart, and it knows a mixed call by
    its tables being this tuple. `count`: how many of the G chunks are real
    (traced, 1..G; the rows of the others are padding, never written and
    never walked) — None where G is 1."""
    chunk: Any
    decode: Any
    count: Any = None


def mixed_tables(chunk_table, block_tables, count=None):
    """`MixedTables` of the chunks' tables and the slots' tables — of each
    kind, where a pool of two kinds passes its tables as a pair."""
    if isinstance(block_tables, tuple):
        return tuple(MixedTables(c, d, count)
                     for c, d in zip(chunk_table, block_tables))
    return MixedTables(chunk_table, block_tables, count)


def offset_tables(block_tables, base):
    """A paged call's tables `base` physical blocks further on (a layer's
    blocks in a flat stack): the tables move, a mixed call's count stays."""
    if isinstance(block_tables, MixedTables):
        return block_tables._replace(chunk=block_tables.chunk + base,
                                     decode=block_tables.decode + base)
    return block_tables + base


def over_chunk_group(count, C, out, carry, run):
    """A mixed call's GROUP of chunks in order, by ONE traced copy of `run`:
    for chunk i = 0 .. `count` - 1 (traced) `run(rows, i, carry) -> (the
    chunk's result [1, C, ...], carry)`, where `rows(a)` is chunk i's C rows
    of an `a` [1, G*C (+ S), ...]. The results land in `out` [1, G*C, ...]
    (an absent chunk's rows stay as given). Returns (out, carry)."""
    def body(i, state):
        out, carry = state
        y, carry = run(
            lambda a: jax.lax.dynamic_slice_in_dim(a, i * C, C, 1), i, carry)
        return jax.lax.dynamic_update_slice_in_dim(
            out, y.astype(out.dtype), i * C, 1), carry

    return jax.lax.fori_loop(0, count, body, (out, carry))


def decode_rows(block_tables, positions, rows=1, group=0):
    """(tables [S, nb], positions [S]) of a paged call's decode rows: every
    row of a decode call, the last S of a mixed call. `rows` > 1 (a block
    of a diffusion generator, `GPTConfig.block_length`): a slot has that
    many rows a GROUP, and a group's position is its LAST row's, the
    frontier they share — group `group` of the slot's (a fused forward has
    two: `block_groups`)."""
    if isinstance(block_tables, MixedTables):
        slots = block_tables.decode.shape[0]
        if rows > 1:
            return block_tables.decode, \
                positions[0, -slots * rows:].reshape(slots, rows)[:, -1]
        return block_tables.decode, positions[0, -slots:]
    return block_tables, positions[:, (group + 1) * rows - 1 if rows > 1
                                   else 0]


def block_groups(cfg, positions, phase):
    """The groups of `cfg.block_length` rows a slot has in a paged call,
    known where it is traced: 1 — but in a diffusion generator's block
    forward (`phase` "denoise", positions [S, C]) that is FUSED, 2: block b's
    clean tokens (its commit) before block b + 1's rows (its first denoise
    step), one pass through every weight, each group walked at its own
    frontier."""
    return positions.shape[1] // cfg.block_length if phase == "denoise" else 1



def make_mixed_paged_fn(cfg, layers_paged, chunk_valid=False):
    """A family's `DecodeModelSpec.mixed_paged_fn` from its layer loop
    `layers_paged(params, x, pool, block_tables, positions) -> (x, pool,
    *counts)`, the one its `prefill_paged_fn` and `decode_paged_fn` run:
    a group of up to G chunks (`chunk_tokens` [G, C], `start_pos` /
    `last_idx` [G], `chunk_table` [G, nb] of each kind; `count`, traced, how
    many of them are real where G > 1) and a decode token a slot through
    embedding, layers, final norm and head as one tensor [1, G*C + S, D], so
    each weight is read once where the programs of their own read it 1 + G
    times. Only the attention half runs the chunks one after another
    (`_paged_attn_half`). Logits [G + S, V]: each chunk's `last_idx` row,
    then the slots' rows. A model that generates by diffusion over blocks
    (`cfg.block_length` B > 1) passes `token` [S, B], a block a slot at
    `pos` .. `pos + B - 1`: the slots' rows are then S * B, slot after slot,
    and the logits [G + S * B, V] (a decode call of such a model commits
    whole blocks, not `window` tokens a slot: `inference/step_programs.py`).
    A family whose loop takes G > 1 says so
    (`DecodeModelSpec.mixed_chunk_groups`); the others are handed G = 1.
    `chunk_valid`: the loop also takes `valid=`, the chunk's real positions
    `last_idx + 1` (a layer with recurrent state must not run it over the
    chunk's padding). `hidden=True` (a block generator's loop, whose chunks
    sample nothing): the slots' rows as the layers leave them, [S * B, D],
    in the logits' place — its rule runs the head over the forwards that
    sample (`DecodeModelSpec.head_fn`). Further keywords of a call go to the
    loop as they are (a routed family's `routing=True`: what it returns
    beside the counters follows them)."""

    def mixed_paged_fn(params, chunk_tokens, start_pos, last_idx, chunk_table,
                       token, pos, pool, block_tables, count=None,
                       hidden=False, **loop):
        G, C = chunk_tokens.shape
        if token.ndim == 2:
            # a diffusion generator's block a slot (`cfg.block_length` rows
            # at pos .. pos + B - 1), slot after slot
            pos = (pos[:, None] + jnp.arange(token.shape[1],
                                             dtype=jnp.int32)[None]).reshape(-1)
            token = token.reshape(-1)
        tokens = jnp.concatenate([chunk_tokens.reshape(1, G * C),
                                  token[None]], axis=1)
        positions = jnp.concatenate(
            [(start_pos[:, None] + jnp.arange(C, dtype=jnp.int32)[None]
              ).reshape(1, G * C), pos[None]], axis=1)
        x = _embed(params, tokens, positions, cfg)
        valid = dict(valid=last_idx + 1) if chunk_valid else {}
        x, pool, *counts = layers_paged(
            params, x, pool, mixed_tables(chunk_table, block_tables, count),
            positions, **valid, **loop)
        if hidden:
            return (x[0, G * C:], pool, *counts)
        D = x.shape[-1]
        last = _last_rows(x[:, :G * C].reshape(G, C, D), last_idx)
        with jax.named_scope("head"):
            rows = jnp.concatenate([last.reshape(1, G, D), x[:, G * C:]],
                                   axis=1)
        logits = _lm_head(params, rows, cfg)[0]
        return (logits, pool, *counts)

    return mixed_paged_fn


def scan_paged(cfg: GPTConfig, blocks, x, pool, block_tables, positions,
               phase=None, pool_writers=None, block_fn=None, aux=None,
               attn_programs=None):
    """The layer loop of every paged program: x through the stacked `blocks`
    against the paged pool. Returns (x, pool), or (x, pool, aux) when `aux`
    is given.

    The pool is a PYTREE of [L, N, ...] leaves (k/v, plus the int8 pool's
    k_scale/v_scale), so the quantized and fp layouts share one scan body —
    a layer's pool arrives as a dict. `phase` labels the dispatch site
    ("verify" for the spec-decode chunk; "denoise" for a diffusion
    generator's block forward, `cfg.block_length` rows a slot that share one
    frontier: the decode site with more rows; None = derive decode/prefill
    from the chunk width; `block_tables` a `MixedTables` makes it "mixed", a
    chunk and the slots' decode rows in one x); `pool_writers[phase]`
    records the writer chosen and `attn_programs[phase]` the attention
    program the layers select.

    `block_fn` (default `_block_paged`) is one layer: `(x, p, pool_l,
    positions, block_tables, cfg, local_flag=, phase=, block_base=,
    decode_work=, attn_programs=) -> (x, pool_l)`, with `layer=` the traced
    layer index
    (what a layer needs beside its slice `p` it addresses in a whole stack
    it closes over, as the pool is) and `decode_work=` the decode kernels'
    work list of this token (None in a chunk). With `aux` (an initial
    value), it returns a third result that is summed over the layers into
    `aux` — the routed experts' counters (`models/moe_gpt.py`); the dense
    family passes neither.

    Two forms of one loop, chosen by `attn_dispatch.kv_pool_writer` (the
    rule and its invariant live there). In place: the leaves are flattened
    to [L*N, ...] (a bitcast: leading dimensions merge), CARRIED, and layer
    l addresses its blocks as `table + l*N`; every write is the aliased
    Mosaic call, so the compiled program holds nothing of the pool's size
    but those calls. Otherwise the pool rides the scan as xs and comes back
    as ys (which never alias: the program copies the pool once, donated or
    not) and each layer's slice takes an XLA scatter."""
    block_fn = block_fn or _block_paged
    counted = aux is not None
    L = pool["k"].shape[0]
    layer_ids = jnp.arange(L, dtype=jnp.int32)
    flags = _layer_local_flags(cfg)
    writer = attn_dispatch.kv_pool_writer(pool)
    mixed = isinstance(block_tables, MixedTables)
    # a diffusion generator's block forward ("denoise": `cfg.block_length`
    # rows a slot that share one frontier) is the decode site with more rows
    site = "mixed" if mixed else "paged_decode" if phase == "denoise" \
        else phase or ("paged_decode" if x.shape[1] == 1 else "prefill_chunk")
    if pool_writers is not None:
        pool_writers[site] = writer
    # the decode kernel's work list is the same for every layer (a layer only
    # offsets the physical blocks): built HERE, once a token, not in the loop
    # (a mixed call's: of its decode rows)
    decode_work = None
    if site in ("paged_decode", "mixed"):
        from deepspeed_tpu.ops.pallas.decode_attention import \
            paged_decode_work
        rows = cfg.block_length if mixed or phase == "denoise" else 1

        def work(group=0):
            return paged_decode_work(
                *decode_rows(block_tables, positions, rows, group),
                pool["k"].shape[3], window=_static_window(cfg))
        # (a block forward's is a LIST, one a group of a slot's rows: the
        # walk takes them by `block_groups` too)
        decode_work = work() if rows == 1 else [
            work(g) for g in range(block_groups(cfg, positions, phase))]

    def layer(x, p, pool_l, flag, acc, layer_id, block_base=None):
        x, pool_l, *counts = block_fn(
            x, p, pool_l, positions, block_tables, cfg, local_flag=flag,
            phase=phase, block_base=block_base, layer=layer_id,
            decode_work=decode_work, attn_programs=attn_programs)
        return x, pool_l, (acc + counts[0] if counted else acc)

    def result(x, pool, acc):
        return (x, pool, acc) if counted else (x, pool)

    if writer == attn_dispatch.KV_POOL_WRITE_KERNEL:
        N = pool["k"].shape[1]

        def body(carry, inputs):
            x, flat, acc = carry
            p, layer_id, flag = inputs
            return layer(x, p, flat, flag, acc, layer_id,
                         block_base=layer_id * N), None

        flat = {k: v.reshape((L * N,) + v.shape[2:])
                for k, v in pool.items()}
        (x, flat, aux), _ = jax.lax.scan(
            body, (x, flat, aux),
            (blocks, layer_ids, flags))
        return result(x, {k: v.reshape(pool[k].shape)
                          for k, v in flat.items()}, aux)

    def body(carry, inputs):
        x, acc = carry
        p, pool_l, flag, layer_id = inputs
        x, pool_l, acc = layer(x, p, pool_l, flag, acc, layer_id)
        return (x, acc), pool_l

    (x, aux), pool = jax.lax.scan(body, (x, aux),
                                  (blocks, pool, flags, layer_ids))
    return result(x, pool, aux)


def _paged_attend(q, k_ctx, v_ctx, q_pos, cfg: GPTConfig, local_flag=None,
                  sink=None):
    """Attend q over table-gathered KV with ABSOLUTE positions.

    q: [B, C, H, hd] (C = 1 for decode, = chunk length for chunked prefill);
    k_ctx [B, Hkv, S, hd] / v_ctx [B, Hkv, S, vd] in logical order (S = nb *
    block — gathered rows ARE position order, so k index == absolute
    position); q_pos: [B, C]; `sink` [H]: `cfg.attn_sink`'s logit a head.
    Causal/window masks and alibi bias are built from absolute positions
    per row — unlike the training path, two rows of a serving batch sit at
    different positions. Returns [B, C, H*vd]; fp32 softmax."""
    B, C, H, hd = q.shape
    Hkv, S = k_ctx.shape[1], k_ctx.shape[2]
    G = H // Hkv
    scale = score_scale(cfg, hd)
    k_pos = jnp.arange(S, dtype=jnp.int32)
    # (`block_length` > 1: causal over blocks, bidirectional inside one — a
    # row sees up to the END of its block)
    frontier = q_pos | (cfg.block_length - 1) if cfg.block_length > 1 \
        else q_pos
    valid = k_pos[None, None, :] <= frontier[:, :, None]       # [B, C, S]
    if cfg.sliding_window:
        win = valid & (q_pos[:, :, None] - k_pos[None, None, :]
                       < cfg.sliding_window)
        valid = win if local_flag is None else jnp.where(local_flag, win, valid)
    qg = q.reshape(B, C, Hkv, G, hd)
    logits = jnp.einsum("bckgd,bksd->bkgcs", qg,
                        k_ctx).astype(jnp.float32) * scale
    if cfg.use_alibi:
        dist = (q_pos[:, :, None] - k_pos[None, None, :]).astype(jnp.float32)
        logits = logits - (_alibi_slopes(H).reshape(Hkv, G)[None, :, :, None, None]
                           * dist[:, None, None, :, :])
    logits = jnp.where(valid[:, None, None, :, :], logits, -1e30)
    probs = _softmax_with_sink(logits, sink).astype(q.dtype)
    out = jnp.einsum("bkgcs,bksd->bckgd", probs, v_ctx)
    return out.reshape(B, C, H * v_ctx.shape[-1])


def _paged_attn_half(x, p, pool_l, positions, block_tables,
                     cfg: GPTConfig, local_flag=None, phase=None,
                     block_base=None, decode_work=None, attn_programs=None):
    """Attention half-block against one layer's paged pool.

    x: [B, C, D]; pool_l: one layer's pool slice — ``k``/``v``
    [N, Hkv, block, hd] plus, for the int8 quantized pool,
    ``k_scale``/``v_scale`` [N, Hkv, block, hd//g] (a head whose keys are a
    lane tile and a half wide keeps them in two leaves, ``k``/``kr``, and
    values of their own width: `ops/pallas/kv_pool.py::kv_leaf_shapes`);
    positions: [B, C]
    absolute; block_tables: [B, nb]. Writes the C new tokens' k/v into each
    row's blocks (logical position -> table -> physical block scatter), then
    attends over the row's whole table. Returns (attn_out, pool_l).

    A MIXED call (`block_tables` a `MixedTables`; x [1, G*C + S, D], or
    [1, G*C + S*B, D] where a slot's rows are a diffusion generator's block
    of B = `cfg.block_length`): one
    QKV and one output projection over all the rows, and between them each
    of the group's chunks in order, then the slots' S rows, written and
    attended as their own program would (`_paged_write_attend`, once a
    chunk and once for the slots): a later chunk of a sequence finds the
    earlier one's keys in the pool, and a window layer's ring holds one
    chunk in flight as it does between two calls.

    In-place form (`block_base` given; `_scan_paged` decides): `pool_l` is
    the WHOLE stack flattened to [L*N, Hkv, block, hd], this layer's blocks
    start at `block_base` (= layer * N, traced), the rows are written by the
    aliased `dstpu_kv_pool_write` call and read through
    `block_tables + block_base` by `dstpu_paged_decode`,
    `dstpu_paged_prefill` or `dstpu_kv_pool_gather` — Mosaic calls only,
    the invariant of
    `attn_dispatch.kv_pool_writer`. It takes each row's positions to be
    consecutive (`positions[b, c] == positions[b, 0] + c`), as every paged
    program builds them.

    `decode_work`: the decode kernels' work list of these tables
    (`paged_decode_work`), where the caller built it outside its layer loop
    — in a generator's block forward a Python list of them, one a
    `block_groups` group of a slot's rows; None leaves it to the kernel's
    wrapper. `attn_programs`: a dict that
    takes the name of the attention program selected, by dispatch phase.

    Quantized pool: K/V are quantized AT CACHE-WRITE TIME (symmetric
    per-group int8 + f32 scales, `quantization.quantize_kv` — the same
    scheme as `ops/pallas/quant.py`), so fp K/V for the cached prefix never
    materializes in HBM. Reads dequantize on the fly: the single-token
    kernel path dequantizes each streamed tile inside the Pallas KV-grid
    walk (`paged_decode_attention_quant`), and the gather path (chunked
    prefill, the spec-decode verify chunk, CPU/arch-flag fallbacks) runs
    the dequantizing gather oracle — one shared numeric definition, so the
    two are parity-testable tile for tile.
    """
    mixed = isinstance(block_tables, MixedTables)
    G = block_tables.chunk.shape[0] if mixed else 1
    # a group of chunks: the product's readers are the chunk loop, the
    # slots' rows behind the barrier below and (a gate) the output. Left
    # alone XLA frees it in between and computes it again for each (compiled
    # for a described v5e at MiMo's size: `fusion.N.remat`, `.remat2`, the
    # 116 MiB QKV matrix read three times a layer)
    q, k, v, gate = _decode_qkv(x, p, positions, cfg, hold=G > 1)
    group = partial(_paged_write_attend, cfg=cfg, local_flag=local_flag,
                    block_base=block_base, attn_programs=attn_programs,
                    sink=p["attn_sink"] if cfg.attn_sink else None)
    if mixed:
        # a mixed call: each chunk's rows [1, C, ...], then a row a slot,
        # [S, 1, ...] as the decode program has them. Each group writes and
        # attends as it would in its own program (same dispatch site, same
        # kernels); their results meet again for ONE output projection
        S = block_tables.decode.shape[0]
        Bk = cfg.block_length       # a slot's rows: one token, or a
                                    # diffusion generator's block
        R = x.shape[1] - S * Bk     # the chunks' rows, C a chunk
        C = R // G
        chunk = partial(group, phase="prefill_chunk",
                        record="mixed/prefill_chunk")
        if G == 1:
            attn_c, pool_l = chunk(
                q[:, :C], k[:, :C], v[:, :C], pool_l, positions[:, :C],
                block_tables.chunk)
        else:
            # the group's real chunks in order on the carried pool; the rows
            # of the absent ones stay zeros, padding through the projection
            attn_c, pool_l = over_chunk_group(
                block_tables.count, C,
                jnp.zeros((1, R, q.shape[2] * v.shape[3]), q.dtype),
                dict(pool_l),
                lambda rows, i, pool_l: chunk(
                    rows(q), rows(k), rows(v), pool_l, rows(positions),
                    jax.lax.dynamic_slice_in_dim(block_tables.chunk, i, 1,
                                                 0)))
        # the chunk's walk has READ the pool before the slots' rows are
        # written into it in place — said as data. Nothing else orders the
        # two (the slots' rows do not depend on the chunk's attention), and
        # XLA, left free, keeps the walk's input alive by copying a whole
        # pool leaf a layer (measured on the chip, PR 33: two copies of
        # K-EXAONE's 1.5 GB full-layer leaf a mixed token, 23% of its time)
        attn_c, pool_l = jax.lax.optimization_barrier((attn_c, pool_l))
        if Bk == 1:
            attn_d, pool_l = group(
                *(jnp.swapaxes(a[:, R:], 0, 1) for a in (q, k, v)), pool_l,
                positions[:, R:].T, block_tables.decode,
                phase="paged_decode", decode_work=decode_work,
                record="mixed/paged_decode")
            attn_d = jnp.swapaxes(attn_d, 0, 1)
        else:
            # a block a slot, [S, Bk, ...] as the denoise program has them
            attn_d, pool_l = group(
                *(a[0, R:].reshape((S, Bk) + a.shape[2:])
                  for a in (q, k, v)), pool_l,
                positions[0, R:].reshape(S, Bk), block_tables.decode,
                phase="denoise", decode_work=decode_work,
                record="mixed/paged_decode")
            attn_d = attn_d.reshape(1, S * Bk, -1)
        attn = jnp.concatenate([attn_c, attn_d], axis=1)
    else:
        attn, pool_l = group(q, k, v, pool_l, positions, block_tables,
                             phase=phase, decode_work=decode_work)
    attn = _gate_output(attn, gate)
    with jax.named_scope("out"):
        attn_out = attn @ p["attn_out_w"] + p["attn_out_b"]
    return attn_out, pool_l


def _paged_write_attend(q, k, v, pool_l, positions, block_tables,
                        cfg: GPTConfig, local_flag=None, phase=None,
                        block_base=None, decode_work=None, attn_programs=None,
                        record=None, sink=None):
    """`_paged_attn_half` between its two matmuls, for rows that share a
    dispatch site: write k [B, C, Hkv, hd] / v [B, C, Hkv, vd] through
    `block_tables` [B, nb] at `positions` [B, C], then attend q [B, C, H,
    hd] over each row's table. Returns (attn [B, C, H*vd], pool_l).
    `record`: the key the selected program's name is kept under in
    `attn_programs` (default: the site's phase); `sink` [H]: the sink logit
    a head (`cfg.attn_sink`)."""
    from deepspeed_tpu.inference.kv_cache import (gather_block_kv_dequant,
                                                  gather_block_leaf)
    from deepspeed_tpu.ops.pallas.kv_pool import (kv_pool_gather, merge_keys,
                                                  pool_rows)

    B, C = positions.shape
    bs = pool_l["k"].shape[2]
    nb = block_tables.shape[1]
    quantized = "k_scale" in pool_l

    # scatter the new k/v through the table: logical block = pos // bs,
    # physical block = table[row, logical], offset = pos % bs. Rows of
    # inactive slots (all-trash tables, pos 0) collide in the trash block —
    # duplicate-index scatter order is unspecified there and irrelevant.
    # (`jax.named_scope`s below cost nothing: they name these regions in the
    # operations' `op_name`, which xprof shows and `telemetry/device_scopes.py`
    # reads back out of the compiled program)
    with jax.named_scope("kv_pool_write"):
        pool_l = dict(pool_l)
        if block_base is not None:
            from deepspeed_tpu.ops.pallas.kv_pool import kv_pool_write
            block_tables = block_tables + block_base
            for leaf, rows in pool_rows(k, v, pool_l).items():
                pool_l[leaf] = kv_pool_write(pool_l[leaf], rows,
                                             positions[:, 0], block_tables)
        else:
            blk = jnp.take_along_axis(block_tables, positions // bs,
                                      axis=1)                       # [B, C]
            off = positions % bs
            if quantized:
                from deepspeed_tpu.inference.quantization import quantize_kv
                g = cfg.head_dim // pool_l["k_scale"].shape[-1]
                qk, sk = quantize_kv(k, g)
                qv, sv = quantize_kv(v, g)
                new_rows = {"k": qk, "v": qv, "k_scale": sk, "v_scale": sv}
            else:
                new_rows = pool_rows(k, v, pool_l)
            for leaf, rows in new_rows.items():
                pool_l[leaf] = pool_l[leaf].at[blk, :, off, :].set(
                    rows.astype(pool_l[leaf].dtype))

    # single-token steps ride the paged Pallas kernel when it is worth it:
    # same engage rule as the contiguous decode path (forced, or auto at
    # serving-scale effective context nb*bs), PLUS the paged-only
    # constraints: the kernel's no-bias/no-window contract, a lane-aligned
    # pool block (it cannot pad physical blocks the way the contiguous
    # kernel pads a whole cache), and C == 1. The int8-pool kernel is an
    # ordinary REGISTERED program keyed on kv_dtype, not a special case
    # here. A prefill chunk on the in-place pool walks the blocks under its
    # frontier (`paged_prefill_kernel`). Each of them is a program with a
    # RUNNER: no branch of its own below. A program without one (the
    # spec-decode verify chunk, a prefill chunk anywhere else) is this
    # site's oracle: gather the row's whole table — dequantized where the
    # pool has scale leaves, whatever the program is called — and attend it
    # densely.
    # A diffusion generator's block forward (`phase` "denoise": C =
    # `cfg.block_length` rows a slot, denoise or commit) HAS a runner: once
    # the block's K/V are written its rows all see the same keys, [0, p + C),
    # so they are the decode walk with C x G query rows a KV head at the
    # block's last position — no mask inside the block. A FUSED forward's 2C
    # rows a slot (`block_groups`) are written together above and walked a
    # group at a time, each at its own block's last position: the first
    # group's walk ends below the second's keys, so it reads what a forward
    # of its own would. The verify chunk still has none: its rows are causal
    # INSIDE the chunk (row i sees pos + i), so they do not share one
    # frontier.
    denoise = phase == "denoise"
    site = _decode_attn_site(
        cfg, "paged_decode" if denoise
        else phase or ("paged_decode" if C == 1 else "prefill_chunk"),
        1 if denoise else C, nb * bs,
        kv_dtype="int8" if quantized else str(jnp.dtype(pool_l["k"].dtype)),
        block_size=bs, pool_in_place=block_base is not None)
    sunk = {} if sink is None else dict(sink=sink)
    if cfg.block_length > 1 and site.phase == "prefill_chunk":
        sunk["block_length"] = cfg.block_length     # the chunk walk's mask
    program = attn_dispatch.select(site)
    if attn_programs is not None:
        attn_programs[record or site.phase] = program
    runner = attn_dispatch.get_program(program).runner
    if runner is not None and denoise:
        H, hd, Hkv = q.shape[2], q.shape[3], k.shape[2]
        Bk = cfg.block_length
        groups = []
        for g in range(block_groups(cfg, positions, phase)):
            work = decode_work[g] if decode_work else None
            with jax.named_scope("walk"):
                rows = jnp.swapaxes(q[:, g * Bk:(g + 1) * Bk].reshape(
                    B, Bk, Hkv, H // Hkv, hd), 1, 2)
                attn = runner(rows.reshape(B, 1, Bk * H, hd), pool_l,
                              block_tables, positions[:, (g + 1) * Bk - 1],
                              sm_scale=sm_scale(cfg),
                              window=site.window or None, work=work, **sunk)
                groups.append(jnp.swapaxes(
                    attn.reshape(B, Hkv, Bk, -1), 1, 2).reshape(B, Bk, -1))
        attn = groups[0] if len(groups) == 1 else jnp.concatenate(groups, 1)
    elif runner is not None:
        with jax.named_scope("walk"):
            attn = runner(q, pool_l, block_tables, positions[:, 0],
                          sm_scale=sm_scale(cfg),
                          window=site.window or None, work=decode_work,
                          **sunk)
    else:
        with jax.named_scope("kv_pool_read"):
            if quantized:
                k_ctx, v_ctx = gather_block_kv_dequant(pool_l, block_tables,
                                                       q.dtype)
            else:
                # an XLA gather on the carried pool slices the WHOLE pool
                # (see ops/pallas/kv_pool.py): reads are Mosaic calls too
                gather = kv_pool_gather if block_base is not None \
                    else gather_block_leaf
                ctx = {leaf: gather(rows, block_tables)
                       for leaf, rows in pool_l.items()}
                v_ctx = ctx.pop("v")
                k_ctx = merge_keys(ctx)
        with jax.named_scope("walk"):
            attn = _paged_attend(q, k_ctx, v_ctx, positions, cfg,
                                 local_flag=local_flag, sink=sink)
    return attn, pool_l


def _block_paged(x, p, pool_l, positions, block_tables,
                 cfg: GPTConfig, local_flag=None, phase=None,
                 block_base=None, layer=None, mlp_fn=None, decode_work=None,
                 attn_programs=None):
    """One transformer block against the paged pool (decode, prefill
    chunk, or the spec-decode verify chunk — `phase` labels the dispatch
    site; `block_base` selects `_paged_attn_half`'s in-place form and
    `decode_work` is its decode kernels' work list and `attn_programs` its
    record of the program selected; `mlp_fn` swaps the dense MLP, as in
    `_residual_mlp`; `layer`, `scan_paged`'s layer index, is for blocks that
    need it)."""
    del layer
    with jax.named_scope("attn"):
        attn_out, pool_l = _paged_attn_half(
            x, p, pool_l, positions, block_tables, cfg, local_flag=local_flag,
            phase=phase, block_base=block_base, decode_work=decode_work,
            attn_programs=attn_programs)
    with jax.named_scope("mlp"):
        x = _residual_mlp(x, attn_out, p, cfg, constrain=False, mlp_fn=mlp_fn)
    return x, pool_l


# ----------------------------------------------------------------------
# layered decode path — for the ZeRO-Inference parameter spill tier
# ----------------------------------------------------------------------


def make_gpt_layered_model(cfg: GPTConfig = None, name="gpt2-125m", params=None,
                           seed=0):
    """LayeredModelSpec: the decode model factored into per-layer functions so
    the spill engine (`inference/zero_inference.py`) can stream one layer's
    weights host->HBM at a time. Same math as `make_gpt_decode_model` — the
    stacked `lax.scan` over resident blocks becomes a Python loop over
    streamed blocks (reference capability:
    `runtime/swap_tensor/partitioned_param_swapper.py:36`,
    `docs/_posts/2022-09-10-zero-inference.md:35`)."""
    from deepspeed_tpu.inference.zero_inference import LayeredModelSpec
    cfg = cfg or GPT2_CONFIGS[name]
    if params is None:
        params = init_gpt_params(cfg, seed=seed)
    assert _layer_local_flags(cfg) is None, \
        "per-layer local/global flags not supported on the spill path yet"

    resident = {k: v for k, v in params.items() if k != "blocks"}
    blocks = params["blocks"]

    def embed_fn(res, tokens, positions):
        return _embed(res, tokens, positions, cfg)

    def layer_prefill_fn(p, x, ck, cv, positions):
        """x: [B,T,D]; ck/cv: [B,Hkv,M,hd] (this layer's cache slice)."""
        T = x.shape[1]
        attn_out, k, v = _attn_half(x, p, cfg, positions)
        ck = ck.at[:, :, :T].set(jnp.moveaxis(k, 1, 2).astype(ck.dtype))
        cv = cv.at[:, :, :T].set(jnp.moveaxis(v, 1, 2).astype(cv.dtype))
        x = _residual_mlp(x, attn_out, p, cfg)
        return x, ck, cv

    def layer_decode_fn(p, x, ck, cv, pos):
        return _block_decode(x, p, ck, cv, pos, cfg)

    def final_fn(res, x):
        return _lm_head(res, x, cfg)

    def init_layer_cache(batch_size, max_len, dtype=jnp.bfloat16):
        shape = (batch_size, cfg.n_kv_head, max_len, cfg.head_dim)
        return jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)

    # TP shardings: the stacked specs' leading (layer) entry drops for the
    # per-layer streamed trees
    specs = gpt_param_specs(cfg)
    resident_specs = {k: v for k, v in specs.items() if k != "blocks"}
    block_specs = jax.tree_util.tree_map(lambda s: P(*tuple(s)[1:]),
                                         specs["blocks"])

    # training-side spill (ZeRO-Infinity params): cache-free block + CE head
    def layer_train_fn(p, x, positions):
        return _block(x, p, cfg, positions)

    def train_loss_fn(res, x, labels):
        logits = _lm_head(res, x, cfg)
        m = jax.lax.stop_gradient(jnp.max(logits, axis=-1, keepdims=True))
        sumexp = jnp.sum(jnp.exp((logits - m).astype(jnp.float32)), axis=-1)
        logz = m[..., 0].astype(jnp.float32) + jnp.log(sumexp)
        safe = jnp.maximum(labels, 0)
        gold = jnp.take_along_axis(logits, safe[..., None],
                                   axis=-1)[..., 0].astype(jnp.float32)
        mask = (labels >= 0).astype(jnp.float32)
        return ((logz - gold) * mask).sum() / jnp.maximum(mask.sum(), 1.0)

    # streamed paged-serving contract (inference/scheduler.py offloaded-
    # weights mode): the same `_block_paged` body as the resident paged
    # path, but the layer index arrives TRACED and the [L, ...] pool is
    # sliced / written back with dynamic_index/update — one compile serves
    # every layer of a streamed walk, and pool donation makes the update
    # write in place
    def layer_paged_fn(p, x, layer, pool, block_tables, positions):
        pool_l = {k: jax.lax.dynamic_index_in_dim(v, layer, 0,
                                                  keepdims=False)
                  for k, v in pool.items()}
        x, pool_l = _block_paged(x, p, pool_l, positions, block_tables, cfg)
        pool = {k: jax.lax.dynamic_update_index_in_dim(
                    pool[k], pool_l[k].astype(pool[k].dtype), layer, 0)
                for k in pool}
        return x, pool

    def init_paged_pool(num_blocks, block_size, dtype=jnp.bfloat16,
                        kv_group_size=0):
        return init_paged_kv_pool(cfg, num_blocks, block_size, dtype,
                                  kv_group_size)

    return LayeredModelSpec(
        embed_fn=embed_fn, layer_prefill_fn=layer_prefill_fn,
        layer_decode_fn=layer_decode_fn, final_fn=final_fn,
        layer_train_fn=layer_train_fn, train_loss_fn=train_loss_fn,
        resident=resident, blocks=blocks, num_layers=cfg.n_layer,
        init_layer_cache=init_layer_cache, resident_specs=resident_specs,
        block_specs=block_specs, name=name,
        layer_paged_fn=layer_paged_fn, init_paged_pool=init_paged_pool,
        cache_fingerprint=gpt_cache_identity(cfg, name))
