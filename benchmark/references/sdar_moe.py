"""Plain reference for SDAR-MoE (`model_type: sdar_moe`, SDAR-30B-A3B-Chat):
the forward pass over a WHOLE sequence under the block-causal mask, and the
block-diffusion sampler as a Python loop over blocks and steps — plain
`jax.numpy`, float32, one sequence at a time, dense attention, no kernels, no
cache, no batching. Every matrix product runs under
`jax.default_matmul_precision("highest")` (on a TPU a float32 product
otherwise runs in bfloat16 passes). Written like `olmoe.py` beside it.

The layer (every one of them alike): RMSNorm -> q, k, v projections without
bias -> RMSNorm over EACH head's columns of q and of k (one scale vector of
`head_dim` the heads share; `assumed`: the Qwen3-MoE family's `q_norm` /
`k_norm`, `config.json` has no key for it) -> rotary on the whole head, theta
1e6 -> softmax(q k^T / sqrt(head_dim)) v where position i sees position j iff
`j // B <= i // B` (causal over blocks of B, BIDIRECTIONAL inside one) ->
output projection -> residual; RMSNorm -> router: softmax over the experts'
logits in float32, the `top_k` largest renormalised to sum 1
(`norm_topk_prob: true`) -> each chosen expert's SwiGLU weighted and summed
-> residual. No shared expert, no dense layer. Final RMSNorm, untied head. A
masked position's OWN logits row predicts its token (no shift).

The sampler (`generate`; the family's released generate script, whose
settings are `assumed`): the prompt's `L // B` whole blocks are context; its
`L mod B` last tokens open the first generated block as clean tokens beside
mask tokens. A block takes up to S denoise forwards of the WHOLE sequence so
far + the block (no cache: what a "forward of the block against the cache"
computes, since earlier blocks never see later ones), each unmasking rows by
`unmask_rule`, then it is committed as it stands and the next block starts as
B mask tokens. The commit forward changes no token: here it is only the
forward whose keys and values a cache would keep (`forward(kv=)` hands them
out for the comparison).

It reads the PROGRAM'S parameter tree (`models/moe_gpt.py`, the `moe_freq` 1
layout: blocks stacked on a leading layer axis, q/k/v fused in one
`[D, (H + 2 Hkv) hd]` matrix in that order, `attn_out_w [H hd, D]`,
`moe_gate_w [D, E]`, `moe_w_gate_up [E, D, 2F]` with gate in the first F
columns and up in the last, `moe_w_down [E, F, D]`), because "the same
weights" is what is compared. A layer's small leaves are cast to float32
together; its EXPERTS ONE AT A TIME (a loop over the stack inside the jitted
layer: an expert's two matrices are cast, used and dropped — never a layer's
604 M parameters at once), every row through each expert, the rows that did
not choose it weighted by zero.

Departures from the published code, shared with the program and stated in
the configuration file: rotary pairs are interleaved (even, odd) rather than
split in halves — the published layout up to a fixed permutation of each
head's columns; the attention biases the program's tree carries are zero.
Departures from the released generate script, shared with the program: the
mask token is never drawn (its logit is excluded from the argmax and from the
confidence's denominator; the script could draw it and leave a row masked
for good); "the n most confident" are taken among the MASKED rows, ties to
the earlier row (the script's `topk` over a row of `-inf` could pick a clean
prompt token and overwrite it); confidence is `softmax(logits)[x0]` in
float32.

`forced` (the chip check): the experts each token uses are GIVEN, [L, T, k]
— the program's own choice on its bfloat16 activations — and weighted by THIS
forward's probabilities of them, renormalised; what this forward would have
chosen comes back beside the logits either way.

`round_to`: None for the reference itself. A dtype (e.g. `float8_e4m3fn`)
rounds every matrix product's inputs (weights and activations) through that
type — the reference "computed in a lower precision", which the benchmark's
limits are set against (PERF.md) and which no check uses.
"""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class Arch:
    n_layer: int
    n_head: int
    n_kv_head: int
    head_dim: int
    d_model: int
    expert_width: int
    num_experts: int
    top_k: int
    norm_topk_prob: bool
    rope_theta: float
    norm_eps: float
    block_length: int
    round_to: object = None


@dataclasses.dataclass(frozen=True)
class Sampler:
    block_length: int
    mask_token_id: int
    denoising_steps: int
    remasking: str = "low_confidence_dynamic"
    confidence_threshold: float = 0.9


def arch_from_config(cfg, round_to=None):
    """The published `config.json` keys (and the `assumed` block length) ->
    what the equations need."""
    if cfg["model_type"] != "sdar_moe":
        raise ValueError(f"no reference for model_type {cfg['model_type']!r}")
    if cfg.get("rope_scaling") is not None or cfg.get("attention_bias") \
            or cfg.get("use_sliding_window") or cfg.get("mlp_only_layers") \
            or cfg.get("decoder_sparse_step", 1) != 1:
        raise ValueError("this reference has every layer routed, no rope "
                         "scaling, no attention bias and no sliding window")
    return Arch(n_layer=cfg["num_hidden_layers"],
                n_head=cfg["num_attention_heads"],
                n_kv_head=cfg["num_key_value_heads"],
                head_dim=cfg["head_dim"], d_model=cfg["hidden_size"],
                expert_width=cfg["moe_intermediate_size"],
                num_experts=cfg["num_experts"],
                top_k=cfg["num_experts_per_tok"],
                norm_topk_prob=cfg["norm_topk_prob"],
                rope_theta=float(cfg["rope_theta"]),
                norm_eps=cfg["rms_norm_eps"],
                block_length=cfg["generator"]["block_length"],
                round_to=round_to)


def sampler_from_config(cfg):
    g = cfg["generator"]
    return Sampler(g["block_length"], g["mask_token_id"],
                   g["denoising_steps"], g["remasking"],
                   g["confidence_threshold"])


def _rounded(x, arch):
    if arch.round_to is None:
        return x
    return x.astype(arch.round_to).astype(jnp.float32)


def _matmul(x, w, arch):
    return _rounded(x, arch) @ _rounded(w, arch)


def _rms_norm(x, scale, arch):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                             + arch.norm_eps) * scale


def _rope(x, positions, arch):
    """x: [T, heads, hd]. Rotates the whole head, in (even, odd) pairs."""
    hd = x.shape[-1]
    freqs = arch.rope_theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    angles = positions.astype(jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     axis=-1).reshape(x.shape)


def _experts(h, gate_w, w_gate_up, w_down, arch, forced):
    """h: [T, D] float32; `w_gate_up` [E, D, 2F] / `w_down` [E, F, D] as the
    tree holds them (cast ONE expert at a time) -> (the routed experts' sum
    [T, D], the experts this forward chose [T, k] int32 ascending)."""
    F = arch.expert_width
    probs = jax.nn.softmax(_matmul(h, gate_w, arch), axis=-1)
    top_p, top_e = jax.lax.top_k(probs, arch.top_k)
    chosen = jnp.sort(top_e.astype(jnp.int32), axis=-1)
    if forced is not None:
        top_e = forced
        top_p = jnp.take_along_axis(probs, forced, axis=-1)
    if arch.norm_topk_prob:
        top_p = top_p / jnp.sum(top_p, -1, keepdims=True)

    def one(out, expert):
        e, both_w, down_w = expert
        weight = jnp.sum(jnp.where(top_e == e, top_p, 0.0), axis=-1)   # [T]
        both = _matmul(h, both_w.astype(jnp.float32), arch)
        inner = jax.nn.silu(both[:, :F]) * both[:, F:]
        return out + weight[:, None] * _matmul(
            inner, down_w.astype(jnp.float32), arch), None

    out, _ = jax.lax.scan(
        one, jnp.zeros_like(h),
        (jnp.arange(arch.num_experts), w_gate_up, w_down))
    return out, chosen


def _layer(x, p, experts, arch, forced):
    """One block on one sequence. x: [T, D] float32; p: that layer's small
    leaves; experts: its (`moe_w_gate_up`, `moe_w_down`). Returns (x, chosen
    experts [T, k], keys [T, Hkv, hd] as a cache holds them, values)."""
    p = jax.tree_util.tree_map(lambda leaf: leaf.astype(jnp.float32), p)
    T = x.shape[0]
    H, Hkv, hd, B = arch.n_head, arch.n_kv_head, arch.head_dim, \
        arch.block_length
    positions = jnp.arange(T)
    h = _rms_norm(x, p["ln1_scale"], arch)
    qkv = _matmul(h, p["attn_qkv_w"], arch) + p["attn_qkv_b"]
    q = _rms_norm(qkv[:, :H * hd].reshape(T, H, hd), p["q_norm_scale"], arch)
    k = _rms_norm(qkv[:, H * hd:(H + Hkv) * hd].reshape(T, Hkv, hd),
                  p["k_norm_scale"], arch)
    q, k = _rope(q, positions, arch), _rope(k, positions, arch)
    v = qkv[:, (H + Hkv) * hd:].reshape(T, Hkv, hd)
    scores = jnp.einsum("thd,shd->hts", _rounded(q, arch), _rounded(
        jnp.repeat(k, H // Hkv, axis=1), arch)) / math.sqrt(hd)
    seen = positions[:, None] // B >= positions[None, :] // B
    probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), axis=-1)
    attn = jnp.einsum("hts,shd->thd", _rounded(probs, arch), _rounded(
        jnp.repeat(v, H // Hkv, axis=1), arch)).reshape(T, H * hd)
    x = x + _matmul(attn, p["attn_out_w"], arch) + p["attn_out_b"]
    h2 = _rms_norm(x, p["ln2_scale"], arch)
    routed, chosen = _experts(h2, p["moe_gate_w"], *experts, arch, forced)
    return x + routed, chosen, k, v


_layer_jit = jax.jit(_layer, static_argnums=3)

_STACKS = ("moe_w_gate_up", "moe_w_down")


@jax.jit
def _embed(wte, tokens):
    return wte[tokens].astype(jnp.float32)


def _head(x, scale, table, arch):
    x = _rms_norm(x, scale.astype(jnp.float32), arch)
    return _matmul(x, table.astype(jnp.float32).T, arch)


_head_jit = jax.jit(_head, static_argnums=3)


def forward(params, tokens, arch, forced=None, kv=None, rows=None,
            pad_to=None):
    """tokens: [T] int32 -> (float32 logits [T, vocab] — of the positions
    `rows` (a slice) alone where given —, the experts each layer chose
    [L, T, k] int32, ascending) of one sequence under the block-causal mask.
    `forced` [L, T, k]: the experts to use instead (module docstring). `kv`:
    a list that takes each layer's (keys [T, Hkv, hd], values). `pad_to`:
    the sequence runs padded with token 0 to a multiple of it, so that few
    lengths compile: under the mask no position sees a later block, so the
    padding changes nothing before it, and its rows are cut off again."""
    T = len(tokens)
    if pad_to:
        more = -T % pad_to
        tokens = jnp.concatenate([jnp.asarray(tokens, jnp.int32),
                                  jnp.zeros((more,), jnp.int32)])
        if forced is not None:
            forced = jnp.pad(jnp.asarray(forced), ((0, 0), (0, more), (0, 0)))
        held = [] if kv is not None else None
        out, sets = forward(params, tokens, arch, forced, held,
                            rows if rows is not None else slice(0, T))
        if kv is not None:
            kv.extend((k[:T], v[:T]) for k, v in held)
        return out, sets[:, :T]
    chosen = []
    blocks = params["blocks"]
    with jax.default_matmul_precision("highest"):
        x = _embed(params["wte"], tokens)
        for layer in range(arch.n_layer):
            p = {name: leaf[layer] for name, leaf in blocks.items()
                 if name not in _STACKS}
            x, experts, k, v = _layer_jit(
                x, p, tuple(blocks[name][layer] for name in _STACKS), arch,
                None if forced is None else jnp.asarray(forced[layer]))
            chosen.append(experts)
            if kv is not None:
                kv.append((k, v))
        if rows is not None:
            x = x[rows]
        out = _head_jit(x, params["lnf_scale"], params["lm_head"], arch)
    return out, jnp.stack(chosen)


def logits(params, tokens, arch):
    """tokens: [T] int32 -> float32 logits [T, vocab] of one sequence."""
    return forward(params, tokens, arch)[0]


def transfers(sampler):
    """n_s: the rows step s unmasks at least, `B // S` and one more in the
    first `B mod S` steps."""
    B, S = sampler.block_length, sampler.denoising_steps
    return [B // S + (s < B % S) for s in range(S)]


def unmask_rule(block_logits, x, masked, n, sampler):
    """One denoise step of one block, in numpy: `block_logits` [B, V]
    float32, `x` [B] the block's tokens, `masked` [B] bool, `n` this step's
    n_s. Returns (x, masked) after it. Greedy: x0 = argmax with the mask
    token excluded, confidence c = softmax(logits)[x0]; unmasked are every
    masked row with c > threshold if they are at least n, else the n most
    confident masked rows (ties: the earlier row): `low_confidence_dynamic`,
    the one rule the program builds."""
    z = np.array(block_logits, np.float64)
    z[:, sampler.mask_token_id] = -np.inf
    x0 = z.argmax(-1)
    conf = 1.0 / np.exp(z - z.max(-1, keepdims=True)).sum(-1)
    conf = np.where(masked, conf.astype(np.float32), -np.inf)
    order = sorted(np.flatnonzero(masked), key=lambda i: (-conf[i], i))
    move = np.zeros_like(masked)
    move[order[:n]] = True
    if sampler.remasking != "low_confidence_dynamic":
        raise ValueError(f"no reference for the rule {sampler.remasking!r}")
    high = conf > sampler.confidence_threshold
    if high.sum() >= n:
        move = high
    return np.where(move, x0, x).astype(np.int32), masked & ~move


def generate(params, prompt, gen_length, arch, sampler, trace=None,
             pad_to=None):
    """The sampler: `prompt` [L] int32 -> the `gen_length` tokens generated
    after it (generated in whole blocks, cut to length). `trace`: a list that
    takes one entry a forward, (block start, the block's tokens as the
    forward saw them, its masked rows, its logits [B, V], "denoise" |
    "commit"). `pad_to`: `forward`'s."""
    B = sampler.block_length
    prompt = np.asarray(prompt, np.int32)
    start = len(prompt) - len(prompt) % B
    seq = list(prompt[:start])
    x = np.full((B,), sampler.mask_token_id, np.int32)
    x[:len(prompt) - start] = prompt[start:]
    masked = np.arange(B) >= len(prompt) - start
    plan = transfers(sampler)
    while len(seq) < len(prompt) + gen_length:
        for step in range(sampler.denoising_steps + 1):
            tokens = jnp.asarray(np.concatenate([seq, x]).astype(np.int32))
            out = np.asarray(forward(
                params, tokens, arch, pad_to=pad_to,
                rows=slice(len(seq), len(seq) + B))[0])
            clean = not masked.any()
            if trace is not None:
                trace.append((len(seq), x.copy(), masked.copy(), out,
                              "commit" if clean else "denoise"))
            if clean:
                break
            x, masked = unmask_rule(out, x, masked, plan[step], sampler)
        seq += list(x)
        x = np.full((B,), sampler.mask_token_id, np.int32)
        masked = np.ones((B,), bool)
    return np.asarray(seq[len(prompt):len(prompt) + gen_length], np.int32)
