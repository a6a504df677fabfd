"""Plain reference for the decoder-only configurations (GPT-NeoX / Pythia and
Mistral): the forward pass and the loss in straightforward `jax.numpy`,
float32, one sequence at a time, dense attention, no kernels, no cache, no
batching, no scan. Every matrix product runs under
`jax.default_matmul_precision("highest")` — on a TPU a float32 product
otherwise runs in bfloat16 passes.

It reads the PROGRAM'S parameter tree (blocks stacked on a leading layer
axis, q/k/v fused in one `[D, (H + 2 Hkv) hd]` matrix in that order), because
"the same weights" is what is compared; layers are cast to float32 one at a
time, so the reference fits beside a served model.

Follows the published descriptions (GPT-NeoX: LayerNorm, parallel residual,
rotary on `rotary_pct` of each head, exact GELU; Mistral: RMSNorm, grouped
key-value heads, SwiGLU, rotary on the whole head, `rope_theta`). One
departure, shared with the program and stated in the configuration files:
rotary pairs are interleaved (even, odd) rather than split in halves, which
is the published layout up to a fixed permutation of each head's columns.
"""

import dataclasses
import math

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class Arch:
    n_layer: int
    n_head: int
    n_kv_head: int
    d_model: int
    rotary_dims: int
    rope_theta: float
    norm_eps: float
    rms_norm: bool
    swiglu: bool
    parallel_residual: bool

    @property
    def head_dim(self):
        return self.d_model // self.n_head


def arch_from_config(cfg):
    """The published `config.json` keys -> what the equations need."""
    heads = cfg["num_attention_heads"]
    head_dim = cfg["hidden_size"] // heads
    if cfg["model_type"] == "gpt_neox":
        return Arch(n_layer=cfg["num_hidden_layers"], n_head=heads,
                    n_kv_head=heads, d_model=cfg["hidden_size"],
                    rotary_dims=int(cfg["rotary_pct"] * head_dim) // 2 * 2,
                    rope_theta=float(cfg["rotary_emb_base"]),
                    norm_eps=cfg["layer_norm_eps"], rms_norm=False,
                    swiglu=False,
                    parallel_residual=cfg["use_parallel_residual"])
    if cfg["model_type"] == "mistral":
        return Arch(n_layer=cfg["num_hidden_layers"], n_head=heads,
                    n_kv_head=cfg["num_key_value_heads"],
                    d_model=cfg["hidden_size"], rotary_dims=head_dim,
                    rope_theta=float(cfg["rope_theta"]),
                    norm_eps=cfg["rms_norm_eps"], rms_norm=True, swiglu=True,
                    parallel_residual=False)
    raise ValueError(f"no reference for model_type {cfg['model_type']!r}")


def _f32(tree):
    return jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), tree)


def _norm(x, scale, bias, arch):
    if arch.rms_norm:
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                                 + arch.norm_eps) * scale
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + arch.norm_eps) * scale + bias


def _rope(x, positions, arch):
    """x: [T, heads, hd]. Rotates the first `rotary_dims` of every head, in
    (even, odd) pairs."""
    rd = arch.rotary_dims
    freqs = arch.rope_theta ** (-jnp.arange(0, rd, 2, dtype=jnp.float32) / rd)
    angles = positions.astype(jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    even, odd = x[..., 0:rd:2], x[..., 1:rd:2]
    rotated = jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                        axis=-1).reshape(x.shape[:-1] + (rd,))
    return jnp.concatenate([rotated, x[..., rd:]], axis=-1)


def _mlp(h, p, arch):
    if arch.swiglu:
        inner = jax.nn.silu(h @ p["mlp_gate_w"]) * (h @ p["mlp_up_w"])
    else:
        inner = jax.nn.gelu(h @ p["mlp_up_w"] + p["mlp_up_b"],
                            approximate=False)
    return inner @ p["mlp_down_w"] + p["mlp_out_b"]


def _layer(x, p, arch):
    """One block on one sequence. x: [T, D] float32; p: that layer's leaves."""
    p = _f32(p)
    T = x.shape[0]
    H, Hkv, hd = arch.n_head, arch.n_kv_head, arch.head_dim
    positions = jnp.arange(T)
    h = _norm(x, p["ln1_scale"], p.get("ln1_bias"), arch)
    qkv = h @ p["attn_qkv_w"] + p["attn_qkv_b"]
    q = _rope(qkv[:, :H * hd].reshape(T, H, hd), positions, arch)
    k = _rope(qkv[:, H * hd:(H + Hkv) * hd].reshape(T, Hkv, hd), positions,
              arch)
    v = qkv[:, (H + Hkv) * hd:].reshape(T, Hkv, hd)
    k = jnp.repeat(k, H // Hkv, axis=1)         # query head h reads kv head
    v = jnp.repeat(v, H // Hkv, axis=1)         # h // (H / Hkv)
    scores = jnp.einsum("thd,shd->hts", q, k) / math.sqrt(hd)
    causal = positions[:, None] >= positions[None, :]
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
    attn = jnp.einsum("hts,shd->thd", probs, v).reshape(T, H * hd)
    attn_out = attn @ p["attn_out_w"] + p["attn_out_b"]
    if arch.parallel_residual:
        h2 = _norm(x, p["ln2_scale"], p.get("ln2_bias"), arch)
        return x + attn_out + _mlp(h2, p, arch)
    x = x + attn_out
    h2 = _norm(x, p["ln2_scale"], p.get("ln2_bias"), arch)
    return x + _mlp(h2, p, arch)


_layer_jit = jax.jit(_layer, static_argnums=2)


@jax.jit
def _embed(wte, tokens):
    return wte[tokens].astype(jnp.float32)


def _head(x, scale, bias, table, arch):
    x = _norm(x, scale.astype(jnp.float32),
              None if bias is None else bias.astype(jnp.float32), arch)
    return x @ table.astype(jnp.float32).T


_head_jit = jax.jit(_head, static_argnums=4)


def logits(params, tokens, arch):
    """tokens: [T] int32 -> float32 logits [T, vocab] of one sequence."""
    with jax.default_matmul_precision("highest"):
        x = _embed(params["wte"], tokens)
        for layer in range(arch.n_layer):
            p = jax.tree_util.tree_map(lambda leaf: leaf[layer],
                                       params["blocks"])
            x = _layer_jit(x, p, arch)
        table = params.get("lm_head", params["wte"])
        return _head_jit(x, params["lnf_scale"], params.get("lnf_bias"),
                         table, arch)


def loss(params, tokens, labels, arch):
    """Mean next-token cross entropy over sequences. tokens, labels:
    [n, T] int32; one sequence at a time."""
    total = 0.0
    for row in range(tokens.shape[0]):
        lg = logits(params, jnp.asarray(tokens[row]), arch)
        logz = jax.nn.logsumexp(lg, axis=-1)
        gold = jnp.take_along_axis(lg, jnp.asarray(labels[row])[:, None],
                                   axis=-1)[:, 0]
        total += float(jnp.mean(logz - gold))
    return total / tokens.shape[0]
