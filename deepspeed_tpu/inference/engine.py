"""Inference engine.

Analog of `InferenceEngine` (`inference/engine.py:39`) + `deepspeed.init_inference`
(`deepspeed/__init__.py:269`). The reference swaps HF modules for fused CUDA blocks
(kernel injection, `module_inject/replace_module.py:182`) or auto-shards linears
(AutoTP, `module_inject/auto_tp.py:175`); the TPU-native equivalent compiles a
decode step with a static-shape KV cache and shards it over the `tensor` mesh axis.

A model for inference is a `DecodeModelSpec`:
  * `prefill_fn(params, tokens, cache) -> (logits, cache)`
  * `decode_fn(params, token, pos, cache) -> (logits, cache)`
  * `init_cache(batch, max_len)` -> KV cache pytree
The model zoo (deepspeed_tpu.models) provides these for GPT-2/LLaMA-style nets;
the adapters in inference/adapters.py build them from HF checkpoints (the
"containers" role, `module_inject/containers/*`).
"""

import dataclasses
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from deepspeed_tpu.comm import mesh as mesh_mod
from deepspeed_tpu import comm
from deepspeed_tpu.inference.config import TpuInferenceConfig
from deepspeed_tpu.utils.logging import logger, log_dist
from deepspeed_tpu.utils.tree import tree_cast


def sample_logits(logits, rng, greedy=True, temperature=1.0, top_k=0,
                  top_p=1.0):
    """One sampling rule for every inference engine (resident + spill +
    serving): greedy argmax, or temperature/top-k/top-p categorical.
    Filters compose in the standard order: temperature, then top-k, then
    nucleus (top-p) on the surviving distribution."""
    if greedy or rng is None:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits / jnp.maximum(temperature, 1e-6)
    want_k = bool(top_k) and top_k > 0
    want_p = top_p is not None and top_p < 1.0
    if want_k or want_p:
        # ONE sort pass for both filters: lax.top_k's descending head is the
        # kth-value source for the top-k cut AND the sorted prefix the
        # nucleus cumsum walks. (The old path paid two full-vocab jnp.sorts —
        # one for kth, one for the nucleus — and the nucleus only ever reads
        # the head anyway: past the kept set the cumulative mass is 1, so no
        # tail entry can pass the `< top_p` test.)
        k_eff = min(int(top_k), logits.shape[-1]) if want_k \
            else logits.shape[-1]
        head = jax.lax.top_k(logits, k_eff)[0]
        if want_k:
            logits = jnp.where(logits < head[..., -1:], -jnp.inf, logits)
        if want_p:
            # nucleus sampling (Holtzman et al.): keep the smallest head of
            # the sorted distribution whose cumulative probability reaches
            # top_p. With top-k active, softmax over the k-entry head equals
            # the softmax of the filtered distribution whenever the kth
            # value is unique — logits tied EXACTLY at the kth value survive
            # the `< kth` filter but fall outside the head, so their mass is
            # missing from this cumsum (the old two-sort path counted it).
            # Tied logits carry equal probability, so either cutoff is a
            # valid nucleus rule; exact ties are measure-zero for real model
            # logits. The exclusive cumsum (cum - probs) keeps the argmax
            # even when its own probability already exceeds top_p; ties at
            # the cutoff logit are all kept (harmless: equal probability).
            probs = jax.nn.softmax(head, axis=-1)
            keep = jnp.cumsum(probs, axis=-1) - probs < top_p
            # top-1 survives unconditionally, including top_p <= 0 (a common
            # spelling of "argmax"): an all-False keep would mask EVERY token
            # and categorical over all -inf degenerates to token id 0
            keep = keep.at[..., 0].set(True)
            cutoff = jnp.min(jnp.where(keep, head, jnp.inf), axis=-1,
                             keepdims=True)
            logits = jnp.where(logits < cutoff, -jnp.inf, logits)
    return jax.random.categorical(rng, logits, axis=-1).astype(jnp.int32)


# what a block-diffusion call counts beside the model's own counters, in this
# order (int32[5], summed over a call's forwards and read back with its
# tokens): FUSED forwards (a block's commit and the next block's first
# denoise step as one pass through the weights), then the four that count
# ROLES and not passes — forwards whose rows the unmask rule read, forwards
# that wrote a block's K/V for good (a fused forward is one of each: passes =
# denoise + commit - fused), (slot, row) pairs the rule unmasked, (slot,
# block) pairs committed for live slots. The four stay LAST, in this order:
# the benchmark's check reads them as `counts[-4:]`
BLOCK_DIFFUSION_COUNTERS = ("fused_forwards", "denoise_forwards",
                            "commit_forwards", "rows_unmasked",
                            "blocks_committed")


@dataclasses.dataclass(frozen=True)
class BlockDiffusion:
    """The generator of a model that generates by DIFFUSION OVER BLOCKS (the
    SDAR family, `models/sdar_moe.py`), as data on its `DecodeModelSpec`.

    Under the block-causal mask (`GPTConfig.block_length`) a block of
    `block_length` positions is generated together: it starts as mask tokens
    (a prompt's last `L mod B` tokens open the first one as clean tokens),
    up to `denoising_steps` DENOISE forwards of its B rows each unmask some
    rows (`unmask`), and when no mask is left ONE COMMIT forward of the clean
    tokens writes the block's K/V. A masked row's own logits predict its
    token (no shift). Greedy: x0 = argmax, confidence = softmax(logits)[x0],
    the mask token itself never drawn."""
    block_length: int
    mask_token_id: int
    denoising_steps: int = 0        # S: quality against speed; 0 =
                                    # `block_length` (a row a step): `steps`
    remasking: str = "low_confidence_dynamic"   # the one rule built
    confidence_threshold: float = 0.9

    def __post_init__(self):
        if self.remasking != "low_confidence_dynamic":
            raise ValueError(f"remasking rule {self.remasking!r} is not "
                             f"built (only 'low_confidence_dynamic' is)")

    @property
    def steps(self):
        """S: the most denoise forwards a block takes before its commit."""
        return self.denoising_steps or self.block_length

    def transfers(self, steps):
        """n_s for s = 0 .. steps - 1: the rows a step unmasks at least,
        `B // S` and one more in the first `B mod S` steps."""
        B = self.block_length
        return [B // steps + (s < B % steps) for s in range(steps)]

    def unmask(self, logits, x, masked, n):
        """One denoise step on the device: `logits` [S * B, V] of the
        blocks' rows, slot after slot (FLAT: a [S, B, V] array has B on the
        sublanes, and every pass over it pays for the padding), `x` [S, B]
        the blocks' tokens, `masked` [S, B] the rows still masked,
        `n` (traced scalar) this step's n_s. Every masked row takes x0 and
        its confidence c; unmasked are, a slot: every masked row with c >
        `confidence_threshold` if they are at least n, else the n most
        confident masked rows (ties: the earlier row; fewer than n masked:
        all of them). Returns (x, masked, rows unmasked [S])."""
        with jax.named_scope("denoise/confidence"):
            logits = logits.astype(jnp.float32)
            ids = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
            logits = jnp.where(ids == self.mask_token_id, -jnp.inf, logits)
            top = jnp.max(logits, axis=-1)
            x0 = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            # softmax(logits)[argmax] = 1 / sum exp(logits - max)
            conf = 1.0 / jnp.sum(jnp.exp(logits - top[:, None]), axis=-1)
            x0, conf = x0.reshape(x.shape), conf.reshape(x.shape)
        with jax.named_scope("denoise/unmask"):
            conf = jnp.where(masked, conf, -jnp.inf)
            # a row's rank by confidence among its slot's rows, ties to the
            # earlier row (B is a handful: the B x B comparison is nothing)
            idx = jnp.arange(x.shape[1])
            ahead = (conf[:, None, :] > conf[:, :, None]) | (
                (conf[:, None, :] == conf[:, :, None])
                & (idx[None, None, :] < idx[None, :, None]))
            move = masked & (jnp.sum(ahead, axis=-1) < n)
            high = conf > self.confidence_threshold
            enough = jnp.sum(high, axis=-1, keepdims=True) >= n
            move = jnp.where(enough, high, move)
            return (jnp.where(move, x0, x), masked & ~move,
                    jnp.sum(move, axis=-1, dtype=jnp.int32))


@dataclasses.dataclass
class DecodeModelSpec:
    prefill_fn: Callable       # (params, tokens[B,T], cache, pad_mask) -> (logits[B,T,V], cache)
    decode_fn: Callable        # (params, token[B], pos[B], cache) -> (logits[B,V], cache)
    init_cache: Callable       # (batch_size, max_len, dtype) -> cache pytree
    params: Any
    param_specs: Any = None
    eos_token_id: Optional[int] = None
    name: str = "model"
    # paged-pool serving contract (inference/scheduler.py). Optional: models
    # without it serve through generate() only. Shapes are FIXED per engine —
    # that is what keeps the serving step at one compile for its lifetime.
    #   prefill_paged_fn(params, tokens[B,C], start_pos[B], last_idx[B],
    #                    pool, block_tables[B,nb]) -> (logits[B,V], pool)
    #     one chunk of chunked prefill: writes the chunk's k/v into the
    #     slot's pool blocks and returns the logits at last_idx (the true
    #     final prompt token on the last chunk; ignored on earlier chunks)
    #   decode_paged_fn(params, token[B], pos[B], pool, block_tables[B,nb])
    #       -> (logits[B,V], pool)
    #   mixed_paged_fn(params, chunk_tokens[G,C], start_pos[G], last_idx[G],
    #                  chunk_table[G,nb], token[S], pos[S], pool,
    #                  block_tables[S,nb][, count]) -> (logits[G+S,V], pool)
    #     a GROUP of up to G prefill chunks AND a decode token of every slot
    #     in one call, all their rows through every weight as ONE tensor (the
    #     scheduler's `mixed_step`: a step's chunks ride its decode call, up
    #     to G chunks a token, and the weights are read once where the
    #     programs of their own read them 1 + G times); the attention half
    #     runs the chunks in order, each as a chunk of its own. Logits of
    #     each chunk's last_idx row, then the slots'. `count` (traced, 1..G):
    #     the group's real chunks, passed where G > 1. No chunk's slot is
    #     among the decoding ones. None: chunks and decode stay two calls.
    #   mixed_chunk_groups: the mixed program takes G > 1. False: the
    #     scheduler hands it one chunk a token whatever its budget.
    #   init_paged_pool(num_blocks, block_size, dtype[, kv_group_size])
    #       -> pool pytree. dtype int8 selects the QUANTIZED pool: the
    #     k/v payload leaves stay [L, N, Hkv, block, hd] but int8, and the
    #     pool grows k_scale/v_scale f32 leaves [L, N, Hkv, block, hd//g]
    #     (g = kv_group_size, 0 = head_dim) — the serving scheduler passes
    #     the 4th arg only for int8, so 3-arg implementations keep working
    #     for fp pools
    #   verify_paged_fn(params, tokens[B,C], pos[B], pool, block_tables[B,nb])
    #       -> (logits[B,C,V], pool)
    #     speculative-decoding verify: writes ALL C tokens' k/v at absolute
    #     positions pos..pos+C-1 (token [b,0] is the slot's last emitted
    #     token at its cursor, [b,1:] are draft tokens) and returns the
    #     logits at EVERY position — row i scores the draft at i+1, the
    #     first disagreeing row supplies the bonus token. Same chunked-
    #     prefill machinery as prefill_paged_fn, at an arbitrary cursor.
    prefill_paged_fn: Optional[Callable] = None
    decode_paged_fn: Optional[Callable] = None
    mixed_paged_fn: Optional[Callable] = None
    mixed_chunk_groups: bool = False
    verify_paged_fn: Optional[Callable] = None
    init_paged_pool: Optional[Callable] = None
    # dispatch phase ("paged_decode" | "prefill_chunk" | "verify" | "mixed")
    # -> the writer that paged program was TRACED with (`attention_dispatch.
    # kv_pool_writer`'s names), filled in by the model as each program is
    # traced. None: the model writes with the XLA scatter throughout.
    kv_pool_writers: Optional[Dict[str, str]] = None
    # the same phases -> the attention program each was traced with
    # (`attention_dispatch`'s registry names: "paged_prefill_kernel",
    # "paged_kernel", "paged_gather", ...; the mixed program's two groups
    # under "mixed/prefill_chunk" and "mixed/paged_decode"). None: the model
    # keeps no record.
    paged_attn_programs: Optional[Dict[str, str]] = None
    # names of the int32 counters the paged programs return as a THIRD
    # result, `(logits, pool, counts[len(step_counters)])`, summed over the
    # model's layers (the routed experts': `parallel.moe.ROUTED_COUNTERS`).
    # The scheduler sums them over a decode window, reads them back with the
    # tokens and keeps them on its step ring. None: two results, no counters.
    step_counters: Optional[tuple] = None
    # a model whose layers keep TWO kinds of cache (full attention beside
    # sliding-window attention): `block_size -> (CacheKind full, CacheKind
    # window)` (`inference/kv_cache.py`). The scheduler then builds the
    # window kind's ring tables, hands `init_paged_pool` the keyword
    # `window_blocks`, and passes every paged program its tables as the PAIR
    # (full tables [B, nb], ring tables [B, nbw]). None: one kind, one table.
    paged_cache_kinds: Optional[Callable] = None
    # a model that generates by diffusion over blocks: the generator
    # (`BlockDiffusion`: block length, mask id, steps, rule, threshold), and
    #   denoise_paged_fn(params, tokens[S,B], pos[S], pool, block_tables[S,nb])
    #       -> (logits[S*B,V], pool[, counts])
    #     ONE forward of a block a slot, denoise or commit alike: writes the B
    #     rows' k/v at pos..pos+B-1 (a later forward of the same block writes
    #     over them) and attends [0, pos + B), every row's logits back, slot
    #     after slot.
    #     With tokens[S,2B] it is a FUSED forward: block b's clean tokens
    #     (its commit) before block b+1's rows (its first denoise step), one
    #     pass through every weight; both blocks' k/v are written, each block
    #     attends to its own end, and the logits are block b+1's.
    #     `mixed_paged_fn` then takes `token` [S, B] and gives logits
    #     [G + S*B, V]. With `hidden=True` both give the sampling rows as
    #     the layers leave them, [S*B, D], in the logits' place, and
    #   head_fn(params, rows[N,D]) -> logits[N,V]
    #     makes the logits of them: the block loop runs it, and the rule,
    #     in the forwards that sample. The scheduler builds its decode and
    #     mixed programs from these (`step_programs.py`); a decode call
    #     commits whole blocks and takes no token from the call before it.
    #     None: one token a slot a forward.
    generator: Optional[BlockDiffusion] = None
    denoise_paged_fn: Optional[Callable] = None
    head_fn: Optional[Callable] = None
    # cache-identity fingerprint for the prefix cache's hash chain
    # (inference/prefix_cache.py): every arch field that changes the KV
    # VALUES written for a given token stream must be folded in, so two
    # specs can never serve each other's cached blocks. None falls back to
    # `name` (weights are engine-local, so the fingerprint guards config
    # divergence, not parameters).
    cache_fingerprint: Optional[str] = None


class InferenceEngine:
    def __init__(self, model: DecodeModelSpec, config: TpuInferenceConfig, mesh=None):
        self.model_spec = model
        self.config = config

        if mesh is not None:
            mesh_mod.set_mesh(mesh)
        elif not mesh_mod.has_mesh():
            from deepspeed_tpu.config.core import MeshConfig
            tp = config.tensor_parallel.tp_size
            comm.init_distributed(mesh_config=MeshConfig(data=-1, tensor=tp))
        self.mesh = mesh_mod.get_mesh()

        dtype = jnp.dtype(config.dtype) if config.dtype != "float" else jnp.float32
        self.dtype = dtype

        # TP placement: params sharded per their specs over the tensor axis,
        # replicated over everything else.
        if model.param_specs is not None:
            shardings = jax.tree_util.tree_map(
                lambda spec: NamedSharding(self.mesh, spec), model.param_specs)
        else:
            shardings = jax.tree_util.tree_map(
                lambda _: NamedSharding(self.mesh, P()), model.params)
        params = jax.device_put(tree_cast(model.params, dtype), shardings)

        self.quant_stats = None
        self._weight_quant = None      # (bits, group_size) once quantized
        self._fn_transform = lambda fn: fn
        self.params = params
        if config.quant.enabled:
            # weight-only quantization: HBM keeps int8/int4, XLA fuses dequant
            # into consumers (inference/quantization.py). enable_weight_quant
            # builds the resident programs against the quantized tree, so the
            # dense-path builds below are skipped
            self.enable_weight_quant(bits=config.quant.bits,
                                     group_size=config.quant.group_size)
        else:
            self._prefill = jax.jit(self._fn_transform(model.prefill_fn))
            self._decode = jax.jit(self._fn_transform(model.decode_fn),
                                   donate_argnums=(3,))
        self._generate_jit = None
        # engine-owned KV cache: forward()/generate() reuse the zeros
        # template when (B, max_len, dtype) matches the previous call
        # instead of re-allocating (and re-zeroing) a fresh cache every
        # call. ONE entry only — a multi-shape store would pin several
        # full-size caches in HBM, a peak-memory regression; a shape miss
        # just re-allocates, which is exactly the old per-call behavior.
        # The template is never mutated: the jitted programs are functional
        # and nothing donates it.
        self._cache_entry = None          # ((B, max_len, dtype), cache)
        self._cache_hits = 0
        log_dist(f"inference engine: {model.name} dtype={dtype} "
                 f"tp={config.tensor_parallel.tp_size} "
                 f"quant={'int%d' % config.quant.bits if config.quant.enabled else 'off'}",
                 ranks=[0])

    def enable_weight_quant(self, bits=8, group_size=64):
        """Pytree-wide weight-only quantization of the RESIDENT params
        (ZeroQuant-style WOQ, `inference/quantization.py`): every large
        float matrix leaf becomes int8 (or int4 packed two-per-byte) with
        per-group scales, and every program factory switches to the
        dequantize-on-use view — XLA fuses the dequant into the consuming
        matmul, so HBM holds the quantized tree and compute still runs in
        the engine dtype. The dense tree is DROPPED (this is where the
        2x/4x weight-memory saving comes from); the resident prefill/decode
        programs are re-jitted against the new param pytree and the
        generate program rebuilds lazily.

        Called at engine build for `config.quant.enabled`, and by the
        serving scheduler for `ServingConfig.quantization.weights` —
        idempotent for matching settings, an error for conflicting ones
        (re-quantizing already-quantized leaves would compound the error)."""
        if self._weight_quant is not None:
            if self._weight_quant == (int(bits), int(group_size)):
                return self.quant_stats
            raise ValueError(
                f"params already quantized as int{self._weight_quant[0]} "
                f"(group {self._weight_quant[1]}) — cannot re-quantize as "
                f"int{bits} (group {group_size}); pick one of config.quant "
                f"and serving.quantization.weights, or make them agree")
        from deepspeed_tpu.inference.quantization import (quantize_param_tree,
                                                          wrap_fn_dequant)
        self.params, self.quant_stats = quantize_param_tree(
            self.params, bits=int(bits), group_size=int(group_size))
        self._weight_quant = (int(bits), int(group_size))
        self._fn_transform = wrap_fn_dequant
        # dstpu: ignore[DT004]: one-shot re-jit — the _weight_quant guard above makes this method run at most once per engine, exactly like __init__'s builds
        self._prefill = jax.jit(self._fn_transform(self.model_spec.prefill_fn))
        # dstpu: ignore[DT004]: same one-shot rebuild as the line above
        self._decode = jax.jit(self._fn_transform(self.model_spec.decode_fn),
                               donate_argnums=(3,))
        self._generate_jit = None
        return self.quant_stats

    def _cache_len(self, min_len):
        """Blocked KV-cache sizing: round up to whole kv_block_size blocks
        (the streaming decode kernel's DMA unit — see init_kv_cache). The
        over-allocation is free at decode time: the kernel walks only the
        blocks covering each row's live prefix."""
        bs = int(getattr(self.config, "kv_block_size", 0) or 0)
        return -(-min_len // bs) * bs if bs else min_len

    def _get_cache(self, batch, max_len):
        """Engine-owned KV cache for (batch, max_len): reused whenever the
        shape matches the last call (the old per-call init_cache was a fresh
        HBM allocation + zero-fill per generate()); a shape change replaces
        the single retained template, so peak HBM never exceeds the old
        behavior by more than one cache."""
        if jnp.dtype(self.config.kv_cache_dtype) == jnp.int8:
            raise ValueError(
                "kv_cache_dtype='int8' is a paged-pool serving feature "
                "(ServingConfig.quantization / engine.serving()): the "
                "contiguous generate() cache has no scale storage — serve "
                "through the continuous-batching scheduler, or keep "
                "kv_cache_dtype float for generate()")
        key = (int(batch), int(max_len), str(self.config.kv_cache_dtype))
        if self._cache_entry is not None and self._cache_entry[0] == key:
            self._cache_hits += 1
            return self._cache_entry[1]
        cache = self.model_spec.init_cache(
            batch, max_len, jnp.dtype(self.config.kv_cache_dtype))
        self._cache_entry = (key, cache)
        return cache

    def _refuse_for_generator(self, entry):
        """The contiguous cache's entries run `prefill_fn` / `decode_fn`:
        the causal mask, one token a forward. For a model that generates by
        diffusion over blocks (`DecodeModelSpec.generator`) that is another
        model's answer, so they refuse by name (`ServingEngine` refuses what
        its generator cannot have the same way)."""
        gen = getattr(self.model_spec, "generator", None)
        if gen is not None:
            raise ValueError(
                f"model spec '{self.model_spec.name}' generates by diffusion "
                f"over blocks of {gen.block_length}: {entry} on the "
                f"contiguous cache is not built for it — it runs the causal "
                f"mask, a token a forward; serve it through `.serving()`")

    def forward(self, tokens, cache=None, pad_mask=None):
        """Prefill forward (logits for a full sequence)."""
        self._refuse_for_generator("forward()")
        tokens = jnp.asarray(tokens)
        if cache is None:
            cache = self._get_cache(
                tokens.shape[0],
                self._cache_len(max(self.config.max_out_tokens,
                                    tokens.shape[1])))
        return self._prefill(self.params, tokens, cache, pad_mask)

    __call__ = forward

    def _build_generate(self):
        decode_fn = self._fn_transform(self.model_spec.decode_fn)
        prefill_fn = self._fn_transform(self.model_spec.prefill_fn)
        greedy = self.config.greedy
        temperature = self.config.temperature
        top_k = self.config.top_k
        top_p = self.config.top_p

        def sample(logits, rng):
            return sample_logits(logits, rng, greedy=greedy,
                                 temperature=temperature, top_k=top_k,
                                 top_p=top_p)

        def generate(params, tokens, cache, prompt_len, max_new, rng, eos_id, pad_id):
            B, T = tokens.shape
            logits, cache = prefill_fn(params, tokens, cache, None)
            # last prompt logits, per sample (ragged batches: rows are
            # right-padded, causal masking keeps pads out of these logits)
            last = jnp.take_along_axis(
                logits, (prompt_len - 1)[:, None, None], axis=1)[:, 0, :]
            first_tok = sample(last, rng)
            done0 = jnp.zeros((B,), bool)

            def body(carry, i):
                tok, pos, cache, rng, done = carry
                rng, sub = jax.random.split(rng)
                lg, cache = decode_fn(params, tok, pos, cache)
                nxt = sample(lg, sub)
                # eos semantics (reference generate(): stop per sequence once
                # eos is emitted): the eos token itself is kept in the output,
                # everything after it is pad_id. eos_id < 0 disables.
                new_done = done | ((tok == eos_id) & (eos_id >= 0))
                nxt = jnp.where(new_done, pad_id, nxt)
                emit = jnp.where(done, pad_id, tok)
                return (nxt, pos + 1, cache, rng, new_done), emit

            (_, _, cache, _, _), toks = jax.lax.scan(
                body, (first_tok, prompt_len, cache, rng, done0),
                jnp.arange(max_new))
            return jnp.moveaxis(toks, 0, 1)  # [B, max_new]

        return jax.jit(generate, static_argnums=(4,))

    @staticmethod
    def _pad_ragged(tokens):
        """Right-pad a list of variable-length sequences to [B, T_max].

        Returns (tokens[B,T], prompt_lens[B]). Right padding (not left) is the
        natural layout for a per-sample-position KV cache: each row's decode
        starts at its own prompt_len and overwrites the pad slots, and causal
        masking keeps trailing pads out of the prompt logits. The reference
        relies on the HF tokenizer's left-pad + attention_mask for the same
        ragged-batch contract (`inference/engine.py:577-606`).

        The fill value is always 0, NOT pad_token_id: pad slots are provably
        never attended, but an out-of-vocab fill (e.g. a sentinel pad id)
        turns the embedding gather out-of-bounds, which is NaN on the TPU
        backend. pad_token_id only masks the *output*.
        """
        lens = np.asarray([len(t) for t in tokens], np.int32)
        T = int(lens.max())
        out = np.zeros((len(tokens), T), np.int32)
        # single boolean-mask scatter instead of a per-row Python loop: the
        # mask enumerates valid slots row-major, matching the concatenation
        # order of the ragged rows
        mask = np.arange(T)[None, :] < lens[:, None]
        out[mask] = np.concatenate([np.asarray(t, np.int32) for t in tokens])
        return out, lens

    def generate(self, tokens, max_new_tokens=32, rng=None, prompt_lens=None,
                 eos_token_id=None, pad_token_id=0, stop_on_eos=True):
        """Greedy/sampled generation with a static-shape decode loop (lax.scan).

        `tokens` may be a rectangular [B, T] batch or a list of ragged
        sequences (padded internally). `prompt_lens` gives per-sample prompt
        lengths for rectangular-but-right-padded input. Sequences stop at
        `eos_token_id` (default: the model spec's) — the eos is kept, later
        slots are `pad_token_id`.
        """
        self._refuse_for_generator("generate()")
        if self._generate_jit is None:
            self._generate_jit = self._build_generate()
        if isinstance(tokens, (list, tuple)) and tokens and np.ndim(tokens[0]) == 1 \
                and len({len(t) for t in tokens}) > 1:
            tokens, prompt_lens = self._pad_ragged(tokens)
        tokens = jnp.asarray(tokens)
        B, T = tokens.shape
        # max_new is a static argnum of the jitted loop (the scan length must
        # be a compile-time constant), so every distinct value used to build
        # a fresh executable. Bucket it to the next power of two and trim the
        # surplus host-side: a mixed-request server compiles O(log max_new)
        # programs instead of one per distinct value. EOS semantics survive
        # the over-generation — finished rows emit pad_token_id, and the
        # extra columns are sliced off before anyone sees them. The trade-off
        # is deliberate: the surplus scan steps (up to 2x decode compute at
        # the bucket edge, ~1.4x expected) run on every call, bought against
        # a multi-second XLA compile per distinct max_new; workloads where
        # per-call decode cost dominates compile amortization should serve
        # through the continuous-batching scheduler, which has neither cost.
        max_new_bucket = max(1, 1 << (int(max_new_tokens) - 1).bit_length())
        max_len = self._cache_len(T + max_new_bucket)
        cache = self._get_cache(B, max_len)
        if prompt_lens is None:
            prompt_len = jnp.full((B,), T, jnp.int32)
        else:
            prompt_len = jnp.asarray(prompt_lens, jnp.int32)
        eos = eos_token_id
        if eos is None:
            eos = getattr(self.config, "eos_token_id", None)
        if eos is None:
            eos = self.model_spec.eos_token_id
        if not stop_on_eos or eos is None:
            eos = -1
        rng = rng if rng is not None else jax.random.PRNGKey(0)
        out = self._generate_jit(self.params, tokens, cache, prompt_len,
                                 max_new_bucket, rng,
                                 jnp.int32(eos), jnp.int32(pad_token_id))
        # dstpu: ignore[DT001]: generate() API boundary — the whole rollout returns to the host caller in one transfer
        return np.asarray(jax.device_get(out))[:, :max_new_tokens]

    def serving(self, **overrides):
        """Continuous-batching serving engine over this engine's params:
        persistent paged KV pool + request scheduler (inference/scheduler.py).
        `overrides` patch `config.serving` fields (max_slots, max_context,
        num_kv_blocks, prefill_chunk, prefill_chunks_per_step, spec_decode
        — pass a dict for the nested speculative-decoding block, plus
        `draft_spec=` for its draft-model drafter). The scheduler also
        reads this config's `telemetry` block: when enabled it records
        TTFT/TPOT/queue-wait/e2e histograms and pool gauges
        (docs/profiling.md "Telemetry")."""
        from deepspeed_tpu.inference.scheduler import ServingEngine
        return ServingEngine(self, **overrides)


def init_inference(model=None, config=None, **kwargs):
    """Reference signature (`deepspeed/__init__.py:269`): accepts config dict/path +
    kwargs overrides."""
    from deepspeed_tpu.platform.device import ensure_compile_cache
    ensure_compile_cache()
    if config is None:
        config = {}
    if isinstance(config, str):
        import json
        with open(config) as f:
            config = json.load(f)
    if isinstance(config, dict):
        config = {**config, **kwargs}
        cfg = TpuInferenceConfig.from_dict(config)
    else:
        cfg = config
    from deepspeed_tpu.inference.zero_inference import (LayeredModelSpec,
                                                        ZeroInferenceEngine)
    off = (cfg.zero or {}).get("offload_param")
    if isinstance(model, LayeredModelSpec):
        off = off or {}
        return ZeroInferenceEngine(
            model, cfg, offload_device=off.get("device", "cpu"),
            nvme_path=off.get("nvme_path"),
            lookahead=int(off.get("lookahead", 1)),
            staging=int(off.get("staging", 3)))
    if off:
        raise ValueError(
            "zero.offload_param (ZeRO-Inference) needs a LayeredModelSpec — "
            "build one with models.gpt.make_gpt_layered_model")
    assert isinstance(model, DecodeModelSpec), \
        "init_inference expects a DecodeModelSpec (see deepspeed_tpu.models / inference.adapters)"
    return InferenceEngine(model, cfg)
