"""Driver for the GLM-4.7-Flash family (`models/glm4_moe_lite.py`,
`model_type: glm4_moe_lite`: latent attention in every layer) served through
`init_inference(...).serving(...)`: the SAME loop, recorder, window and
estimators as `drivers/serve.py` — that module's `run` is called as it is —
and the SAME schedule, ticks and warm-up as `drivers/serve_nemotron_h.py`,
imported and not restated. This file has the family's set-up (`model_config`,
`_build`), the ticks' program on a pool of the latent kind (tables bare, no
state rows), its reference and its LIMITS.

The check, in short: every call is one `mixed_paged_fn` — one prefill chunk
riding a decode token of every live slot, the body of the served
`mixed_step` — on the SERVED pool, up to 127 of the 128 slots live beside the
chunk: a prompt past 8k tokens chunk by chunk (its last chunk mostly
padding), one of two chunks, parts of one chunk, slots handed on to a second
request. LOGITS at every chunk's end and every decode token against
`references/glm4_moe_lite.py`'s full forward (the EXPANDED form, float32)
with the routing held equal (the program's four-expert sets given to the
reference; what it would have chosen compared on its own), AND the pool's
latent entries of the compared sequences after the last call against the
reference's `c` and `k_r` at every position and layer: what the cache holds
is the reference's numbers in bfloat16 and nothing else.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np

import deepspeed_tpu
from deepspeed_tpu.comm import mesh as mesh_mod
from deepspeed_tpu.config.core import MeshConfig

import harness
from drivers import gpt_family, serve
from drivers import serve_nemotron_h as hybrid_check
from drivers.serve_exaone_moe import _errors

try:
    from deepspeed_tpu.models import glm4_moe_lite as glm
except ImportError:
    raise SystemExit("benchmark: this program has no GLM-4.7-Flash family "
                     "(models/glm4_moe_lite.py); the cell cannot run on it")

# THE LIMITS (the configuration file's `check_limits` has the two readings
# each sits between: the program's over the seeds of my chip runs, PR 43, and
# this same verdict on the reference computed in a lower precision).
LIMITS = {
    "rms_error_share": 0.02,
    "max_error_share": 0.02,
    "expert_set_mismatch_share": 0.07,
    "decode_set_mismatch_share": 0.07,
    "latent_rms_error_share": 0.012,
}
# the long prompt, in tokens whatever the chunk: past 8k (and never more than
# 5/8 of the table, which a rehearsal's tiny one would otherwise not hold),
# its last chunk a part of one
LONG_TOKENS = 8352
LONG_TABLE_SHARE = 5 / 8

_built = {}


def model_config(cfg, max_seq_len):
    """The program's configuration for the file's keys (the published
    `config.json`'s, cut as the file says). Every width is the file's."""
    if cfg["model_type"] != "glm4_moe_lite":
        raise ValueError(f"model_type {cfg['model_type']!r} is not "
                         f"GLM-4.7-Flash's")
    if cfg["n_group"] != 1 or cfg["topk_group"] != 1 \
            or cfg["n_shared_experts"] != 1 or cfg["attention_bias"] \
            or cfg["hidden_act"] != "silu" or cfg["tie_word_embeddings"] \
            or cfg["partial_rotary_factor"] != 1 or cfg["rope_scaling"] \
            or cfg["num_key_value_heads"] != cfg["num_attention_heads"]:
        raise ValueError("this driver serves the sigmoid router without "
                         "groups, one shared expert, SiLU, no bias, an "
                         "untied head and a whole unscaled rotation")
    return glm.Glm4MoeLiteConfig(
        vocab_size=cfg["vocab_size"], n_layer=cfg["num_hidden_layers"],
        n_head=cfg["num_attention_heads"], d_model=cfg["hidden_size"],
        q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"],
        first_k_dense_replace=cfg["first_k_dense_replace"],
        d_ff=cfg["moe_intermediate_size"],
        d_ff_dense=cfg["intermediate_size"], max_seq_len=max_seq_len,
        rope_theta=float(cfg["rope_theta"]), norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=False,
        num_experts=cfg["published_n_routed_experts"],
        num_shared_experts=cfg["n_shared_experts"],
        experts_held=tuple(cfg["experts_held_range"]),
        top_k=cfg["num_experts_per_tok"],
        norm_topk_prob=cfg["norm_topk_prob"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        # the deployment's choice (configuration file)
        use_flash_attention=True, dtype=jnp.bfloat16)


def _build(cell, seed, device):
    cfg = cell["config_json"]
    knobs = dict(cfg["serving"])
    block = knobs.pop("kv_block_size")
    gcfg = model_config(cfg, max_seq_len=knobs["max_context"])
    mesh_mod.clear_mesh()
    mesh_mod.init_mesh(MeshConfig(data=1), devices=[device])
    t0 = time.perf_counter()
    init = jax.jit(glm.glm4_moe_lite_init_fn(
        gcfg, dtype=jnp.bfloat16, embedding_std=cfg["embedding_range"],
        router_std=cfg["router_range"]),
                   out_shardings=jax.sharding.SingleDeviceSharding(device))
    params = init(gpt_family.seed_key(seed))
    engine = deepspeed_tpu.init_inference(
        glm.make_glm4_moe_lite_decode_model(gcfg, params=params,
                                            name=cell["config"]),
        config={"dtype": "bfloat16", "kv_cache_dtype": "bfloat16",
                "greedy": True, "kv_block_size": block,
                "max_out_tokens": knobs["max_context"]})
    serving = engine.serving(**knobs)
    jax.block_until_ready((engine.params, serving.pool))
    _built["serving"] = serving
    return gcfg, engine, serving, time.perf_counter() - t0


def _mixed_ticks(spec, keep):
    """`serve_nemotron_h._mixed_ticks` for a pool of one kind: every tick in
    one scan on the carried pool, the body the served `mixed_step`'s with the
    logits of the rows `keep` and every row's experts kept; the tables go in
    bare and a row is live where the ticks give it a state row."""
    def run(params, pool, ticks):
        def body(carry, x):
            tok, pool = carry
            live = x["state"][:, 0] != 0
            logits, pool, _counts, sets = spec.mixed_paged_fn(
                params, x["chunk"], x["start"], x["last"], x["chunk_kv"],
                jnp.where(live, tok, 0), x["pos"], pool, x["kv"],
                routing=True)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            tok = jnp.where(live, nxt[1:], tok)
            tok = tok.at[x["slot"]].set(
                jnp.where(x["final"], nxt[0], tok[x["slot"]]))
            return (tok, pool), (logits[keep], nxt, sets[:, 0])
        tok = jnp.zeros((ticks["pos"].shape[1],), jnp.int32)
        (_, pool), out = jax.lax.scan(body, (tok, pool), ticks)
        return out, pool
    return jax.jit(run, donate_argnums=(1,))


def program_sequences(spec, params, serving, vocab, seed):
    """`serve_nemotron_h.schedule`'s sequences through the SERVED spec's
    mixed program on the SERVED pool (borrowed: donated to the scan and
    handed back) -> (rows: [(the sequence's tokens, its prompt's length, the
    experts chosen [layers, T, k], [(position, the program's logits), ...],
    the pool's entries of its positions [layers, T, stored width] float32)]
    of the compared sequences, the ticks)."""
    chunk, slots = serving.chunk, serving.max_slots
    long_was = hybrid_check.LONG_PROMPT
    hybrid_check.LONG_PROMPT = min(
        LONG_TOKENS, LONG_TABLE_SHARE * serving.max_context) / chunk
    try:
        sequences, ticks = hybrid_check.schedule(
            slots, chunk, serving.block_size, serving.window, vocab,
            np.random.default_rng([seed, 0xC4EC]))
    finally:
        hybrid_check.LONG_PROMPT = long_was
    compared = [s for s in sequences if s["compared"]]
    leaf = serving.cache_kinds[0].leaves[0]
    if max(s["blocks"][-1] for s in sequences) >= serving.pool[leaf].shape[1]:
        raise ValueError("the check's sequences do not fit the served pool")
    keep = np.asarray([0] + [1 + s["slot"] for s in compared])
    (logits, nxt, sets), pool = _mixed_ticks(spec, keep)(
        params, serving.pool, hybrid_check._tick_inputs(
            sequences, ticks, slots, chunk, serving.nb))
    # a block at a time, by a static index: a gather of a few blocks out of
    # a 10 GB leaf is compiled into slices of the WHOLE leaf (PERF.md
    # section 6, PR 25)
    held = [np.stack([np.asarray(pool[leaf][:, int(b), 0])
                      for b in s["blocks"]], axis=1).astype(np.float32)
            for s in compared]
    serving.pool = pool
    logits, nxt = np.asarray(logits, np.float32), np.asarray(nxt)
    sets = np.asarray(sets)             # [ticks, layers, chunk + slots, k]
    out = []
    for i, seq in enumerate(compared):
        slot, prompt = seq["slot"], seq["prompt"]
        mine = 1 + slot
        decoded = range(seq["live"], seq["end"])
        # a decode tick's input: the last chunk's argmax, then its own
        fed = [nxt[t - 1, 0 if t == seq["live"] else mine] for t in decoded]
        chose = [sets[seq["first"] + n, :, :len(prompt) - n * chunk][:, :chunk]
                 for n in range(seq["live"] - seq["first"])] \
            + [sets[t, :, chunk + slot][:, None] for t in decoded]
        ours = [(min((n + 1) * chunk, len(prompt)) - 1,
                 logits[seq["first"] + n, 0])
                for n in range(seq["live"] - seq["first"])] \
            + [(len(prompt) + t - seq["live"], logits[t, 1 + i])
               for t in decoded]
        tokens = np.concatenate([prompt, np.asarray(fed, np.int32)])
        entries = held[i].reshape(held[i].shape[0], -1, held[i].shape[-1])
        out.append((tokens, len(prompt), np.concatenate(chose, axis=1), ours,
                    entries[:, :len(tokens)]))
    return out, ticks


def verdict(got, want, differs, decode_from, entries, want_entries):
    """The five limits on one set of sequences -> (ok, note). `got`, `want`:
    logits [positions compared, vocab]; `differs`: a list, a sequence, of
    bool [sparse layers, T]; `decode_from`: a sequence, where its decode
    tokens begin; `entries`, `want_entries`: a list, a sequence, of the
    pool's and the reference's `[c | k_r]` [layers, T, r + d_r]."""
    rms, worst, scale, same = _errors(got, want)
    prefill = np.concatenate([d[:, :t].ravel()
                              for d, t in zip(differs, decode_from)])
    decode = np.concatenate([d[:, t:].ravel()
                             for d, t in zip(differs, decode_from)])
    err2 = sum(float(np.square(e - w).sum())
               for e, w in zip(entries, want_entries))
    ref2 = sum(float(np.square(w).sum()) for w in want_entries)
    note = {
        "rms_error_share": rms, "max_error_share": worst,
        "expert_set_mismatch_share": float(prefill.mean()),
        "decode_set_mismatch_share": float(decode.mean()),
        "latent_rms_error_share": float(np.sqrt(err2 / ref2)),
        "latent_rms_error_share_by_sequence": [
            float(np.sqrt(np.square(e - w).sum() / np.square(w).sum()))
            for e, w in zip(entries, want_entries)],
        "latent_entries_compared": int(sum(w.shape[0] * w.shape[1]
                                           for w in want_entries)),
        "max_abs_logit": scale, "argmax_equal": f"{same}/{len(got)}",
        "positions_compared": len(got), "routing": "held equal",
        "expert_set_pairs": int(prefill.size),
        "decode_set_pairs": int(decode.size),
        "set_mismatch_share_by_row": [float(d.mean()) for d in differs],
        "limits": LIMITS}
    ok = bool(np.isfinite(worst)
              and all(note[k] <= limit for k, limit in LIMITS.items()))
    return ok, note


def check(ref, arch, params, sequences):
    """`verdict` of the program's `sequences` (`program_sequences`' rows)
    against the reference `ref` at `arch`."""
    got, want, differs, entries, want_entries = [], [], [], [], []
    for seq, _, chose, ours, held in sequences:
        logits, sets, latents = ref.forward(
            params, jnp.asarray(seq, jnp.int32), arch, forced=chose,
            head_rows=[t for t, _ in ours])
        got += [out for _, out in ours]
        want += list(np.asarray(logits, np.float32))
        differs.append((chose != np.asarray(sets)).any(-1))
        want_entries.append(np.asarray(latents, np.float32))
        # the stored width's tail is zeros the reference does not have
        entries.append(held[..., :want_entries[-1].shape[-1]])
    return verdict(np.stack(got), np.stack(want), differs,
                   [s[1] for s in sequences], entries, want_entries)


def _check_logits(cell, engine, serving, gcfg, seed):
    ref = harness.load_module("references", cell["config_json"]["reference"])
    sequences, ticks = program_sequences(
        engine.model_spec, engine.params, serving, gcfg.vocab_size, seed)
    ok, note = check(ref, ref.arch_from_config(cell["config_json"]),
                     engine.params, sequences)
    note["mixed_calls_checked"] = ticks
    note["prompts_checked"] = [s[1] for s in sequences]
    note["decode_tokens_checked"] = [len(s[0]) - s[1] for s in sequences]
    return ok, note


def run(cell, seconds, seed, devices, profiler, compiles, t_process):
    # `serve.run` finds its set-up, its warm-up and its check as module
    # globals
    serve._build, serve._check_logits = _build, _check_logits
    serve._warm = hybrid_check._warm
    result = serve.run(cell, seconds, seed, devices, profiler, compiles,
                       t_process)
    stats = _built["serving"].stats()
    for key in ("kv_pool_writer", "attention_program", "step_counters",
                "kv_pool_kinds"):
        result["notes"][key] = stats[key]
    result["notes"]["decode_steps_per_sync"] = _built["serving"].window
    memory = devices[0].memory_stats() or {}
    result["notes"]["memory_stats"] = {
        k: int(memory[k]) for k in ("bytes_in_use", "peak_bytes_in_use",
                                    "bytes_limit") if k in memory}
    return result
