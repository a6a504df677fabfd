"""Driver for the MiMo-V2-Flash family (`models/mimo_v2_flash.py`,
`model_type: mimo_v2_flash`: window layers with a sink and 8 KV heads beside
full layers with 4, keys 192 wide and values 128) served through
`init_inference(...).serving(...)`: the SAME loop, recorder, window and
estimators as `drivers/serve.py` — that module's `run` is called as it is —
and the SAME schedule, tick inputs and warm-up as
`drivers/serve_nemotron_h.py`, imported and not restated. This file has the
family's set-up (`model_config`, `_build`), the ticks' program on a pool of
two kinds (the tables a pair: the full kind's blocks, the window kind's
rings), its reference and its LIMITS.

The check, in short: every call is one `mixed_paged_fn` — one prefill chunk
riding a decode token of every live slot, the body of the served
`mixed_step` — on the SERVED pool and the SERVED rings (borrowed: donated to
the scan and handed back), up to 127 of the 128 slots live beside the chunk:
a prompt past 8k tokens chunk by chunk (the window layers' rings wrap dozens
of times, the full layers' walk at 4 KV heads reaches 17 blocks; its last
chunk mostly padding), one of two chunks, parts of one chunk, slots handed on
to a second request on the same ring. LOGITS at every chunk's end and every
decode token against `references/mimo_v2_flash.py`'s full forward (float32,
the sink as a concatenated column) with the routing held equal (the
program's eight-expert sets given to the reference; what it would have
chosen compared on its own).
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
    from deepspeed_tpu.models import mimo_v2_flash as mimo
except ImportError:
    raise SystemExit("benchmark: this program has no MiMo-V2-Flash family "
                     "(models/mimo_v2_flash.py); the cell cannot run on it")

# THE LIMITS (the configuration file's `check_limits` has the two readings
# each sits between: the program's over the seeds of my chip runs, PR 50, and
# this same verdict on the reference computed in a lower precision and with
# one of the family's mechanisms left out).
LIMITS = {
    "rms_error_share": 0.02,
    "max_error_share": 0.02,
    "expert_set_mismatch_share": 0.10,
    "decode_set_mismatch_share": 0.12,
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
    if cfg["model_type"] != "mimo_v2_flash":
        raise ValueError(f"model_type {cfg['model_type']!r} is not "
                         f"MiMo-V2-Flash's")
    if cfg["scoring_func"] != "sigmoid" or cfg["n_group"] != 1 \
            or cfg["topk_group"] != 1 or cfg["n_shared_experts"] \
            or cfg["attention_bias"] or cfg["hidden_act"] != "silu" \
            or cfg["tie_word_embeddings"] or not cfg["norm_topk_prob"] \
            or cfg["routed_scaling_factor"] not in (None, 1, 1.0) \
            or cfg["swa_head_dim"] != cfg["head_dim"] \
            or cfg["swa_v_head_dim"] != cfg["v_head_dim"] \
            or cfg["swa_num_attention_heads"] != cfg["num_attention_heads"] \
            or cfg["sliding_window_size"] != cfg["sliding_window"]:
        raise ValueError("this driver serves the sigmoid router without "
                         "groups, shared expert or scale, SiLU, no bias, an "
                         "untied head and one query head count and one pair "
                         "of widths for both kinds")
    return mimo.MiMoV2FlashConfig(
        vocab_size=cfg["vocab_size"], n_layer=cfg["num_hidden_layers"],
        n_head=cfg["num_attention_heads"], d_model=cfg["hidden_size"],
        attn_head_dim=cfg["head_dim"], attn_value_dim=cfg["v_head_dim"],
        attn_value_scale=cfg["attention_value_scale"],
        rotary_pct=cfg["partial_rotary_factor"],
        n_kv_head=cfg["num_key_value_heads"],
        swa_n_kv_head=cfg["swa_num_key_value_heads"],
        rope_theta=float(cfg["rope_theta"]),
        swa_rope_theta=float(cfg["swa_rope_theta"]),
        swa_sink=cfg["add_swa_attention_sink_bias"],
        full_sink=cfg["add_full_attention_sink_bias"],
        sliding_window=cfg["sliding_window"],
        window_block=cfg["window_block"],
        layer_types=mimo.layer_types(cfg["hybrid_layer_pattern"]),
        mlp_layer_types=mimo.mlp_layer_types(cfg["moe_layer_freq"]),
        pattern_period=cfg["pattern_period"],
        d_ff=cfg["moe_intermediate_size"],
        d_ff_dense=cfg["intermediate_size"], max_seq_len=max_seq_len,
        norm_eps=cfg["layernorm_epsilon"], tie_embeddings=False,
        num_experts=cfg["published_n_routed_experts"],
        experts_held=tuple(cfg["experts_held_range"]),
        top_k=cfg["num_experts_per_tok"], norm_topk_prob=True,
        router_scoring="sigmoid", routed_scaling_factor=1.0,
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
    init = jax.jit(mimo.mimo_v2_flash_init_fn(
        gcfg, dtype=jnp.bfloat16, embedding_std=cfg["embedding_range"],
        router_std=cfg["router_range"]),
                   out_shardings=jax.sharding.SingleDeviceSharding(device))
    params = init(gpt_family.seed_key(seed))
    engine = deepspeed_tpu.init_inference(
        mimo.make_mimo_v2_flash_decode_model(gcfg, params=params,
                                             name=cell["config"]),
        config={"dtype": "bfloat16", "kv_cache_dtype": "bfloat16",
                "greedy": True, "kv_block_size": block,
                "max_out_tokens": knobs["max_context"]})
    serving = engine.serving(**knobs)
    jax.block_until_ready((engine.params, serving.pool))
    _built["serving"] = serving
    return gcfg, engine, serving, time.perf_counter() - t0


def _mixed_ticks(spec, keep, rings):
    """`serve_nemotron_h._mixed_ticks` for a pool of two kinds: every tick in
    one scan on the carried pool, the body the served `mixed_step`'s with the
    logits of the rows `keep` and every row's experts kept. The tables go in
    as the pair (the full kind's, the window kind's rings `rings`
    [slots, ring table]): a row is live where the ticks give it a state row,
    and a row that is not has both its tables at the trash block, as
    `ServingEngine._tables_arg` leaves it."""
    def run(params, pool, ticks):
        def body(carry, x):
            tok, pool = carry
            live = x["state"][:, 0] != 0
            logits, pool, _counts, sets = spec.mixed_paged_fn(
                params, x["chunk"], x["start"], x["last"],
                (x["chunk_kv"], rings[x["slot"]][None]),
                jnp.where(live, tok, 0), x["pos"], pool,
                (x["kv"], jnp.where(live[:, None], rings, 0)),
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
    mixed program on the SERVED pool and rings (borrowed: donated to the
    scan and handed back) -> (rows: [(the sequence's tokens, its prompt's
    length, the experts chosen [layers, T, k], [(position, the program's
    logits), ...])] of the compared sequences, the ticks)."""
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
    full = serving.cache_kinds[0].leaves[0]
    if max(s["blocks"][-1] for s in sequences) >= serving.pool[full].shape[1]:
        raise ValueError("the check's sequences do not fit the served pool")
    keep = np.asarray([0] + [1 + s["slot"] for s in compared])
    (logits, nxt, sets), serving.pool = _mixed_ticks(
        spec, keep, jnp.asarray(serving.ring_tables))(
        params, serving.pool, hybrid_check._tick_inputs(
            sequences, ticks, slots, chunk, serving.nb))
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
        out.append((tokens, len(prompt), np.concatenate(chose, axis=1), ours))
    return out, ticks


def verdict(got, want, differs, decode_from):
    """The four limits on one set of sequences -> (ok, note). `got`, `want`:
    logits [positions compared, vocab]; `differs`: a list, a sequence, of
    bool [sparse layers, T]; `decode_from`: a sequence, where its decode
    tokens begin."""
    rms, worst, scale, same = _errors(got, want)
    prefill = np.concatenate([d[:, :t].ravel()
                              for d, t in zip(differs, decode_from)])
    decode = np.concatenate([d[:, t:].ravel()
                             for d, t in zip(differs, decode_from)])
    note = {
        "rms_error_share": rms, "max_error_share": worst,
        "expert_set_mismatch_share": float(prefill.mean()),
        "decode_set_mismatch_share": float(decode.mean()),
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
    got, want, differs = [], [], []
    for seq, _, chose, ours in sequences:
        logits, sets = ref.forward(
            params, jnp.asarray(seq, jnp.int32), arch, forced=chose,
            head_rows=[t for t, _ in ours])
        got += [out for _, out in ours]
        want += list(np.asarray(logits, np.float32))
        differs.append((chose != np.asarray(sets)).any(-1))
    return verdict(np.stack(got), np.stack(want), differs,
                   [s[1] for s in sequences])


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
