"""Driver for the Qwen3-Next family (`models/qwen3_next.py`, `model_type:
qwen3_next`) served through `init_inference(...).serving(...)`: the SAME
loop, recorder, window and estimators as `drivers/serve.py` — that module's
`run` is called as it is — and the SAME check as
`drivers/serve_nemotron_h.py`, whose schedule, ticks, warm-up and comparison
are imported and not restated: the three hybrid families run one loop
(`models/hybrid.py`) on one state kind, so what that driver's docstring says
of its check holds here word for word, with Gated DeltaNet's matrix state a
value head in the place of Mamba-2's. This file has the family's set-up
(`model_config`, `_build`), its reference and its LIMITS.

The check, in short: every call is one `mixed_paged_fn` — one prefill chunk
riding a decode token of every live slot, the body of the served
`mixed_step` — on the SERVED pool (896 blocks, 193 state rows), up to 191 of
the 192 slots live beside the chunk: a prompt of seven chunks through the
carried state (its last chunk mostly padding), one of two chunks, parts of
one chunk, slots handed on to a second request. LOGITS at every chunk's end
and every decode token against `references/qwen3_next.py`'s full forward
(the delta rule a position at a time) with the routing held equal (the
program's ten-expert sets given to the reference; what it would have chosen
compared on its own), and the nine Gated DeltaNet mixers' recurrent state
after the last call, over all heads and over the slow heads
(`serve_nemotron_h.slow_heads`: `softplus(dt_bias) exp(A_log)`, the decay a
step at a zero input, is this family's `-g` too).
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

try:
    from deepspeed_tpu.models import qwen3_next
except ImportError:
    raise SystemExit("benchmark: this program has no Qwen3-Next family "
                     "(models/qwen3_next.py); the cell cannot run on it")

# THE LIMITS: `serve_nemotron_h.verdict`'s six readings under this
# configuration's own limits (the configuration file's `check_limits` has
# the readings each sits between: the program's over the seeds of my chip
# runs, PR 47, and the reference's own verdict computed in a lower
# precision).
LIMITS = {
    "rms_error_share": 0.025,
    "max_error_share": 0.025,
    "expert_set_mismatch_share": 0.2,
    "decode_set_mismatch_share": 0.2,
    "state_rms_error_share": 0.0117,
    "state_slow_head_error_share": 0.03,
}
# the note's key of each reading's limit, as `serve_nemotron_h.verdict` names
# them (the two logit limits are its `tolerances` pair)
_LIMIT_KEYS = {
    "expert_set_mismatch_share": "expert_set_mismatch_limit",
    "decode_set_mismatch_share": "decode_set_mismatch_limit",
    "state_rms_error_share": "state_rms_limit",
    "state_slow_head_error_share": "state_slow_head_limit",
}

_built = {}


def model_config(cfg, max_seq_len):
    """The program's configuration for the file's keys (the published
    `config.json`'s, cut as the file says). Every width is the file's."""
    if cfg["model_type"] != "qwen3_next":
        raise ValueError(f"model_type {cfg['model_type']!r} is not "
                         f"Qwen3-Next")
    if cfg["hidden_act"] != "silu" or cfg["tie_word_embeddings"] \
            or not cfg["norm_topk_prob"] or cfg["mlp_only_layers"] \
            or cfg["decoder_sparse_step"] != 1 or cfg["rope_scaling"] \
            or cfg["use_sliding_window"]:
        raise ValueError("this driver serves SiLU, an untied head, "
                         "renormalised top-k weights, experts in every "
                         "layer, plain rotary and no window")
    return qwen3_next.Qwen3NextConfig(
        vocab_size=cfg["vocab_size"],
        pattern=tuple(qwen3_next.BLOCKS[t] for t in qwen3_next.layer_types(
            cfg["num_hidden_layers"], cfg["full_attention_interval"])),
        n_head=cfg["num_attention_heads"],
        n_kv_head=cfg["num_key_value_heads"], d_model=cfg["hidden_size"],
        attn_head_dim=cfg["head_dim"], max_seq_len=max_seq_len,
        norm_eps=cfg["rms_norm_eps"],
        rotary_pct=cfg["partial_rotary_factor"],
        rope_theta=float(cfg["rope_theta"]),
        gdn_key_heads=cfg["linear_num_key_heads"],
        gdn_value_heads=cfg["linear_num_value_heads"],
        gdn_key_dim=cfg["linear_key_head_dim"],
        gdn_value_dim=cfg["linear_value_head_dim"],
        conv_kernel=cfg["linear_conv_kernel_dim"],
        chunk_size=cfg["delta_rule_chunk_size"],
        time_step_min=cfg["time_step_min"],
        time_step_max=cfg["time_step_max"],
        time_step_floor=cfg["time_step_floor"],
        d_ff=cfg["moe_intermediate_size"],
        shared_d_ff=cfg["shared_expert_intermediate_size"],
        num_experts=cfg["published_num_experts"],
        experts_held=tuple(cfg["experts_held_range"]),
        top_k=cfg["num_experts_per_tok"],
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
    init = jax.jit(qwen3_next.qwen3_next_init_fn(
        gcfg, dtype=jnp.bfloat16, embedding_std=cfg["embedding_range"],
        router_std=cfg["router_range"]),
                   out_shardings=jax.sharding.SingleDeviceSharding(device))
    params = init(gpt_family.seed_key(seed))
    engine = deepspeed_tpu.init_inference(
        qwen3_next.make_qwen3_next_decode_model(gcfg, params=params,
                                                name=cell["config"]),
        config={"dtype": "bfloat16", "kv_cache_dtype": "bfloat16",
                "greedy": True, "kv_block_size": block,
                "max_out_tokens": knobs["max_context"]})
    serving = engine.serving(**knobs)
    jax.block_until_ready((engine.params, serving.pool))
    _built["serving"] = serving
    return gcfg, engine, serving, time.perf_counter() - t0


def judge(note):
    """`serve_nemotron_h.verdict`'s note under THIS configuration's limits
    -> (ok, the note with these limits in the places of that driver's)."""
    ok = bool(np.isfinite(note["max_error_share"])
              and all(note[k] <= limit for k, limit in LIMITS.items()))
    note["tolerances"] = [LIMITS["rms_error_share"],
                          LIMITS["max_error_share"]]
    for reading, key in _LIMIT_KEYS.items():
        note[key] = LIMITS[reading]
    return ok, note


def check(ref, arch, params, sequences):
    """`judge` of the program's `sequences` (`serve_nemotron_h.
    program_sequences`' rows) against the reference `ref` at `arch`."""
    def reference(seq, chose):
        states = []
        logits, sets = ref.forward(params, jnp.asarray(seq, jnp.int32), arch,
                                   forced=chose, states=states)
        return logits, sets, jnp.stack(states)

    _, note = hybrid_check.compare(
        sequences, reference,
        hybrid_check.slow_heads(ref.layer_trees(params, arch)))
    return judge(note)


def _check_logits(cell, engine, serving, gcfg, seed):
    ref = harness.load_module("references", cell["config_json"]["reference"])
    sequences, ticks = hybrid_check.program_sequences(
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
