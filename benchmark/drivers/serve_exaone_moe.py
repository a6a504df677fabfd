"""Driver for the K-EXAONE family (`models/exaone_moe.py`, `model_type:
exaone_moe`) served through `init_inference(...).serving(...)`: the SAME loop,
recorder, window and estimators as `drivers/serve.py` — that module's `run` is
called as it is — with this file's set-up (`_build`) and reference check
(`_check_logits`) in the two places where `serve.run` looks its own up by name,
as `drivers/serve_moe.py` does for OLMoE.

What the check covers that the others' do not:

- a pool of TWO KINDS: the full layer's blocks come from a small pool of the
  check's own, the window layers' rings from three rings of its own (rows 0,
  slots - 1 and slots // 2 of the served table shapes), so the served pool
  is not touched;
- a prompt of MORE THAN TWO CHUNKS, prefilled chunk by chunk: its ring blocks
  are reused (the ring holds window + chunk, the prompt is longer) and the
  windowed walks' lower bound is above block 0; then a part of a chunk, and
  a prompt of two chunks. EVERY chunk's last logits are compared, not the
  prompt's last alone;
- then one decode WINDOW (`decode_paged_fn` inside one `lax.scan`, the body
  of the served `decode_step`) on the three rows, every step's logits kept.
  The program feeds itself (its own argmax after the prompt, then inside the
  window), and the reference is given the program's tokens, so both see one
  sequence.

THE ROUTING IS HELD EQUAL, AND COMPARED ON ITS OWN. Top-8 of 128 sigmoid
scores is discontinuous: the program routes from bfloat16 activations, the
reference from float32 ones, and a ninth score within rounding of the eighth
swaps. On this chip's share a swap is LOUD (the weights are renormalised over
the 8 chosen, a token has about ONE of its 8 experts here, and the layer's
output is RMS-normalised after the experts: one expert more or fewer turns
the layer's contribution by tens of percent), so logits compared under free
routing measure the router's near-ties and not the arithmetic (my chip runs,
PR 32: one seed 0.8% rms, the next 5.2% rms / 15.4% largest). So the check
reads what the SERVED spec's own paged functions chose (`engine.model_spec`'s
`prefill_paged_fn` / `decode_paged_fn` called with `routing=True`: one more
result of the same functions) and gives the reference THOSE sets
(`forward(forced=)`): its weights from its own float32 scores, its sum over
the program's experts. The logits are then compared at EVERY position of all
three rows, and a fault anywhere upstream of a position shows there. What
the reference would have chosen on that same stream is compared with what
the program chose as a share of (token, sparse layer) pairs, over the
prompts' positions and over the decode window's apart.

Four limits decide `correct`, each with its two readings below.
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

try:
    from deepspeed_tpu.models import exaone_moe
except ImportError:
    raise SystemExit("benchmark: this program has no K-EXAONE family "
                     "(models/exaone_moe.py); the cell cannot run on it")

# THE LIMITS. Each sits between two readings at the committed weights
# (`embedding_range` 8, `router_range` 0.0025; my chip runs, PR 32, PERF.md
# section 6): the program's largest over its seeds, and this same verdict on
# the reference ITSELF computed through float8_e4m3 with a scale a row (the
# nearest precision below the configuration's bfloat16; it fails by its
# precision, nothing overflows), which comes out not correct by every one of
# them. For scale: the reference through bfloat16 passes all four (0.20% /
# 0.24%, 1.75%, 1.4%), and the program with a ring two blocks short (a fault
# in the window kind's cache, one row of three) fails three (6.4% / 10.9%,
# 26.0%, 16.7%).
#
# Logits, routing held equal, all 24 positions: root-mean-square error as a
# share of the reference's root-mean-square logit (program 0.430-0.435%,
# float8 18.2-19.0%), and the largest error as a share of the largest
# |logit| (program 0.42-0.56%, float8 17.1-20.1%): 3.6 times the program's.
LOGIT_RMS_LIMIT = 0.02
LOGIT_MAX_LIMIT = 0.02
# Share of (token, sparse layer) pairs whose SET of 8 experts differs from
# the one the reference would choose on the same stream: a ninth score within
# bfloat16's rounding of the eighth. Over the prompts' positions (8,512
# pairs: program 2.73-3.18%, float8 53.7-54.0%): 2.2 times the program's ...
EXPERT_SET_MISMATCH_LIMIT = 0.07
# ... and over the decode window's (3 rows x 6 steps x 4 layers = 72 pairs,
# where ONE pair is 1.4%: program 0-5 pairs over fourteen seeds, mean 2.4;
# float8 48.6-66.7%).
# 9 of 72: at the prompts' rate of 3% ten or more pairs come once in ~10,000
# runs, and a fault in the window's path moves most of the 72.
DECODE_SET_MISMATCH_LIMIT = 0.125
CHECK_PROMPTS = (21 / 8, 5 / 32, 11 / 8)   # of a prefill chunk: three
                                    # chunks (the third partial), a part of
                                    # one, and two
CHECK_POOL_BLOCKS = 8

_built = {}


def model_config(cfg, max_seq_len):
    """The program's configuration for the file's keys (the published
    `config.json`'s, cut as the file says). Every width is the file's."""
    if cfg["model_type"] != "exaone_moe":
        raise ValueError(f"model_type {cfg['model_type']!r} is not K-EXAONE")
    if cfg["scoring_func"] != "sigmoid" or cfg["n_group"] != 1 \
            or cfg["topk_group"] != 1 or cfg["hidden_act"] != "silu" \
            or cfg["rope_parameters"]["rope_type"] != "default":
        raise ValueError("this driver serves the sigmoid router without "
                         "groups, SiLU and plain rotary")
    return exaone_moe.ExaoneMoEConfig(
        vocab_size=cfg["vocab_size"], n_layer=cfg["num_hidden_layers"],
        n_head=cfg["num_attention_heads"],
        n_kv_head=cfg["num_key_value_heads"], d_model=cfg["hidden_size"],
        attn_head_dim=cfg["head_dim"], d_ff=cfg["moe_intermediate_size"],
        d_ff_dense=cfg["intermediate_size"], max_seq_len=max_seq_len,
        sliding_window=cfg["sliding_window"],
        rope_theta=float(cfg["rope_parameters"]["rope_theta"]),
        norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=cfg["tie_word_embeddings"],
        layer_types=tuple(cfg["layer_types"]),
        mlp_layer_types=tuple(cfg["mlp_layer_types"]),
        pattern_period=len(cfg["sliding_window_pattern"]),
        num_experts=cfg["published_num_experts"],
        experts_held=tuple(cfg["experts_held_range"]),
        num_shared_experts=cfg["num_shared_experts"],
        top_k=cfg["num_experts_per_tok"],
        norm_topk_prob=cfg["norm_topk_prob"], router_scoring="sigmoid",
        routed_scaling_factor=cfg["routed_scaling_factor"],
        window_block=cfg["window_block"],
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
    init = jax.jit(exaone_moe.exaone_moe_init_fn(
        gcfg, dtype=jnp.bfloat16, embedding_std=cfg["embedding_range"],
        router_std=cfg["router_range"]),
                   out_shardings=jax.sharding.SingleDeviceSharding(device))
    params = init(gpt_family.seed_key(seed))
    engine = deepspeed_tpu.init_inference(
        exaone_moe.make_exaone_moe_decode_model(gcfg, params=params,
                                                name=cell["config"]),
        config={"dtype": "bfloat16", "kv_cache_dtype": "bfloat16",
                "greedy": True, "kv_block_size": block,
                "max_out_tokens": knobs["max_context"]})
    serving = engine.serving(**knobs)
    jax.block_until_ready((engine.params, serving.pool))
    _built["serving"] = serving
    return gcfg, engine, serving, time.perf_counter() - t0


def _window_logits(spec, window):
    """`window` decode steps in one scan on the carried pool, the served
    decode window's body, keeping every step's logits and the experts it
    routed every slot to."""
    def run(params, tok, pos, pool, tables):
        def body(carry, _):
            tok, pos, pool = carry
            logits, pool, _counts, sets = spec.decode_paged_fn(
                params, tok, pos, pool, tables, routing=True)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return (nxt, pos + 1, pool), (logits, nxt, sets[:, :, 0])
        (_, _, pool), (logits, toks, sets) = jax.lax.scan(
            body, (tok, pos, pool), None, length=window)
        return logits, toks, sets, pool
    return jax.jit(run, donate_argnums=(3,))


def _errors(got, want):
    """(rms share, largest share, largest |logit|, argmax agreements) of
    logits [positions, vocab] against the reference's."""
    return (float(np.sqrt(np.square(got - want).sum()
                          / np.square(want).sum())),
            float(np.abs(got - want).max() / np.abs(want).max()),
            float(np.abs(want).max()),
            int((got.argmax(-1) == want.argmax(-1)).sum()))


def verdict(got, want, differs, decode_from):
    """The four limits on one set of sequences -> (ok, note). `got`, `want`:
    logits [positions compared, vocab]; `differs`: a list, a sequence, of
    bool [sparse layers, T] (the chosen set differs from the reference's);
    `decode_from`: a sequence, where its decode window begins."""
    rms, worst, scale, same = _errors(got, want)
    prefill = np.concatenate([d[:, :t].ravel()
                              for d, t in zip(differs, decode_from)])
    decode = np.concatenate([d[:, t:].ravel()
                             for d, t in zip(differs, decode_from)])
    mismatch, mismatch_decode = float(prefill.mean()), float(decode.mean())
    ok = bool(np.isfinite(worst) and rms <= LOGIT_RMS_LIMIT
              and worst <= LOGIT_MAX_LIMIT
              and mismatch <= EXPERT_SET_MISMATCH_LIMIT
              and mismatch_decode <= DECODE_SET_MISMATCH_LIMIT)
    return ok, {
        "rms_error_share": rms, "max_error_share": worst,
        "max_abs_logit": scale, "argmax_equal": f"{same}/{len(got)}",
        "tolerances": [LOGIT_RMS_LIMIT, LOGIT_MAX_LIMIT],
        "positions_compared": len(got), "routing": "held equal",
        "expert_set_mismatch_share": mismatch,
        "expert_set_mismatch_limit": EXPERT_SET_MISMATCH_LIMIT,
        "expert_set_pairs": int(prefill.size),
        "decode_set_mismatch_share": mismatch_decode,
        "decode_set_mismatch_limit": DECODE_SET_MISMATCH_LIMIT,
        "decode_set_pairs": int(decode.size),
        "set_mismatch_share_by_row": [float(d.mean()) for d in differs]}


def _check_logits(cell, engine, serving, gcfg, seed):
    """Chunked prefill, then one decode WINDOW through the two-kind pool,
    against the reference's full forward pass with the routing held equal:
    LOGITS on a seeded sample, at the served widths, table widths and slot
    count (so the dispatch picks the served attention programs), over a
    small pool and three rings of its own; and the expert sets of the same
    sequences."""
    ref = harness.load_module("references", cell["config_json"]["reference"])
    arch = ref.arch_from_config(cell["config_json"])
    spec = engine.model_spec        # the served one: its paged functions
                                    # give their routing as one more result
    block, chunk, nb = serving.block_size, serving.chunk, serving.nb
    slots, window, ring = serving.max_slots, serving.window, serving.ring
    rng = np.random.default_rng([seed, 0xC4EC])
    prompts = [rng.integers(0, gcfg.vocab_size, (max(3, int(f * chunk)),),
                            np.int32) for f in CHECK_PROMPTS]
    rows = (0, slots - 1, slots // 2)
    pool = spec.init_paged_pool(CHECK_POOL_BLOCKS, block, jnp.bfloat16,
                                window_blocks=1 + len(rows) * ring)
    prefill = jax.jit(
        lambda *args: spec.prefill_paged_fn(*args, routing=True),
        donate_argnums=(4,))
    tables = np.zeros((slots, nb), np.int32)        # 0 is the trash block
    # the rings of the check's rows; every other row at the trash block
    rings = np.zeros_like(serving.ring_tables)
    for i, row in enumerate(rows):
        rings[row] = 1 + i * ring + np.arange(rings.shape[1]) % ring
    free = iter(range(1, CHECK_POOL_BLOCKS))
    chunk_ends = {row: [] for row in rows}  # (position, the program's logits)
    routed = {row: [] for row in rows}      # the programs' sets, in position
                                            # order: [layers, positions, k]

    tok = np.zeros((slots,), np.int32)
    pos = np.zeros((slots,), np.int32)
    for row, prompt in zip(rows, prompts):
        need = -(-(len(prompt) + window + 1) // block)
        tables[row, :need] = [next(free) for _ in range(need)]
        for start in range(0, len(prompt), chunk):
            seg = prompt[start:start + chunk]
            toks = np.zeros((1, chunk), np.int32)
            toks[0, :len(seg)] = seg
            out, pool, _counts, sets = prefill(
                engine.params, toks, np.asarray([start], np.int32),
                np.asarray([len(seg) - 1], np.int32), pool,
                (tables[row][None], rings[row][None]))
            routed[row].append(np.asarray(sets)[:, 0, :len(seg)])
            chunk_ends[row].append((start + len(seg) - 1,
                                    np.asarray(out[0], np.float32)))
        # the first decode token: the program's own argmax after the prompt
        tok[row], pos[row] = int(chunk_ends[row][-1][1].argmax()), len(prompt)
    logits, emitted, sets, pool = _window_logits(spec, window)(
        engine.params, tok, pos, pool, (tables, rings))
    del pool
    logits, emitted = np.asarray(logits, np.float32), np.asarray(emitted)
    sets = np.asarray(sets)                 # [window, layers, slots, k]
    got, want, differs, where = [], [], [], []
    for row, prompt in zip(rows, prompts):
        seq = np.concatenate([prompt, [tok[row]], emitted[:-1, row]])
        chose = np.concatenate(
            routed[row] + [sets[:, :, row].swapaxes(0, 1)], axis=1)
        ref_logits, ref_sets = ref.forward(
            engine.params, jnp.asarray(seq, jnp.int32), arch, forced=chose)
        ref_logits = np.asarray(ref_logits, np.float32)
        ours = chunk_ends[row] + [(len(prompt) + step, logits[step, row])
                                  for step in range(window)]
        for t, out in ours:
            got.append(out)
            want.append(ref_logits[t])
            where.append((row, t))
        differs.append((chose != np.asarray(ref_sets)).any(-1))
    got, want = np.stack(got), np.stack(want)
    ok, note = verdict(got, want, differs, [len(p) for p in prompts])
    note["decode_window_checked"] = window
    note["prompts_checked"] = [len(p) for p in prompts]
    # every position's own largest error (a share of its largest |logit|)
    note["per_position"] = [
        [int(row), int(t), round(float(np.abs(g - w).max()
                                       / np.abs(w).max()), 4)]
        for (row, t), g, w in zip(where, got, want)]
    return ok, note


def run(cell, seconds, seed, devices, profiler, compiles, t_process):
    # `serve.run` finds its set-up and its check as module globals
    serve._build, serve._check_logits = _build, _check_logits
    result = serve.run(cell, seconds, seed, devices, profiler, compiles,
                       t_process)
    stats = _built["serving"].stats()
    result["notes"]["kv_pool_writer"] = stats["kv_pool_writer"]
    result["notes"]["attention_program"] = stats["attention_program"]
    result["notes"]["step_counters"] = stats["step_counters"]
    result["notes"]["kv_pool_kinds"] = stats["kv_pool_kinds"]
    result["notes"]["decode_steps_per_sync"] = _built["serving"].window
    memory = devices[0].memory_stats() or {}
    result["notes"]["memory_stats"] = {
        k: int(memory[k]) for k in ("bytes_in_use", "peak_bytes_in_use",
                                    "bytes_limit") if k in memory}
    return result
