"""Driver for the Keye-VL-2.0 family (`models/keye_vl2.py`, `model_type:
KeyeVL2`: a learned sparse-attention indexer in every layer) served through
`init_inference(...).serving(...)`: the SAME loop, recorder, window and
estimators as `drivers/serve.py` — that module's `run` is called as it is —
and the warm-up of `drivers/serve_nemotron_h.py` (all three step programs in
set-up), imported and not restated. This file has the family's set-up
(`model_config`, `_build`), its check and its LIMITS.

The check, in short: what the SERVED spec's three paged programs produce on
the SERVED pool (borrowed and handed back) for two sequences — a SHORT one
(a few chunks, past `topk`) prefilled by `prefill_paged_fn` calls and then
decoding; a LONG one (over 16k tokens, its last chunk a part of one) whose
every chunk RIDES a decode token of the short one (`mixed_paged_fn`, the
served `mixed_step`'s body); then both decoding (`decode_paged_fn`) — against
`references/keye_vl2.py`'s full forward, float32, with the routing held
equal. Three comparisons (`LIMITS`):

- the INDEX SCORES `I[t, :]` of probed rows (every chunk's last real row and
  every decode token, every layer) against the reference's;
- the SELECTED SETS of the same rows: the share of the program's `topk`
  positions the reference did not select, and — exactly zero — how many of
  the differing positions have a reference score further from the
  reference's k-th than twice the row's largest score error (a k-th order
  statistic moves by at most the largest error, so a selection that follows
  the program's own scores can only differ inside that band);
- the LOGITS at every chunk's end and every decode token.
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
    from deepspeed_tpu.models import keye_vl2 as keye
except ImportError:
    raise SystemExit("benchmark: this program has no Keye-VL-2.0 family "
                     "(models/keye_vl2.py); the cell cannot run on it")

# THE LIMITS (the configuration file's `check_limits` has the two readings
# each sits between: the program's over the seeds of my chip runs, PR 60, and
# this same verdict on the reference computed in a lower precision).
LIMITS = {
    "rms_error_share": 0.02,
    "max_error_share": 0.025,
    "expert_set_mismatch_share": 0.10,
    "index_score_error_share": 0.025,
    "selected_mismatch_share": 0.012,
    "selected_outside_band": 0,
}
# the two sequences, in tokens whatever the chunk (and never more than 5/16
# and 1/16 of the table, which a rehearsal's tiny one would otherwise not
# hold): the long one past 16k, its last chunk a part of one
LONG_TOKENS, LONG_TABLE_SHARE = 16900, 5 / 16
SHORT_TOKENS, SHORT_TABLE_SHARE = 3000, 1 / 16
DECODE_TOKENS = 6       # calls of the decode program after the last chunk

_built = {}


def model_config(cfg, max_seq_len):
    """The program's configuration for the file's keys (the published
    `config.json`'s, cut as the file says). Every width is the file's."""
    if cfg["model_type"] != "KeyeVL2":
        raise ValueError(f"model_type {cfg['model_type']!r} is not "
                         f"Keye-VL-2.0's")
    sa = cfg["sa_config"]
    if cfg["attention_bias"] or cfg["hidden_act"] != "silu" \
            or cfg["tie_word_embeddings"] or cfg["use_sliding_window"] \
            or cfg["decoder_sparse_step"] != 1 or cfg["mlp_only_layers"] \
            or sa["indexer_num_kv_heads"] != 1 \
            or cfg["rope_scaling"]["rope_type"] != "default":
        raise ValueError("this driver serves every layer routed, SiLU "
                         "experts, no bias, an untied head, no sliding "
                         "window, ONE index key head and the default rotary")
    return keye.KeyeVL2Config(
        vocab_size=cfg["vocab_size"], n_layer=cfg["num_hidden_layers"],
        n_head=cfg["num_attention_heads"],
        n_kv_head=cfg["num_key_value_heads"], d_model=cfg["hidden_size"],
        attn_head_dim=cfg["head_dim"], d_ff=cfg["moe_intermediate_size"],
        max_seq_len=max_seq_len, rope_theta=float(cfg["rope_theta"]),
        norm_eps=cfg["rms_norm_eps"], tie_embeddings=False,
        num_experts=cfg["published_num_experts"],
        experts_held=tuple(cfg["experts_held_range"]),
        top_k=cfg["num_experts_per_tok"],
        norm_topk_prob=cfg["norm_topk_prob"],
        index_n_head=sa["indexer_num_heads"],
        index_head_dim=sa["indexer_head_dim"], index_topk=sa["topk"],
        # the deployment's choice (configuration file)
        use_flash_attention=True, dtype=jnp.bfloat16)


def _build(cell, seed, device):
    cfg = cell["config_json"]
    knobs = dict(cfg["serving"])
    block = knobs.pop("kv_block_size")
    kcfg = model_config(cfg, max_seq_len=knobs["max_context"])
    mesh_mod.clear_mesh()
    mesh_mod.init_mesh(MeshConfig(data=1), devices=[device])
    t0 = time.perf_counter()
    init = jax.jit(keye.keye_vl2_init_fn(
        kcfg, dtype=jnp.bfloat16, embedding_std=cfg["embedding_range"],
        router_std=cfg["router_range"]),
                   out_shardings=jax.sharding.SingleDeviceSharding(device))
    params = init(gpt_family.seed_key(seed))
    engine = deepspeed_tpu.init_inference(
        keye.make_keye_vl2_decode_model(kcfg, params=params,
                                        name=cell["config"]),
        config={"dtype": "bfloat16", "kv_cache_dtype": "bfloat16",
                "greedy": True, "kv_block_size": block,
                "max_out_tokens": knobs["max_context"]})
    serving = engine.serving(**knobs)
    jax.block_until_ready((engine.params, serving.pool))
    _built["serving"] = serving
    return kcfg, engine, serving, time.perf_counter() - t0


def _lengths(serving):
    """(long, short) prompt lengths: the stated ones, or what a tiny table
    holds of them; the long one never a whole number of chunks."""
    room = serving.max_context - DECODE_TOKENS - 2 * serving.chunk
    long = int(min(LONG_TOKENS, LONG_TABLE_SHARE * serving.max_context, room))
    short = int(min(SHORT_TOKENS, SHORT_TABLE_SHARE * serving.max_context))
    if long % serving.chunk == 0:
        long -= serving.chunk // 4
    return long, max(short, 2)


def program_sequences(spec, params, serving, vocab, seed):
    """The two sequences through the SERVED spec's programs on the SERVED
    pool -> [per sequence: (tokens, the prompt's length, the experts chosen
    [layers, T, k], [(position, logits, index scores [layers, nb * block],
    selection) a compared row])], the calls made a program."""
    chunk, slots, block, nb = (serving.chunk, serving.max_slots,
                               serving.block_size, serving.nb)
    rng = np.random.default_rng([seed, 0xC4EC])
    long_n, short_n = _lengths(serving)
    prompts = [rng.integers(0, vocab, (n,), np.int32)
               for n in (long_n, short_n)]
    rows = (slots - 1, 0)                       # long, short: their slots
    tables = np.zeros((slots, nb), np.int32)    # 0 is the trash block
    free = iter(range(1, serving.pool["k"].shape[1]))
    for row, prompt in zip(rows, prompts):
        need = -(-(len(prompt) + 2 * chunk + DECODE_TOKENS) // block)
        tables[row, :need] = [next(free) for _ in range(need)]
    prefill = jax.jit(lambda p, t, s, l, pool, bt: spec.prefill_paged_fn(
        p, t, s, l, pool, bt, routing=True, probe=l[0]), donate_argnums=(4,))
    mixed = jax.jit(
        lambda p, ct, s, l, cbt, tok, pos, pool, bt: spec.mixed_paged_fn(
            p, ct, s, l, cbt, tok, pos, pool, bt, routing=True, probe=l[0]),
        donate_argnums=(7,))
    decode = jax.jit(lambda p, tok, pos, pool, bt: spec.decode_paged_fn(
        p, tok, pos, pool, bt, routing=True, probe=jnp.int32(0)),
        donate_argnums=(3,))
    pool = serving.pool
    seqs = [dict(tokens=list(p), prompt=len(p), sets=[], rows=[])
            for p in prompts]
    calls = {"prefill": 0, "mixed": 0, "decode": 0}

    def chunk_args(prompt, start):
        seg = prompt[start:start + chunk]
        toks = np.zeros((1, chunk), np.int32)
        toks[0, :len(seg)] = seg
        return toks, np.asarray([start], np.int32), \
            np.asarray([len(seg) - 1], np.int32), len(seg)

    def keep(seq, position, logits, sets, scores, chosen):
        # what the reference is asked at `position`, as numpy
        seq["rows"].append((position, np.asarray(logits, np.float32),
                            np.asarray(scores, np.float32),
                            np.asarray(chosen)))
        seq["sets"].append(np.asarray(sets))

    def decode_rows(live):
        tok = np.zeros((slots,), np.int32)
        pos = np.zeros((slots,), np.int32)
        for i in live:
            tok[rows[i]] = seqs[i]["tokens"][-1]
            pos[rows[i]] = len(seqs[i]["tokens"]) - 1
        masked = np.where(np.isin(np.arange(slots), [rows[i] for i in live])
                          [:, None], tables, 0)
        return tok, pos, masked

    # the short prompt: chunks of their own, then it decodes
    long_seq, short_seq = seqs
    for start in range(0, short_n, chunk):
        toks, s, last, n = chunk_args(prompts[1], start)
        logits, pool, _counts, sets, (scores, chosen) = prefill(
            params, toks, s, last, pool, tables[rows[1]][None])
        calls["prefill"] += 1
        keep(short_seq, start + n - 1, logits[0], sets[:, 0, :n],
             scores[:, 0], chosen[:, 0])
    short_seq["tokens"].append(int(np.asarray(logits[0]).argmax()))
    # the long prompt: every chunk rides a decode token of the short one
    for start in range(0, long_n, chunk):
        toks, s, last, n = chunk_args(prompts[0], start)
        tok, pos, live_tables = decode_rows([1])
        logits, pool, _counts, sets, (scores, chosen) = mixed(
            params, toks, s, last, tables[rows[0]][None], tok, pos, pool,
            live_tables)
        calls["mixed"] += 1
        keep(long_seq, start + n - 1, logits[0], sets[:, 0, :n],
             scores[:, 0], chosen[:, 0])
        at = 1 + rows[1]
        keep(short_seq, int(pos[rows[1]]), logits[at],
             sets[:, 0, chunk + rows[1]][:, None], scores[:, at],
             chosen[:, at])
        short_seq["tokens"].append(int(np.asarray(logits[at]).argmax()))
    long_seq["tokens"].append(int(np.asarray(logits[0]).argmax()))
    # both decode
    for _ in range(DECODE_TOKENS):
        tok, pos, live_tables = decode_rows([0, 1])
        logits, pool, _counts, sets, (scores, chosen) = decode(
            params, tok, pos, pool, live_tables)
        calls["decode"] += 1
        for i, seq in enumerate(seqs):
            keep(seq, int(pos[rows[i]]), logits[rows[i]],
                 sets[:, rows[i]], scores[:, rows[i]], chosen[:, rows[i]])
            seq["tokens"].append(int(np.asarray(logits[rows[i]]).argmax()))
    serving.pool = pool
    out = []
    for seq in seqs:
        # the last token fed nothing: the reference sees what was attended
        tokens = np.asarray(seq["tokens"][:-1], np.int32)
        out.append((tokens, seq["prompt"],
                    np.concatenate(seq["sets"], axis=1), seq["rows"]))
    return out, calls


def _selection_errors(position, scores, chosen, want_scores, want_chosen,
                      topk):
    """One probed row of one layer -> (squared score error, squared
    reference score, positions the program selected and the reference did
    not, of how many, of them outside the band)."""
    n = position + 1
    got, want = scores[:n], np.asarray(want_scores, np.float32)[:n]
    mine, theirs = chosen[:n].astype(bool), np.asarray(want_chosen)[:n]
    err = got - want
    differ = mine & ~theirs
    outside = 0
    if n > topk and differ.any():
        kth = np.sort(want[theirs])[0]          # the reference's k-th score
        outside = int((np.abs(want[differ] - kth)
                       > 2 * np.abs(err).max()).sum())
    return (float(np.square(err).sum()), float(np.square(want).sum()),
            int(differ.sum()), int(min(n, topk)), outside,
            int(mine.sum() != theirs.sum()))


def verdict(got, want, differs, selection, topk):
    """The limits on one set of sequences -> (ok, note). `got`, `want`:
    logits [positions compared, vocab]; `differs`: a list, a sequence, of
    bool [layers, T] (the chosen experts differ from the reference's);
    `selection`: `_selection_errors` of every probed (row, layer)."""
    rms, worst, scale, same = _errors(got, want)
    sel = np.asarray(selection, np.float64)
    active = sel[:, 3] >= topk          # rows whose selection is not all
    note = {
        "rms_error_share": rms, "max_error_share": worst,
        "expert_set_mismatch_share": float(
            np.concatenate([d.ravel() for d in differs]).mean()),
        "index_score_error_share": float(np.sqrt(sel[:, 0].sum()
                                                 / sel[:, 1].sum())),
        "selected_mismatch_share": float(
            sel[active, 2].sum() / max(sel[active, 3].sum(), 1)),
        "selected_outside_band": int(sel[:, 4].sum() + sel[:, 5].sum()),
        "selected_rows_active": int(active.sum()),
        "selected_rows_compared": int(len(sel)),
        "max_abs_logit": scale, "argmax_equal": f"{same}/{len(got)}",
        "positions_compared": len(got), "routing": "held equal",
        "limits": LIMITS}
    ok = bool(np.isfinite(worst)
              and all(note[k] <= limit for k, limit in LIMITS.items()))
    return ok, note


def check(ref, arch, params, sequences):
    """`verdict` of the program's `sequences` (`program_sequences`' rows)
    against the reference `ref` at `arch`."""
    got, want, differs, selection = [], [], [], []
    for tokens, _, chose, rows in sequences:
        positions = [r[0] for r in rows]
        logits, sets, probes = ref.forward(
            params, jnp.asarray(tokens, jnp.int32), arch, forced=chose,
            head_rows=positions, probe_rows=tuple(positions))
        got += [r[1] for r in rows]
        want += list(np.asarray(logits, np.float32))
        differs.append((chose != np.asarray(sets)).any(-1))
        for position, _, scores, chosen in rows:
            for layer, probed in enumerate(probes):
                selection.append(_selection_errors(
                    position, scores[layer], chosen[layer],
                    *probed[position], arch.topk))
    return verdict(np.stack(got), np.stack(want), differs, selection,
                   arch.topk)


def _check_logits(cell, engine, serving, kcfg, seed):
    ref = harness.load_module("references", cell["config_json"]["reference"])
    sequences, calls = program_sequences(
        engine.model_spec, engine.params, serving, kcfg.vocab_size, seed)
    ok, note = check(ref, ref.arch_from_config(cell["config_json"]),
                     engine.params, sequences)
    note["calls_checked"] = calls
    note["prompts_checked"] = [s[1] for s in sequences]
    note["tokens_checked"] = [len(s[0]) for s in sequences]
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
