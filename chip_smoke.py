#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process, no arguments. Drives the two normal entry points once, through
the public API, at the full width of gpt2-1.3b (24 layers x d_model 2048,
16 heads of 128; random weights from a seed):

  train   `deepspeed_tpu.initialize` -> ZeRO-3 / bf16 state / bf16 grad
          accumulator, mesh left at its default (`data: -1`), seq 1024 (the
          length at which the dispatch selects the flash kernel by itself),
          gas 2 -> a warm-up and 3 timed `train_batch` calls on one batch;
  serve   `deepspeed_tpu.init_inference` -> `engine.serving(...)` at an 8k
          context (the paged decode kernel's own crossover) -> 6 requests of
          200-3000 prompt tokens drained through the paged scheduler.

and checks what comes out by the repo's own means: the step-1 loss against
the plain dense-attention XLA forward of the same weights and batch on ONE
device (so a four-chip run is pinned to the one-chip answer); finite, falling
losses; ZeRO-3 state spread evenly over the devices; the flash and
paged-decode programs selected by dispatch and present in the compiled step as
Mosaic calls on per-shard operands; every request complete and in-vocabulary;
one compile per serving program; a drained pool; kernel-vs-gather decode
logits on one shared state.

Exit code 0 and a last stdout line `{"ok": true, "device": {...}}` only if
every phase held. No TPU -> exit 1 with one line and no result. Any phase
that raises or any assertion that fails -> the traceback and a non-zero
exit; nothing here turns a failure into a log line.
"""

import collections
import dataclasses
import gc
import importlib.metadata
import json
import re
import sys
import time

import jax
import jax.numpy as jnp
import jaxlib
import numpy as np

import deepspeed_tpu
from deepspeed_tpu.comm import mesh as mesh_mod
from deepspeed_tpu.config.core import MeshConfig
from deepspeed_tpu.inference.scheduler import Request
from deepspeed_tpu.models.gpt import (GPT2_CONFIGS, gpt_loss, init_gpt_params,
                                      make_gpt_decode_model, make_gpt_model)
from deepspeed_tpu.ops import attention_dispatch
from deepspeed_tpu.platform.device import ensure_compile_cache

MODEL = "gpt2-1.3b"
SEQ = 1024                    # gpt2's published context; >= FLASH_MIN_SEQ
GAS = 2
# sequences per optimizer step, largest first. One chip: micro-batch 4, 2, 1
# (the largest that fits); a four-chip host takes the SAME 8 sequences as
# micro-batch 1 per chip, which is what pins its step-1 loss to one chip's
GLOBAL_BATCHES = (8, 4, 2)
TIMED_STEPS = 3
# a device may hold this much over 1/n of the ZeRO-3 state (the leaves under
# stage3_param_persistence_threshold stay replicated: norms, biases)
EVEN_SHARE_SLACK = 1.1

MAX_CONTEXT = 8192            # >= DECODE_KERNEL_MIN_CTX: kernel chosen, not forced
PREFILL_CHUNK = 512
POOL_TOKENS = 16384
MAX_SLOTS = 4
# (prompt tokens, new tokens) per request
REQUESTS = ((200, 32), (640, 48), (1100, 40), (1800, 56), (2400, 64), (3000, 48))
LOGIT_TOLERANCE = 5e-2        # kernel-vs-gather decode logits, of max |logit|

MOSAIC = 'custom_call_target="tpu_custom_call"'


def say(msg):
    print(f"chip_smoke: {msg}", flush=True)


def _state_bytes_per_device(engine):
    """{device: bytes} of the ZeRO-3 params + optimizer state each device
    holds, and the bytes of one full copy."""
    leaves = jax.tree_util.tree_leaves((engine.state.params,
                                        engine.state.opt_state))
    per = collections.Counter()
    for leaf in leaves:
        for shard in leaf.addressable_shards:
            per[shard.device] += shard.data.nbytes
    return per, sum(leaf.nbytes for leaf in leaves)


def _reference_loss(engine, cfg, batch, micro):
    """The global batch's loss at the engine's CURRENT params through the
    plain path on one device: dense XLA attention (no kernel), no remat, no
    mesh. Evaluated a micro-batch at a time (equal sizes, no masked labels:
    the mean of means is the global mean)."""
    ref_cfg = dataclasses.replace(cfg, use_flash_attention=False, remat=False)
    params = engine.params
    one = jax.devices()[0]
    if len(jax.devices()) > 1:
        params = jax.device_put(params, one)       # gather the ZeRO-3 shards
    loss_fn = jax.jit(lambda p, b: gpt_loss(p, b, None, cfg=ref_cfg))
    n = batch["tokens"].shape[0]
    with mesh_mod.constraints_disabled():
        losses = [float(loss_fn(params, {k: jax.device_put(v[i:i + micro], one)
                                         for k, v in batch.items()}))
                  for i in range(0, n, micro)]
    return float(np.mean(losses))


def _check_train_hlo(text, micro, cfg, seq, on_tpu):
    """The compiled step must hold the flash kernel as Mosaic calls whose
    q/k/v operands are ONE device's shard — [micro * heads, T, hd] — with
    no all-gather producing an activation of that geometry."""
    calls = [line for line in text.splitlines() if MOSAIC in line]
    if not on_tpu:
        assert not calls
        return
    assert calls, "no Mosaic call in the compiled train step"
    qkv = rf"bf16\[(\d+),{seq},{cfg.head_dim}\]"
    leading = {int(m) for line in calls for m in re.findall(qkv, line)}
    say(f"train step: {len(calls)} Mosaic call sites; flash operand leading "
        f"dims {sorted(leading)} (one device's share = micro {micro} x "
        f"{cfg.n_head} heads = {micro * cfg.n_head})")
    assert leading == {micro * cfg.n_head}, (
        f"flash kernel operands are not the per-shard shapes: {leading}")
    # ZeRO-3 gathers parameters (rank <= 3, no dim of length T but the
    # [T, d_model] position table); a gathered float array of rank >= 3
    # with a T-long dim is an activation — q/k/v on their way to a kernel
    # that was not handed its shard
    gathered = []
    for line in text.splitlines():
        result = re.split(r" all-gather(?:-start)?\(", line)
        if len(result) == 2:
            shapes = [tuple(map(int, dims.split(","))) for dims in
                      re.findall(r"(?:bf16|f32)\[([\d,]+)\]", result[0])]
            if any(len(sh) >= 3 and seq in sh for sh in shapes):
                gathered.append(line.strip()[:200])
    assert not gathered, f"activations are all-gathered: {gathered}"


def train_phase(model_name, seq, global_batches, gas, on_tpu):
    cfg = GPT2_CONFIGS[model_name]
    cfg = dataclasses.replace(cfg, max_seq_len=max(cfg.max_seq_len, seq))
    n_dev = len(jax.devices())
    site = attention_dispatch.AttnSite(phase="train", q_len=seq, kv_len=seq)
    assert attention_dispatch.select(site) == "flash", \
        f"dispatch selected {attention_dispatch.select(site)!r} for train T={seq}"

    for global_batch in global_batches:
        if global_batch % (gas * n_dev):
            continue
        micro = global_batch // (gas * n_dev)
        mesh_mod.clear_mesh()
        t0 = time.perf_counter()
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=make_gpt_model(cfg=cfg, name=model_name, abstract=True),
            config={
                "train_batch_size": global_batch,
                "gradient_accumulation_steps": gas,
                "optimizer": {"type": "AdamW",
                              "params": {"lr": 1e-4, "weight_decay": 0.1}},
                "bf16": {"enabled": True, "master_weights": False},
                "data_types": {"grad_accum_dtype": "bf16"},
                "gradient_clipping": 1.0,
                "zero_optimization": {"stage": 3},
                "steps_per_print": 10**9,
            })
        init_s = time.perf_counter() - t0
        assert engine.micro_batch_size == micro and engine.spec.data == n_dev

        per_dev, total = _state_bytes_per_device(engine)
        share = {str(d): round(b / total, 4) for d, b in per_dev.items()}
        say(f"ZeRO-3 params+optimizer: {total / 2**30:.2f} GiB in all; share "
            f"held per device {share} (even = {1 / n_dev:.4f})")
        assert len(per_dev) == n_dev and \
            max(per_dev.values()) <= EVEN_SHARE_SLACK * total / n_dev, share

        rng = np.random.default_rng(0)
        tokens = rng.integers(0, cfg.vocab_size,
                              (global_batch, seq + 1)).astype(np.int32)
        # explicit labels keep the model's T == seq (a 128-multiple)
        batch = {"tokens": tokens[:, :-1], "labels": tokens[:, 1:]}

        ref_loss = _reference_loss(engine, cfg, batch, micro)

        too_big = None
        try:
            t0 = time.perf_counter()
            losses = [float(engine.train_batch(batch))]   # compiles; fenced
            first_s = time.perf_counter() - t0
        except jax.errors.JaxRuntimeError as err:
            # the ONE failure this script steps past: the issue's "largest
            # micro-batch of {4, 2, 1} that fits". Anything else propagates
            if "RESOURCE_EXHAUSTED" not in str(err) \
                    or global_batch == global_batches[-1]:
                raise
            too_big = str(err).splitlines()[0][:160]
        if too_big is None:
            break
        say(f"micro-batch {micro} does not fit ({too_big}); next size")
        del engine              # outside the handler: its traceback held it
        gc.collect()
    else:
        raise RuntimeError(f"no global batch of {global_batches} divides "
                           f"gas {gas} x {n_dev} devices")

    step_s = []
    for _ in range(TIMED_STEPS):
        t0 = time.perf_counter()
        losses.append(float(engine.train_batch(batch)))   # fenced by the fetch
        step_s.append(time.perf_counter() - t0)
    timed = losses[1:]
    say(f"{model_name} ZeRO-3 bf16 seq {seq}: dp={n_dev} micro={micro} "
        f"gas={gas} global_batch={global_batch}")
    say(f"step-1 loss {losses[0]:.4f}; one-device dense-attention reference "
        f"{ref_loss:.4f}; timed losses {[round(x, 4) for x in timed]}")
    assert all(np.isfinite(losses)), losses
    # bf16 compute: kernel-vs-dense and reduction-order deltas live in the
    # bf16 band (the multichip dry run holds its phases to the same 2e-2)
    np.testing.assert_allclose(losses[0], ref_loss, rtol=2e-2)
    assert all(x < losses[0] for x in timed) and timed[-1] < timed[0], \
        f"loss is not falling on a repeated batch: {losses}"

    _check_train_hlo(engine.lower_train_step(batch).compile().as_text(),
                     micro, cfg, seq, on_tpu)

    median = float(np.median(step_s))
    stats = jax.local_devices()[0].memory_stats() or {}
    say(f"initialize {init_s:.1f} s; first step {first_s:.1f} s of which "
        f"compile ~{max(first_s - median, 0.0):.1f} s; step seconds "
        f"{[round(s, 3) for s in step_s]} (median {median:.3f}); "
        f"peak_bytes_in_use {stats.get('peak_bytes_in_use')}")


def _decode_kernel_vs_gather(engine, cfg, model_name, block, num_blocks):
    """ONE decode step over the same paged state through both decode
    programs — the dispatch-selected Pallas kernel, and the same weights
    with the kernel forbidden (table gather + dense attend) — compared on
    LOGITS. (Greedy tokens of two free-running rollouts are no measure at
    this width: the first bf16 near-tie that flips sends the sequences
    apart for good — measured 39.6% positional agreement, 20 distinct
    tokens in 48, PR 21.) Rows sit at ragged depths up to the last slot of
    the table; the pool holds seeded unit-normal K/V."""
    gather_spec = make_gpt_decode_model(
        cfg=dataclasses.replace(cfg, use_flash_attention=False),
        name=model_name, params=engine.params)
    nb = cfg.max_seq_len // block
    pos = np.array([200, 1500, 4000, cfg.max_seq_len - 1], np.int32)
    tables = np.zeros((len(pos), nb), np.int32)  # 0 = the trash block
    free = iter(range(1, num_blocks))
    for row, p in enumerate(pos):
        for j in range(p // block + 1):
            tables[row, j] = next(free)
    shape = (cfg.n_layer, num_blocks, cfg.n_kv_head, block, cfg.head_dim)
    kk, kv = jax.random.split(jax.random.PRNGKey(2))
    pool = {"k": jax.random.normal(kk, shape, jnp.bfloat16),
            "v": jax.random.normal(kv, shape, jnp.bfloat16)}
    tok = np.random.default_rng(3).integers(
        0, cfg.vocab_size, (len(pos),)).astype(np.int32)
    args = (engine.params, jnp.asarray(tok), jnp.asarray(pos), pool,
            jnp.asarray(tables))
    kernel = np.asarray(jax.jit(engine.model_spec.decode_paged_fn)(*args)[0],
                        np.float32)
    gather = np.asarray(jax.jit(gather_spec.decode_paged_fn)(*args)[0],
                        np.float32)
    err = float(np.abs(kernel - gather).max())
    scale = float(np.abs(gather).max())
    same = int((kernel.argmax(-1) == gather.argmax(-1)).sum())
    say(f"decode step, kernel vs gather on the same state: max |dlogit| "
        f"{err:.4f} against max |logit| {scale:.3f}; argmax equal on "
        f"{same}/{len(pos)} rows")
    assert np.isfinite(kernel).all() and np.isfinite(gather).all()
    # 24 bf16 layers between two attention programs that round differently
    assert err <= LOGIT_TOLERANCE * scale, (err, scale)


def serve_phase(model_name, max_context, on_tpu):
    mesh_mod.clear_mesh()                       # the trainer's mesh
    n_dev = len(jax.devices())
    if n_dev > 1:
        # init_inference would build data=-1, replicate params and pool,
        # and run one replica's work on every chip (ROADMAP R7 owns that)
        say(f"serving on an explicit ONE-device mesh; {n_dev - 1} of "
            f"{n_dev} devices idle in this phase")
        mesh_mod.init_mesh(MeshConfig(data=1))

    cfg = dataclasses.replace(GPT2_CONFIGS[model_name], max_seq_len=max_context)
    config = {"dtype": "bfloat16", "kv_cache_dtype": "bfloat16", "greedy": True}
    t0 = time.perf_counter()
    # bf16 from the start: the spec keeps its params alive, and an fp32
    # tree beside the engine's bf16 copy would be 5 GiB of dead weight
    params = init_gpt_params(cfg, seed=0, dtype=jnp.bfloat16)
    engine = deepspeed_tpu.init_inference(
        make_gpt_decode_model(cfg=cfg, name=model_name, params=params),
        config=config)
    del params
    block = engine.config.kv_block_size
    knobs = dict(max_slots=MAX_SLOTS, max_context=max_context,
                 prefill_chunk=PREFILL_CHUNK,
                 num_kv_blocks=POOL_TOKENS // block + 1)
    serving = engine.serving(**knobs)
    init_s = time.perf_counter() - t0

    site = attention_dispatch.AttnSite(
        phase="paged_decode", q_len=1, kv_len=serving.nb * block,
        block_size=block, kv_dtype="bfloat16")
    assert attention_dispatch.select(site) == "paged_kernel", \
        f"dispatch selected {attention_dispatch.select(site)!r} for decode"
    # the decode program the scheduler jits, at the scheduler's shapes
    lowered = jax.jit(engine.model_spec.decode_paged_fn).lower(
        engine.params, jnp.zeros((MAX_SLOTS,), jnp.int32),
        jnp.zeros((MAX_SLOTS,), jnp.int32), serving.pool,
        jnp.asarray(serving.tables)).as_text()
    assert ("tpu_custom_call" in lowered) == on_tpu, \
        "decode step holds no Mosaic call" if on_tpu else "Mosaic off-TPU"

    rng = np.random.default_rng(1)
    requests = [Request(uid=i, tokens=rng.integers(0, cfg.vocab_size, (p,))
                        .astype(np.int32), max_new_tokens=n, stop_on_eos=False)
                for i, (p, n) in enumerate(REQUESTS)]
    t0 = time.perf_counter()
    done = serving.run(requests)
    run_s = time.perf_counter() - t0
    assert sorted(done) == list(range(len(requests))), sorted(done)
    for req in requests:
        out = done[req.uid]
        assert out.finish_reason == "length", (req.uid, out.finish_reason)
        assert len(out.tokens) == req.max_new_tokens, (req.uid, len(out.tokens))
        assert ((0 <= out.tokens) & (out.tokens < cfg.vocab_size)).all()
    # one compile a step program: the chunk's, the decode call's, and the
    # two as one call where a chunk rode a decode call
    programs = {"decode_step": 1, "prefill_step": 1}
    if serving.fused_chunks:
        programs["mixed_step"] = 1
    assert serving.compile_stats() == programs, serving.compile_stats()
    assert serving.allocator.num_free == serving.allocator.capacity, \
        (serving.allocator.num_free, serving.allocator.capacity)
    new_tokens = sum(n for _, n in REQUESTS)
    say(f"served {len(done)} requests ({sum(p for p, _ in REQUESTS)} prompt + "
        f"{new_tokens} new tokens) in {run_s:.1f} s, compiles included; "
        f"engine+pool build {init_s:.1f} s; compile_stats "
        f"{serving.compile_stats()}; pool drained "
        f"({serving.allocator.num_free}/{serving.allocator.capacity} free)")

    assert serving.close().ok
    del serving
    gc.collect()                                 # its pool
    _decode_kernel_vs_gather(engine, cfg, model_name, block,
                             knobs["num_kv_blocks"])
    stats = jax.local_devices()[0].memory_stats() or {}
    say(f"serve peak_bytes_in_use {stats.get('peak_bytes_in_use')}")


def main():
    cache_dir = ensure_compile_cache()
    devices = jax.devices()                      # first touch of the backend
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    say(f"platform={device['platform']} device_kind={device['kind']!r} "
        f"devices={device['count']} jax={jax.__version__} "
        f"jaxlib={jaxlib.__version__} "
        f"libtpu={importlib.metadata.version('libtpu')} "
        f"compile_cache={cache_dir}")
    if device["platform"] != "tpu":
        # JAX falls back to the CPU with a warning when libtpu cannot start
        say(f"FAIL: needs a TPU, JAX found platform {device['platform']!r}")
        return 1

    train_phase(MODEL, SEQ, GLOBAL_BATCHES, GAS, on_tpu=True)
    gc.collect()                                 # the trainer's buffers
    serve_phase(MODEL, MAX_CONTEXT, on_tpu=True)

    say(f"OK on {device['count']} x {device['kind']}")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
