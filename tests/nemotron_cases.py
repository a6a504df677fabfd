"""What the Nemotron-H tests share (`test_nemotron_h.py`: the serving path;
`test_nemotron_h_layers.py`: the layers' pieces): a small configuration, its
parameters, a serving engine on it, and the float32 reference
(`benchmark/references/nemotron_h.py`, which imports nothing of the
program)."""

import functools
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np

import deepspeed_tpu
from deepspeed_tpu.comm import mesh as mesh_mod
from deepspeed_tpu.config.core import MeshConfig
from deepspeed_tpu.models import nemotron_h as nh
from deepspeed_tpu.ops.pallas import ssm


def _reference():
    path = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                        "benchmark", "references", "nemotron_h.py")
    spec = importlib.util.spec_from_file_location("ref_nemotron_h", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _reference()


def _cfg(dtype=jnp.float32, held=(0, 16), pattern="EMEM*", **over):
    kw = dict(vocab_size=128, pattern=pattern, n_head=4, n_kv_head=2,
              d_model=32, attn_head_dim=16, d_ff=24, shared_d_ff=40,
              moe_latent_size=16, max_seq_len=256, norm_eps=1e-5,
              num_experts=16, top_k=4, norm_topk_prob=True,
              routed_scaling_factor=5.0, experts_held=held,
              mamba_num_heads=8, mamba_head_dim=8, ssm_state_size=16,
              n_groups=2, conv_kernel=4, chunk_size=8, dtype=dtype,
              use_flash_attention=False)
    kw.update(over)
    return nh.NemotronHConfig(**kw)


def _arch(cfg, held="cfg", **over):
    return ref.Arch(
        pattern=cfg.pattern, runs=ref.pattern_runs(cfg.pattern),
        d_model=cfg.d_model, n_head=cfg.n_head, n_kv_head=cfg.n_kv_head,
        head_dim=cfg.head_dim, mamba_num_heads=cfg.mamba_num_heads,
        mamba_head_dim=cfg.mamba_head_dim, n_groups=cfg.n_groups,
        ssm_state_size=cfg.ssm_state_size, conv_kernel=cfg.conv_kernel,
        num_experts=cfg.num_experts,
        experts_held=cfg.experts_held if held == "cfg" else held,
        top_k=cfg.top_k, norm_topk_prob=cfg.norm_topk_prob,
        routed_scaling_factor=cfg.routed_scaling_factor,
        norm_eps=cfg.norm_eps, **over)


def _params(cfg, seed=0, dtype=jnp.float32):
    return nh.nemotron_h_init_fn(cfg, dtype=dtype)(jax.random.PRNGKey(seed))


def _serving(cfg, params, dtype="float32", one_device=False, **knobs):
    mesh_mod.clear_mesh()
    if one_device:      # else `init_inference` spans every device there is
        mesh_mod.init_mesh(MeshConfig(data=1), devices=jax.devices()[:1])
    spec = nh.make_nemotron_h_decode_model(cfg, params=params, name="tiny")
    engine = deepspeed_tpu.init_inference(
        spec, config={"dtype": dtype, "kv_cache_dtype": dtype, "greedy": True,
                      "kv_block_size": 16, "max_out_tokens": 256})
    knobs = {"max_slots": 3, "max_context": 256, "prefill_chunk": 16,
             "num_kv_blocks": 40, "decode_steps_per_sync": 3, **knobs}
    return engine, engine.serving(**knobs)


# ----------------------------------------------------------------------
# `dstpu_ssm_update` in the interpreter (both hybrid families' layer tests)
# ----------------------------------------------------------------------


def _update_inputs(seed, M, H, P, N, G, b, whole):
    """`whole`: small dyadic values — every product and every sum of the
    update is exact in float32, so the result has ONE value whatever order a
    backend rounds in (the CPU contracts `a * S + dtx * B` into either of two
    fused multiply-adds; the TPU into none, and there the kernel's state is
    the jitted reference's bit for bit on normal draws too: PERF.md, PR 45)."""
    rng = np.random.default_rng(seed)
    f32 = lambda v: np.asarray(v, np.float32)
    if not whole:
        return (f32(rng.normal(size=(M, H, P, N))), f32(rng.random((b, H))),
                f32(rng.normal(size=(b, H, P))),
                f32(rng.normal(size=(b, G, N))),
                f32(rng.normal(size=(b, G, N))))
    ints = lambda shape, top: f32(rng.integers(-top, top + 1, shape))
    return (ints((M, H, P, N), 8), f32(rng.integers(1, 5, (b, H))) / 4,
            ints((b, H, P), 4), ints((b, G, N), 4), ints((b, G, N), 4))


def assert_update_kernel_is_the_jnp_update(monkeypatch, H, P, N, G, rows,
                                           rows_a_step=None, M=9):
    """`ssm.ssm_update` through the interpreter against `ssm_update_reference`
    on `rows` of an `[M, H, P, N]` state: y and state to the float32
    tolerances on normal draws, BOTH bit-equal on exact values. Row 0 is the
    trash row: entries that name it may share it, and what they leave there
    (and their y) is not compared. `rows_a_step`: the rows a grid step owns
    (steered through the byte budget the kernel derives it from)."""
    if rows_a_step is not None:
        monkeypatch.setattr(ssm, "_BURST_BYTES", rows_a_step * H * P * N * 4)
        assert ssm._rows_per_step(len(rows), H * P * N * 4) == rows_a_step
    rows = np.asarray(rows, np.int32)
    live = np.flatnonzero(rows != 0)
    named = rows[live]
    assert len(set(named)) == len(live)
    others = np.setdiff1d(np.arange(1, M), named)
    reference = jax.jit(ssm.ssm_update_reference)
    update = jax.jit(functools.partial(ssm.ssm_update, interpret=True))
    for whole in (False, True):
        state, *small = _update_inputs(H + G, M, H, P, N, G, len(rows), whole)
        want_y, want_s = map(np.asarray, reference(state, rows, *small))
        got_y, got_s = map(np.asarray, update(state, rows, *small))
        if whole:
            np.testing.assert_array_equal(got_s[named], want_s[named])
            np.testing.assert_array_equal(got_y[live], want_y[live])
        else:
            np.testing.assert_allclose(got_y[live], want_y[live], rtol=1e-5,
                                       atol=1e-5)
            np.testing.assert_allclose(got_s[named], want_s[named],
                                       rtol=1e-6, atol=1e-6)
        # rows nobody named are untouched
        np.testing.assert_array_equal(got_s[others], state[others])
