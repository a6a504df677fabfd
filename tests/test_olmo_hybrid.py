"""The Olmo-Hybrid family (`models/olmo_hybrid.py` through `models/hybrid.py`)
on the TRAINING path, at small sizes on the CPU: forward, loss and every
gradient leaf against the float32 reference
(`benchmark/references/olmo_hybrid.py`, which imports nothing of the
program), the chunked delta rule's own backward against autodiff of the
position-at-a-time recurrence, what that backward keeps of the forward, the
engine's step under ZeRO-0 and ZeRO-3, the step's scope table, and the
served Qwen3-Next programs' text, which this family's knobs must leave
alone."""

import hashlib
import importlib.util
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.comm import mesh as mesh_mod
from deepspeed_tpu.models import hybrid
from deepspeed_tpu.models import olmo_hybrid as oh
from deepspeed_tpu.ops.pallas import gdn
from tests.test_held_residuals import _flash_forwards

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _reference():
    path = os.path.join(ROOT, "benchmark", "references", "olmo_hybrid.py")
    spec = importlib.util.spec_from_file_location("ref_olmo_hybrid", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _reference()

# two periods of the published pattern; H 4, K 24, V 48 keep the published
# 1 : 2 of key to value width; 200 positions: three chunks of 64 and a tail
PERIOD = ("linear_attention",) * 3 + ("full_attention",)
PUBLISHED = dict(
    model_type="olmo_hybrid", vocab_size=256, hidden_size=64,
    intermediate_size=96, num_hidden_layers=8, num_attention_heads=4,
    num_key_value_heads=4, hidden_act="silu", max_position_embeddings=256,
    attention_bias=False, rms_norm_eps=1e-6, tie_word_embeddings=False,
    layer_types=list(PERIOD * 2), linear_num_key_heads=4,
    linear_num_value_heads=4, linear_key_head_dim=24,
    linear_value_head_dim=48, linear_conv_kernel_dim=4,
    linear_allow_neg_eigval=True, rope_parameters={"rope_theta": None})
T = 200


@pytest.fixture(autouse=True)
def _no_mesh():
    mesh_mod.clear_mesh()
    yield
    mesh_mod.clear_mesh()


def _cfg(dtype=jnp.float32, **published):
    return oh.olmo_hybrid_config({**PUBLISHED, **published}, dtype=dtype,
                                 use_flash_attention=False)


def lively(params, seed=7):
    """Seeded weights at which every mechanism says something: a stream of
    order one (at the initialiser's 0.02 and 64 columns a half's output is
    under the norms' epsilon), gates and decays off their centre (beta
    covers (0, 2)), attention scores that pick, norm scales off one."""
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 64))

    def leaf(path, w):
        name = path[-1].key
        if name == "wte":
            return w * 50.0
        if name == "gdn_ba_w":
            return w * 10.0
        if name == "attn_qkv_w":
            return w * 20.0
        if name.endswith("_scale"):
            return w + 0.3 * jax.random.normal(next(keys), w.shape, w.dtype)
        return w
    return jax.tree_util.tree_map_with_path(leaf, params)


@pytest.fixture(scope="module")
def model():
    cfg = _cfg()
    params = lively(oh.olmo_hybrid_init_fn(cfg)(jax.random.PRNGKey(0)))
    tokens = np.random.default_rng(3).integers(
        0, cfg.vocab_size, (2, T + 1)).astype(np.int32)
    return cfg, params, {"tokens": tokens[:, :-1], "labels": tokens[:, 1:]}


def _worst(got, want):
    """The largest leaf error, each leaf's by its own largest entry."""
    errors = jax.tree_util.tree_map(
        lambda g, w: float(jnp.abs(g.astype(jnp.float32) - w).max()
                           / jnp.abs(w).max()), got, want)
    return max(jax.tree_util.tree_leaves(errors)), errors


# float32 against float32: the program's chunked scan, row-blocked head and
# fused projections reassociate sums the reference takes in one order. The
# SAME reference with every product's input, the state and the stream
# rounded through bfloat16 (`round_to`) misses the logits' and the gradients'
# limits by a factor of ten or more, which the tests read too.
LOGITS_TOL, LOSS_TOL, GRAD_TOL = 5e-4, 2e-5, 2e-3


def test_the_family_is_data_over_the_hybrid_loop(model):
    cfg, params, _ = model
    assert cfg.pattern == ("DF", "DF", "DF", "*F") * 2
    assert hybrid.layer_runs(cfg) == [("DFDFDF*F", 2)]
    assert hybrid.layer_runs(_cfg(num_hidden_layers=4,
                                  layer_types=list(PERIOD))) \
        == [("DF", 3), ("*F", 1)]
    assert (cfg.post_norm, cfg.qk_norm, cfg.rotary_attention,
            cfg.gdn_beta_scale, cfg.tie_embeddings) == (True, True, False,
                                                        2.0, False)
    arch = ref.arch_from_config(PUBLISHED)
    assert arch.runs == ((4, 2),) and arch.beta_scale == 2.0
    mixer, dense = params["runs"][0][0], params["runs"][0][1]
    assert mixer["gdn_qkvz_w"].shape == (2, 64, 4 * (24 + 24 + 48 + 48))
    assert set(dense) == {"ln1_scale", "mlp_gate_w", "mlp_up_w",
                          "mlp_down_w", "mlp_out_b"}
    attention = params["runs"][0][6]
    assert attention["q_norm_scale"].shape == (2, 4 * 16)   # whole projection
    specs = hybrid.hybrid_param_specs(cfg, oh._layer_shapes)
    assert jax.tree_util.tree_structure(
        jax.tree_util.tree_map(lambda s: 0, specs,
                               is_leaf=lambda s: isinstance(
                                   s, jax.sharding.PartitionSpec))) \
        == jax.tree_util.tree_structure(
            jax.tree_util.tree_map(lambda a: 0, params))
    with pytest.raises(ValueError, match="without rotation"):
        _cfg(rope_parameters={"rope_theta": 10000.0})
    with pytest.raises(NotImplementedError, match="post_norm"):
        hybrid.make_hybrid_decode_model(cfg, params, "tiny", None, (), "")


def test_forward_and_loss_are_the_references(model):
    cfg, params, batch = model
    arch = ref.arch_from_config(PUBLISHED)
    got = oh.olmo_hybrid_forward(params, batch["tokens"], cfg)
    loss = float(hybrid.hybrid_loss(params, batch, None, cfg))
    rounded = ref.arch_from_config(PUBLISHED, round_to=jnp.bfloat16)
    for row in range(2):
        want = ref.logits(params, batch["tokens"][row], arch)
        scale = float(jnp.abs(want).max())
        assert float(jnp.abs(got[row] - want).max()) < LOGITS_TOL * scale
        control = ref.logits(params, batch["tokens"][row], rounded)
        assert float(jnp.abs(control - want).max()) > 10 * LOGITS_TOL * scale
    want = ref.loss(params, batch["tokens"], batch["labels"], arch)
    assert abs(loss - want) < LOSS_TOL * want
    # (the bfloat16 control's LOSS is within 4e-6 of it: near ln V a loss
    # hardly reads the logits; the logits above and the gradients below are
    # what a lower precision fails)
    # the loss the engine's step takes (blocks recomputed, runs scanned, the
    # head in chunks) is the plain forward's
    logz = jax.nn.logsumexp(got, -1)
    gold = jnp.take_along_axis(got, batch["labels"][..., None], -1)[..., 0]
    assert abs(loss - float(jnp.mean(logz - gold))) < LOSS_TOL * want
    # negative eigenvalues are in play: the first layer's beta covers (0, 2)
    x = jnp.take(params["wte"], batch["tokens"], axis=0)
    beta = 2 * jax.nn.sigmoid((x @ params["runs"][0][0]["gdn_ba_w"][0])
                              [..., :4])
    assert float(beta.max()) > 1.9 and float(beta.min()) < 0.1


def test_every_gradient_leaf_is_the_references(model):
    cfg, params, batch = model
    arch = ref.arch_from_config(PUBLISHED)
    loss, got = jax.jit(jax.value_and_grad(
        lambda p: hybrid.hybrid_loss(p, batch, None, cfg)))(params)
    want_loss, want = ref.loss_and_grads(params, batch["tokens"],
                                         batch["labels"], arch)
    assert abs(float(loss) - float(want_loss)) < LOSS_TOL * float(want_loss)
    worst, by_leaf = _worst(got, want)
    assert worst < GRAD_TOL, by_leaf
    _, control = ref.loss_and_grads(
        params, batch["tokens"], batch["labels"],
        ref.arch_from_config(PUBLISHED, round_to=jnp.bfloat16))
    assert _worst(control, want)[0] > 10 * GRAD_TOL


def test_the_references_blocked_backward_is_its_plain_one(model, monkeypatch):
    """What lets the reference's gradient stand beside a training state at
    32768 positions — a half's backward a stretch of positions at a time on
    the carried state, the recurrence's states kept a run apart, rows in
    blocks — changes none of its numbers: 192 positions as three stretches
    of 64, states 16 apart, against the same functions whole."""
    _, params, batch = model
    arch = ref.arch_from_config(PUBLISHED)
    tokens, labels = batch["tokens"][:, :192], batch["labels"][:, :192]
    want_loss, want = ref.loss_and_grads(params, tokens, labels, arch)
    jax.clear_caches()
    for name, size in (("SEGMENT", 64), ("STATES", 16), ("MLP_ROWS", 64),
                       ("ROW_BLOCK", 32)):
        monkeypatch.setattr(ref, name, size)
    loss, got = ref.loss_and_grads(params, tokens, labels, arch)
    jax.clear_caches()
    assert abs(loss - want_loss) < 1e-6 * want_loss
    worst, by_leaf = _worst(got, want)
    assert worst < 1e-4, by_leaf


def test_a_recurrent_half_in_segments_is_the_half(model, monkeypatch):
    """200 positions as three blocks of 64 and a last one of 8 on the
    carried state and convolution tail (what 32768 positions are in blocks
    of 4096, and 32767 with a ragged last block): the loss and every
    gradient of the one-block step."""
    cfg, params, batch = model
    step = lambda: jax.jit(jax.value_and_grad(
        lambda p: hybrid.hybrid_loss(p, batch, None, cfg)))(params)
    want_loss, want = step()
    monkeypatch.setattr(hybrid, "SEGMENT", 64)
    loss, got = step()
    assert abs(float(loss) - float(want_loss)) < 1e-5 * float(want_loss)
    worst, by_leaf = _worst(got, want)
    assert worst < 2e-4, by_leaf


# ----------------------------------------------------------------------
# the chunked scan's own backward
# ----------------------------------------------------------------------


def _scan_case(K, V, T=100, b=1, G=2, H=4, seed=0):
    """Inputs with beta up to 2, a state that is not zero, and cotangents on
    both results; 100 positions: a chunk of 64 and one with a padded tail."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (b, T, G, K))) * K ** -0.5
    k = unit(jax.random.normal(ks[1], (b, T, G, K)))
    v = jax.random.normal(ks[2], (b, T, H, V))
    g = -0.3 * jax.nn.softplus(jax.random.normal(ks[3], (b, T, H)))
    beta = 2 * jax.nn.sigmoid(2 * jax.random.normal(ks[4], (b, T, H)))
    state = 0.3 * jax.random.normal(ks[5], (b, H, K, V))
    weigh = lambda o, S: jnp.sum(o * jax.random.normal(ks[6], o.shape)) \
        + jnp.sum(S * jax.random.normal(ks[7], S.shape))
    return (q, k, v, g, beta, state), weigh


@pytest.mark.parametrize("K, V, T", [
    (96, 192, 100), (128, 128, 100), (96, 192, 64)])
def test_chunk_scans_backward_is_autodiff_of_the_recurrence(K, V, T):
    args, weigh = _scan_case(K, V, T)
    assert float(args[4].max()) > 1.9
    with jax.default_matmul_precision("highest"):
        got = jax.grad(lambda *a: weigh(*gdn.gdn_chunk_scan(*a, 64)),
                       argnums=range(6))(*args)
        want = jax.grad(lambda *a: weigh(*gdn.gdn_scan_reference(*a)),
                        argnums=range(6))(*args)
    for name, a, w in zip(("q", "k", "v", "g", "beta", "state"), got, want):
        assert a.shape == w.shape and a.dtype == w.dtype
        assert float(jnp.abs(a - w).max()) < 1e-5 * float(jnp.abs(w).max()), \
            name


def test_the_system_is_inverted_where_its_powers_cannot_be_summed():
    """Keys that resemble each other at beta near 2: `(I + L)^-1` is bounded
    (its entries are those of a product of reflections) while L's powers
    are not, and float32 cannot cancel them: the series by squarings, which
    served until PR 56, returns noise here."""
    (q, k, v, g, beta, state), _ = _scan_case(24, 48, T=128)
    k = k + 2.0 * k[:, :1]                      # a shared direction
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    beta = jnp.full_like(beta, 1.9)
    with jax.default_matmul_precision("highest"):
        want, _ = gdn.gdn_scan_reference(q, k, v, g, beta, state)
        got, _ = gdn.gdn_chunk_scan(q, k, v, g, beta, state, 64)
        L = gdn._system(*(lambda q, k, v, g, beta: (
            k, beta, gdn._decays(g)[1]))(*gdn._chunks(q, k, v, g, beta, 64)))
        inverse = gdn._inverse_of_unit_lower(L)
        series, power = jnp.eye(64) - L, L
        for _ in range(5):
            power = power @ power
            series = series + series @ power
    scale = float(jnp.abs(want).max())
    assert float(jnp.abs(got - want).max()) < 1e-4 * scale
    eye = jnp.eye(64)
    residual = lambda X: float(jnp.abs((eye + L) @ X - eye).max())
    assert residual(inverse) < 1e-4
    assert not residual(series) < 1e-1


def test_backward_keeps_nothing_of_a_chunks_system(model):
    """What `jax.vjp` of the tiny model's loss holds for the backward: no
    float32 array of the triangular system's shape (chunks x heads x Q x Q)
    — plain autodiff through the forward held ten a layer — and no state a
    chunk either: of the scan, its inputs and the state it started from."""
    _, _, batch = model
    cfg = _cfg(num_hidden_layers=1, layer_types=["linear_attention"])
    params = oh.olmo_hybrid_init_fn(cfg)(jax.random.PRNGKey(0))
    import deepspeed_tpu.models.hybrid as hybrid_mod
    plain = lambda f, **_: f                    # see through the checkpoints
    saved = jax.checkpoint
    try:
        hybrid_mod.jax.checkpoint = plain
        _, pull = jax.vjp(
            lambda p: hybrid.hybrid_loss(p, batch, None, cfg), params)
    finally:
        hybrid_mod.jax.checkpoint = saved
    held = [leaf.shape for leaf in jax.tree_util.tree_leaves(pull)
            if hasattr(leaf, "shape")]
    Q, (K, V) = cfg.chunk_size, (cfg.gdn_key_dim, cfg.gdn_value_dim)
    assert not [s for s in held if len(s) >= 2 and s[-2:] == (Q, Q)]
    states = [s for s in held if len(s) >= 2 and s[-2:] == (K, V)]
    assert states and all(np.prod(s) == 2 * 4 * K * V for s in states), states
    # the scan's inputs ARE held: q and k, [repeats, b, T, H, K]
    assert (1, 2, T, 4, K) in held


# ----------------------------------------------------------------------
# what a block holds for its backward (`hybrid.held_candidates`)
# ----------------------------------------------------------------------

V5E = 16909336064               # memory_stats()["bytes_limit"] of one v5e


def _held_case(T, flash, monkeypatch):
    """One period at T positions in blocks of 64 (`SEGMENT`): (cfg, params,
    batch, the candidates' table, the bytes the step keeps with nothing
    held when it has no gradients to count)."""
    monkeypatch.setattr(hybrid, "SEGMENT", 64)
    cfg = oh.olmo_hybrid_config(
        {**PUBLISHED, "num_hidden_layers": 4, "layer_types": list(PERIOD)},
        dtype=jnp.float32, use_flash_attention=flash)
    params = lively(oh.olmo_hybrid_init_fn(cfg)(jax.random.PRNGKey(0)))
    tokens = np.random.default_rng(3).integers(
        0, cfg.vocab_size, (2, T + 1)).astype(np.int32)
    batch = {"tokens": tokens[:, :-1], "labels": tokens[:, 1:]}
    table = hybrid.held_candidates(cfg, 2, T)
    floor = max(at["carried_bytes"] + max(at.get("loss_bytes", 0),
                                          at["backward_bytes"])
                for at in table[2])
    return cfg, params, batch, table, floor


def _budgeted(cfg, batch, free):
    """loss and gradients traced with `free` bytes on offer (None: no
    budget at all), and the plans the loop reported."""
    from deepspeed_tpu.runtime.activation_checkpointing import held_budget
    plans = []

    def run(params):
        fn = jax.value_and_grad(
            lambda p: hybrid.hybrid_loss(p, batch, None, cfg))
        if free is None:
            return fn(params)
        with held_budget(free, report=plans.append):
            return fn(params)

    return run, plans


def _scans_made_again(jaxpr, times=1):
    """The delta rule's forward scans that stand in a block's
    recomputation, each as often as the loops around it run it."""
    n = 0
    for eqn in jaxpr.eqns:
        stack = str(eqn.source_info.name_stack)
        scan = eqn.primitive.name == "scan"
        if scan and "gdn/scan" in stack and "rematted_computation" in stack:
            n += times
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += _scans_made_again(
                sub, times * (eqn.params["length"] if scan else 1))
    return n


# (T, flash, the names' blocks a plan should hold) -> the scan's forwards
# made again: one a block that does not hold its output, of the twelve that
# three `D` layers run in four blocks each (T 200: three of 64 and a tail).
# A name's blocks are dealt from the stack's END.
HELD_CASES = {
    "nothing": (256, True, {}, 12),
    "flash_alone": (256, True, {"flash_residuals": 1}, 12),
    "flash_qkv_and_the_last_ffn": (
        256, True, {"flash_residuals": 1, "qkv_product": 1, "mlp_product": 1},
        12),
    "the_last_two_segments_of_four": (
        256, True, {"flash_residuals": 1, "qkv_product": 1, "mlp_product": 4,
                    "gdn_scan_output": 6}, 6),
    "every_name": (
        256, True, {"flash_residuals": 1, "qkv_product": 1, "mlp_product": 4,
                    "gdn_scan_output": 12}, 0),
    "the_tail_alone": (
        200, False, {"qkv_product": 1, "mlp_product": 4,
                     "gdn_scan_output": 3}, 9),
    "every_name_and_the_tail": (
        200, False, {"qkv_product": 1, "mlp_product": 4,
                     "gdn_scan_output": 12}, 0),
}


@pytest.mark.parametrize("case", list(HELD_CASES))
def test_a_held_scan_output_is_not_made_again(case, monkeypatch):
    """Counted on the jaxpr of the gradient: with `gdn.SCAN_OUTPUT` held a
    block's recomputation holds NO forward scan (its every equation is dead
    there: the rule keeps its inputs alone) and the backward is
    `_chunk_scan_backward`'s two loops; the flash forward runs once where
    its residuals are held."""
    T, flash, blocks, again = HELD_CASES[case]
    cfg, params, batch, (held, carriers, _), floor = _held_case(
        T, flash, monkeypatch)
    assert carriers[gdn.SCAN_OUTPUT] == (3,) * -(-T // 64)
    free = floor + sum(n * held[name] for name, n in blocks.items())
    run, plans = _budgeted(cfg, batch, free)
    jaxpr = jax.make_jaxpr(run)(params).jaxpr
    assert plans[-1].blocks == blocks
    assert _scans_made_again(jaxpr) == again
    if flash:
        assert _flash_forwards(jaxpr) == (1 if blocks else 2)


# XLA:CPU fuses two programs differently (a held result changes what stands
# beside a product), and a fusion rounds differently: 4e-6 on a gradient.
# With its fusion passes off the two programs run the same operations, and
# the comparison reads the program's own arithmetic.
UNFUSED = {"xla_disable_hlo_passes": "fusion,cpu-instruction-fusion"}


@pytest.mark.parametrize("case", [c for c in HELD_CASES if c != "nothing"])
def test_loss_and_every_gradient_are_equal_whatever_is_held(case,
                                                            monkeypatch):
    """EQUAL, not close: a held result IS the one the block would make
    again (float32 scan output, no narrower copy), so a plan changes what
    a step keeps and nothing it computes. Where a name stops midway
    through a half the half's segments run as two scans, and a weight's
    gradient is summed over the segments in two parts: the same terms in
    another order, equal to rounding."""
    T, flash, blocks, _ = HELD_CASES[case]
    cfg, params, batch, (held, _, _), floor = _held_case(T, flash,
                                                         monkeypatch)
    unfused = lambda run: jax.jit(run).lower(params).compile(
        compiler_options=UNFUSED)(params)
    want_loss, want = unfused(_budgeted(cfg, batch, None)[0])
    run, plans = _budgeted(
        cfg, batch, floor + sum(n * held[name] for name, n in blocks.items()))
    loss, got = unfused(run)
    assert plans[-1].blocks == blocks
    assert float(loss) == float(want_loss)
    # whole segments that hold the output beside whole segments that do not
    # (the tail of T 200 is a block of its own in either program, and the
    # first to hold)
    groups = blocks.get("gdn_scan_output", 0) // 3
    split = 0 < groups - (1 if T % 64 and groups else 0) < T // 64
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree_util.tree_leaves(want)):
        if split:
            np.testing.assert_allclose(g, w, rtol=2e-5, atol=1e-6 * float(
                jnp.abs(w).max()), err_msg=jax.tree_util.keystr(path))
        else:
            assert bool((g == w).all()), jax.tree_util.keystr(path)


@pytest.mark.parametrize("free", [None, 0], ids=["no_budget", "no_room"])
def test_with_nothing_held_the_gradient_lowers_to_the_parents_text(
        free, monkeypatch):
    """No budget installed (the CPU harness, any caller outside the
    engine), or one with no room: `nothing_saveable`, one scan a recurrent
    half, and the text `hybrid_loss`'s gradient lowered to on the commit
    this PR started from (`tests/step_program_hashes.json`, key
    `olmo_hybrid_grad`, written there by this function on that commit)."""
    cfg, params, batch, _, _ = _held_case(256, True, monkeypatch)
    run, _ = _budgeted(cfg, batch, free)
    text = _strip(jax.jit(run).lower(params).as_text())
    with open(os.path.join(ROOT, "tests", "step_program_hashes.json")) as f:
        assert hashlib.sha256(text.encode()).hexdigest()[:16] == \
            json.load(f)["olmo_hybrid_grad"]["T256_flash_segments_of_64"]


# ----------------------------------------------------------------------
# through the engine
# ----------------------------------------------------------------------


def _train(stage, devices, steps=3):
    mesh_mod.clear_mesh()
    cfg = _cfg(num_hidden_layers=4, layer_types=list(PERIOD))
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=oh.make_olmo_hybrid_model(cfg, name="tiny", abstract=True),
        config={"train_batch_size": 4,
                "optimizer": {"type": "AdamW",
                              "params": {"lr": 1e-3, "weight_decay": 0.1}},
                "gradient_clipping": 1.0,
                "zero_optimization": {"stage": stage},
                "mesh": {"data": devices}, "seed": 11,
                "steps_per_print": 10**9})
    tokens = np.random.default_rng(5).integers(0, 256, (4, 97)).astype(
        np.int32)
    batch = {"tokens": tokens[:, :-1], "labels": tokens[:, 1:]}
    return engine, [float(engine.train_batch(batch)) for _ in range(steps)]


def test_three_steps_agree_under_zero_0_and_zero_3():
    _, alone = _train(0, 1)
    engine, sharded = _train(3, 4)
    np.testing.assert_allclose(alone, sharded, rtol=2e-5)
    assert alone[-1] < alone[0]
    assert abs(alone[0] - np.log(256)) < 0.05
    # ZeRO-3 placed the leaves: a big one lives in four shards
    leaf = engine.state.params["runs"][0][0]["gdn_qkvz_w"]
    assert len({s.index for s in leaf.addressable_shards}) == 4


def test_the_cells_comparison_reads_the_steps_gradient_off_its_moment():
    """`benchmark/drivers/train_olmo_hybrid.py`: after ONE step the AdamW
    first moment is a tenth of the clipped gradient, so the cell reads the
    step's gradient back from the engine's state and holds every leaf of it
    to the reference's (`compare`, `LIMITS`). The engine's own step passes;
    the reference rounded through float8_e4m3fn in its place does not, nor
    does a state the step left as it was (every leaf reads 1)."""
    import sys
    bench = os.path.join(ROOT, "benchmark")
    sys.path[:0] = [p for p in (bench,) if p not in sys.path]
    import harness
    driver = harness.load_module("drivers", "train_olmo_hybrid")
    engine, _ = _train(0, 1, steps=0)
    tokens = np.random.default_rng(5).integers(0, 256, (4, 97)).astype(
        np.int32)                               # `_train`'s batch
    batch = {"tokens": tokens[:, :-1], "labels": tokens[:, 1:]}
    published = {**PUBLISHED, "num_hidden_layers": 4,
                 "layer_types": list(PERIOD), "reference": "olmo_hybrid",
                 "assumed": {"gradient_clipping": 1.0}}
    cell = {"config_json": published}
    start = jax.tree_util.tree_map(jnp.copy, engine.params)
    want_loss, want = driver.reference(cell, start, **batch)
    loss = float(engine.train_batch(batch))
    moment, scale, norm = driver.step_gradient(engine, published)
    assert norm > 1.0 and scale == pytest.approx(10.0 * norm, rel=1e-3)
    first = driver.compare(loss, moment, scale, want_loss, want)
    assert first["ok"] and first["numbers"]["gradient"] < 5e-3, first
    assert first["gradient_norm_reference"] == pytest.approx(norm, rel=1e-3)
    idle = driver.compare(loss, jax.tree_util.tree_map(jnp.zeros_like, moment),
                          scale, want_loss, want)
    assert not idle["ok"] and set(idle["by_leaf"].values()) == {1.0}
    low_loss, low = driver.reference(cell, start, **batch,
                                     round_to=jnp.float8_e4m3fn)
    control = driver.compare(low_loss, low, 1.0, want_loss, want)
    assert not control["ok"]
    assert control["numbers"]["gradient"] > driver.LIMITS["gradient"]


def test_training_step_names_the_delta_rules_backward():
    engine, _ = _train(0, 1, steps=2)
    rows = engine.steptrace.device_scopes()
    assert {r.program for r in rows} == {"train_step"}
    named = lambda scope, backward: [
        r for r in rows if r.backward is backward
        and (r.scope == scope or r.scope.startswith(scope + "/"))]
    for scope in ("gdn/in_proj", "gdn/conv", "gdn/scan", "gdn/out_proj",
                  "attn/qkv", "attn/out", "mlp", "embed", "head_loss"):
        assert named(scope, False), scope
        assert named(scope, True), scope
    # the custom backward's own loop stands under the scan, backward
    assert [r for r in named("gdn/scan", True) if r.opcode == "while"]
    from deepspeed_tpu.telemetry import device_scopes as ds
    assert not {r.scope for r in rows if ds.segments(r.scope) is None}


@pytest.mark.parametrize("limit, names", [
    (0, ()),                                  # the CPU reports no limit
    (V5E, ("qkv_product", "mlp_product", "gdn_scan_output")),
    (2**19, ()),                              # a limit the state fills
])
def test_engine_reports_the_hybrid_loops_plan(monkeypatch, limit, names):
    """The engine offers the hybrid loop what it offers the GPT block: the
    plan in `engine.held_plan` and the step ring's facts, one compile, the
    same losses whatever is held."""
    from deepspeed_tpu.platform.accelerator import get_accelerator
    _, want = _train(0, 1)
    monkeypatch.setattr(type(get_accelerator()), "total_memory",
                        lambda self, device=None: limit)
    engine, losses = _train(0, 1)
    np.testing.assert_allclose(losses, want, rtol=1e-5)
    plan = engine.held_plan
    assert plan.names == names
    # a name's blocks over the stack: one period, 96 positions a block
    assert plan.layers == {"qkv_product": 1, "mlp_product": 4,
                           "gdn_scan_output": 3}
    assert plan.blocks == {name: plan.layers[name] for name in names}
    assert plan.bytes_per_layer["gdn_scan_output"] == 4 * 96 * 4 * 48 * 4
    assert engine.steptrace.facts["held_residuals"] == plan.to_dict()
    assert engine._compiled_train_programs() == 1
    if not names:
        assert plan.first_unfit == "qkv_product"
    else:
        assert plan.first_unfit is None and 0 < plan.held_bytes


# ----------------------------------------------------------------------
# the served family that shares the loop keeps its programs
# ----------------------------------------------------------------------


def _strip(text):
    text = re.sub(r'loc\([^)]*\)|#loc\d*( = .*)?', '', text)
    return re.sub(r'@(_?[A-Za-z_]+?)_\d+\b', r'@\1', text)


@pytest.mark.parametrize("family, cases, serving", [
    ("qwen3_next", "qwen3_next_cases", {"prefill_chunks_per_step": 4}),
    ("granite_moe_hybrid", "granite_cases", {}),
    ("nemotron_h", "nemotron_cases", {})])
def test_served_hybrid_step_programs_lower_to_the_parents_text(
        family, cases, serving):
    """The families that share `hybrid.py`'s loop keep their programs:
    `gdn_beta_scale` 1.0, no `post_norm`, and a chunked scan nobody
    differentiates. `decode_step`, `prefill_step` and `mixed_step` of a tiny
    engine a family lower to the text they had on the commit this PR started
    from (`tests/step_program_hashes.json`, written there by this function
    on that commit) — but for Qwen3-Next's `prefill_step` and `mixed_step`,
    whose ONE difference is the triangular system's inverse
    (`gdn._inverse_of_unit_lower`, the block rule for the series by
    squarings): those two were written on this commit."""
    import importlib
    module = importlib.import_module("tests." + cases)
    cfg = module._cfg()
    assert (cfg.gdn_beta_scale, cfg.post_norm) == (1.0, False)
    engine, srv = module._serving(cfg, module._params(cfg), one_device=True,
                                  **serving)
    got = {}
    for name, fn, args in srv.programs.examples(
            engine.params, srv.pool, srv._tables_arg(srv.tables), srv._rng):
        text = _strip(jax.jit(fn).lower(*args).as_text())
        got[name] = hashlib.sha256(text.encode()).hexdigest()[:16]
    with open(os.path.join(ROOT, "tests", "step_program_hashes.json")) as f:
        assert got == json.load(f)[family]


# ----------------------------------------------------------------------
# the benchmark's files
# ----------------------------------------------------------------------


def test_benchmark_holds_the_cells_files():
    bench_dir = os.path.join(ROOT, "benchmark")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = {w["name"]: w for w in bench["workloads"]}[
        "train_olmohybrid_seq32k_1chip"]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "olmo-hybrid-7b-4l-vp8", "train_seq32768", 1)
    config = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(os.path.join(ROOT, config["file"])) as f:
        published = json.load(f)
    assert config["reduced"] == published["reduced"] == [
        "num_hidden_layers", "layer_types", "vocab_size"]
    # the program builds the file's model, and counts what the file says
    cfg = oh.olmo_hybrid_config(published)
    shapes = jax.eval_shape(oh.olmo_hybrid_init_fn(cfg), jax.random.PRNGKey(0))
    assert sum(int(np.prod(leaf.shape)) for leaf in
               jax.tree_util.tree_leaves(shapes)) == published["parameters"]
    assert ref.arch_from_config(published).runs == ((1, 3), (1, 1))
    for kind, name in (("drivers", published["driver"] + ".py"),
                       ("references", published["reference"] + ".py"),
                       ("traffic", cell["traffic"] + ".json")):
        assert os.path.exists(os.path.join(bench_dir, kind, name)), name
    reported = [m["name"] for m in bench["end_to_end"]
                if cell["name"] in m.get("workloads", [cell["name"]])]
    assert sorted(reported) == ["setup_s", "train_tokens_per_s_per_chip"]
    # the table is full: the cell JOINED the training entries, last in each
    assert len(bench["per_layer"]) <= 128
    layered = [m for m in bench["per_layer"]
               if cell["name"] in m.get("workloads", ())]
    assert len(layered) == 7
    for metric in layered:
        assert metric["workloads"][-1] == cell["name"]
        with open(os.path.join(bench_dir, "layer_metrics",
                               metric["name"] + ".json")) as f:
            spec = json.load(f)
        assert os.path.exists(os.path.join(bench_dir, "readers",
                                           spec["reader"] + ".py"))
