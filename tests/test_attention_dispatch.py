"""Unified attention dispatch layer (`ops/attention_dispatch.py`).

The PR 14 refactor: ONE registry decides which attention program every call
site runs — training flash/ring/dense, contiguous decode, paged
decode (fp + int8), chunked prefill, spec-decode verify. These tests pin

  * the selection table (phase × shape × flags × backend → program),
  * the single-home predicate regression: `models/gpt.py` carries NO local
    copy of the flash/decode engage predicates anymore, so the historical
    two-copies-drift failure mode (gpt.py:436 vs :855) is structurally
    impossible — monkeypatching the ONE predicate flips every call site,
  * registry extensibility (a program registered at runtime is selectable,
    and RUN BY ITS RUNNER at every kind of site: no site knows a name),
  * compile-stability: selection is pure trace-time — a serving engine
    still compiles exactly one program per bucket.
"""

import dataclasses
import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import attention_dispatch as ad
from tests.paged_cases import assert_one_compile_each

pytestmark = pytest.mark.longctx


def site(**kw):
    base = dict(phase="train", q_len=2048, kv_len=2048, causal=True,
                has_bias=False, has_window=False, scale_attn=True,
                mesh_axes=(), force_flash=None,
                backend=None, external_fn=False)
    base.update(kw)
    return ad.AttnSite(**base)


class TestSelectionTable:
    def test_train_auto_crossover(self):
        assert ad.select(site(q_len=512, kv_len=512)) == "dense"
        assert ad.select(site(q_len=ad.FLASH_MIN_SEQ,
                              kv_len=ad.FLASH_MIN_SEQ)) == "flash"
        assert ad.select(site(q_len=256, kv_len=256,
                              force_flash=True)) == "flash"
        assert ad.select(site(force_flash=False)) == "dense"

    def test_train_kernel_disqualifiers(self):
        assert ad.select(site(has_bias=True)) == "dense"       # alibi
        assert ad.select(site(has_window=True)) == "dense"     # sliding win
        assert ad.select(site(scale_attn=False)) == "dense"    # GPT-Neo
        assert ad.select(site(q_len=2000, kv_len=2000)) == "dense"  # %128
        assert ad.select(site(kv_len=4096)) == "dense"         # non-square

    def test_train_external_fn_always_wins(self):
        assert ad.select(site(external_fn=True)) == "external"
        assert ad.select(site(external_fn=True, backend="ring",
                              mesh_axes=("sequence",))) == "external"

    def test_ring_needs_backend_request_and_sequence_axis(self):
        assert ad.select(site(backend="ring",
                              mesh_axes=("sequence",))) == "ring"
        assert ad.select(site(backend="ring_ulysses",
                              mesh_axes=("data", "sequence"))) \
            == "ring_ulysses"
        # no sequence axis installed: the request falls through to auto
        assert ad.select(site(backend="ring")) == "flash"
        # no request: sequence axis alone keeps the SPMD-Ulysses default
        assert ad.select(site(mesh_axes=("sequence",))) == "flash"
        # ring carries the kernel's no-bias/no-window contract: an
        # EXPLICIT request on an ineligible site fails loudly — the dense
        # fallback at 128k would be an HBM OOM far from its cause
        with pytest.raises(ValueError, match="ineligible"):
            ad.select(site(backend="ring", mesh_axes=("sequence",),
                           has_bias=True))
        # an explicit attn_fn still outranks the request (user's choice)
        assert ad.select(site(backend="ring", mesh_axes=("sequence",),
                              has_bias=True, external_fn=True)) \
            == "external"
        # a typo'd backend is a config error, not a silent single-chip run
        with pytest.raises(ValueError, match="unknown attention_backend"):
            ad.select(site(backend="ring-ulysses",
                           mesh_axes=("sequence",)))

    def test_decode_phase(self):
        d = dict(phase="decode", q_len=1)
        assert ad.select(site(**d, kv_len=1024)) == "decode_dense"
        assert ad.select(site(**d, kv_len=ad.DECODE_KERNEL_MIN_CTX)) \
            == "decode_kernel"
        assert ad.select(site(**d, kv_len=ad.DECODE_KERNEL_MIN_CTX + 1)) \
            == "decode_dense"                                  # not %128
        assert ad.select(site(**d, kv_len=1024, force_flash=True)) \
            == "decode_kernel"
        assert ad.select(site(**d, kv_len=ad.DECODE_KERNEL_MIN_CTX,
                              has_window=True)) == "decode_dense"

    def test_paged_phase_incl_quant(self):
        d = dict(phase="paged_decode", q_len=1,
                 kv_len=ad.DECODE_KERNEL_MIN_CTX, block_size=128)
        assert ad.select(site(**d)) == "paged_kernel"
        assert ad.select(site(**d, kv_dtype="int8")) == "paged_kernel_quant"
        # unaligned pool block: gather path, still keyed on kv dtype
        d2 = dict(d, block_size=64)
        assert ad.select(site(**d2)) == "paged_gather"
        assert ad.select(site(**d2, kv_dtype="int8")) == "paged_gather_quant"
        # chunked prefill / verify never take the single-token kernel
        assert ad.select(site(phase="prefill_chunk", q_len=16,
                              kv_len=ad.DECODE_KERNEL_MIN_CTX,
                              block_size=128)) == "paged_gather"
        assert ad.select(site(phase="verify", q_len=5,
                              kv_len=ad.DECODE_KERNEL_MIN_CTX,
                              block_size=128,
                              kv_dtype="int8")) == "paged_gather_quant"

    # (phase, pool form, mask flags, shapes) -> program: the chunk kernel
    # engages on the in-place pool for plain causal chunks of whole lane
    # tiles, whatever `use_flash_attention` and the context say; verify, the
    # int8 pool and everything the shapes disqualify stay on the gathers
    PREFILL_SITES = {
        "mistral_chunk": (dict(), "paged_prefill_kernel"),
        "olmoe_chunk": (dict(q_len=256, kv_len=1536), "paged_prefill_kernel"),
        "forced_off_is_not_consulted": (dict(force_flash=False),
                                        "paged_prefill_kernel"),
        "short_table": (dict(kv_len=512), "paged_prefill_kernel"),
        "scatter_form": (dict(pool_in_place=False), "paged_gather"),
        "alibi": (dict(has_bias=True), "paged_gather"),
        "window": (dict(has_window=True), "paged_gather"),
        "chunk_off_the_lane_tile": (dict(q_len=64), "paged_gather"),
        "block_off_the_lane_tile": (dict(block_size=64, kv_len=2048),
                                    "paged_gather"),
        "int8_pool": (dict(pool_in_place=False, kv_dtype="int8"),
                      "paged_gather_quant"),
        "verify": (dict(phase="verify", q_len=5), "paged_gather"),
        "verify_of_a_whole_tile": (dict(phase="verify", q_len=128),
                                   "paged_gather"),
        "verify_int8": (dict(phase="verify", q_len=5, pool_in_place=False,
                             kv_dtype="int8"), "paged_gather_quant"),
        "decode_keeps_its_kernel": (dict(phase="paged_decode", q_len=1),
                                    "paged_kernel"),
    }

    @pytest.mark.parametrize("case", sorted(PREFILL_SITES))
    def test_prefill_chunk_sites(self, case):
        changes, program = self.PREFILL_SITES[case]
        base = dict(phase="prefill_chunk", q_len=512, kv_len=16384,
                    block_size=512, pool_in_place=True)
        assert ad.select(site(**{**base, **changes})) == program

    # a kernel program and a site it is built for
    KERNEL_SITES = {
        "paged_prefill_kernel": dict(phase="prefill_chunk", q_len=512,
                                     kv_len=16384, block_size=512,
                                     pool_in_place=True),
        "paged_kernel": dict(phase="paged_decode", q_len=1, kv_len=8192,
                             block_size=128),
        "paged_kernel_quant": dict(phase="paged_decode", q_len=1, kv_len=8192,
                                   block_size=128, kv_dtype="int8"),
        "mla_decode_kernel": dict(phase="paged_decode", q_len=1, kv_len=8192,
                                  block_size=128, latent=True),
        "mla_prefill_kernel": dict(phase="prefill_chunk", q_len=512,
                                   kv_len=16384, block_size=512,
                                   pool_in_place=True, latent=True),
        "decode_kernel": dict(phase="decode", q_len=1, kv_len=8192),
    }

    @pytest.mark.parametrize("name", sorted(KERNEL_SITES))
    def test_a_kernel_program_has_a_runner_and_outranks_the_oracles(self,
                                                                    name):
        """Every kernel is run by its runner; what has none is a site's own
        dense / gather oracle, and ranks under every kernel of its sites."""
        prog, at = ad.get_program(name), site(**self.KERNEL_SITES[name])
        assert prog.runner is not None
        assert ad.select(at) == name
        oracles = [p for p in ad.registered_programs(at.phase)
                   if p.runner is None and p.matches(at)]
        assert oracles and all(p.priority < prog.priority for p in oracles)

    def test_dispatch_table_is_total_and_ordered(self):
        table = ad.dispatch_table()
        for phase, rows in table.items():
            names = [n for n, _ in rows]
            assert names, f"phase {phase} has no programs"
            # a priority-0 always-true fallback closes every phase
            fallback = names[-1]
            assert ad.get_program(fallback).priority == 0


class TestSingleHomePredicates:
    """The regression the satellite demands: the two call sites
    (training want-flash at the old gpt.py:436, decode engage at :855)
    can never disagree again — there is exactly ONE definition."""

    def test_gpt_carries_no_local_predicate_copy(self):
        import deepspeed_tpu.models.gpt as gpt
        src = inspect.getsource(gpt)
        assert "use_flash_attention is True" not in src, \
            "models/gpt.py regrew a local copy of the engage predicate"
        assert "use_flash_attention is None" not in src
        # every attention call site resolves through the dispatch layer
        assert src.count("attn_dispatch.select(") >= 3
        # and the re-exported constants ARE the dispatch layer's
        assert gpt.FLASH_MIN_SEQ == ad.FLASH_MIN_SEQ
        assert gpt.DECODE_KERNEL_MIN_CTX == ad.DECODE_KERNEL_MIN_CTX

    def test_monkeypatched_predicate_flips_all_decode_sites(self, monkeypatch):
        """Forcing the ONE decode predicate off switches BOTH the
        contiguous-cache decode and the paged decode to the dense path in
        the same breath — the call sites share the definition, they cannot
        drift."""
        from deepspeed_tpu.models.gpt import (GPTConfig,
                                              make_gpt_decode_model)
        cfg = GPTConfig(n_layer=1, n_head=2, d_model=64, max_seq_len=256,
                        vocab_size=128, dtype=jnp.float32, remat=False,
                        use_flash_attention=True)      # forced ON
        spec = make_gpt_decode_model(cfg=cfg)

        def contiguous_uses_pallas():
            cache = spec.init_cache(1, 1024, jnp.float32)
            jaxpr = jax.make_jaxpr(
                lambda p, t, s, c: spec.decode_fn(p, t, s, c))(
                    spec.params, jnp.zeros((1,), jnp.int32),
                    jnp.zeros((1,), jnp.int32), cache)
            return "pallas_call" in str(jaxpr)

        def paged_uses_pallas():
            pool = spec.init_paged_pool(9, 128, jnp.float32)
            tables = jnp.zeros((1, 8), jnp.int32)
            jaxpr = jax.make_jaxpr(
                lambda p, t, s, pl, bt: spec.decode_paged_fn(p, t, s, pl, bt))(
                    spec.params, jnp.zeros((1,), jnp.int32),
                    jnp.zeros((1,), jnp.int32), pool, tables)
            return "pallas_call" in str(jaxpr)

        assert contiguous_uses_pallas() and paged_uses_pallas()
        monkeypatch.setattr(ad, "decode_kernel_wanted",
                            lambda force, M: False)
        assert not contiguous_uses_pallas()
        assert not paged_uses_pallas()

    def test_verify_call_site_dispatches_as_verify_phase(self, monkeypatch):
        """The spec-decode verify chunk is dispatched under phase='verify'
        (not folded into prefill_chunk) — a verify-specific registered
        program would actually engage there."""
        from deepspeed_tpu.models.gpt import (GPTConfig,
                                              make_gpt_decode_model)
        cfg = GPTConfig(n_layer=1, n_head=2, d_model=64, max_seq_len=256,
                        vocab_size=128, dtype=jnp.float32, remat=False)
        spec = make_gpt_decode_model(cfg=cfg)
        seen = []
        orig = ad.select

        def spy(site):
            seen.append(site.phase)
            return orig(site)

        monkeypatch.setattr(ad, "select", spy)
        pool = spec.init_paged_pool(9, 128, jnp.float32)
        tables = jnp.zeros((1, 2), jnp.int32)
        jax.make_jaxpr(
            lambda p, t, s, pl, bt: spec.verify_paged_fn(p, t, s, pl, bt))(
                spec.params, jnp.zeros((1, 5), jnp.int32),
                jnp.zeros((1,), jnp.int32), pool, tables)
        assert "verify" in seen and "prefill_chunk" not in seen

    @pytest.mark.parametrize("in_place", [False, True],
                             ids=["scatter_form", "in_place_pool"])
    def test_mixed_call_site_selects_what_its_two_groups_select_alone(
            self, monkeypatch, in_place):
        """A chunk riding the decode call (`mixed_paged_fn`) dispatches its
        chunk rows as a `prefill_chunk` site and its slots' rows as a
        `paged_decode` site — the SAME keys, so the same programs, as the
        chunk-only and decode-only programs of the same shapes; and it keeps
        its own record of them beside theirs."""
        from deepspeed_tpu.models.gpt import (GPTConfig,
                                              make_gpt_decode_model)
        cfg = GPTConfig(n_layer=1, n_head=2, d_model=256, max_seq_len=1024,
                        vocab_size=128, dtype=jnp.float32, remat=False,
                        use_rotary=True, use_flash_attention=True)
        spec = make_gpt_decode_model(cfg=cfg)
        if in_place:    # the carried pool, its kernels in the interpreter
            monkeypatch.setattr(ad, "kv_pool_writer",
                                lambda pool: ad.KV_POOL_WRITE_KERNEL)
        seen = []
        orig = ad.select

        def spy(site):
            seen.append(site)
            return orig(site)

        monkeypatch.setattr(ad, "select", spy)
        pool = spec.init_paged_pool(9, 128, jnp.float32)
        i32 = jnp.int32
        chunk = (jnp.zeros((1, 128), i32), jnp.zeros((1,), i32),
                 jnp.zeros((1,), i32))
        table, tables = jnp.zeros((1, 8), i32), jnp.zeros((3, 8), i32)
        slots = (jnp.zeros((3,), i32), jnp.zeros((3,), i32))
        jax.make_jaxpr(spec.prefill_paged_fn)(spec.params, *chunk, pool,
                                              table)
        jax.make_jaxpr(spec.decode_paged_fn)(spec.params, *slots, pool,
                                             tables)
        alone, seen[:] = list(seen), []
        jax.make_jaxpr(spec.mixed_paged_fn)(spec.params, *chunk, table,
                                            *slots, pool, tables)
        assert [s.phase for s in alone] == ["prefill_chunk", "paged_decode"]
        assert seen == alone
        programs = spec.paged_attn_programs
        assert programs["mixed/prefill_chunk"] == programs["prefill_chunk"] \
            == ("paged_prefill_kernel" if in_place else "paged_gather")
        assert programs["mixed/paged_decode"] == programs["paged_decode"] \
            == "paged_kernel"
        assert spec.kv_pool_writers["mixed"] \
            == spec.kv_pool_writers["prefill_chunk"]

    def test_monkeypatched_flash_predicate_flips_training(self, monkeypatch):
        from deepspeed_tpu.models.gpt import (GPTConfig, gpt_forward,
                                              init_gpt_params)
        cfg = GPTConfig(n_layer=1, n_head=2, d_model=64, max_seq_len=2048,
                        vocab_size=128, dtype=jnp.float32, remat=False)
        params = init_gpt_params(cfg, seed=0)

        def uses_pallas():
            toks = jnp.zeros((1, 2048), jnp.int32)
            jaxpr = jax.make_jaxpr(
                lambda p, t: gpt_forward(p, t, cfg))(params, toks)
            return "pallas_call" in str(jaxpr)

        assert uses_pallas()
        monkeypatch.setattr(ad, "flash_wanted", lambda force, T: False)
        assert not uses_pallas()


class TestRegistryExtensibility:
    def test_runtime_registered_program_is_selected(self):
        calls = []

        def runner(q, k, v, causal=True, sm_scale=None):
            calls.append(q.shape)
            return q

        prog = ad.AttentionProgram(
            name="test_variant", phases=("train",), priority=999,
            matches=lambda s: s.backend == "test_variant",
            when="test fixture", runner=runner)
        ad.register_program(prog)
        try:
            assert ad.select(site(backend="test_variant")) == "test_variant"
            # an unrelated site is untouched by the registration
            assert ad.select(site()) == "flash"
            # and the zoo invokes the registered runner end to end
            from deepspeed_tpu.models.gpt import (GPTConfig, gpt_forward,
                                                  init_gpt_params)
            cfg = GPTConfig(n_layer=1, n_head=2, d_model=32, max_seq_len=64,
                            vocab_size=64, dtype=jnp.float32, remat=False,
                            attention_backend="test_variant")
            params = init_gpt_params(cfg, seed=0)
            gpt_forward(params, jnp.zeros((1, 16), jnp.int32), cfg)
            assert calls, "registered runner was never invoked"
        finally:
            ad._REGISTRY.pop("test_variant", None)

    # a site of each kind beside the training one: how the test's program
    # knows it (a shape nothing else uses), the model function that holds
    # it, and what the runner must be handed
    @staticmethod
    def _gpt_spec():
        from deepspeed_tpu.models.gpt import GPTConfig, make_gpt_decode_model
        return make_gpt_decode_model(cfg=GPTConfig(
            n_layer=1, n_head=2, d_model=64, max_seq_len=256, vocab_size=128,
            dtype=jnp.float32, remat=False))

    @staticmethod
    def _latent_spec():
        from deepspeed_tpu.models import glm4_moe_lite as gm
        from tests import glm_cases
        cfg = glm_cases._cfg(layers=2)
        return gm.make_glm4_moe_lite_decode_model(
            cfg, params=glm_cases._params(cfg), name="tiny"), cfg

    def _paged_call(self, spec, pool, C):
        """Trace `spec`'s decode (C == 1) or chunk program over `pool`."""
        i32 = jnp.int32
        if C == 1:
            jax.eval_shape(spec.decode_paged_fn, spec.params,
                           jnp.zeros((3,), i32), jnp.full((3,), 50, i32),
                           pool, jnp.ones((3, 2), i32))
        else:
            jax.eval_shape(spec.prefill_paged_fn, spec.params,
                           jnp.zeros((1, C), i32), jnp.zeros((1,), i32),
                           jnp.zeros((1,), i32), pool, jnp.ones((1, 2), i32))

    @pytest.mark.parametrize("case", ["paged_decode", "prefill_chunk",
                                      "latent_decode", "latent_chunk"])
    def test_a_paged_site_runs_a_registered_runner(self, case):
        """`(q, pool_l, block_tables, start, *, sm_scale, window, work)`,
        and `rank` at a latent site: q [B, C, H, hd], the pool's leaves, the
        tables, each row's first position; the decode sites hand the work
        list they built outside their layer loop."""
        latent = case.startswith("latent")
        C = 1 if case.endswith("decode") else 48
        B = 3 if C == 1 else 1
        calls = []

        def runner(q, pool_l, block_tables, start, *, sm_scale, window,
                   work=None, rank=None):
            calls.append(dict(q=q.shape, leaves=sorted(pool_l),
                              tables=block_tables.shape, start=start.shape,
                              sm_scale=sm_scale, window=window,
                              work=work is not None, rank=rank))
            return jnp.zeros(q.shape[:2] + (q.shape[2] * (rank or q.shape[3]),),
                             q.dtype)

        ad.register_program(ad.AttentionProgram(
            name="test_variant", phases=("paged_decode", "prefill_chunk"),
            priority=999, when="test fixture", runner=runner,
            matches=lambda s: s.block_size == 48 and s.latent == latent))
        try:
            if latent:
                spec, cfg = self._latent_spec()
                pool = spec.init_paged_pool(5, 48, jnp.float32)
                self._paged_call(spec, pool, C)
                assert len(calls) == cfg.n_layer
                want = dict(q=(B, C, cfg.n_head, pool["ckv"].shape[-1]),
                            leaves=["ckv"],
                            rank=cfg.kv_lora_rank,
                            sm_scale=1 / np.sqrt(cfg.head_dim))
            else:
                spec = self._gpt_spec()
                self._paged_call(spec, spec.init_paged_pool(5, 48,
                                                            jnp.float32), C)
                assert len(calls) == 1
                want = dict(q=(B, C, 2, 32), leaves=["k", "v"], rank=None,
                            sm_scale=None)
            assert calls[0] == dict(want, tables=(B, 2), start=(B,),
                                    window=None, work=C == 1)
            assert spec.paged_attn_programs[case.replace("latent_", "paged_")
                                            if C == 1 else "prefill_chunk"] \
                == "test_variant"
        finally:
            ad._REGISTRY.pop("test_variant", None)

    def test_the_contiguous_decode_site_runs_a_registered_runner(self):
        """`(q, cache_k, cache_v, pos, *, sm_scale)`: q [B, H, hd] and the
        head-major cache, the new token already written."""
        calls = []

        def runner(q, cache_k, cache_v, pos, *, sm_scale):
            calls.append((q.shape, cache_k.shape, cache_v.shape, pos.shape,
                          sm_scale))
            return jnp.zeros_like(q)

        ad.register_program(ad.AttentionProgram(
            name="test_variant", phases=("decode",), priority=999,
            matches=lambda s: s.kv_len == 136, when="test fixture",
            runner=runner))
        try:
            spec = self._gpt_spec()
            jax.eval_shape(spec.decode_fn, spec.params,
                           jnp.zeros((3,), jnp.int32),
                           jnp.zeros((3,), jnp.int32),
                           spec.init_cache(3, 136, jnp.float32))
            assert calls == [((3, 2, 32), (3, 2, 136, 32), (3, 2, 136, 32),
                              (3,), None)]
        finally:
            ad._REGISTRY.pop("test_variant", None)

    def test_a_runnerless_program_on_an_int8_pool_dequantizes(self):
        """What has no runner is the site's gather oracle, which reads what
        the POOL holds: a program of any name on an int8 pool attends the
        dequantized K/V (no name can read an int8 payload as K/V)."""
        spec = self._gpt_spec()
        rng = np.random.default_rng(0)
        pool = spec.init_paged_pool(5, 48, jnp.int8, 32)
        pool = {leaf: jnp.asarray(
            rng.integers(-127, 128, x.shape) if x.dtype == jnp.int8
            else rng.uniform(0.01, 0.02, x.shape), x.dtype)
            for leaf, x in pool.items()}
        args = (spec.params, jnp.asarray([5, 9, 3], jnp.int32),
                jnp.full((3,), 50, jnp.int32), pool,
                jnp.asarray([[1, 2], [3, 4], [1, 3]], jnp.int32))
        want, _ = spec.decode_paged_fn(*args)
        assert spec.paged_attn_programs["paged_decode"] == "paged_gather_quant"
        ad.register_program(ad.AttentionProgram(
            name="test_variant", phases=("paged_decode",), priority=999,
            matches=lambda s: s.block_size == 48, when="test fixture"))
        try:
            got, _ = spec.decode_paged_fn(*args)
            assert spec.paged_attn_programs["paged_decode"] == "test_variant"
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        finally:
            ad._REGISTRY.pop("test_variant", None)

    def test_selection_is_total(self):
        for phase in ("train", "decode", "paged_decode", "prefill_chunk",
                      "verify"):
            assert ad.select(site(phase=phase, has_bias=True,
                                  has_window=True, scale_attn=False,
                                  q_len=7, kv_len=13))


class TestBackendConfigEndToEnd:
    def test_gpt_ring_backend_matches_default(self):
        """GPTConfig.attention_backend='ring' routes training attention
        through the registered ring program (no per-call-site wiring) and
        reproduces the default dense loss on a sequence mesh."""
        from deepspeed_tpu.comm import mesh as mesh_mod
        from deepspeed_tpu.config.core import MeshConfig
        from deepspeed_tpu.models.gpt import (GPTConfig, gpt_loss,
                                              init_gpt_params)
        mesh_mod.clear_mesh()
        mesh_mod.init_mesh(MeshConfig(data=2, sequence=4))
        cfg = GPTConfig(n_layer=2, n_head=4, d_model=64, d_ff=256,
                        max_seq_len=64, vocab_size=256, dtype=jnp.float32,
                        remat=False)
        ring_cfg = dataclasses.replace(cfg, attention_backend="ring")
        params = init_gpt_params(cfg, seed=0)
        batch = {"tokens": jnp.asarray(
            np.random.default_rng(0).integers(0, 256, (4, 33)), jnp.int32)}
        loss_ring = jax.jit(
            lambda p: gpt_loss(p, batch, None, cfg=ring_cfg))(params)
        loss_ref = jax.jit(
            lambda p: gpt_loss(p, batch, None, cfg=cfg))(params)
        np.testing.assert_allclose(float(loss_ring), float(loss_ref),
                                   rtol=2e-5, atol=2e-5)

    def test_ring_backend_without_mesh_falls_through(self):
        """attention_backend='ring' on a mesh-less run must not crash —
        the dispatch key's mesh_axes is empty, so auto programs carry."""
        from deepspeed_tpu.models.gpt import (GPTConfig, gpt_forward,
                                              init_gpt_params)
        cfg = GPTConfig(n_layer=1, n_head=2, d_model=32, max_seq_len=64,
                        vocab_size=64, dtype=jnp.float32, remat=False,
                        attention_backend="ring")
        params = init_gpt_params(cfg, seed=0)
        out = gpt_forward(params, jnp.zeros((1, 16), jnp.int32), cfg)
        assert np.isfinite(np.asarray(out)).all()


class TestCompileStability:
    @pytest.mark.serving
    def test_serving_compiles_one_program_per_bucket(self):
        """Dispatch decisions are trace-time-static: a serving trace still
        compiles exactly {decode_step: 1, prefill_step: 1}."""
        import deepspeed_tpu
        from deepspeed_tpu.inference.scheduler import Request
        from deepspeed_tpu.models.gpt import GPTConfig, make_gpt_decode_model
        cfg = GPTConfig(n_layer=2, n_head=2, d_model=64, d_ff=128,
                        max_seq_len=128, vocab_size=128, dtype=jnp.float32)
        spec = make_gpt_decode_model(cfg=cfg, name="dispatch-compile")
        engine = deepspeed_tpu.init_inference(
            spec, config={"dtype": "float32", "max_out_tokens": 128})
        serving = engine.serving(max_slots=2, max_context=128,
                                 prefill_chunk=16)
        rng = np.random.default_rng(0)
        reqs = [Request(uid=i, tokens=list(rng.integers(0, 128, 12 + i)),
                        max_new_tokens=8) for i in range(4)]
        done = serving.run(reqs)
        assert len(done) == 4
        assert_one_compile_each(serving)


class TestFlashRunsPerShard:
    """A compiled pallas_call is opaque to GSPMD: bare inside the engine's
    partitioned jit, its q/k/v are all-gathered over `data` and the whole
    batch runs on every chip (seen in the four-chip HLO, PR 21 — and in the
    CPU interpreter's HLO too). The flash runner hands the kernel one
    device's batch/head shard through shard_map instead."""

    def _qkv(self, B=4, T=128, H=4, hd=32):
        rng = np.random.default_rng(0)
        return tuple(jnp.asarray(rng.normal(0, 1, (B, T, H, hd)), jnp.float32)
                     for _ in range(3))

    @staticmethod
    def _jaxpr(q, k, v):
        # a fresh callable per trace: the runner reads the installed mesh at
        # trace time, and jax caches traces by function identity
        runner = ad.get_program("flash").runner
        return str(jax.make_jaxpr(lambda q, k, v: runner(q, k, v))(q, k, v))

    def test_shard_map_over_batch_and_heads(self, devices8):
        from deepspeed_tpu.comm import mesh as mesh_mod
        from deepspeed_tpu.config.core import MeshConfig
        q, k, v = self._qkv()
        runner = ad.get_program("flash").runner
        bare = runner(q, k, v)                         # no mesh: bare kernel
        assert "shard_map" not in self._jaxpr(q, k, v)

        mesh_mod.init_mesh(MeshConfig(data=4, tensor=2))
        jaxpr = self._jaxpr(q, k, v)
        assert "shard_map" in jaxpr and "pallas_call" in jaxpr
        # the kernel inside sees ONE device's share: B/4 x H/2 -> BH = 2
        assert "f32[2,128,32]" in jaxpr and "f32[16,128,32]" not in jaxpr
        sharded = jax.jit(lambda q, k, v: runner(q, k, v))
        np.testing.assert_allclose(np.asarray(sharded(q, k, v)),
                                   np.asarray(bare), rtol=1e-5, atol=1e-5)

        # compiled: no activation-shaped all-gather feeds the kernel
        spec = jax.sharding.NamedSharding(
            mesh_mod.get_mesh(),
            jax.sharding.PartitionSpec(mesh_mod.BATCH_AXES, None,
                                       mesh_mod.TENSOR_AXIS, None))
        placed = jax.device_put((q, k, v), spec)
        hlo = sharded.lower(*placed).compile().as_text()
        assert " all-gather(" not in hlo and " all-gather-start(" not in hlo

    def test_left_bare_when_nothing_divides_or_inside_a_shard_map(
            self, devices8):
        from deepspeed_tpu.comm import mesh as mesh_mod
        from deepspeed_tpu.config.core import MeshConfig
        mesh_mod.init_mesh(MeshConfig(data=8))
        q, k, v = self._qkv(B=4)                       # 4 rows, 8-way data
        assert "shard_map" not in self._jaxpr(q, k, v)
        q, k, v = self._qkv(B=8)
        assert "shard_map" in self._jaxpr(q, k, v)
        with mesh_mod.constraints_disabled():          # a shard_map body
            assert "shard_map" not in self._jaxpr(q, k, v)


# ----------------------------------------------------------------------
# the streaming flash kernel has no sequence cap (the bound the chunked
# escape hatch, deleted in PR 46, existed for)
# ----------------------------------------------------------------------


# the retired whole-slab VMEM cap: 4 double-buffered [T, D] k/v slabs in
# ~14 MiB of scoped VMEM (the bound the streaming kernels removed)
def _legacy_vmem_cap(d_head, itemsize):
    return (14 * 2**20) // (4 * d_head * itemsize)


def test_flash_streams_past_legacy_vmem_domain():
    """The HBM-streaming kernel has no whole-slab VMEM cap: seq 16384 at
    head_dim 128 bf16 (the shape that used to raise "VMEM domain") traces
    through the Pallas kernel, and flash_max_seq now reports the HBM-scale
    bound."""
    from deepspeed_tpu.ops.pallas.flash_attention import (flash_attention,
                                                          flash_max_seq)
    legacy = _legacy_vmem_cap(128, 2)
    assert 8192 <= legacy < 16384, legacy
    cap = flash_max_seq(128, 2)
    assert cap > 1_000_000, cap  # HBM-bound: millions of tokens, not ~14k
    q = jnp.zeros((1, 16384, 2, 128), jnp.bfloat16)
    jaxpr = str(jax.make_jaxpr(
        lambda q: flash_attention(q, q, q, causal=True))(q))
    assert "pallas_call" in jaxpr


def test_gpt_auto_dispatch_stays_in_kernel_beyond_legacy_cap():
    """models/gpt._attention: T past the legacy VMEM cap stays on the
    streaming flash kernel (the old routing degraded to a ~2.8x-slower
    rematerialized XLA fallback)."""
    from deepspeed_tpu.models.gpt import GPTConfig, gpt_forward, gpt_loss
    from deepspeed_tpu.models.gpt import init_gpt_params
    # tiny dims but a REAL beyond-legacy-cap T for head_dim 512 (the cap
    # scaled with 1/head_dim, so a modest T exercises the branch cheaply)
    hd = 512
    legacy = _legacy_vmem_cap(hd, 4)  # fp32 params -> itemsize 4
    T = 2048
    assert T > legacy, (T, legacy)
    cfg = GPTConfig(n_layer=1, n_head=1, d_model=hd, d_ff=512, max_seq_len=T,
                    vocab_size=256, dtype=jnp.float32, remat=False)
    params = init_gpt_params(cfg, seed=0)
    toks = jnp.zeros((1, T), jnp.int32)
    jaxpr = str(jax.make_jaxpr(
        lambda p, t: gpt_forward(p, t, cfg))(params, toks))
    assert "pallas_call" in jaxpr, "beyond-legacy-cap T left the kernel path"
    # and the kernel path trains: finite loss at a beyond-legacy-cap T
    rtoks = np.random.default_rng(0).integers(0, 256, (1, T + 1)).astype(np.int32)
    loss = float(gpt_loss(params, {"tokens": rtoks}, None, cfg=cfg))
    assert np.isfinite(loss)
