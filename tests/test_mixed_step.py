"""A step's prefill chunk rides its decode call (`inference/scheduler.py`'s
`mixed_step`, the models' `mixed_paged_fn`): one device call whose rows are
the chunk's and the slots', every weight read once.

The oracle is the scheduler itself with `_chunks_riding` stubbed to 0: the
same engine then runs every chunk and every decode window as the two calls
they were. Under greedy sampling the tokens must be identical.

Everything here rides the `serving` marker (tier-1).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.comm import mesh as mesh_mod
from deepspeed_tpu.config.core import MeshConfig
from deepspeed_tpu.inference.scheduler import Request, _DECODE, _HANDOFF
from deepspeed_tpu.models.gpt import GPTConfig, make_gpt_decode_model
from deepspeed_tpu.models.moe_gpt import (MoEGPTConfig, init_moe_gpt_params,
                                          make_moe_gpt_decode_model)
from tests import (exaone_cases, mimo_cases, nemotron_cases,
                   qwen3_next_cases)

pytestmark = pytest.mark.serving

CHUNK = 16
DENSE = GPTConfig(n_layer=2, n_head=4, n_kv_head=2, d_model=64,
                  max_seq_len=256, vocab_size=256, use_rotary=True,
                  dtype=jnp.float32, remat=False)
ROUTED = MoEGPTConfig(n_layer=2, n_head=4, d_model=64, d_ff=32,
                      max_seq_len=256, vocab_size=256, use_rotary=True,
                      use_swiglu=True, use_rmsnorm=True, num_experts=8,
                      top_k=2, moe_freq=1, dtype=jnp.float32, remat=False)

# prompts of under one chunk, exactly one, several, ending mid-chunk and on
# the grid; budgets of one token and of more than a window
LENGTHS = (5, 40, 17, 33, 64, 9, 16, 48)
NEW = (7, 3, 12, 5, 9, 1, 6, 10)
# ... and prompts of many chunks, for tokens that carry GROUPS of them: a
# group's three chunks are 48 positions, more than the small two-kind models'
# ring of 32 holds (window 8 + a chunk of 16, in blocks of 8, + 1), so one
# group's chunks wrap it; 100 and 90 end mid-chunk and mid-group
LONG = (5, 100, 17, 90, 64, 9, 112, 48)


def _one_device():
    mesh_mod.clear_mesh()
    mesh_mod.init_mesh(MeshConfig(data=1), devices=jax.devices()[:1])


def _engine(spec, **config):
    return deepspeed_tpu.init_inference(spec, config={
        "dtype": "float32", "kv_cache_dtype": "float32", "greedy": True,
        "kv_block_size": CHUNK, "max_out_tokens": 128, **config})


def _dense(**knobs):
    _one_device()
    engine = _engine(make_gpt_decode_model(cfg=DENSE, name="tiny"))
    return engine.serving(**{"max_slots": 4, "max_context": 128,
                             "prefill_chunk": CHUNK, **knobs})


def _routed(**knobs):
    _one_device()
    engine = _engine(make_moe_gpt_decode_model(
        ROUTED, params=init_moe_gpt_params(ROUTED, seed=1), name="routed"))
    return engine.serving(**{"max_slots": 4, "max_context": 128,
                             "prefill_chunk": CHUNK, **knobs})


def _two_kinds(**knobs):
    """The EXAONE family at its small size: window layers' rings beside
    full layers' blocks, routed experts, on ONE device."""
    cfg = exaone_cases._cfg()
    return exaone_cases._serving(cfg, exaone_cases._params(cfg),
                                 one_device=True, max_slots=4, **knobs)[1]


def _sink_kinds(**knobs):
    """The MiMo family at its small size: the two kinds with their own KV
    heads, a learned sink in the window layers, keys wider than values."""
    cfg = mimo_cases._cfg()
    return mimo_cases._serving(cfg, mimo_cases._params(cfg), one_device=True,
                               max_slots=4, **knobs)[1]


def _hybrid(cases):
    """A hybrid family at its small size: recurrent layers' state rows (and
    convolution tails) beside attention layers' blocks, routed experts."""
    def serving(**knobs):
        cfg = cases._cfg()
        return cases._serving(cfg, cases._params(cfg), one_device=True,
                              max_slots=4, **knobs)[1]
    return serving


def _one_chunk_a_token(**knobs):
    """The dense family as a family whose mixed program takes no group."""
    import dataclasses
    _one_device()
    spec = dataclasses.replace(make_gpt_decode_model(cfg=DENSE, name="tiny"),
                               mixed_chunk_groups=False)
    return _engine(spec).serving(**{"max_slots": 4, "max_context": 128,
                                    "prefill_chunk": CHUNK, **knobs})


FAMILIES = {"dense": _dense, "routed": _routed, "two_kinds": _two_kinds,
            "sink_kinds": _sink_kinds, "ungrouped": _one_chunk_a_token,
            "mamba": _hybrid(nemotron_cases),
            "deltanet": _hybrid(qwen3_next_cases)}


def _two_calls(serving):
    """The oracle: nothing rides, every chunk and window is its own call."""
    serving._chunks_riding = lambda due, decoding: 0
    return serving


def _requests(lengths=LENGTHS, new=NEW, vocab=128, seed=0, **kwargs):
    rng = np.random.default_rng(seed)
    return [Request(uid=i, tokens=rng.integers(0, vocab, (n,), np.int32),
                    max_new_tokens=m, stop_on_eos=False, **kwargs)
            for i, (n, m) in enumerate(zip(lengths, new))]


def _tokens(done):
    return {uid: d.tokens.tolist() for uid, d in done.items()}


def _ring_sums(serving, fields):
    recs = serving.steptrace.records()
    return {f: sum(getattr(r, f) for r in recs) for f in fields}


# ----------------------------------------------------------------------
# token identity against the two-call path
# ----------------------------------------------------------------------


def _group(knobs):
    """G of an engine with `knobs` whose mixed program takes a group."""
    return -(-knobs.get("prefill_chunks_per_step", 1)
             // knobs["decode_steps_per_sync"])


@pytest.mark.parametrize("family,knobs,lengths", [
    ("dense", dict(decode_steps_per_sync=1), LENGTHS),
    ("dense", dict(decode_steps_per_sync=4), LENGTHS),
    ("dense", dict(decode_steps_per_sync=4, prefill_chunks_per_step=6),
     LENGTHS),
    ("dense", dict(decode_steps_per_sync=1, prefill_chunks_per_step=3),
     LENGTHS),
    ("routed", dict(decode_steps_per_sync=1), LENGTHS),
    ("routed", dict(decode_steps_per_sync=3, prefill_chunks_per_step=2),
     LENGTHS),
    ("two_kinds", dict(decode_steps_per_sync=1), LENGTHS),
    ("two_kinds", dict(decode_steps_per_sync=3, prefill_chunks_per_step=4),
     LENGTHS),
    # tokens that carry groups of up to three chunks (G = ceil(budget /
    # window)), the last group of a step partial
    ("dense", dict(decode_steps_per_sync=2, prefill_chunks_per_step=5), LONG),
    ("dense", dict(decode_steps_per_sync=1, prefill_chunks_per_step=3), LONG),
    ("routed", dict(decode_steps_per_sync=2, prefill_chunks_per_step=5),
     LONG),
    ("two_kinds", dict(decode_steps_per_sync=2, prefill_chunks_per_step=6),
     LONG),
    ("two_kinds", dict(decode_steps_per_sync=1, prefill_chunks_per_step=3),
     LONG),
    ("sink_kinds", dict(decode_steps_per_sync=2, prefill_chunks_per_step=6),
     LONG),
    ("sink_kinds", dict(decode_steps_per_sync=3, prefill_chunks_per_step=4),
     LONG),
    # the state halves run a group's chunks in order too, each on the state
    # and the convolution tail the one before it wrote back
    ("mamba", dict(decode_steps_per_sync=2, prefill_chunks_per_step=5), LONG),
    ("deltanet", dict(decode_steps_per_sync=2, prefill_chunks_per_step=6),
     LONG),
    ("deltanet", dict(decode_steps_per_sync=3, prefill_chunks_per_step=3),
     LONG),
    # a family whose mixed program takes no group: one chunk a token
    ("ungrouped", dict(decode_steps_per_sync=2, prefill_chunks_per_step=5),
     LONG),
], ids=lambda v: v if isinstance(v, str) else "long" if v is LONG
    else "short" if v is LENGTHS else "-".join(
        f"{k[0]}{n}" for k, n in v.items()))
def test_fused_tokens_and_counts_equal_the_two_call_paths(family, knobs,
                                                          lengths):
    fused = FAMILIES[family](**knobs)
    oracle = _two_calls(FAMILIES[family](**knobs))
    got = fused.run(_requests(lengths))
    want = oracle.run(_requests(lengths))
    assert _tokens(got) == _tokens(want)
    G = 1 if family == "ungrouped" else _group(knobs)
    assert fused.programs.group == G

    # it engaged, and often: all but the chunks that found nobody decoding
    assert oracle.fused_chunks == 0 and "mixed_step" not in \
        oracle.compile_stats()
    assert fused.fused_chunks >= 5
    assert fused.stats()["fused_chunks"] == fused.fused_chunks
    # every program at one compile over the whole ragged trace
    assert fused.compile_stats()["mixed_step"] == 1
    assert set(fused.compile_stats().values()) <= {0, 1}
    recs = fused.steptrace.records()
    assert sum(r.compiles for r in recs) == sum(
        fused.compile_stats().values())

    # a fused call is up to G chunks a token AND one decode step
    assert fused.prefill_chunks == oracle.prefill_chunks
    assert fused.tokens_generated == oracle.tokens_generated
    assert fused.decode_steps == sum(1 for r in recs if r.decoding)
    assert all(r.fused_chunks <= min(r.prefill_chunks, fused.window * G)
               and (r.decoding or not r.fused_chunks) for r in recs)
    # full groups first: a step's groups are the fewest that hold its riding
    # chunks, and what they lack of G chunks each is padding
    assert all(r.chunk_groups == -(-r.fused_chunks // G)
               and r.padded_chunks == r.chunk_groups * G - r.fused_chunks
               for r in recs)
    assert fused.stats()["chunk_groups"] == sum(r.chunk_groups for r in recs)
    assert fused.stats()["padded_chunks"] == sum(r.padded_chunks
                                                 for r in recs)
    if G > 1 and lengths is LONG:
        assert any(r.fused_chunks > fused.window for r in recs)
        assert fused.padded_chunks > 0
    else:
        assert G > 1 or fused.padded_chunks == 0
    sums = ("prefill_chunks", "emitted", "admitted", "decode_live_blocks",
            "decode_window_live_blocks", "decode_window_table_blocks",
            "prefill_live_blocks", "prefill_table_blocks",
            "prefill_kept_pairs", "prefill_window_kept_pairs")
    assert _ring_sums(fused, sums) == _ring_sums(oracle, sums)
    assert _ring_sums(fused, ("fused_chunks",)) == {
        "fused_chunks": fused.fused_chunks}
    if fused.step_counter_names:
        # the routed experts see the same assignments (and a padded chunk's
        # rows); a fused call's router runs once a layer over the chunks'
        # rows and the slots' together
        for serving in (fused, oracle):
            counted = serving.stats()["step_counters"]
            calls = serving.prefill_chunks - serving.fused_chunks \
                + serving.decode_steps * serving.window
            rows = (serving.prefill_chunks + serving.padded_chunks) * CHUNK \
                + serving.decode_steps * serving.window * serving.max_slots
            assert counted["moe_router_calls"] % calls == 0
            routed_layers = counted["moe_router_calls"] // calls
            assert counted["moe_assignments"] % (rows * routed_layers) == 0
    # the pool drains as it did
    assert fused.allocator.num_free == fused.allocator.capacity


@pytest.mark.parametrize("window", [1, 3])
def test_on_the_in_place_pool_both_groups_run_their_kernels(monkeypatch,
                                                             window):
    """The chip's form, steered on (the CPU's own rule declines it; the
    kernels run in the interpreter): the mixed program writes with
    `dstpu_kv_pool_write`, walks the chunk with `dstpu_paged_prefill` and the
    slots with `dstpu_paged_decode`, and the tokens and the walks' counts
    are the two-call path's."""
    from deepspeed_tpu.ops import attention_dispatch
    monkeypatch.setattr(attention_dispatch, "kv_pool_writer",
                        lambda pool: attention_dispatch.KV_POOL_WRITE_KERNEL)
    cfg = GPTConfig(n_layer=2, n_head=2, n_kv_head=1, d_model=256, d_ff=128,
                    max_seq_len=512, vocab_size=256, use_rotary=True,
                    use_flash_attention=True, dtype=jnp.float32, remat=False)

    def serving():
        _one_device()
        engine = deepspeed_tpu.init_inference(
            make_gpt_decode_model(cfg=cfg, name="tiny"), config={
                "dtype": "float32", "kv_cache_dtype": "float32",
                "greedy": True, "kv_block_size": 128, "max_out_tokens": 512})
        return engine.serving(max_slots=3, max_context=512, prefill_chunk=128,
                              decode_steps_per_sync=window,
                              prefill_chunks_per_step=2)

    reqs = _requests((200, 70, 300, 128), (5, 9, 4, 6), vocab=256)
    fused, oracle = serving(), _two_calls(serving())
    got, want = fused.run(reqs), oracle.run(reqs)
    assert _tokens(got) == _tokens(want)
    assert fused.fused_chunks >= 2
    assert fused.stats()["attention_program"]["mixed_step"] \
        == "paged_prefill_kernel+paged_kernel"
    assert fused.stats()["kv_pool_writer"]["mixed_step"] \
        == attention_dispatch.KV_POOL_WRITE_KERNEL
    sums = ("prefill_chunks", "prefill_live_blocks", "prefill_table_blocks",
            "decode_live_blocks")
    assert _ring_sums(fused, sums) == _ring_sums(oracle, sums)
    assert _ring_sums(fused, sums)["prefill_live_blocks"] > 0


# ----------------------------------------------------------------------
# the cases the window makes
# ----------------------------------------------------------------------


def _step_until(serving, cond, limit=200):
    for _ in range(limit):
        if cond():
            return
        serving.step()
    raise AssertionError("condition never held")


def test_a_prompts_chunks_ride_consecutive_tokens_of_one_window():
    serving = _dense(max_slots=2, decode_steps_per_sync=4,
                     prefill_chunks_per_step=4)
    short, long = _requests((5, 60), (40, 4))
    serving.submit(short)
    _step_until(serving, lambda: any(s.state == _DECODE
                                     for s in serving.slots))
    serving.submit(long)
    serving.step()
    rec = serving.steptrace.records()[-1]
    # 60 tokens are four chunks, the last ending mid-chunk: all four rode
    # the ONE decode call of this step, and the prompt's first token came
    # back with the window's tokens
    assert (rec.prefill_chunks, rec.fused_chunks, rec.decoding) == (4, 4, 1)
    slot = next(s for s in serving.slots if s.uid == long.uid)
    # the call is in flight: the slot decodes from the next one, on a token
    # that stays on the device; what a reader may count has not moved yet
    assert slot.state == _DECODE and slot.flying == 1 and slot.pos == 60
    assert not slot.emitted and slot.cursor == 0 and slot.planned == 64
    serving.step()          # ... which the next step reads back
    assert len(slot.emitted) == 1 and slot.cursor == 64
    done = {}
    while serving.queue or serving.num_active:
        done.update({d.uid: d for d in serving.step()})
    want = _two_calls(_dense(max_slots=2, decode_steps_per_sync=4,
                             prefill_chunks_per_step=4)).run([short, long])
    assert done[long.uid].tokens.tolist() == want[long.uid].tokens.tolist()
    assert serving.compile_stats()["mixed_step"] == 1


@pytest.mark.parametrize("family,rode,groups,padded", [
    # one chunk a token (G = 1): the last two of the five ride the window's
    # two tokens, the three before them run first as calls of their own
    ("ungrouped", 2, 2, 0),
    # G = ceil(5 / 2) = 3: window * G covers every budget's worth of chunks,
    # so all five ride — three on the first token, two and a padded one on
    # the second — and no `prefill_step` call goes out
    ("dense", 5, 2, 1),
])
def test_chunks_beyond_window_times_g_run_first_as_their_own_calls(
        family, rode, groups, padded):
    serving = FAMILIES[family](max_slots=2, decode_steps_per_sync=2,
                               prefill_chunks_per_step=5)
    short, long = _requests((5, 80), (40, 4))
    serving.submit(short)
    _step_until(serving, lambda: any(s.state == _DECODE
                                     for s in serving.slots))
    serving.submit(long)
    serving.step()
    rec = serving.steptrace.records()[-1]
    assert (rec.prefill_chunks, rec.fused_chunks) == (5, rode)
    assert (rec.chunk_groups, rec.padded_chunks) == (groups, padded)
    phases = [name for name, _ in rec.phases]
    assert phases.count("serving/decode_window") == 1
    assert phases.count("serving/prefill_chunk") == (5 > rode)
    slot = next(s for s in serving.slots if s.uid == long.uid)
    assert slot.state == _DECODE and slot.planned == 80
    serving.step()          # the read-back that covers the five chunks
    assert slot.cursor == 80


def test_a_final_chunk_mid_group_feeds_the_next_call_its_first_token():
    """Two prompts' chunks in one step's groups (G = 3), the slots in order:
    [B0 B1 A0] ride the window's first token and [A1 A2 A3] its second — B's
    last chunk in the middle of its group, A's last chunk last — and both
    prompts decode from the NEXT call on first tokens that stay on the
    device (chunks 1 and 5 of the call's six)."""
    knobs = dict(max_slots=3, decode_steps_per_sync=2,
                 prefill_chunks_per_step=6)
    serving = _dense(**knobs)
    assert serving.programs.group == 3
    short, a, b = _requests((5, 60, 20), (40, 4, 5))
    serving.submit(short)
    _step_until(serving, lambda: any(s.state == _DECODE
                                     for s in serving.slots))
    serving.submit(a)
    serving.submit(b)
    serving.step()
    rec = serving.steptrace.records()[-1]
    assert (rec.prefill_chunks, rec.fused_chunks, rec.chunk_groups,
            rec.padded_chunks, rec.decoding) == (6, 6, 2, 0, 1)
    call = serving._pending
    slots = {s.uid: s for s in serving.slots}
    assert [s.uid for s in serving.slots] == [b.uid, a.uid, short.uid]
    assert [(s.uid, i) for s, i in call.firsts] == [(b.uid, 1), (a.uid, 5)]
    assert slots[b.uid].feed == (call.id, 2 + 1)
    assert slots[a.uid].feed == (call.id, 2 + 5)
    assert all(slots[r.uid].state == _DECODE and slots[r.uid].flying == 1
               and not slots[r.uid].emitted for r in (a, b))
    assert jax.tree_util.tree_map(np.shape, call.out[0]) == ((6,), (3, 2))
    done = {}
    while serving.queue or serving.num_active:
        done.update({d.uid: d for d in serving.step()})
    want = _two_calls(_dense(**knobs)).run([short, a, b])
    assert _tokens(done) == _tokens(want)
    assert serving.compile_stats()["mixed_step"] == 1


def test_a_slot_that_retires_mid_window_while_a_chunk_rides():
    knobs = dict(max_slots=2, decode_steps_per_sync=4)
    reqs = _requests((5, 40), (7, 6))       # first, a window, two of the next
    fused, oracle = _dense(**knobs), _two_calls(_dense(**knobs))
    fused.submit(reqs[0])
    _step_until(fused, lambda: any(s.state == _DECODE for s in fused.slots))
    fused.submit(reqs[1])
    assert fused.step() == []       # the end by count is decided at dispatch
    rec = fused.steptrace.records()[-1]
    assert rec.fused_chunks == 1 and rec.decoding == 1
    assert 0 not in [s.uid for s in fused.slots] and fused.num_active == 2
    finished = fused.step()         # ... and delivered when the call is read
    assert [d.uid for d in finished] == [0] and len(finished[0].tokens) == 7
    done = {0: finished[0]}
    while fused.queue or fused.num_active:
        done.update({d.uid: d for d in fused.step()})
    assert _tokens(done) == _tokens(oracle.run(reqs))
    assert fused.allocator.num_free == fused.allocator.capacity


def test_eos_on_a_first_token_that_rode_retires_the_slot_at_once():
    knobs = dict(max_slots=2)
    reqs = _requests((5, 20), (30, 8))
    free = _two_calls(_dense(**knobs)).run(reqs)
    eos = int(free[1].tokens[0])
    serving = _dense(**knobs)
    serving.submit(reqs[0])
    _step_until(serving, lambda: any(s.state == _DECODE
                                     for s in serving.slots))
    serving.submit(Request(uid=1, tokens=reqs[1].tokens, max_new_tokens=8,
                           eos_token_id=eos))
    _step_until(serving, lambda: serving.fused_chunks == 2)
    serving.step()          # the read-back that brings the first token
    done = serving.steptrace.requests()[-1]
    assert (done.uid, done.finish_reason, done.emitted) == (1, "eos", 1)


# ----------------------------------------------------------------------
# what has to stay honest around the call
# ----------------------------------------------------------------------


def test_the_prefix_cache_registers_the_same_blocks():
    def served(stub):
        serving = _dense(max_slots=2, enable_prefix_caching=True,
                         num_kv_blocks=24)
        if stub:
            _two_calls(serving)
        shared = np.arange(48, dtype=np.int32) % 97
        reqs = [Request(uid=i, tokens=np.concatenate(
            [shared, np.full((3 + i,), 100 + i, np.int32)]),
            max_new_tokens=5, stop_on_eos=False) for i in range(4)]
        done = {}
        for req in reqs:        # a wave a request: each finds the last one's
            serving.submit(req)
            serving.step()
        while serving.queue or serving.num_active:
            done.update({d.uid: d for d in serving.step()})
        return serving, done

    fused, got = served(False)
    oracle, want = served(True)
    assert fused.fused_chunks > 0
    assert _tokens(got) == _tokens(want)
    assert {u: d.cached_prefix_tokens for u, d in got.items()} == {
        u: d.cached_prefix_tokens for u, d in want.items()}
    a, b = fused.stats()["prefix_cache"], oracle.stats()["prefix_cache"]
    assert a == b and a["hit_blocks"] > 0
    assert fused.allocator.available == fused.allocator.capacity


def test_a_prefill_only_slot_whose_last_chunk_rode_parks_for_handoff():
    serving = _dense(max_slots=2)
    decoding, parked = _requests((5, 20), (30, 8))
    serving.submit(decoding)
    _step_until(serving, lambda: any(s.state == _DECODE
                                     for s in serving.slots))
    serving.submit(parked, prefill_only=True)
    _step_until(serving, lambda: serving.handoff_ready())
    assert serving.fused_chunks == 2
    slot = next(s for s in serving.slots if s.uid == parked.uid)
    assert slot.state == _HANDOFF and len(slot.emitted) == 1
    want = _two_calls(_dense(max_slots=2)).run([parked])
    assert slot.emitted[0] == want[parked.uid].tokens[0]
    state = serving.export_handoff(parked.uid)
    assert state["pos"] == 20 and len(state["emitted"]) == 1


def test_tracer_and_tpot_see_the_chunk_and_the_window(tmp_path):
    from deepspeed_tpu.telemetry.tracing import load_spans
    _one_device()
    engine = _engine(make_gpt_decode_model(cfg=DENSE, name="tiny"),
                     telemetry={"enabled": True, "tracing": True,
                                "prometheus": False, "jsonl": False,
                                "output_path": str(tmp_path)})
    serving = engine.serving(max_slots=4, max_context=128,
                             prefill_chunk=CHUNK)
    serving.run(_requests())
    assert serving.fused_chunks > 0
    lat = serving.latency_snapshot()
    assert lat["ttft_ms"]["count"] == len(LENGTHS)
    assert lat["tpot_ms"]["count"] == sum(NEW) - len(NEW)
    spans = load_spans(tmp_path / "serving.trace.jsonl")
    chunks = [s for s in spans if s["name"] == "prefill_chunk"]
    assert len(chunks) == serving.prefill_chunks
    assert sum(1 for s in chunks if s.get("attrs", {}).get("fused")) \
        == serving.fused_chunks
    windows = [s for s in spans if s["name"] == "decode_window"]
    assert sum(s["attrs"]["emitted"] for s in windows) == sum(NEW) - len(NEW)


# ----------------------------------------------------------------------
# where it never engages
# ----------------------------------------------------------------------


def test_the_rule_is_what_the_step_holds():
    serving = _dense(decode_steps_per_sync=4)
    assert serving._chunks_riding(0, 3) == 0         # no chunk due
    assert serving._chunks_riding(2, 0) == 0         # nobody decoding
    assert serving._chunks_riding(1, 1) == 1
    assert serving._chunks_riding(9, 2) == 4         # one a window token
    grouped = _dense(decode_steps_per_sync=4, prefill_chunks_per_step=10)
    assert grouped.programs.group == 3               # ceil(10 / 4)
    assert grouped._chunks_riding(9, 2) == 9
    assert grouped._chunks_riding(40, 2) == 12       # window * G
    # a family whose mixed program takes no group: one a token, whatever
    # the budget
    single = _one_chunk_a_token(decode_steps_per_sync=4,
                                prefill_chunks_per_step=10)
    assert single.programs.group == 1
    assert single._chunks_riding(9, 2) == 4


@pytest.mark.parametrize("family,knobs", [
    ("dense", dict(decode_steps_per_sync=2)),
    ("dense", dict(decode_steps_per_sync=4, prefill_chunks_per_step=4)),
    ("routed", dict(decode_steps_per_sync=3, prefill_chunks_per_step=2)),
    ("two_kinds", dict(decode_steps_per_sync=3, prefill_chunks_per_step=3)),
    ("ungrouped", dict(decode_steps_per_sync=2, prefill_chunks_per_step=7)),
], ids=lambda v: v if isinstance(v, str) else "-".join(
    f"{k[0]}{n}" for k, n in v.items()))
def test_within_the_window_the_mixed_programs_arguments_are_one_chunk_a_token(
        monkeypatch, family, knobs):
    """`prefill_chunks_per_step` <= `decode_steps_per_sync` (or a family
    without `mixed_chunk_groups`): G = 1, and the mixed program takes what
    it took before a token could carry a group — a chunk a window position,
    no count — at one compile."""
    serving = FAMILIES[family](**knobs)
    S, W, C = serving.max_slots, serving.window, serving.chunk
    assert serving.programs.group == 1
    assert jax.tree_util.tree_map(np.shape, serving.programs.no_prev) \
        == ((W,), (S, W))
    fn, args = _examples(serving)["mixed_step"]
    _, chunks, starts, lasts, chunk_tables, n = args[:6]
    assert (chunks.shape, starts.shape, lasts.shape, np.shape(n)) \
        == ((W, 1, C), (W, 1), (W, 1), ())
    assert all(t.shape[:2] == (W, 1)
               for t in jax.tree_util.tree_leaves(chunk_tables))
    # no traced count reaches the model: its tables' `count` stays None
    from deepspeed_tpu.models import gpt
    counts, tables = [], gpt.mixed_tables
    monkeypatch.setattr(gpt, "mixed_tables", lambda chunk, slots, count: (
        counts.append(count), tables(chunk, slots, count))[1])
    serving.run(_requests())
    assert counts == [None]
    assert serving.fused_chunks > 0 and serving.padded_chunks == 0
    assert serving.chunk_groups == serving.fused_chunks
    assert serving.compile_stats()["mixed_step"] == 1


def test_spec_decode_never_engages_it():
    serving = _dense(spec_decode={"drafter": "ngram", "draft_k": 2})
    assert serving.programs.mixed is None
    serving.run(_requests())
    assert serving.fused_chunks == 0
    assert "mixed_step" not in serving.compile_stats()


def test_pressure_degraded_steps_never_engage_it():
    serving = _dense(decode_steps_per_sync=2, degradation={
        "enabled": True, "eval_interval": 1, "queue_high": 2,
        "queue_low": 1, "hold_steps": 2})
    assert serving._chunks_riding(1, 1) == 1         # the ladder at rest
    serving.pressure.level = 1
    assert serving._chunks_riding(1, 1) == 0
    serving.pressure.level = 3                       # window forced to 1
    done = {}
    for req in _requests():
        serving.submit(req)
    while serving.queue or serving.num_active:
        before = serving.fused_chunks
        level = serving.pressure.level
        done.update({d.uid: d for d in serving.step()})
        assert level == 0 or serving.fused_chunks == before
    want = _two_calls(_dense(decode_steps_per_sync=2)).run(_requests())
    assert _tokens(done) == _tokens(want)


def test_a_mesh_never_engages_it():
    mesh_mod.clear_mesh()
    mesh_mod.init_mesh(MeshConfig(data=2), devices=jax.devices()[:2])
    engine = _engine(make_gpt_decode_model(cfg=DENSE, name="tiny"))
    serving = engine.serving(max_slots=4, max_context=128,
                             prefill_chunk=CHUNK)
    assert serving._chunks_riding(1, 1) == 0
    done = serving.run(_requests())
    assert serving.fused_chunks == 0
    assert "mixed_step" not in serving.compile_stats()
    assert _tokens(done) == _tokens(_two_calls(_dense()).run(_requests()))


def test_streamed_serving_never_engages_it():
    from deepspeed_tpu.models.gpt import make_gpt_layered_model
    _one_device()
    plain = GPTConfig(n_layer=2, n_head=4, d_model=64, max_seq_len=256,
                      vocab_size=256, dtype=jnp.float32, remat=False)
    engine = _engine(make_gpt_layered_model(cfg=plain, name="spill"),
                     zero={"offload_param": {"device": "cpu",
                                             "lookahead": 2}})
    serving = engine.serving(max_slots=2, max_context=128,
                             prefill_chunk=CHUNK)
    assert serving.streamed and serving.programs.mixed is None
    serving.run(_requests(LENGTHS[:3], NEW[:3]))
    assert serving.fused_chunks == 0
    assert all(r.fused_chunks == 0 for r in serving.steptrace.records())
    assert "mixed_step" not in serving.compile_stats()


def test_a_model_without_the_mixed_program_keeps_its_two_calls():
    import dataclasses
    _one_device()
    spec = dataclasses.replace(make_gpt_decode_model(cfg=DENSE, name="tiny"),
                               mixed_paged_fn=None)
    serving = _engine(spec).serving(max_slots=4, max_context=128,
                                    prefill_chunk=CHUNK)
    done = serving.run(_requests())
    assert serving.fused_chunks == 0
    assert _tokens(done) == _tokens(_dense().run(_requests()))


# ----------------------------------------------------------------------
# one shape, one home (`inference/step_programs.py`): every step program
# returns ((tokens...), counts), pool — counts the model's counters or ()
# ----------------------------------------------------------------------


def _examples(serving):
    return {name: (fn, args) for name, fn, args in serving.programs.examples(
        serving.engine.params, serving.pool,
        serving._tables_arg(serving.tables), serving._rng)}


@pytest.mark.parametrize("family", ["dense", "routed"])
@pytest.mark.parametrize("program", ["decode_step", "prefill_step",
                                     "mixed_step"])
def test_a_step_program_returns_tokens_counts_and_pool(family, program):
    serving = FAMILIES[family](decode_steps_per_sync=2)
    fn, args = _examples(serving)[program]
    (toks, counts), pool = jax.eval_shape(fn, *args)
    S, W = serving.max_slots, serving.window
    assert jax.tree_util.tree_map(lambda t: t.shape, toks) == {
        "decode_step": (S, W), "prefill_step": (1,),
        "mixed_step": ((W,), (S, W))}[program]
    names = serving.step_counter_names
    assert bool(names) == (family == "routed")
    if names:
        assert counts.shape == (len(names),) and counts.dtype == jnp.int32
    else:
        assert counts == ()
    assert jax.tree_util.tree_map(lambda x: (x.shape, x.dtype), pool) \
        == jax.tree_util.tree_map(lambda x: (x.shape, x.dtype), serving.pool)


@pytest.mark.parametrize("family", ["dense", "routed"])
def test_counters_are_reported_only_where_the_model_names_some(family):
    """The programs' arity is one; what is REPORTED is not: a model without
    counters keeps `StepRecord.counters == ()` and no `step_counters` key."""
    serving = FAMILIES[family](decode_steps_per_sync=2)
    requests = _requests(LENGTHS[:4], NEW[:4])
    done = serving.run(requests)
    assert {uid: len(d.tokens) for uid, d in done.items()} \
        == {r.uid: r.max_new_tokens for r in requests}
    assert serving.fused_chunks and serving._parked_counts == []
    records, names = serving.steptrace.records(), serving.step_counter_names
    if family == "dense":
        assert names == () and all(r.counters == () for r in records)
        assert "step_counters" not in serving.stats()
    else:
        assert all(len(r.counters) == len(names) for r in records)
        totals = np.sum([r.counters for r in records], axis=0)
        # ... and beside the model's, the decode walks' pairs and the rows
        # they moved (whole blocks: these tables' blocks are one tile)
        walks = {f: sum(getattr(r, f) for r in records)
                 for f in ("decode_live_blocks", "decode_walk_rows")}
        assert walks["decode_walk_rows"] \
            == walks["decode_live_blocks"] * serving.block_size > 0
        assert dict(zip(names, totals), **walks) \
            == serving.stats()["step_counters"]
        # every token of every call routed once a layer, chunks included
        assert totals[names.index("moe_router_calls")] > 0


def _memscope_programs(serving):
    from deepspeed_tpu.telemetry.memscope import ServingMemScope
    return [name for name, _, _ in ServingMemScope(serving)._program_args()]


def test_the_programs_name_themselves_once_resident():
    """`compile_stats()`, the compile count a step and memscope read ONE
    list, the programs' own."""
    serving = _dense(decode_steps_per_sync=2)
    built = [name for name, _ in serving.programs.built()]
    assert built == ["decode_step", "prefill_step", "mixed_step"]
    assert _memscope_programs(serving) == built
    assert serving.compile_stats() == {"decode_step": 0, "prefill_step": 0}
    serving.run(_requests(LENGTHS[:3], NEW[:3]))
    assert serving.fused_chunks
    assert list(serving.compile_stats()) == built
    assert serving._compiled_programs() == 3
    w1 = serving.programs.decode_w1()
    assert w1 is serving.programs.decode_w1() is not serving.programs.decode
    assert [name for name, _ in serving.programs.built()] \
        == built + ["decode_step_w1"]
    assert _memscope_programs(serving) == built + ["decode_step_w1"]
    assert serving.compile_stats()["decode_step_w1"] == 0


def test_the_programs_name_themselves_once_streamed():
    from deepspeed_tpu.models.gpt import make_gpt_layered_model
    _one_device()
    plain = GPTConfig(n_layer=2, n_head=4, d_model=64, max_seq_len=256,
                      vocab_size=256, dtype=jnp.float32, remat=False)
    engine = _engine(make_gpt_layered_model(cfg=plain, name="spill"),
                     zero={"offload_param": {"device": "cpu",
                                             "lookahead": 2}})
    serving = engine.serving(max_slots=2, max_context=128,
                             prefill_chunk=CHUNK)
    built = [name for name, _ in serving.programs.built()]
    assert sorted(built) == sorted(
        f"{role}_{phase}" for role in ("embed", "layer", "head")
        for phase in ("prefill", "decode"))
    serving.run(_requests(LENGTHS[:2], NEW[:2]))
    assert serving.compile_stats() == dict.fromkeys(built, 1)
    # its steps are host loops: no whole-step executable to analyse, and
    # the window is one token already
    assert _memscope_programs(serving) == []
    assert serving.programs.decode_w1() is serving.programs.decode
    assert all(r.counters == () for r in serving.steptrace.records())
