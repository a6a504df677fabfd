"""What the serving loop BOOKS, pinned for every shape of pool and generator
the benchmark's cells use: one fixed request script through a tiny engine of
each shape, and the step ring's work fields, the call ring's work and the
tokens compared with the values the commit before PR 58 booked
(`tests/step_ring_fields.json`, written there by `JAX_PLATFORMS=cpu python -m
tests.test_step_ring_fields --record`).

The families' own files pin most of these fields by hand counts on their own
traffic; this file pins ALL of them on one script, so that a change to how a
call's work is computed or where it is booked (PR 58: dicts of record fields
from the kernels' own `work`, a generator's contract) moves no number.

The CPU's rule traces the gather oracle for a chunk, for which the loop books
no chunk walk. So the engine's record of traced attention programs is SAID
here (`DecodeModelSpec.paged_attn_programs`: the chunk-walk kernels the chip
traces, the latent pool's for a latent pool): every chunk field is booked as
on the chip, at tiny shapes, with nothing more compiled.

Everything here rides the `serving` marker (tier-1).
"""

import json
import os
import sys

import jax
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.comm import mesh as mesh_mod
from deepspeed_tpu.config.core import MeshConfig
from deepspeed_tpu.inference.scheduler import Request
from deepspeed_tpu.models.gpt import make_gpt_decode_model

pytestmark = pytest.mark.serving

PINNED = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "step_ring_fields.json")

# the step record's fields that count work (every field past `counters` whose
# value the loop computes; the stamps, `queued`, `free_blocks`, `blocked_on`
# and `compiles` are the families' files')
STEP_FIELDS = (
    "admitted", "prefill_chunks", "decoding", "emitted",
    "decode_live_blocks", "decode_grid_steps", "prefill_live_blocks",
    "prefill_table_blocks", "decode_window_live_blocks",
    "decode_window_table_blocks", "prefill_window_live_blocks",
    "prefill_window_table_blocks", "latent_walk_blocks",
    "latent_chunk_positions", "prefill_kept_pairs",
    "prefill_window_kept_pairs", "fused_chunks", "ssm_state_bytes",
    "ssm_chunk_tokens", "device_calls", "overlapped_calls", "chunk_groups",
    "padded_chunks", "decode_walk_rows")
CALL_FIELDS = ("program", "rows", "win", "firsts", "chunks", "forwards",
               "block_rows", "emitted")

# (prompt length, max_new): under a chunk, on the grid, several chunks ending
# mid-chunk, a budget of one token and of more than a window
SCRIPT = ((37, 9), (5, 22), (50, 6), (16, 1), (70, 15), (18, 17))
ARRIVALS = (2, 3, 2, 2, 2)      # submit 2, step 3, submit 2, step 2, submit 2


def _full():
    from tests.test_mixed_step import DENSE, _engine
    mesh_mod.clear_mesh()
    mesh_mod.init_mesh(MeshConfig(data=1), devices=jax.devices()[:1])
    engine = _engine(make_gpt_decode_model(cfg=DENSE, name="tiny"))
    return engine.serving(max_slots=3, max_context=128, prefill_chunk=16,
                          decode_steps_per_sync=3, prefill_chunks_per_step=2)


def _of(cases, cfg=None, **knobs):
    cfg = cfg or cases._cfg()
    knobs = {"prefill_chunks_per_step": 2, **knobs}
    return cases._serving(cfg, cases._params(cfg), one_device=True,
                          **knobs)[1]


def _window():
    # (a window token takes a GROUP of two chunks: budget 4 over a window of 2)
    from tests import exaone_cases
    return _of(exaone_cases, decode_steps_per_sync=2,
               prefill_chunks_per_step=4)


def _mamba():
    # (the fewest layers that keep both kinds: a Mamba-2 and an attention one)
    from tests import nemotron_cases
    return _of(nemotron_cases, nemotron_cases._cfg(pattern="M*"))


def _gdn():
    from deepspeed_tpu.models import qwen3_next
    from tests import qwen3_next_cases
    return _of(qwen3_next_cases, qwen3_next_cases._cfg(
        layers=qwen3_next.layer_types(2, 2)))


def _latent():
    from tests import glm_cases
    return _of(glm_cases)


def _blocks():
    from deepspeed_tpu.models import sdar_moe
    from tests import test_sdar_moe as cases
    cfg = sdar_moe.sdar_moe_config(cases.PUBLISHED, 256, cases.B,
                                   dtype=jax.numpy.float32)
    params = cases.lively(jax.jit(sdar_moe.sdar_moe_init_fn(
        cfg, dtype=jax.numpy.float32, embedding_std=1.0))(
            jax.random.PRNGKey(3)))
    return cases._serving((cfg, params, params), 2, False, max_slots=3)[1]


SHAPES = {"full": _full, "window_and_full": _window,
          "mamba2_state_and_full": _mamba, "gdn_state_and_full": _gdn,
          "latent": _latent, "block_diffusion": _blocks}


def booked(shape):
    """Run the script through a new engine of `shape`: what it booked."""
    srv = SHAPES[shape]()
    chunk_walk, decode_walk = ("mla_prefill_kernel", "mla_decode_kernel") \
        if shape == "latent" else ("paged_prefill_kernel", "paged_kernel")
    srv.engine.model_spec.paged_attn_programs = {
        "prefill_chunk": chunk_walk, "mixed/prefill_chunk": chunk_walk,
        "paged_decode": decode_walk, "mixed/paged_decode": decode_walk}
    rng = np.random.default_rng(58)
    requests = [Request(uid=i, tokens=rng.integers(1, 120, (n,)),
                        max_new_tokens=m, stop_on_eos=False)
                for i, (n, m) in enumerate(SCRIPT)]
    done = {}
    arriving = iter(requests)
    for i, n in enumerate(ARRIVALS):
        for _ in range(n):
            if i % 2:
                done.update((d.uid, d) for d in srv.step())
            else:
                srv.submit(next(arriving))
    done.update(srv.run([]))
    assert sorted(done) == list(range(len(SCRIPT)))
    assert srv.allocator.num_free == srv.allocator.capacity
    assert set(srv.compile_stats().values()) == {1}
    steps = srv.steptrace.records()
    calls = sorted(srv.steptrace.calls(), key=lambda c: c.id)
    out = {
        "steps": {f: [int(getattr(r, f)) for r in steps]
                  for f in STEP_FIELDS if any(getattr(r, f) for r in steps)},
        "calls": [[getattr(c, f) for f in CALL_FIELDS] for c in calls],
        "tokens": {str(uid): [int(t) for t in d.tokens]
                   for uid, d in sorted(done.items())},
        "finish": {str(uid): d.finish_reason
                   for uid, d in sorted(done.items())}}
    stats = srv.stats()
    out["stats"] = {k: stats[k] for k in (
        "steps", "device_calls", "overlapped_calls", "decode_steps",
        "prefill_chunks", "fused_chunks", "chunk_groups", "padded_chunks",
        "tokens_generated")}
    if "generator" in stats:
        out["stats"]["generator"] = stats["generator"]
    return out


@pytest.mark.parametrize("shape", list(SHAPES))
def test_the_rings_book_what_the_parent_booked(shape):
    with open(PINNED) as f:
        want = json.load(f)[shape]
    got = json.loads(json.dumps(booked(shape)))     # tuples as JSON has them
    for part in want:
        assert got[part] == want[part], part
    # the shape books what it is there for
    steps = got["steps"]
    assert "decode_live_blocks" in steps and "prefill_kept_pairs" in steps
    assert ("decode_window_live_blocks" in steps) == (
        shape == "window_and_full")
    assert ("ssm_state_bytes" in steps) == ("state" in shape)
    assert ("latent_chunk_positions" in steps) == (shape == "latent")
    assert any(c[CALL_FIELDS.index("forwards")] for c in got["calls"]) == (
        shape == "block_diffusion")


if __name__ == "__main__":
    assert sys.argv[1:] == ["--record"], "usage: ... --record"
    with open(PINNED, "w") as f:     # a shape a line
        f.write("{\n" + ",\n".join(
            json.dumps(shape) + ": " + json.dumps(booked(shape),
                                                  sort_keys=True)
            for shape in SHAPES) + "\n}\n")
