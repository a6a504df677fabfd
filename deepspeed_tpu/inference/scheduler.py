"""Continuous-batching serving scheduler over the paged KV-cache pool.

The Orca insight, TPU-style: a static-batch `generate()` call stalls its
whole batch on the slowest sequence and pays one XLA compile per request
shape. This scheduler instead owns `max_slots` fixed sequence slots and ONE
paged KV pool (`inference/kv_cache.py`), and drives every request through
persistent jitted programs whose shapes never change
(`inference/step_programs.py` builds them; the loop holds `self.programs`):

  * `prefill_step` — [1, chunk] slice of a prompt, its K/V written through
    the slot's block table; chunks interleave with in-flight decode
    (`prefill_chunks_per_step` bounds the stall an arriving prompt imposes);
  * `decode_step` — one token for ALL slots at once: inactive slots ride
    along pointed at the trash block, so liveness never changes the shape;
  * `mixed_step` — the two as ONE call: a step with chunks due and slots
    already decoding sends their rows through the model as one tensor, up
    to G chunks a token of the decode window, every weight read once
    (`_chunks_riding` is the whole rule).

Iteration-level scheduling happens between the calls, on the host, in
plain Python: admit queued requests into freed slots (admission is a
free-list pop — all-or-nothing, so a too-big request waits instead of
half-occupying the pool), retire sequences the step they emit EOS, free
their blocks immediately. The result is one compile per program for the
lifetime of the engine — the recompile tax and the convoy effect die
together.

The loop runs ONE CALL DEEP: `step()` k builds and dispatches call k and
only then blocks on call k-1's tokens, so the device's queue holds the next
call when the current one ends. Call k's input tokens are call k-1's
outputs, selected on the device (`step_programs.py`: `pick`); positions,
tables and chunks never depended on token values. An end the host can count
(`max_new`) is decided at dispatch — the request gives up its slot and
blocks then, and takes its last tokens when its call is read (`_Call`, held
apart from the slots) — and an end only the token decides (EOS) costs one
speculative window, dropped at read-back. What needs the tokens first (spec
decode, the streamed per-layer walk, a pressure ladder off rest, a prompt's
last chunk as a call of its own, cancel, handoff, audit, close) reads the
call in flight back before it goes on: `_drain` is the one place.
Every such call has a record of its own in the step timeline's call ring
(`telemetry/steptrace.py::CallRecord`): opened where it goes out
(`_dispatching`), closed where it is read (`_read_back`).

What KIND of decode call the model makes is a GENERATOR's to say
(`inference/generators.py`; the engine always holds one, `self.gen`, and the
loop calls it and tests nothing): the window a slot advances a call and the
forwards a chunk may ride, what a slot feeds the call and which leading
tokens of its row are not generated (`skip`), what the next call picks from
this one, whether a prompt's last chunk samples a first token, where a
call's walks are counted and when they join the step's sums, how a read-back
closes the call's record. The plain instance is one token a slot a forward;
DIFFUSION OVER BLOCKS (`DecodeModelSpec.generator`) commits whole blocks of B
tokens a slot through denoise + commit forwards of B rows and takes nothing
from the call before. docs/inference.md states the contract and what each
instance refuses.

What a call DOES is booked as `StepRecord` fields by name (`_book`): a decode
call's walk and each chunk's are dicts of them, the arithmetic kept beside
the kernel that does the work (`ops/pallas/*::*_walk_counts`; a chunk's
through the `work` of the attention program it was traced with,
`ops/attention_dispatch.py`), summed a step and handed to `end_step` whole.

Compile accounting is first-class: `compile_stats()` reads the jit caches,
and the serving tests assert <= 1 compile per bucket across any trace.

Automatic prefix caching (`serving.enable_prefix_caching`,
`inference/prefix_cache.py`) rides the same machinery: at admission the
prompt's hash chain is matched against previously written full blocks, hit
blocks are mapped into the new slot's table with a refcount bump, and the
chunked-prefill cursor starts at the cached boundary. Only host-side state
changes; the compiled programs and their shapes are untouched.

Speculative decoding (`serving.spec_decode`, `inference/spec_decode.py`)
swaps the decode step for a draft+verify loop: a drafter proposes `draft_k`
tokens per slot, ONE fixed-shape verify call scores them for all slots, and
the longest agreeing prefix plus a bonus token is emitted — 1..k+1 tokens
per model step. Rejection is an O(1) rewind of the slot's length cursor
(`_verify_decode`).
"""

import collections
import contextlib
import dataclasses
import time
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.inference import generators, step_programs
from deepspeed_tpu.inference.audit import PoolAuditor, PoolCorruptionError
from deepspeed_tpu.inference.kv_cache import (BlockAllocator, TRASH_BLOCK,
                                              blocks_needed, max_written_pos,
                                              ring_blocks, ring_tables,
                                             state_rows,
                                              transplant_blocks)
from deepspeed_tpu.inference.spec_decode import accept_greedy, make_drafter
from deepspeed_tpu.telemetry import Telemetry
from deepspeed_tpu.telemetry.device_scopes import ProgramTable
from deepspeed_tpu.utils.logging import log_dist


class InadmissibleRequestError(ValueError):
    """The request can NEVER be admitted by this engine — the prompt plus
    its generation budget exceeds `max_context`, or it needs more KV blocks
    than the whole pool holds. Raised at submit() so an impossible request
    fails fast instead of wedging the FIFO head forever; the serving router
    catches it per replica to find one whose limits do fit."""


@dataclasses.dataclass
class Request:
    """One generation request. `eos_token_id=None` falls back to the engine /
    model default; `stop_on_eos=False` disables early stop entirely.

    `deadline_ms` is a hard end-to-end budget from submission: unlike the
    router's TTL (which only cancels QUEUED requests), the deadline is
    enforced past admission — a request still generating when its budget
    runs out retires at the next scheduler sync with
    ``finish_reason="deadline"`` (tokens emitted so far are kept).
    `priority` orders degradation-time shedding (`serving/degradation.py`):
    under the ladder's top level, queued requests with priority below the
    configured threshold are shed first; it never affects FIFO order."""
    uid: Any
    tokens: Sequence[int]
    max_new_tokens: int = 32
    eos_token_id: Optional[int] = None
    stop_on_eos: bool = True
    deadline_ms: Optional[float] = None
    priority: int = 0


@dataclasses.dataclass
class CompletedRequest:
    uid: Any
    prompt_len: int
    tokens: np.ndarray        # generated tokens; the EOS (if emitted) is kept
    finish_reason: str        # "eos" | "length" | "cancelled" (withdrawn via
                              # cancel() before finishing; router TTL/shedding
                              # surfaces as this too) | "deadline" (hard
                              # per-request budget expired mid-flight)
    cached_prefix_tokens: int = 0  # prompt tokens whose KV came from the
                              # prefix cache (0 when caching is off/missed)
    timing: Optional[Dict[str, float]] = None  # telemetry only: monotonic
                              # arrival/admit/first_token/finish stamps
                              # (None when telemetry is disabled)


_FREE, _PREFILL, _DECODE, _HANDOFF = 0, 1, 2, 3

# Which field of the step record a count of a walk lands in. A kernel's host
# twin names what the kernel does (`ops/pallas/*::*_walk_counts`); the loop
# alone knows the phase it ran in and the kind of layer it was asked for.
_DECODE_FIELDS = {"live_blocks": "decode_live_blocks",
                  "grid_steps": "decode_grid_steps",
                  "rows": "decode_walk_rows"}
_DECODE_WINDOW_FIELDS = {"live_blocks": "decode_window_live_blocks",
                         "table_blocks": "decode_window_table_blocks"}
_CHUNK_FIELDS = {"live_blocks": "prefill_live_blocks",
                 "table_blocks": "prefill_table_blocks",
                 "kept_pairs": "prefill_kept_pairs",
                 "latent_positions": "latent_chunk_positions"}
_CHUNK_WINDOW_FIELDS = {"live_blocks": "prefill_window_live_blocks",
                        "table_blocks": "prefill_window_table_blocks",
                        "kept_pairs": "prefill_window_kept_pairs"}


def _lands(counts, fields):
    """The counts that `fields` names, under their fields."""
    return {fields[name]: n for name, n in counts.items() if name in fields}


class _Slot:
    __slots__ = ("idx", "state", "uid", "prompt", "prompt_len", "padded_len",
                 "max_new", "eos", "blocks", "cursor", "pos", "emitted",
                 "hashes", "reg", "cached", "prefill_only", "deadline",
                 "t_arrive", "t_admit", "t_first", "t_prev", "trace",
                 "step_first", "req", "planned", "flying", "feed")

    def __init__(self, idx):
        self.idx = idx
        self.state = _FREE
        self.uid = self.prompt = None
        self.prompt_len = self.padded_len = self.max_new = 0
        self.eos = None
        self.blocks = []
        # FINISHED work, what a reader of the slot may count: `cursor` the
        # prompt tokens whose chunks the device has run (it moves when the
        # call that covers a chunk is read back), `emitted` the tokens
        # delivered. What the next call is planned from is kept beside
        # them: `planned` the prompt tokens whose chunks are dispatched,
        # `pos` the position the next decode call writes at, `flying` the
        # tokens dispatched and not yet delivered
        self.cursor = self.planned = self.pos = self.flying = 0
        self.emitted = []
        self.feed = None        # (`_Call.id` of the call that samples this
                                # slot's next input token, where in its
                                # output: 1 = the window's last token,
                                # 2 + i = first token i)
        self.hashes = None      # prefix-cache hash chain (full prompt blocks)
        self.reg = 0            # blocks [0, reg) already registered/cached
        self.cached = 0         # blocks mapped from the cache at admission
        self.prefill_only = False  # disaggregated serving: park in _HANDOFF
                                # after the last chunk instead of decoding
        self.deadline = None    # absolute hard deadline (engine clock)
        self.t_arrive = self.t_admit = self.t_first = None  # lifecycle stamps
        self.t_prev = None      # last emission sync (TPOT interpolation anchor)
        self.trace = None       # TraceContext (None unless tracing is on)
        self.step_first = 0     # step-timeline index of the first token's step
        self.req = None         # open steptrace.RequestRecord


class _Call:
    """A dispatched decode or mixed call whose tokens nobody has read yet:
    what `_read` needs to hand them out. Held apart from the slots, which
    may belong to the next requests by then — a `_Slot` OBJECT is one
    request's life (`_vacate` puts a new one in its place), so a row here
    can only ever reach the request it was sampled for."""
    __slots__ = ("id", "out", "prev", "mixed", "win", "rows", "firsts",
                 "chunks", "riding", "leaving", "skip", "work")

    def __init__(self, id, out, prev, mixed, win, rows, firsts, chunks,
                 riding, skip, work):
        self.id = id            # what a slot's `feed` names (a number, not
                                # the call: a slot holds no call alive) and
                                # its `steptrace.CallRecord`, which has its
                                # stamps
        self.out = out          # the program's output, still on the device
        self.prev = prev        # of it, what the next call picks its input
                                # from: (first [W * G], nxt [S, win]), or
                                # None (`generators.py`: `opens`)
        self.mixed = mixed      # `out` has first tokens beside the window
        self.win = win
        self.rows = rows        # requests in the decode window, row `idx` each
        self.firsts = firsts    # (request, i): first token i (riding chunk
                                # i's) is its first
        self.chunks = chunks    # (request, prompt tokens prefilled once this
                                # call has run): its own and every chunk
                                # dispatched before it
        self.riding = riding    # (request, start) of the chunks that rode
        self.leaving = []       # requests that gave up their slot at dispatch
        self.skip = skip        # slot index -> the leading tokens of its
                                # row that are not generated (a request's
                                # prompt tail opening its first block; empty
                                # for a token a row)
        self.work = work        # `_decode_walk`'s `StepRecord` fields: what
                                # of them is due at the read-back is the
                                # generator's to say (`due`)

    def awaited(self):
        """Does anything wait for this call's read-back: a token a live
        request takes, or a chunk whose progress it confirms."""
        return bool(self.chunks) or any(
            r.state != _FREE for r in self.rows) or any(
            r.state != _FREE for r, _ in self.firsts)


class ServingEngine:
    """Continuous-batching server on top of an `InferenceEngine` whose model
    spec carries the paged contract (prefill_paged_fn / decode_paged_fn /
    init_paged_pool — the GPT zoo provides it).

    Usage::

        serving = engine.serving(max_slots=8, max_context=2048)
        serving.submit(Request(uid=0, tokens=prompt, max_new_tokens=64))
        while True:
            for done in serving.step():
                ...                       # done.tokens, done.finish_reason
        # or, batch-style: results = serving.run(requests)
    """

    def __init__(self, engine, draft_spec=None, clock=None, **overrides):
        spec = engine.model_spec
        # streamed (offloaded-weights) mode: a LayeredModelSpec served
        # through a ZeroInferenceEngine — the per-layer paged contract
        # (layer_paged_fn + embed/final) replaces the whole-model one
        # (`step_programs.build_streamed`)
        self.streamed = getattr(spec, "layer_paged_fn", None) is not None \
            and getattr(spec, "prefill_paged_fn", None) is None
        if self.streamed:
            missing = [n for n in ("layer_paged_fn", "init_paged_pool",
                                   "embed_fn", "final_fn")
                       if getattr(spec, n, None) is None]
            if missing:
                raise ValueError(
                    f"layered model spec '{spec.name}' has no streamed "
                    f"paged contract (missing {missing}); build it with "
                    f"make_gpt_layered_model")
        else:
            missing = [n for n in ("prefill_paged_fn", "decode_paged_fn",
                                   "init_paged_pool")
                       if getattr(spec, n, None) is None]
            if missing:
                raise ValueError(
                    f"model spec '{spec.name}' has no paged serving contract "
                    f"(missing {missing}); build it with make_gpt_decode_model "
                    f"or serve through generate()")
        self.engine = engine
        self.config = engine.config
        scfg = dataclasses.replace(engine.config.serving, **overrides)
        # `serving(spec_decode={"drafter": "ngram", ...})`, `degradation=
        # {"enabled": True, ...}`, `quantization={"kv_cache_dtype": "int8",
        # ...}`: an override given as a dict
        from deepspeed_tpu.inference import config as icfg
        for block, cls in (("spec_decode", icfg.SpecDecodeConfig),
                           ("degradation", icfg.DegradationConfig),
                           ("quantization", icfg.ServingQuantizationConfig)):
            if isinstance(getattr(scfg, block), dict):
                scfg = dataclasses.replace(
                    scfg, **{block: cls.from_dict(getattr(scfg, block))})
        self.serving_config = scfg

        # quantized serving (inference/quantization.py). Weight-only quant
        # runs FIRST — it replaces the engine's resident param tree (and its
        # dequantize-on-use fn transform), which everything below snapshots:
        # the step programs close over the transform, memscope's preflight
        # sizes params_bytes from the live tree, and the pool capacity math
        # should see the post-quant weights footprint.
        qcfg = scfg.quantization
        weights = str(qcfg.weights or "off")
        if weights not in ("off", "int8", "int4"):
            raise ValueError(
                f"unknown serving.quantization.weights {weights!r} "
                f"(expected 'off', 'int8' or 'int4')")
        self.weight_quant = weights
        self.weight_quant_stats = None
        if weights != "off":
            self.weight_quant_stats = engine.enable_weight_quant(
                bits=8 if weights == "int8" else 4,
                group_size=int(qcfg.weight_group_size))
        # effective KV-pool dtype: the quantization block wins, else the
        # engine-level kv_cache_dtype (so a plain engine config can still
        # select the int8 pool for every serving engine it builds)
        kvd = str(qcfg.kv_cache_dtype or "") or str(engine.config.kv_cache_dtype)
        # ONE alias table for dtype spellings (bf16/fp16/torch.* etc.):
        # the engine config's legacy map, not a second copy that drifts
        kvd = getattr(type(engine.config), "_LEGACY_DTYPES", {}).get(kvd, kvd)
        # int8 is the ONE quantized layout (scale leaves + quantized write
        # path); every other integer dtype would silently truncate float
        # K/V into a handful of levels through the fp write path's cast —
        # refuse it here instead of serving garbage with a happy log line
        if kvd != "int8" and not jnp.issubdtype(jnp.dtype(kvd),
                                                jnp.floating):
            raise ValueError(
                f"unsupported KV-cache dtype {kvd!r}: expected a float "
                f"dtype or 'int8' (the quantized paged pool — "
                f"serving.quantization.kv_cache_dtype)")
        self.kv_cache_dtype = kvd
        self.kv_quant = kvd == "int8"
        self.kv_group_size = int(qcfg.kv_group_size or 0)
        # injectable clock (tests pin TTFT/TPOT interpolation with it; the
        # router injects its own for TTL — this one stamps request timing
        # and the step timeline). perf_counter is the clock the chrome sink
        # and the benchmark stamp with; on Linux it and time.monotonic both
        # read CLOCK_MONOTONIC
        self._clock = clock if clock is not None else time.perf_counter

        bs = int(getattr(engine.config, "kv_block_size", 0) or 0)
        if bs <= 0:
            raise ValueError("serving needs kv_block_size > 0 (the paged "
                             "pool's physical block unit)")
        self.block_size = bs
        self.max_context = int(scfg.max_context or engine.config.max_out_tokens)
        self.nb = -(-self.max_context // bs)       # block-table width
        self.max_slots = int(scfg.max_slots)
        self.chunk = int(scfg.prefill_chunk or bs)
        self.prefill_budget = max(1, int(scfg.prefill_chunks_per_step))
        # speculative decoding: the verify step REPLACES the decode step
        # (and its window) when a drafter is configured
        self.spec_on = str(scfg.spec_decode.drafter or "off") != "off"
        # the kind of decode call the model makes (`generators.py`): the
        # positions a slot advances a call (`window`) and the forwards its
        # chunks may ride (`ride_window`) are the generator's; what one
        # cannot have yet it refuses HERE, by name, as the pools of two
        # kinds refuse theirs. `generator` is the model's own data
        # (`DecodeModelSpec.generator`), None for a token a forward
        self.gen = generators.build(
            spec, scfg, engine.config, streamed=self.streamed,
            window=max(1, int(scfg.decode_steps_per_sync)), chunk=self.chunk,
            block_size=bs, spec_on=self.spec_on, max_slots=self.max_slots)
        self.generator = self.gen.spec
        self.window, self.ride_window = self.gen.window, self.gen.ride_window
        self.blocks_per_call = self.gen.blocks_per_call
        self.denoising_steps = self.gen.denoising_steps
        if self.streamed:
            # streamed-mode envelope: a K-step jitted window or a verify
            # chunk cannot host a per-layer Python walk — both are refused
            # rather than silently degraded
            if self.spec_on:
                raise ValueError(
                    "speculative decoding is a resident-engine feature: the "
                    "streamed (offloaded-weights) serving mode walks one "
                    "jitted per-layer program per token and has no verify "
                    "contract — drop spec_decode, or serve resident")
            if self.window != 1:
                raise ValueError(
                    f"decode_steps_per_sync={self.window} needs the whole "
                    f"stack resident inside one jitted scan; the streamed "
                    f"(offloaded-weights) mode streams layers through HBM "
                    f"per token — set decode_steps_per_sync=1")
        self.draft_k = int(scfg.spec_decode.draft_k) if self.spec_on else 0
        if self.spec_on and spec.verify_paged_fn is None:
            raise ValueError(
                f"model spec '{spec.name}' has no verify_paged_fn — "
                f"speculative decoding needs the k-token paged verify "
                f"contract (make_gpt_decode_model provides it)")
        num_blocks = int(scfg.num_kv_blocks or
                         (self.max_slots * self.nb + 1))
        # a pool of kinds (`DecodeModelSpec.paged_cache_kinds`): the first
        # is the allocator's blocks (full-context K/V, or a latent kind's
        # one entry a token under leaves of its own); a second is a window
        # kind's per-slot rings, or a state kind's per-slot rows, and their
        # tables, fixed for this engine's lifetime. What is not built on
        # such a pool is refused HERE, with the reason, rather than run
        # wrong.
        self.cache_kinds = None
        self.window_kind = self.state_kind = None
        self.ring = 0
        self.ring_tables = None     # the second kind's tables, a row a slot
        kinds_of = getattr(spec, "paged_cache_kinds", None)
        if kinds_of is not None and not self.streamed:
            self.cache_kinds = kinds_of(bs)
            first, second = (*self.cache_kinds, None)[:2]
            if second is None:
                # one kind of allocator blocks: what the host keeps of a
                # sequence (tables, refcounts, registered prefixes, blocks
                # to transplant) is what it keeps of K/V blocks
                kept = f"a KV pool of the {first.name} kind"
                unbuilt = {
                    "kv_cache_dtype int8":
                        f"its leaves ({', '.join(first.leaves)}) have no "
                        f"scale leaves and its walks no dequantizing twin"}
                if first.index_topk:
                    # a sparse layer's index key rides the full kind's
                    # blocks (`models/sparse_attn.py`): the prefix cache and
                    # block transplant take it with them (a verify chunk is
                    # refused above: the family has no `verify_paged_fn`)
                    kept += (" with an index key a position (a learned "
                             "sparse-attention indexer)")
            elif second.state:
                self.state_kind = second
                kept = ("a KV pool of two kinds (per-slot recurrent state "
                        "beside full-context blocks)")
                unbuilt = {
                    "enable_prefix_caching":
                        "a hit would need the state as it was at the block "
                        "boundary, and a slot keeps only the state after "
                        "its last position",
                    "kv_cache_dtype int8":
                        "a recurrent state has no scale leaves",
                    "spec_decode":
                        "a rejected draft would need the state rolled back, "
                        "and a decode token rewrites it in place"}
            else:
                self.window_kind = second
                kept = ("a KV pool of two kinds (window rings beside "
                        "full-context blocks)")
                unbuilt = {
                    "enable_prefix_caching":
                        "a registered block names a full layer's blocks "
                        "only; the window layers' rings of the matching "
                        "prefix are gone with the slot that wrote them",
                    "kv_cache_dtype int8":
                        "the window kind's rings have no scale leaves and "
                        "the windowed walks no dequantizing twin",
                    "spec_decode":
                        "a verify chunk writes k drafts ahead into a ring "
                        "whose size counts prefill chunks and decode "
                        "windows only"}
            asked = {"enable_prefix_caching": scfg.enable_prefix_caching,
                     "kv_cache_dtype int8": self.kv_quant,
                     "spec_decode": self.spec_on}
            for what, why in unbuilt.items():
                if asked[what]:
                    raise ValueError(
                        f"model spec '{spec.name}' keeps {kept}: "
                        f"{what} is not built for it — {why}")
            if self.state_kind is not None:
                self.ring_tables = state_rows(self.max_slots)
            elif self.window_kind is not None:
                self.ring = ring_blocks(second.window, second.block,
                                        self.chunk, self.window)
                self.ring_tables = ring_tables(
                    self.max_slots, -(-self.max_context // second.block),
                    self.ring)

        # telemetry (deepspeed_tpu/telemetry/): TTFT/TPOT/queue-wait/e2e
        # histograms + queue/slot/pool gauges + per-phase spans — built
        # BEFORE the step programs so the compile watchdog can wrap them.
        # Disabled by default — then every record site below is a single
        # attribute check and NOTHING is written anywhere.
        self.telemetry = Telemetry(getattr(engine.config, "telemetry", None),
                                   subsystem="serving")
        # the step timeline (telemetry/steptrace.py) is the exception: ON by
        # default, telemetry block or not — one in-memory record a step and
        # per request, read through `serving.steptrace` (or
        # `steptrace.latest("serving")`)
        self.steptrace = self.telemetry.new_steptrace(self._clock)
        if self.telemetry.enabled and self.spec_on:
            # acceptance rates live in [0, 1] — the default log-scale ms
            # buckets would smear them into one decade; pin linear bounds
            self.telemetry.registry.histogram(
                "serving/spec_accept_rate",
                bounds=[i / 20 for i in range(1, 21)])
        # request tracing + flight recorder: the engine's own (from its
        # telemetry config) until a router injects the POOL-shared ones
        # via attach_observability — then every replica's spans land in
        # one file under one trace id, on one Perfetto track per replica
        self.tracer = self.telemetry.tracer
        self.flightrec = self.telemetry.flightrec
        self.trace_tid = 0

        # memscope pre-flight runs BEFORE the pool device_put below: the
        # plan is pure shape arithmetic (jax.eval_shape over
        # init_paged_pool — no device memory touched), so a predicted-OOM
        # config can warn or refuse ahead of the allocation that would
        # otherwise crash a real chip with a raw RESOURCE_EXHAUSTED
        tcfg = getattr(engine.config, "telemetry", None)
        self._memscope_on = self.telemetry.enabled and \
            getattr(tcfg, "memscope", False)
        self._preflight_plan = None
        if self._memscope_on:
            from deepspeed_tpu.telemetry import memscope as _ms
            mode = str(getattr(tcfg, "memscope_preflight", "warn"))
            if mode != "off":
                cap = int(getattr(tcfg, "memscope_capacity_bytes", 0) or 0) \
                    or int(_ms.device_memory_stats().get("bytes_limit", 0)
                           or 0)
                plan = _ms.plan_serving_prealloc(
                    spec, num_kv_blocks=num_blocks, kv_block_size=bs,
                    kv_cache_dtype=self.kv_cache_dtype,
                    kv_group_size=self.kv_group_size,
                    params=engine.params,
                    draft_spec=draft_spec
                    if scfg.spec_decode.drafter == "model" else None,
                    param_dtype=engine.dtype, capacity_bytes=cap)
                self._preflight_plan = _ms.preflight_check(
                    plan, refuse=(mode == "refuse"))

        # place the pool with the engine mesh's (replicated) NamedSharding up
        # front: the step programs RETURN pools with exactly this sharding,
        # so an uncommitted pool would give the first call of each program a
        # different signature than every later call — a phantom extra compile
        from jax.sharding import NamedSharding, PartitionSpec
        if self.kv_quant:
            # int8 pool: payload + per-group scale leaves. The 4-arg call is
            # part of the quantized paged contract — a 3-arg legacy spec
            # raises TypeError right here, and a spec that accepts the group
            # arg but returns a scale-less pool is caught just below; both
            # get the same pointer at the contract
            contract = ("it does not implement the quantized-pool contract "
                        "(init_paged_kv_pool in models/gpt.py is the "
                        "reference)")
            try:
                pool = spec.init_paged_pool(num_blocks, bs, jnp.int8,
                                            self.kv_group_size)
            except TypeError as e:
                raise ValueError(
                    f"model spec '{spec.name}' init_paged_pool does not "
                    f"accept the 4-arg quantized form (num_blocks, "
                    f"block_size, dtype, kv_group_size) — {contract}: {e}"
                ) from e
            if not (isinstance(pool, dict) and "k_scale" in pool):
                raise ValueError(
                    f"model spec '{spec.name}' init_paged_pool returned no "
                    f"k_scale/v_scale leaves for dtype int8 — {contract}")
        elif self.state_kind is not None:
            pool = spec.init_paged_pool(num_blocks, bs, jnp.dtype(kvd),
                                        state_rows=1 + self.max_slots)
        elif self.window_kind is not None:
            pool = spec.init_paged_pool(
                num_blocks, bs, jnp.dtype(kvd),
                window_blocks=1 + self.max_slots * self.ring)
        else:
            pool = spec.init_paged_pool(num_blocks, bs, jnp.dtype(kvd))
        self._replicated = NamedSharding(engine.mesh, PartitionSpec())
        self.pool = jax.device_put(pool, self._replicated)
        self.allocator = BlockAllocator(
            num_blocks, policy=str(scfg.prefix_cache_policy or "lru"))
        self.prefix_cache = None
        if scfg.enable_prefix_caching:
            from deepspeed_tpu.inference.prefix_cache import PrefixCache
            self.prefix_cache = PrefixCache(
                self.allocator, bs,
                fingerprint=spec.cache_fingerprint or spec.name)
        # the allocator's blocks hold a latent kind's entries (the step ring
        # then books the decode walk's pairs as the latent walk's)
        self._latent = bool(self.cache_kinds) \
            and self.cache_kinds[0].name == "latent"
        # ... or entries with an index key, of which a learned indexer
        # selects `index_topk` a query (`CacheKind.index_topk`)
        self._index_topk = self.cache_kinds[0].index_topk \
            if self.cache_kinds else 0
        # the last dimensions of the leaves a full layer's decode walk reads
        # (`decode_attention._frontier_rows`: what it moves of a short
        # table's frontier block follows them)
        self._walk_widths = tuple(
            self.pool[leaf].shape[-1] for leaf in
            (self.cache_kinds[0].leaves if self.cache_kinds else self.pool))
        # what a decode token of one slot reads + writes of a state kind's
        # state proper (its first leaf), all layers
        self._state_token_bytes = 0
        if self.state_kind is not None:
            from deepspeed_tpu.ops.pallas.ssm import state_token_bytes
            self._state_token_bytes = state_token_bytes(
                self.pool[self.state_kind.leaves[0]])
        self.tables = np.full((self.max_slots, self.nb), TRASH_BLOCK, np.int32)
        self.slots = [_Slot(i) for i in range(self.max_slots)]
        self.queue = collections.deque()

        self._rng = jax.random.PRNGKey(0)
        if self.streamed and self.telemetry.enabled:
            # the staging pool's offload/* metrics (stage-wait, occupancy,
            # in-flight bytes) land in THIS engine's serving registry
            engine.streamer.telemetry = self.telemetry
            engine.store.telemetry = self.telemetry
        # a model's own per-call counters (`DecodeModelSpec.step_counters`,
        # e.g. the routed experts'; `()` where it names none): every step
        # program returns them beside its tokens, they are read back WITH
        # the tokens (a program whose tokens are not read — a prompt's
        # earlier chunks — parks its counts until the next read-back),
        # summed here and put on the step ring
        self.step_counter_names = () if self.streamed \
            else step_programs.step_counter_names(engine.model_spec)
        self.step_counter_totals = np.zeros(len(self.step_counter_names),
                                            np.int64)
        self._step_counts = np.zeros_like(self.step_counter_totals)
        # ... and, reported beside them, what the decode walks had to visit
        # and what they moved of it, summed over the steps (the step ring's
        # fields of these names: a short table's walk moves fewer rows than
        # its pairs' blocks hold, `decode_attention._frontier_rows`)
        self.walk_totals = {"decode_live_blocks": 0, "decode_walk_rows": 0}
        self._call_counts = ()      # of the call read last (`_fetch`)
        self._work = {}             # `StepRecord` field -> what the open
                                    # step's calls do of it (`_book`)
        self._parked_counts = []
        # the loop runs one call deep (`_step_impl`): the newest dispatched
        # call whose tokens are unread, the completions a read-back outside
        # `step()` produced (the next `step()` returns them), and the chunks
        # dispatched since the last such call ((request, prompt tokens
        # prefilled once they have run): the next read-back that covers them
        # moves the requests' `cursor`)
        self._pending = None
        self._early: List[CompletedRequest] = []
        self._unread_chunks = []
        # the compiled step programs (`inference/step_programs.py`): built
        # once, shapes pinned for the engine's lifetime; every one returns
        # ((tokens...), counts), pool
        wd = self.telemetry.watchdog
        if self.streamed:
            self.programs = step_programs.build_streamed(
                spec, engine.config, num_layers=engine.store.num_layers,
                streamer=engine.streamer, watchdog=wd)
        else:
            # the chunks a token of a mixed call takes: what the settings
            # already say (at most `prefill_budget` chunks a step, `window`
            # tokens a call), where the model's mixed program takes a group
            group = -(-self.prefill_budget // self.ride_window) \
                if getattr(spec, "mixed_chunk_groups", False) else 1
            self.programs = step_programs.build_resident(
                spec, engine.config, engine._fn_transform,
                window=self.ride_window, max_slots=self.max_slots,
                chunk=self.chunk, spec_on=self.spec_on, draft_k=self.draft_k,
                replicated=self._replicated, watchdog=wd, group=group,
                **self.gen.program_args)
        # the device's side of the timeline, on demand: the recorder is
        # handed the built programs (`mixed_step` too, before any chunk has
        # ridden) and the SHAPES of their arguments, and lowers nothing
        # until `steptrace.device_scopes()` is asked (a streamed engine has
        # no whole-step program to hand over)
        self.steptrace.scope_provider = table = ProgramTable()
        for name, fn, args in self.programs.examples(
                engine.params, self.pool, self._tables_arg(self.tables),
                self._rng):
            table.add(name, fn, args)

        # drafter AFTER pool/allocator: the draft-model drafter mirrors the
        # pool geometry and shares the block tables (spec_decode.py)
        if draft_spec is not None and scfg.spec_decode.drafter != "model":
            raise ValueError(
                f"draft_spec was passed but spec_decode.drafter is "
                f"{scfg.spec_decode.drafter!r} — only the 'model' drafter "
                f"consumes it (did you mean spec_decode="
                f"{{'drafter': 'model', ...}}?)")
        self.drafter = make_drafter(self, scfg.spec_decode,
                                    draft_spec=draft_spec) \
            if self.spec_on else None

        # HBM memory ledger + OOM forensics (telemetry/memscope.py):
        # per-subsystem byte attribution as mem/* gauges plus the
        # ledger+planner+flight dump on RESOURCE_EXHAUSTED in step().
        # Built AFTER the drafter so the draft mirror is on the ledger;
        # the capacity verdict already ran pre-allocation above (its plan
        # becomes last_plan — the OOM dump's "was this foreseeable" base);
        # disabled default = no object, no gauges, untouched compile_stats
        self.memscope = None
        if self._memscope_on:
            from deepspeed_tpu.telemetry.memscope import ServingMemScope
            self.memscope = ServingMemScope(self)
            self.memscope.last_plan = self._preflight_plan

        # self-healing: pool invariant auditor (inference/audit.py) — pure
        # host-side reads, run every `audit_interval` syncs / on demand /
        # at close(); on violation: flight dump, then repair-or-raise
        self.audit_interval = int(scfg.audit_interval or 0)
        self.audit_action = str(scfg.audit_action or "repair")
        if self.audit_action not in ("repair", "raise"):
            raise ValueError(f"unknown audit_action {self.audit_action!r} "
                             f"(expected 'repair' or 'raise')")
        self._auditor = PoolAuditor(self)
        self.audits_run = 0
        self.audit_violations_total = 0
        self.audit_repairs = 0

        # graceful degradation (serving/degradation.py): disabled default
        # means the controller is never built — the hot path, the compiled
        # programs and compile_stats() are byte-identical without it
        self.pressure = None
        if scfg.degradation.enabled:
            from deepspeed_tpu.serving.degradation import PressureController
            self.pressure = PressureController(self, scfg.degradation)
        self._deadlines = False       # any live request carries a deadline

        # observability
        self.steps = 0
        self.device_calls = 0               # calls whose tokens a blocking read
                                            # fetched (decode, mixed, verify, a
                                            # prompt's last chunk on its own)
        self.overlapped_calls = 0           # of them, calls dispatched while
                                            # the call before was unread
        self.decode_steps = 0
        self.prefill_chunks = 0
        self.fused_chunks = 0               # of them, chunks that rode a decode
                                            # call (`mixed_step`) ...
        self.chunk_groups = 0               # ... in this many groups (one a
                                            # window token, up to G chunks)
        self.padded_chunks = 0              # ... with this many absent chunks
        self.prefill_chunks_skipped = 0     # chunks the prefix cache elided
        self.prefix_hit_blocks = 0
        self.prefix_hit_tokens = 0
        self.tokens_generated = 0
        self.peak_active = 0
        self.cancelled = 0                  # requests withdrawn via cancel()
        self.deadline_cancelled = 0         # requests retired reason="deadline"
        self.degradation_sheds = 0          # queued requests shed by the
                                            # pressure controller's top rung
        self.handoffs_out = 0               # slots exported to a decode engine
        self.handoffs_in = 0                # slots adopted from a prefill engine
        self.verify_calls = 0               # spec decode: jitted verify steps
        self.verify_slot_steps = 0          # per-slot verify participations
                                            # (tokens/step's denominator)
        self.drafted_tokens = 0             # real (non-padding) proposals scored
        self.accepted_tokens = 0            # drafts that matched the target
        self.spec_emitted_tokens = 0        # tokens emitted by verify steps
                                            # (accepted + one bonus each)

        pool_mb = sum(x.size * x.dtype.itemsize
                      for x in jax.tree_util.tree_leaves(self.pool)) / 2**20
        log_dist(f"serving engine: {spec.name} slots={self.max_slots} "
                 f"blocks={num_blocks}x{bs} ({pool_mb:.0f} MB pool, "
                 f"kv={self.kv_cache_dtype}) table_width={self.nb} "
                 f"prefill_chunk={self.chunk} weights={self.weight_quant}",
                 ranks=[0])

    def _whole(self, prompt_len):
        """The prompt tokens that PREFILL covers: its whole blocks of the
        generator's (all of them at a token a row; the `L mod B` tokens a
        block generator leaves open the first generated block as clean
        tokens)."""
        return prompt_len - prompt_len % self.gen.block

    def _next_rng(self):
        if self.config.greedy:
            return self._rng                        # unused by the sampler
        self._rng, sub = jax.random.split(self._rng)
        return sub

    # ------------------------------------------------------------------
    # request lifecycle
    # ------------------------------------------------------------------

    def check_admissible(self, prompt_len: int, max_new: int,
                         prefill_only: bool = False, uid: Any = "?",
                         padded_prompt: int = None) -> int:
        """Sizing validation shared by submit() and the serving router's
        replica scoring: raises `InadmissibleRequestError` when the request
        can NEVER fit this engine (max_context table width, whole-pool
        block budget), else returns the blocks it will occupy. A
        `prefill_only` request never decodes here (its slot hands off to a
        decode replica), so only the padded prompt counts — no decode-write
        or window-rounding tail. `padded_prompt` overrides this engine's
        own chunk-grid padding: a handoff TARGET adopts a slot padded on
        the PREFILL replica's grid, so the router validates decode
        replicas against that width, not their own."""
        prompt_len = int(prompt_len)
        max_new = int(max_new)
        padded = (int(padded_prompt) if padded_prompt else
                  -(-self._whole(prompt_len) // self.chunk) * self.chunk)
        if prompt_len < 1:
            raise InadmissibleRequestError(f"request {uid}: empty prompt")
        if max_new < 1:
            raise InadmissibleRequestError(
                f"request {uid}: max_new_tokens < 1")
        eff_new = 1 if prefill_only else max_new
        eff_window = 1 if prefill_only else self.window
        # decode calls write from the prompt's last whole block on: its tail
        # too, and one token more where the last chunk samples no first one
        whole = self._whole(prompt_len)
        eff_new += prompt_len - whole + (not self.gen.samples_first)
        prompt_len = whole
        # a verify step always writes its full k-draft overhang, so spec
        # decode sizes past the window math (which it replaces); a
        # prefill-only slot never verifies here
        eff_spec = 0 if prefill_only else self.draft_k
        need = blocks_needed(prompt_len, padded, eff_new, self.block_size,
                             window=eff_window, spec_k=eff_spec)
        if max_written_pos(prompt_len, padded, eff_new, eff_window,
                           eff_spec) >= self.max_context:
            raise InadmissibleRequestError(
                f"request {uid}: prompt {prompt_len} + max_new "
                f"{max_new} (window {eff_window}, draft_k {eff_spec}) "
                f"exceeds max_context {self.max_context} "
                f"(raise serving.max_context)")
        if need > self.allocator.capacity:
            raise InadmissibleRequestError(
                f"request {uid}: needs {need} KV blocks, pool has "
                f"{self.allocator.capacity} (raise serving.num_kv_blocks)")
        return need

    def attach_observability(self, tracer=None, flightrec=None, tid=None):
        """Router injection point: share the POOL's tracer / flight
        recorder (so every replica's spans land in one trace file and one
        black box) and take this engine's Perfetto track id. Standalone
        engines keep their own from the telemetry config."""
        if tracer is not None:
            self.tracer = tracer
        if flightrec is not None:
            self.flightrec = flightrec
        if tid is not None:
            self.trace_tid = int(tid)

    def set_clock(self, clock):
        """Unified clock injection (the router calls this on every replica,
        and again after a restart): TTL at the router, the TTFT/TPOT stamps
        and hard-deadline sweep here, and the watchdog/hedging timers all
        read ONE time source, so a chaos test drives the whole pool's time
        deterministically. Absolute `deadline_at` values stay comparable
        across replicas because every engine shares the router's clock."""
        self._clock = self.steptrace.clock = clock

    def _phase(self, name, **attrs):
        """One phase of the step timeline (`telemetry/steptrace.py::Phase`):
        trace annotation (which `attrs` ride), chrome event when that sink
        is on, the step ring, and the `t0`/`t1` stamps a call's record
        takes."""
        return self.steptrace.phase(name, tid=self.trace_tid, **attrs)

    def submit(self, request: Request, prefill_only: bool = False,
               hashes: Optional[List[bytes]] = None, trace=None,
               deadline_at: Optional[float] = None):
        """Queue a request. Raises `InadmissibleRequestError` if it can
        NEVER be admitted (it exceeds the engine's max_context table width
        or the whole pool); a request that merely doesn't fit *right now*
        waits in the queue (admission backpressure). The prompt copy and
        sizing math happen once, here — the admission loop re-reads the
        precomputed record every step while backpressured.

        `prefill_only=True` is the disaggregated-serving entry: the slot
        runs chunked prefill, samples its first token, then parks in a
        handoff state (`export_handoff` / `adopt_handoff`) instead of
        decoding — the router transplants its blocks into a decode
        replica. `hashes` hands in a precomputed chain (the router hashes
        once per request for affinity scoring; chains are
        fingerprint-identical across a pool, so re-hashing per dispatch —
        and again per failover re-dispatch — would be pure waste).
        `trace` carries the router's `TraceContext`; a standalone engine
        with tracing on mints its own here, so the request's whole life is
        one connected span tree either way. `deadline_at` pins the hard
        deadline ABSOLUTELY (on this engine's clock) — the router passes
        the original submit-time deadline through every re-dispatch so a
        failover rerun or a hedged duplicate never extends the budget;
        without it, `request.deadline_ms` anchors at arrival here."""
        if prefill_only:
            self._refuse_transplant()
        prompt = np.asarray(request.tokens, np.int32).reshape(-1)
        prompt_len = int(prompt.shape[0])
        padded = -(-self._whole(prompt_len) // self.chunk) * self.chunk
        need = self.check_admissible(prompt_len, request.max_new_tokens,
                                     prefill_only=prefill_only,
                                     uid=request.uid)
        # hash once at submit; the admission loop re-matches the chain every
        # step while backpressured (cache contents change between steps)
        if self.prefix_cache is None:
            hashes = None
        elif hashes is None:
            hashes = self.prefix_cache.hash_chain(prompt)
        t_arrive = self._clock()
        if deadline_at is None and request.deadline_ms is not None:
            deadline_at = t_arrive + float(request.deadline_ms) / 1e3
        if deadline_at is not None:
            self._deadlines = True
        if self.tracer.enabled:
            if trace is None:
                # no router above: this engine owns the trace end to end
                trace = self.tracer.start(request.uid, t0=t_arrive,
                                          owner="engine")
            self.tracer.event(trace, "submit", t_arrive, tid=self.trace_tid,
                              attrs={"prompt_len": prompt_len,
                                     "max_new": int(request.max_new_tokens)})
        self.queue.append((request, prompt, prompt_len, padded, need, hashes,
                           t_arrive, prefill_only, trace, deadline_at))

    def _refuse_transplant(self):
        if self.gen.no_transplant:
            raise ValueError(f"model spec '{self.engine.model_spec.name}' "
                             f"{self.gen.no_transplant}")
        if self.ring_tables is not None:
            what = "a layer's recurrent state" if self.state_kind is not None \
                else "a window layer's ring"
            raise ValueError(
                f"model spec '{self.engine.model_spec.name}' keeps a KV pool "
                f"of two kinds: block transplant (prefill-only slots, "
                f"handoff) is not built for it — `transplant_blocks` copies "
                f"allocator blocks, and {what} belongs to "
                f"the slot, not to the request")

    def _tables_arg(self, tables, rows=None):
        """What a paged program takes as its tables: the block tables, or
        for a pool of two kinds the pair (full tables, ring tables) — the
        ring rows of `rows` (slot indices; None = every slot) with those of
        slots whose full table is all trash (not in this call) at the ring
        kind's trash block (a state kind: its rows, and the trash row)."""
        if self.ring_tables is None:
            return tables
        ring = self.ring_tables if rows is None else self.ring_tables[rows]
        live = (tables != TRASH_BLOCK).any(axis=1, keepdims=True)
        return tables, np.where(live, ring, TRASH_BLOCK).astype(np.int32)

    def _resolve_eos(self, req: Request):
        if not req.stop_on_eos:
            return None
        eos = req.eos_token_id
        if eos is None:
            eos = getattr(self.config, "eos_token_id", None)
        if eos is None:
            eos = self.engine.model_spec.eos_token_id
        return eos

    def _admit(self, finished: List[CompletedRequest]):
        """FIFO admission. Returns (requests admitted, why the head of a
        still non-empty queue was not: "pool", "slots" or "")."""
        free = [s for s in self.slots if s.state == _FREE]
        admitted, blocked_on = 0, ""
        while self.queue and free:
            (req, prompt, prompt_len, padded, need, hashes,
             t_arrive, prefill_only, trace, deadline_at) = self.queue[0]
            if deadline_at is not None and self._clock() >= deadline_at:
                # dead on arrival at the slot: don't burn prefill compute
                # on a request whose budget already expired in the queue
                self.queue.popleft()
                finished.append(self._expire_queued(req.uid, prompt_len))
                continue
            hit = []
            if hashes:
                # longest-prefix match, capped so at least the final prompt
                # token is always prefilled — its logits seed the first
                # sampled token, so a 100%-cached prompt still runs one
                # chunk. The hit is then truncated to whole-CHUNK coverage:
                # prefill chunks start on the absolute j*chunk grid, so a
                # partial-chunk hit saves nothing (its chunk re-runs in
                # full) and would overstate every hit counter — and
                # dropping it means no chunk ever overlaps a shared block,
                # so registered blocks are never written again, period.
                # incref BEFORE alloc: the hit blocks may be sitting
                # refcount-0 on the reclaimable list, and our own alloc's
                # eviction must not recycle them out from under the match.
                limit = (prompt_len - 1) // self.block_size
                hit = self.prefix_cache.match(hashes[:limit])
                m = len(hit)
                while m and (m * self.block_size) % self.chunk:
                    m -= 1
                hit = hit[:m]
                for b in hit:
                    self.allocator.incref(b)
            ev0 = self.allocator.evictions
            blocks = self.allocator.alloc(need - len(hit))
            if blocks is None:
                if self.flightrec.enabled:
                    self.flightrec.record(
                        "backpressure", uid=req.uid, need=need - len(hit),
                        available=self.allocator.available,
                        queued=len(self.queue))
                # pool exhausted: FIFO backpressure — the head waits for
                # retirements to free blocks (no reordering: a stream of
                # small requests must not starve a big one). Decref the
                # tentative hit tail-first, like _retire: the chain head
                # must park most-recent so demand eviction trims tails
                # before it strands a whole chain
                if hit:
                    self.allocator.free(hit[::-1])
                blocked_on = "pool"
                break
            blocks = hit + blocks
            self.queue.popleft()
            slot = free.pop()
            slot.state = _PREFILL
            slot.uid = req.uid
            slot.prompt = prompt
            slot.prompt_len = prompt_len
            slot.padded_len = padded
            slot.max_new = int(req.max_new_tokens)
            slot.eos = self._resolve_eos(req)
            slot.blocks = blocks
            # prefill resumes at the cached boundary — exactly on the chunk
            # grid, because the hit was truncated to whole-chunk coverage
            # above. With the default prefill_chunk == kv_block_size every
            # hit block skips a whole chunk.
            slot.cursor = slot.planned = len(hit) * self.block_size
            slot.hashes = hashes
            slot.reg = len(hit)
            slot.cached = len(hit)
            slot.pos = self._whole(prompt_len)
            if not padded:
                # (a block-diffusion prompt shorter than a block: nothing to
                # prefill, its tokens open the first generated block)
                slot.state = _DECODE
            slot.emitted = []
            slot.prefill_only = prefill_only
            slot.deadline = deadline_at
            slot.t_arrive = t_arrive
            slot.t_admit = t_adm = self._clock()
            admitted += 1
            slot.req = self.steptrace.open_request(
                req.uid, t_arrive, t_adm, prompt_len,
                len(hit) * self.block_size)
            if self.telemetry.enabled:
                self.telemetry.observe("serving/queue_wait_ms",
                                       (t_adm - t_arrive) * 1e3)
            slot.trace = trace
            if self.tracer.enabled and trace is not None:
                # the queue-wait span + an admit mark; flow_end lands the
                # router's dispatch arrow on THIS replica's Perfetto track
                self.tracer.flow_end(trace, t_adm, tid=self.trace_tid)
                self.tracer.record(trace, "queued", t_arrive,
                                   max(0.0, t_adm - t_arrive),
                                   tid=self.trace_tid)
                self.tracer.event(trace, "admit", t_adm, tid=self.trace_tid,
                                  attrs={"slot": slot.idx,
                                         "blocks": len(blocks),
                                         "cached_blocks": len(hit)})
            if self.flightrec.enabled:
                # admission decision: the black box's bread and butter
                self.flightrec.record("admit", uid=req.uid, slot=slot.idx,
                                      blocks=len(blocks),
                                      cached_blocks=len(hit),
                                      queued=len(self.queue))
                if self.allocator.evictions > ev0:
                    self.flightrec.record(
                        "eviction", uid=req.uid,
                        evicted=self.allocator.evictions - ev0)
            self.tables[slot.idx, :] = TRASH_BLOCK
            self.tables[slot.idx, :len(blocks)] = blocks
            if hit:
                self.prefix_hit_blocks += len(hit)
                self.prefix_hit_tokens += len(hit) * self.block_size
                self.prefill_chunks_skipped += slot.cursor // self.chunk
        if self.queue and not blocked_on:
            blocked_on = "slots"        # the loop ran out of free slots
        return admitted, blocked_on

    def _vacate(self, slot: _Slot):
        """`slot`'s request gives up its place and its blocks, once: a new
        `_Slot` takes the place (never the old object again — a call in
        flight may still hold it as the request its tokens go to)."""
        if self.slots[slot.idx] is not slot:
            return                  # it left at dispatch (`_leave`)
        # blocks return to the pool the step the sequence finishes — a
        # DECREF: blocks shared through the prefix cache stay live until
        # their last reader retires, and registered refcount-0 blocks park
        # on the reclaimable list instead of the free list. Freed in
        # REVERSE block order so the hash-chain TAIL parks LRU-oldest:
        # demand eviction then trims chains tail-first, and the surviving
        # prefix stays matchable (match walks head-first and stops at the
        # first unregistered hash — evicting a head strands its whole tail)
        self.allocator.free(slot.blocks[::-1])
        self.tables[slot.idx, :] = TRASH_BLOCK
        if self.drafter is not None:
            self.drafter.retire(slot)       # stateful drafters drop slot state
        self.slots[slot.idx] = _Slot(slot.idx)

    def _leave(self, slot: _Slot, call: _Call):
        """`slot`'s request ends BY COUNT inside `call`, which is dispatched:
        its slot and blocks are free for the next call's admission now (the
        device runs calls in order, so a next owner's writes land after its
        own), and it takes its last tokens, and returns its
        `CompletedRequest`, when `call` is read back."""
        self._vacate(slot)
        call.leaving.append(slot)

    def _retire(self, slot: _Slot, reason: str) -> CompletedRequest:
        self._vacate(slot)
        timing = None
        t_finish = self._clock()
        self.steptrace.close_request(
            slot.req, slot.t_first, slot.step_first, t_finish,
            len(slot.emitted), reason)
        if self.telemetry.enabled and slot.t_admit is not None:
            self.telemetry.observe("serving/e2e_ms",
                                   (t_finish - slot.t_arrive) * 1e3)
            # TPOT (serving/tpot_ms) is recorded per emission burst in
            # _observe_tpot — per-token interpolation that stays honest
            # when a decode window or an accepted draft emits several
            # tokens in one sync — not as a per-request mean here
            timing = {"arrival": slot.t_arrive, "admit": slot.t_admit,
                      "first_token": slot.t_first, "finish": t_finish}
        if self.tracer.enabled and slot.trace is not None:
            self.tracer.event(slot.trace, "retire", t_finish,
                              tid=self.trace_tid,
                              attrs={"reason": reason,
                                     "tokens": len(slot.emitted)})
            if slot.trace.owner == "engine":
                # no router above: this engine closes the root (e2e) span
                self.tracer.finish(slot.trace, t_finish, tid=self.trace_tid,
                                   attrs={"reason": reason})
        if self.flightrec.enabled:
            self.flightrec.record("retire", uid=slot.uid, reason=reason,
                                  tokens=len(slot.emitted),
                                  freed_blocks=len(slot.blocks))
        done = CompletedRequest(uid=slot.uid, prompt_len=slot.prompt_len,
                                tokens=np.asarray(slot.emitted, np.int32),
                                finish_reason=reason,
                                cached_prefix_tokens=slot.cached
                                * self.block_size,
                                timing=timing)
        slot.state = _FREE      # a call in flight drops what it holds for it
        return done

    def _emit(self, slot: _Slot, tok: int, finished: List[CompletedRequest]):
        slot.emitted.append(int(tok))
        self.tokens_generated += 1
        if len(slot.emitted) == 1 and slot.t_arrive is not None:
            slot.t_first = slot.t_prev = self._clock()
            slot.step_first = self.steptrace.step
            if self.telemetry.enabled:
                self.telemetry.observe("serving/ttft_ms",
                                       (slot.t_first - slot.t_arrive) * 1e3)
        if slot.eos is not None and int(tok) == slot.eos:
            finished.append(self._retire(slot, "eos"))
        elif len(slot.emitted) >= slot.max_new:
            finished.append(self._retire(slot, "length"))

    def _observe_tpot(self, slot, anchor, j, t_now):
        """Per-token TPOT with intra-burst interpolation: a decode sync
        (read back at `t_now`, the end of its step phase) that emits `j`
        tokens for a slot since `anchor` (the previous emission sync)
        interpolates the j timestamps evenly across the
        interval — j samples of dt/j each — so `serving/tpot_ms` stays
        honest whether a step emits exactly one token, a K-token decode
        window, or 1..k+1 tokens from a verify step's accepted draft. (A
        single per-request mean would hide the burst cadence; dividing
        wall time by steps instead of tokens would overstate it.)"""
        if not self.telemetry.enabled or anchor is None or j <= 0:
            return
        per_tok = (t_now - anchor) / j * 1e3
        for _ in range(j):
            self.telemetry.observe("serving/tpot_ms", per_tok)
        if slot.state != _FREE:            # retired slots were reset already
            slot.t_prev = t_now

    # ------------------------------------------------------------------
    # cancellation + queue extraction (router TTL / failover build on these)
    # ------------------------------------------------------------------

    def _expire_queued(self, uid, prompt_len) -> CompletedRequest:
        """Complete a queued request whose hard deadline passed before it
        ever touched a slot."""
        self.deadline_cancelled += 1
        if self.telemetry.enabled:
            self.telemetry.inc("serving/deadline_cancelled")
        if self.flightrec.enabled:
            self.flightrec.record("deadline", uid=uid, queued=True)
        return CompletedRequest(uid=uid, prompt_len=prompt_len,
                                tokens=np.zeros((0,), np.int32),
                                finish_reason="deadline")

    def _sweep_deadlines(self, finished: List[CompletedRequest]):
        """Hard-deadline enforcement at the scheduler sync point: an active
        slot (generating OR parked for handoff) past its budget retires
        with reason "deadline" — blocks freed the same call — and queued
        requests past theirs complete without ever occupying a slot. Gated
        by `_deadlines`, so traffic without deadlines never pays the scan."""
        if not self._deadlines:
            return
        now = self._clock()
        for slot in self.slots:
            if slot.state != _FREE and slot.deadline is not None \
                    and now >= slot.deadline:
                self.deadline_cancelled += 1
                if self.telemetry.enabled:
                    self.telemetry.inc("serving/deadline_cancelled")
                if self.flightrec.enabled:
                    self.flightrec.record("deadline", uid=slot.uid,
                                          tokens=len(slot.emitted))
                finished.append(self._retire(slot, "deadline"))
        if any(rec[9] is not None for rec in self.queue):
            keep = collections.deque()
            for rec in self.queue:
                if rec[9] is not None and now >= rec[9]:
                    finished.append(self._expire_queued(rec[0].uid, rec[2]))
                else:
                    keep.append(rec)
            self.queue = keep

    def cancel(self, uid, queued_only: bool = False,
               reason: str = "cancelled") -> Optional[CompletedRequest]:
        """Withdraw a request wherever it lives. A queued request is removed
        before it ever touches a slot; an active one retires immediately —
        its blocks freed/decref'd the same call, exactly like an EOS
        retirement. Returns a `CompletedRequest` with
        ``finish_reason=reason`` (whatever tokens were already emitted are
        kept), or None when `uid` is unknown — or not cancellable under
        `queued_only=True`, the router-TTL mode that must never kill a
        request already generating. A slot PARKED in the handoff state is
        "not generating" for that purpose and IS cancelled under
        `queued_only` — it holds exported blocks on the source pool while
        waiting for a decode replica, and skipping it would leak them for
        as long as the handoff stays deferred."""
        for i, rec in enumerate(self.queue):
            if rec[0].uid == uid:
                del self.queue[i]
                self.cancelled += 1
                if self.flightrec.enabled:
                    self.flightrec.record("cancel", uid=uid, queued=True,
                                          reason=reason)
                return CompletedRequest(uid=uid, prompt_len=rec[2],
                                        tokens=np.zeros((0,), np.int32),
                                        finish_reason=reason)
        for slot in self._live():
            if slot.uid != uid:
                continue
            if queued_only and slot.state != _HANDOFF:
                return None
            if self._pending is not None:
                # its tokens may be in the call in flight: that is read
                # first, and where the request ended there, that is its end
                self._drain(self._early)
                for i, done in enumerate(self._early):
                    if done.uid == uid:
                        return self._early.pop(i)
            self.cancelled += 1
            return self._retire(slot, reason)
        return None

    def drain_queued(self) -> List[Request]:
        """Extract every queued-but-unstarted request, emptying the queue —
        the router's failover path: a quarantined replica's waiting requests
        are re-submitted elsewhere verbatim (they never touched this
        engine's pool, so nothing needs freeing)."""
        out = [rec[0] for rec in self.queue]
        self.queue.clear()
        return out

    def _live(self) -> List[_Slot]:
        """Every request that is not finished: those in slots, and those
        that left theirs at dispatch and wait for their last tokens."""
        live = [s for s in self.slots if s.state != _FREE]
        if self._pending is not None:
            live += [r for r in self._pending.leaving if r.state != _FREE]
        return live

    def active_uids(self) -> List[Any]:
        """Uids of requests that are not finished (prefilling, decoding,
        parked for handoff, or waiting for their last tokens) — in-flight
        work that dies with the engine."""
        return [s.uid for s in self._live()]

    def has_output(self, uid) -> bool:
        """True once the request has emitted its first token here — the
        router's hedging probe: a dispatched request with no output past
        `hedge_after_ms` earns a speculative duplicate elsewhere."""
        for s in self._live():
            if s.uid == uid:
                return len(s.emitted) > 0
        return False

    def shed_queued_below_priority(self, min_priority: int
                                   ) -> List[CompletedRequest]:
        """Degradation-ladder top rung: complete (reason "cancelled") every
        QUEUED request whose priority is strictly below `min_priority`.
        Active slots are never shed — their compute is already sunk."""
        out: List[CompletedRequest] = []
        keep = collections.deque()
        for rec in self.queue:
            req = rec[0]
            if int(getattr(req, "priority", 0)) < min_priority:
                self.cancelled += 1
                self.degradation_sheds += 1
                if self.telemetry.enabled:
                    self.telemetry.inc("serving/degradation_sheds")
                if self.flightrec.enabled:
                    self.flightrec.record("degrade_shed", uid=req.uid,
                                          priority=int(req.priority))
                out.append(CompletedRequest(uid=req.uid, prompt_len=rec[2],
                                            tokens=np.zeros((0,), np.int32),
                                            finish_reason="cancelled"))
            else:
                keep.append(rec)
        self.queue = keep
        return out

    # ------------------------------------------------------------------
    # pool invariant auditing (inference/audit.py)
    # ------------------------------------------------------------------

    def audit_state(self) -> Dict[str, Any]:
        """Portable JSON snapshot of the pool bookkeeping — what
        `bin/dstpu_audit` consumes, and what a flight dump embeds."""
        return self._auditor.snapshot()

    def audit(self, repair: bool = False):
        """Run the pool invariant auditor now. On violations: dump the
        flight recorder (ring + report + portable state snapshot), then —
        with `repair=True` — rebuild the free list/refcounts/reclaimable
        LRU from the slot tables (ground truth) and re-audit; a repair
        that cannot reach a clean state raises `PoolCorruptionError`.
        Returns the (pre-repair) `AuditReport`."""
        self._drain(self._early)        # the books and the device agree
        report = self._auditor.audit()
        self.audits_run += 1
        if report.ok:
            return report
        self.audit_violations_total += len(report.violations)
        if self.telemetry.enabled:
            self.telemetry.inc("serving/audit_violations",
                               len(report.violations))
        if self.flightrec.enabled:
            self.flightrec.record("audit_violation",
                                  violations=len(report.violations),
                                  by_kind=report.by_kind())
            try:
                stats = self.stats()
            except Exception as e:                    # a corrupt pool may
                stats = {"error": str(e)}             # break stats() itself
            self.flightrec.dump(
                f"pool audit failed: {report.summary()}",
                state={"audit": report.to_dict(),
                       "audit_state": self._auditor.snapshot(),
                       "stats": stats})
        if repair:
            summary = self._auditor.repair()
            self.audit_repairs += 1
            if self.telemetry.enabled:
                self.telemetry.inc("serving/audit_repairs")
            if self.flightrec.enabled:
                self.flightrec.record("audit_repair", **{
                    k: summary[k] for k in ("violations_before",
                                            "violations_after", "clean")})
            log_dist(f"serving audit: repaired {report.summary()} -> "
                     f"{'clean' if summary['clean'] else 'STILL DIRTY'}",
                     ranks=[0])
            if not summary["clean"]:
                raise PoolCorruptionError(report)
        return report

    def _scheduled_audit(self):
        """The every-N-syncs audit: repair in place or raise so the router
        quarantines this replica, per `serving.audit_action`."""
        report = self.audit(repair=(self.audit_action == "repair"))
        if not report.ok and self.audit_action == "raise":
            raise PoolCorruptionError(report)

    def close(self):
        """Engine shutdown: one final invariant audit (always — leaked
        blocks at teardown are the cheapest possible time to catch) plus a
        telemetry flush. Returns the final `AuditReport`."""
        report = self.audit(repair=(self.audit_action == "repair"))
        self.telemetry.close()
        return report

    # ------------------------------------------------------------------
    # router surface: affinity scoring + load signals
    # ------------------------------------------------------------------

    def hash_chain(self, prompt) -> Optional[List[bytes]]:
        """The prompt's chained block hashes (None when caching is off) —
        computed once by the router and matched against every replica."""
        if self.prefix_cache is None:
            return None
        return self.prefix_cache.hash_chain(
            np.asarray(prompt, np.int32).reshape(-1))

    def prefix_affinity(self, hashes) -> int:
        """Longest registered prefix (in blocks) this engine already holds
        for a prompt's hash chain — the router's affinity score. Read-only:
        no refcounts move, no LRU entry is touched. 0 when caching is off."""
        if self.prefix_cache is None or not hashes:
            return 0
        return self.prefix_cache.match_len(hashes)

    @property
    def queue_depth(self) -> int:
        return len(self.queue)

    @property
    def has_free_slot(self) -> bool:
        return any(s.state == _FREE for s in self.slots)

    # ------------------------------------------------------------------
    # disaggregated prefill/decode: block handoff between engines
    # ------------------------------------------------------------------

    def handoff_ready(self) -> List[Any]:
        """Uids of prefill-only slots whose prefill finished: their blocks
        hold the full prompt KV and their first sampled token is emitted —
        ready for `export_handoff` into a decode engine."""
        return [s.uid for s in self.slots
                if s.state == _HANDOFF and not s.flying]

    def export_handoff(self, uid) -> Dict[str, Any]:
        """Snapshot a handoff-parked slot for transplant. The blocks stay
        OWNED by this engine (refcounts untouched) until `release_handoff`
        — the copy must complete before the source can be reclaimed, the
        same protocol as the checkpoint saver's tmp->rename commit."""
        self._drain(self._early)        # its first token may be in flight
        slot = self._handoff_slot(uid)
        # blocks the prefill cursor actually wrote: the padded prompt only
        # (a prefill-only slot never decodes here, so no window tail)
        n_used = (slot.padded_len - 1) // self.block_size + 1
        return {"uid": slot.uid, "prompt": slot.prompt,
                "prompt_len": slot.prompt_len, "padded_len": slot.padded_len,
                "max_new": slot.max_new, "eos": slot.eos,
                "emitted": list(slot.emitted), "pos": slot.pos,
                "blocks": list(slot.blocks[:n_used]),
                "cached": slot.cached, "t_arrive": slot.t_arrive,
                "t_admit": slot.t_admit, "t_first": slot.t_first,
                "trace": slot.trace}

    def adopt_handoff(self, state: Dict[str, Any], src_pool) -> bool:
        """Adopt a prefilled slot exported by another engine: allocate the
        full-lifetime blocks here, gather the prompt's KV blocks out of
        `src_pool` into them (`transplant_blocks` — a block-indexed copy,
        axis 1 of the pool layout), and seed a _DECODE slot that continues
        from the first sampled token. Returns False when this engine has no
        free slot or blocks RIGHT NOW (the router retries later — source
        blocks are still held; a pool of two kinds refuses, see
        `_refuse_transplant`); raises `InadmissibleRequestError` when the
        request can never fit here."""
        self._refuse_transplant()
        need = blocks_needed(state["prompt_len"], state["padded_len"],
                             state["max_new"], self.block_size,
                             window=self.window, spec_k=self.draft_k)
        if max_written_pos(state["prompt_len"], state["padded_len"],
                           state["max_new"], self.window,
                           self.draft_k) >= self.max_context:
            raise InadmissibleRequestError(
                f"request {state['uid']}: handoff target max_context "
                f"{self.max_context} too small (prompt {state['prompt_len']}"
                f" + max_new {state['max_new']}, window {self.window})")
        if need > self.allocator.capacity:
            raise InadmissibleRequestError(
                f"request {state['uid']}: handoff needs {need} KV blocks, "
                f"decode pool has {self.allocator.capacity}")
        free = [s for s in self.slots if s.state == _FREE]
        if not free:
            return False
        blocks = self.allocator.alloc(need)
        if blocks is None:
            return False
        n_src = len(state["blocks"])
        try:
            self.pool = transplant_blocks(src_pool, state["blocks"],
                                          self.pool, blocks[:n_src],
                                          pad_to=self.nb)
        except Exception:
            self.allocator.free(blocks)    # don't leak the reservation
            raise
        slot = free[-1]
        slot.state = _DECODE
        slot.uid = state["uid"]
        slot.prompt = state["prompt"]
        slot.prompt_len = state["prompt_len"]
        slot.padded_len = state["padded_len"]
        slot.max_new = state["max_new"]
        slot.eos = state["eos"]
        slot.blocks = blocks
        slot.cursor = slot.planned = state["padded_len"]
        slot.pos = state["pos"]
        slot.emitted = list(state["emitted"])
        slot.hashes = None          # adopted blocks stay private: this pool
        slot.reg = 0                # never registers them (the prefill
        slot.cached = state["cached"]  # replica's cache owns the prefix)
        # carry the PREFILL replica's stamps: TTFT/TPOT must measure from
        # the real first token, not from adoption time (a parked slot would
        # otherwise report an inflated, decode-attributed TTFT)
        slot.t_arrive = state["t_arrive"]
        slot.t_admit = state.get("t_admit")
        slot.t_first = state.get("t_first")
        slot.t_prev = slot.t_first         # TPOT interpolation re-anchors here
        slot.trace = state.get("trace")    # decode spans continue the trace
        # the request's record here opens at adoption, under its uid
        slot.req = self.steptrace.open_request(
            slot.uid, slot.t_arrive, self._clock(), slot.prompt_len,
            slot.cached * self.block_size, t_first_token=slot.t_first)
        self.tables[slot.idx, :] = TRASH_BLOCK
        self.tables[slot.idx, :len(blocks)] = blocks
        self.handoffs_in += 1
        return True

    def release_handoff(self, uid):
        """Free the source side of a completed transplant: decref the
        slot's blocks (registered prefix blocks park reclaimable and stay
        matchable for affinity) and recycle the slot."""
        slot = self._handoff_slot(uid)
        self._vacate(slot)
        self.steptrace.close_request(
            slot.req, slot.t_first, slot.step_first, self._clock(),
            len(slot.emitted), "handoff")
        slot.state = _FREE
        self.handoffs_out += 1

    def _handoff_slot(self, uid) -> _Slot:
        for s in self.slots:
            if s.state == _HANDOFF and s.uid == uid:
                return s
        raise KeyError(f"no handoff-ready slot for request {uid!r}")

    # ------------------------------------------------------------------
    # speculative decoding: draft -> one fixed-shape verify -> accept+rewind
    # ------------------------------------------------------------------

    def _verify_decode(self, dec, pos, tables, finished):
        """Draft+verify replacing the decode step: the drafter proposes up
        to `draft_k` tokens per slot, ONE jitted verify call scores drafts
        for ALL slots (writing their k/v at pos..pos+k through the tables),
        and each slot emits its longest agreeing prefix plus the bonus
        token from the first disagreeing row — 1..k+1 tokens per model
        step. Rejection is the O(1) rollback the paged layout buys: the
        cursor advances only past accepted tokens, the rejected tokens'
        k/v sits beyond it (overwritten by the next verify's writes, never
        attended — the causal mask stops at the cursor), and the slot's
        blocks and table rows do not move."""
        st = self.steptrace
        with self._phase("serving/draft"):
            # (nothing is in flight under spec decode, `_overlaps`: a slot's
            # input is the token the host emitted last)
            tok = np.zeros((self.max_slots,), np.int32)
            for s in dec:
                tok[s.idx] = s.emitted[-1]
            drafts, dlens = self.drafter.propose(dec, tok, pos, tables)
        if self.pressure is not None and self.pressure.draft_cap is not None:
            # ladder rung 1: cap the ACCEPTED draft length only — the
            # verify program keeps its compiled [S, k+1] shape, drafts past
            # the cap score as padding and land past the cursor (dead)
            dlens = np.minimum(dlens, self.pressure.draft_cap)
        toks = np.concatenate([tok[:, None], drafts], axis=1)
        with self._dispatching("serving/verify", "verify", rows=len(dec),
                               win=self.draft_k + 1):
            st.dispatched()
            out, self.pool = self.programs.verify(
                self.engine.params, toks, pos, self.pool, tables,
                self._next_rng())
        # THE one host roundtrip per verify step — acceptance runs host-side, amortized over k+1 tokens x all slots
        with self._read_back(self.device_calls, out) as (scored, rec):
            self._accept(dec, drafts, dlens, np.asarray(scored),  # [S, k+1]
                         rec, finished)

    def _accept(self, dec, drafts, dlens, tgt, rec, finished):
        """Hand out what the verify call `rec` scored: a slot's longest
        agreeing prefix and its bonus token."""
        tr_on = self.tracer.enabled
        self.verify_calls += 1
        self.decode_steps += 1
        with self._phase("serving/emit"):
            for s in dec:
                dlen = int(dlens[s.idx])
                ctx, uid = s.trace, s.uid         # _retire resets the slot
                n, emitted = accept_greedy(drafts[s.idx], tgt[s.idx], dlen)
                # O(1) rollback/advance: the cursor moves past the accepted
                # prefix + bonus only; everything else written this step is
                # dead weight the next verify overwrites
                s.pos += n + 1
                self.verify_slot_steps += 1
                self.drafted_tokens += dlen
                self.accepted_tokens += n
                if self.telemetry.enabled:
                    if dlen:
                        self.telemetry.observe("serving/spec_accept_rate",
                                               n / dlen)
                    self.telemetry.inc("serving/spec_accepted_tokens", n)
                    self.telemetry.inc("serving/spec_drafted_tokens", dlen)
                anchor, j = s.t_prev, 0
                for t in emitted:
                    # EOS inside an accepted draft retires the slot right here,
                    # at the EOS position — the accepted tail past it (and the
                    # bonus) is discarded exactly like a window tail
                    self._emit(s, t, finished)
                    j += 1
                    if s.state == _FREE:
                        break
                # j, not len(emitted): an EOS or max_new retirement mid-burst
                # truncates the accepted tail — only tokens that actually
                # reached the output count toward the tokens/step multiple
                self.spec_emitted_tokens += j
                self._observe_tpot(s, anchor, j, rec.t_wait1)
                if tr_on and ctx is not None:
                    self.tracer.record(ctx, "verify", rec.t_launch0,
                                       rec.t_wait1 - rec.t_launch0,
                                       tid=self.trace_tid,
                                       attrs={"drafted": dlen, "accepted": n,
                                              "emitted": j, "call": rec.id})
                if self.flightrec.enabled and n < dlen:
                    # spec-decode rollback: the cursor rewound past dlen-n
                    # rejected draft tokens — O(1), but worth the black box
                    self.flightrec.record("rollback", uid=uid,
                                          rejected=dlen - n, accepted=n)
            if self.telemetry.enabled:
                self.telemetry.inc("serving/spec_verify_steps")

    # ------------------------------------------------------------------
    # the engine step: admit -> prefill chunk(s) -> decode all slots (the
    # step's last chunks riding the decode call where slots decode already)
    # ------------------------------------------------------------------

    def step(self) -> List[CompletedRequest]:
        """One scheduler iteration. Returns the requests that finished.

        The try/except is the OOM-forensics dispatch boundary: a
        RESOURCE_EXHAUSTED escaping the compiled calls dumps the memory
        ledger + planner delta + flight-recorder ring (memscope enabled)
        before re-raising — the error itself is never swallowed."""
        try:
            return self._step_impl()
        except Exception as e:
            if self.memscope is not None:
                self.memscope.on_step_error(e)
            raise

    def _step_impl(self) -> List[CompletedRequest]:
        """Admit, prefill up to `prefill_budget` chunks, decode every slot.
        The device calls of a step: each chunk a `prefill_step` call and then
        one `decode_step` call — or, where slots were decoding as the step
        began, the last min(chunks due, window * G) chunks and the decode
        window as ONE `mixed_step` call, up to G chunks a token
        (`_chunks_riding`, `_launch`; G = `programs.group`), the chunks
        before them as their own calls first. One blocking read-back a
        decode or mixed call, one for a prompt's last chunk where that was
        a call of its own.

        The read-back of a decode or mixed call comes one step LATE: this
        step dispatches its call and then reads the call the step before
        left in flight (`_launch`), so the device always has the next call
        queued. `_overlaps` says which steps may leave a call in flight; the
        others read their own call back before they return, through the
        same code with nothing pending."""
        finished, self._early = self._early, []
        self.steps += 1
        params = self.engine.params
        # the step timeline: phases tile the step (admit, each prefill
        # chunk that is a call of its own, decode_build, decode_window — the
        # decode call's dispatch, with or without chunks riding it — or
        # draft + verify, read_back — the blocking read of the call in
        # flight: the host's whole wait — emit, housekeeping);
        # dispatched()/ready() bracket the device calls
        st = self.steptrace
        st.begin_step()
        compiled0 = self._compiled_programs()
        chunks0, tokens0 = self.prefill_chunks, self.tokens_generated
        calls0, overlapped0 = self.device_calls, self.overlapped_calls
        groups0, padded0 = self.chunk_groups, self.padded_chunks

        overlap = self._overlaps()
        if not overlap:
            # this step's decisions need the tokens: nothing stays in flight
            self._drain(finished)

        with self._phase("serving/admit"):
            admitted, blocked_on = self._admit(finished)

        # chunked prefill, bounded per step so arriving prompts cannot stall
        # the running batch for more than prefill_budget chunk-times. Where
        # slots are decoding already, the step's last chunks (up to G a token
        # of the decode window) RIDE the decode call below as one mixed
        # program; the others, and every chunk of a step that fuses
        # nothing, are a `prefill_step` call each
        due = self._chunks_due()
        ride = self._chunks_riding(len(due), bool(due) and sum(
            s.state == _DECODE for s in self.slots))
        riding = due[len(due) - ride:]
        for slot, start in due[:len(due) - ride]:
            self._prefill_chunk(slot, start, params, finished)

        # decode: ONE fixed-shape call for every slot; non-decoding slots
        # ride along against the trash block. With window > 1 the call
        # emits a whole window per slot; a slot finishing mid-window
        # discards the tail (already written to its own blocks — the
        # blocks_needed window padding covers it). With spec decode on,
        # the verify step replaces this call entirely. A slot whose final
        # chunk ran above decodes in this call; one whose final chunk RIDES
        # this call decodes from the next, on that call's first token.
        dec = [s for s in self.slots if s.state == _DECODE]
        if dec:
            with self._phase("serving/decode_build"):
                self.peak_active = max(self.peak_active, len(dec))
                pos = np.zeros((self.max_slots,), np.int32)
                tables = np.full_like(self.tables, TRASH_BLOCK)
                for s in dec:
                    pos[s.idx] = s.pos
                    tables[s.idx] = self.tables[s.idx]
                spec_active = self.spec_on and not (
                    self.pressure is not None and self.pressure.spec_disabled)
                # what the slots feed the call is the generator's to build
                # (the verify call takes the host's tokens: `_verify_decode`)
                feed = None if spec_active else self.gen.feed(
                    dec, self._pending, self.programs.no_prev)
            if spec_active:
                self._verify_decode(dec, pos, tables, finished)
            else:
                self._launch(dec, riding, params, feed, pos, tables, finished)
        if not (overlap and dec) or not self._pending.awaited():
            # nothing was put behind the call in flight, this step may leave
            # none, or no live request waits for what it left (every row
            # ended while it ran): it is read now
            self._drain(finished)

        # sync-point housekeeping: hard deadlines, the pressure ladder, and
        # the scheduled pool audit all run here — between compiled calls,
        # on host state only
        audit_due = self.audit_interval and \
            self.steps % self.audit_interval == 0
        if audit_due:
            self._drain(finished)
        with self._phase("serving/housekeeping"):
            self._sweep_deadlines(finished)
            if self.pressure is not None:
                self.pressure.update(finished)
            if audit_due:
                self._scheduled_audit()

            if self.telemetry.enabled:
                self.telemetry.set_gauge("serving/queue_depth",
                                         len(self.queue))
                self.telemetry.set_gauge("serving/active_slots",
                                         self.num_active)
                self.telemetry.set_gauge("serving/free_blocks",
                                         self.allocator.available)
                if self.memscope is not None:
                    # mem/* ledger gauges; the first publish also runs the
                    # lazy per-program memory_analysis pass (AOT — no
                    # jit-cache hit)
                    self.memscope.publish()
                self.telemetry.maybe_export(self.steps)

        counters = ()
        if self.step_counter_names:
            # of the calls READ in this step (a call in flight brings its
            # counters with its tokens, a step after the one it ran in)
            counters = tuple(int(v) for v in self._step_counts)
            self.step_counter_totals += self._step_counts
            self._step_counts[:] = 0
        # what the step's calls do, by field (`_book`): the calls it
        # dispatched, or where the generator says so the calls it READ (whose
        # shares by the forwards taken are whole numbers in sum only)
        work, self._work = self._work, {}
        work = {name: int(round(n)) for name, n in work.items()}
        for name in self.walk_totals:
            self.walk_totals[name] += work.get(name, 0)
        st.end_step(counters=counters, **work,
                    admitted=admitted,
                    prefill_chunks=self.prefill_chunks - chunks0,
                    fused_chunks=len(riding),
                    decoding=len(dec),
                    emitted=self.tokens_generated - tokens0,
                    queued=len(self.queue),
                    free_blocks=self.allocator.available,
                    blocked_on=blocked_on,
                    compiles=self._compiled_programs() - compiled0,
                    device_calls=self.device_calls - calls0,
                    overlapped_calls=self.overlapped_calls - overlapped0,
                    chunk_groups=self.chunk_groups - groups0,
                    padded_chunks=self.padded_chunks - padded0)
        return finished

    def _overlaps(self):
        """May this step leave its decode or mixed call in flight, to be
        read by the next step after THAT step's call is dispatched? The
        whole rule, from what the step holds: the call is a resident
        engine's decode or mixed program (the streamed walk runs host
        numpy between its layers), spec decode is off (acceptance decides
        the next input), and the pressure ladder is at rest (its rungs
        reshape the call and read the pool's state). Nothing is set."""
        return not self.streamed and not self.spec_on and not (
            self.pressure is not None and self.pressure.level)

    @contextlib.contextmanager
    def _dispatching(self, phase, program, **work):
        """THE place a call whose tokens a blocking read fetches goes out (a
        decode or mixed call, a verify call, a prompt's last chunk as a call
        of its own): counted, named — `device_calls` is its id from here —
        and dispatched inside `phase`, whose trace annotation carries the
        id; then its `steptrace.CallRecord` is opened with the phase's two
        stamps, whether a call was unread as it went out, and its `work`
        (`rows`, `win`, `firsts`, and `chunks`: the chunks riding it and
        those dispatched before it whose progress its read-back confirms).
        `_read_back` closes it."""
        queued = self._pending is not None
        self.device_calls += 1
        self.overlapped_calls += queued
        with self._phase(phase, call=self.device_calls) as ph:
            yield ph
        self.steptrace.open_call(self.device_calls, program, ph.t0, ph.t1,
                                 queued, **work)

    def _drain(self, finished):
        """THE place a call in flight is read back ahead of its turn: by a
        step that may not overlap, a prompt's last chunk as a call of its
        own, a step with nothing to put behind it, the scheduled audit — and
        outside `step()` (`cancel`, `export_handoff`, `audit`, `close`, the
        end of `run()`), where the completions go to `_early` and the next
        `step()` returns them."""
        call, self._pending = self._pending, None
        if call is not None:
            self._read(call, finished)

    def _chunks_due(self):
        """This step's prefill chunks in dispatch order, (slot, start) each:
        the slots in order, a slot's chunks back to back, `prefill_budget`
        of them in all."""
        due = []
        for slot in self.slots:
            if slot.state != _PREFILL:
                continue
            for start in range(slot.planned, slot.padded_len, self.chunk):
                if len(due) == self.prefill_budget:
                    return due
                due.append((slot, start))
        return due

    def _chunks_riding(self, due, decoding):
        """How many of a step's `due` chunks (its last ones) ride its decode
        call as one mixed program, given `decoding` slots in the decode
        state as the step begins. The whole rule: there is a chunk and a
        slot that decodes, the model can run the two as one
        (`mixed_paged_fn`; built for resident engines without spec decode),
        the program is one device's, and the pressure ladder is at rest (its
        rungs reshape the decode call). Up to G chunks a token of the window
        (`programs.group`: ceil(prefill budget / window) where the model's
        mixed program takes a group, else one) — what a step holds decides,
        nothing is set."""
        if not (due and decoding) or self.programs.mixed is None \
                or self.engine.mesh.size != 1 \
                or (self.pressure is not None and self.pressure.level):
            return 0
        return min(due, self.ride_window * self.programs.group)

    def _chunk_input(self, slot, start):
        """The chunk of `slot`'s prompt at `start`: (tokens [1, chunk], index
        of the row whose logits count, whether it is the prompt's last)."""
        chunk = np.zeros((1, self.chunk), np.int32)
        whole = self._whole(slot.prompt_len)
        seg = slot.prompt[start:min(start + self.chunk, whole)]
        chunk[0, :len(seg)] = seg
        final = start + self.chunk >= slot.padded_len
        last = (whole - 1 - start) if final else self.chunk - 1
        return chunk, last, final

    def _chunk_planned(self, slot, start):
        """Book a dispatched chunk of `slot`: what is planned of its prompt
        (`cursor` follows at the read-back that covers the chunk,
        `_chunks_run`), counter, the prefix cache registrations it
        completes. What the chunk DOES is `_chunk_walk`'s to count."""
        slot.planned = start + self.chunk
        self._unread_chunks.append((slot, slot.planned))
        self.prefill_chunks += 1
        if self.prefix_cache is not None and slot.hashes:
            # register blocks the chunk just dispatched writes —
            # full blocks strictly below prompt_len only (the
            # padded tail and decode-written blocks stay private,
            # so shared blocks are immutable by construction). A
            # block becomes matchable only here, AFTER the call that
            # writes its content is dispatched: registering at admission
            # would let a same-step sibling map garbage.
            hi = min(slot.planned, slot.prompt_len) // self.block_size
            for i in range(slot.reg, hi):
                self.prefix_cache.register(slot.hashes[i],
                                           slot.blocks[i])
            slot.reg = max(slot.reg, hi)

    def _chunk_walk(self, start, phase):
        """What a chunk from `start` does, a layer, as `StepRecord` fields:
        the positions a state kind's chunked scan runs over, and what the
        attention program the chunk was traced with at `phase`
        (`DecodeModelSpec.paged_attn_programs`: "prefill_chunk", or
        "mixed/prefill_chunk" where it rides) counts of its walk — the
        program's own `work` (`ops/attention_dispatch.py`), asked once for
        the full layers' blocks and, for a pool of two kinds, once for a
        WINDOW layer's walk in ITS blocks. Nothing of a walk where the
        program has none (the gather oracle attends the whole table)."""
        from deepspeed_tpu.ops.attention_dispatch import get_program
        work = {"ssm_chunk_tokens": self.chunk} \
            if self.state_kind is not None else {}
        if self._index_topk:
            work.update(self._sparse_work(
                start + 1 + np.arange(self.chunk, dtype=np.int64)))
        traced = getattr(self.engine.model_spec, "paged_attn_programs",
                         None) or {}
        count = get_program(traced[phase]).work if phase in traced else None
        if count is None:
            return work
        work.update(_lands(count(
            start, self.chunk, self.block_size, self.tables.shape[1]),
            _CHUNK_FIELDS))
        if self.window_kind is not None:
            wkind = self.window_kind
            work.update(_lands(count(
                start, self.chunk, wkind.block, self.ring_tables.shape[1],
                wkind.window), _CHUNK_WINDOW_FIELDS))
        return work

    def _sparse_work(self, seen):
        """What a sparse layer's indexer makes of queries that see `seen`
        positions each (numpy; t + 1 for a query at t), a layer, as
        `StepRecord` fields: the pairs scored, the pairs selected, and the
        pairs the attention walks read for them — every position a query
        sees, whatever was selected: the selection rides the dense walks as
        a mask (`models/sparse_attn.py`)."""
        scored = int(np.sum(seen))
        return {"index_scored_positions": scored,
                "selected_positions": int(np.sum(np.minimum(
                    seen, self._index_topk))),
                "sparse_walk_positions": scored}

    def _book(self, work):
        """Add `work` (`StepRecord` field -> count) to the open step's sums:
        `end_step` takes them whole."""
        sums = self._work
        for name, n in work.items():
            sums[name] = sums.get(name, 0) + n

    def _chunks_run(self, chunks):
        """A read-back returned that was dispatched after `chunks`
        ((request, prompt tokens prefilled) each): the device has run them."""
        for slot, upto in chunks:
            slot.cursor = max(slot.cursor, upto)

    def _first_token(self, slot, tok, finished):
        """A prompt's last chunk is in: the slot decodes from here (or parks
        for handoff) and emits the token that chunk's last row sampled."""
        # a prefill-only slot parks for handoff instead of
        # decoding; _emit may still retire it right here when
        # the first sampled token is EOS or max_new == 1 — the
        # router then sees a normal completion from this engine
        slot.state = _HANDOFF if slot.prefill_only else _DECODE
        if self.gen.samples_first:
            self._emit(slot, tok, finished)

    def _prefill_chunk(self, slot, start, params, finished):
        """One prefill chunk of `slot` (its prompt from `start`) as a call of
        its own — a chunk that does not ride the decode call (`_launch`):
        input build, dispatch, the cache registrations it completes and,
        after the final chunk, the first-token read-back — a blocking read
        of this step's own, so a call in flight is read before it
        (`_drain`)."""
        st = self.steptrace
        ctx = slot.trace                      # _emit may retire the slot
        chunk, last, final = self._chunk_input(slot, start)
        with (self._dispatching("serving/prefill_chunk", "prefill",
                                firsts=int(self.gen.samples_first),
                                chunks=len(self._unread_chunks) + 1)
              if final else self._phase("serving/prefill_chunk")) as ph:
            st.dispatched()
            out, self.pool = self.programs.prefill(
                params, chunk, np.asarray([start], np.int32),
                np.asarray([last], np.int32), self.pool,
                self._tables_arg(self.tables[slot.idx][None], [slot.idx]),
                self._next_rng())
            if self.drafter is not None:
                # a stateful drafter (the draft model) shadows the chunk
                # into its own pool through the same table — the draft
                # cache is warm the moment this slot starts verifying
                self.drafter.prefill_chunk(
                    slot, chunk, np.asarray([start], np.int32),
                    np.asarray([last], np.int32),
                    self.tables[slot.idx][None])
            # counted here, while the device runs
            self._chunk_planned(slot, start)
            self._book(self._chunk_walk(start, "prefill_chunk"))
            if not final:
                # nobody reads this chunk's token: its counts wait for the
                # next read-back
                self._parked_counts.append(out[1])
        t1 = ph.t1
        if final:
            self._drain(finished)       # in the device's order, and its own
                                        # phases: the call in flight first
            # first-token readback at prefill completion — one scalar per prompt, the TTFT emission point
            with self._read_back(self.device_calls, out) as (first, rec):
                with self._phase("serving/emit"):
                    self._chunks_run(self._unread_chunks)
                    self._unread_chunks = []
                    self._first_token(slot, int(np.asarray(first)[0]),
                                      finished)
            t1 = rec.t_wait1
        if self.tracer.enabled and ctx is not None:
            attrs = {"start": start, "chunk": self.chunk}
            if final:
                attrs["call"] = rec.id
            self.tracer.record(ctx, "prefill_chunk", ph.t0, t1 - ph.t0,
                               tid=self.trace_tid, attrs=attrs)

    def _launch(self, dec, riding, params, feed, pos, tables, finished):
        """Dispatch the decode call for every slot in `dec` — `decode_step`,
        or with the chunks `riding` it ((slot, start) each; up to G a token
        of the window, full groups first: token i takes chunks [i * G,
        (i + 1) * G), so only the last riding token may carry fewer and the
        tokens after it are plain decode tokens) ONE `mixed_step` call — and
        only then
        read the call the step before left in flight (`_read`): this one is
        queued behind it on the device meanwhile. `feed` is the generator's
        (the call's token argument, `skip`): for a token a row the host's
        token, or the output of the call in flight, still on the device.
        What the host can count is booked at dispatch: positions,
        each chunk as `_prefill_chunk` books its own, the call's walks where
        the generator says they are due (`_decode_walk`, `due`), a prompt
        whose last chunk rides (it decodes from the next call; on this call's
        first token, where one is sampled), and a request that reaches
        `max_new` inside this call (`_leave`). The read-back brings the
        window's tokens, those first tokens and the model's counters; it is
        left to the next step, which `_step_impl` decides."""
        st = self.steptrace
        gen = self.gen
        # the degraded paths (spec decode pressure-disabled, the ladder's
        # window-shrink rung) run the 1-STEP program: `programs.decode_w1`
        use_w1 = self.spec_on or (
            self.pressure is not None
            and self.pressure.force_window_1)
        win, n = 1 if use_w1 else self.window, len(riding)
        G = self.programs.group
        ride = self.ride_window     # the forwards a chunk group may ride
        prior = self._pending
        no_prev = self.programs.no_prev
        tok, skip = feed
        finals = []
        if riding:
            with self._phase("serving/decode_build"):
                # chunk i of `riding` is chunk i % G of window token (a block
                # generator's forward) i // G: row i of the arrays' flat
                # [ride * G, ...] view, and first token i of the call's
                chunks = np.zeros((ride * G, self.chunk), np.int32)
                starts = np.zeros((ride * G,), np.int32)
                lasts = np.zeros((ride * G,), np.int32)
                for i, (slot, start) in enumerate(riding):
                    chunks[i:i + 1], lasts[i], final = self._chunk_input(
                        slot, start)
                    starts[i] = start
                    if final:
                        finals.append((slot, i))
                chunks = chunks.reshape(ride, G, self.chunk)
                starts, lasts = starts.reshape(ride, G), lasts.reshape(ride, G)
                # chunks past `n` are never read: any slot's row fills them
                idx = [slot.idx for slot, _ in riding]
                idx += idx[-1:] * (ride * G - n)
                chunk_tables = jax.tree_util.tree_map(
                    lambda t: t.reshape(ride, G, -1),
                    self._tables_arg(self.tables[idx], idx))
        step_fn = self.programs.mixed if riding else \
            self.programs.decode_w1() if use_w1 else self.programs.decode
        # the dispatch phase holds the jitted call alone: its two stamps are
        # the call record's launch, the arguments' hand-off and the enqueue
        with self._dispatching(
                "serving/decode_window",
                "mixed" if riding else "decode_w1" if use_w1 else "decode",
                rows=len(dec), win=win * gen.row_forwards,
                firsts=len(finals) * gen.samples_first,
                chunks=len(self._unread_chunks) + n):
            st.dispatched()
            if riding:
                out, self.pool = step_fn(
                    params, chunks, starts, lasts, chunk_tables, np.int32(n),
                    tok, pos, self.pool, self._tables_arg(tables),
                    self._next_rng())
            else:
                out, self.pool = step_fn(params, tok, pos, self.pool,
                                         self._tables_arg(tables),
                                         self._next_rng())
        # what the call planned is booked here, while the device runs
        with self._phase("serving/decode_build"):
            for slot, start in riding:
                self._chunk_planned(slot, start)
                self._book(self._chunk_walk(start, "mixed/prefill_chunk"))
            self.fused_chunks += n
            self.chunk_groups += -(-n // G)
            self.padded_chunks += -n % G
            work = self._decode_walk(dec, pos, win)
            self._book(gen.due(work))
            prev, mixed = gen.opens(out, riding, no_prev)
            call = _Call(self.device_calls, out, prev, mixed, win, dec,
                         finals if gen.samples_first else [],
                         self._unread_chunks, riding, skip, work)
            self._unread_chunks = []
            self._pending = call
            for s in dec:
                s.pos += win
                s.flying += win - skip.get(s.idx, 0)
                s.feed = (call.id, 1)
                if len(s.emitted) + s.flying >= s.max_new:
                    self._leave(s, call)
            for slot, i in finals:
                slot.state = _HANDOFF if slot.prefill_only else _DECODE
                if not gen.samples_first:
                    continue    # it opens its first block in the next call
                slot.flying = 1
                slot.feed = (call.id, 2 + i)
                if slot.max_new <= 1:
                    self._leave(slot, call)
        if prior is not None:
            self._read(prior, finished)

    def _read(self, call, finished):
        """THE one host roundtrip of a decode or mixed call — its blocking
        read-back, and the emission of what it sampled: the window's tokens
        to the requests that were in it, the first token of every prompt
        whose last chunk rode it. EOS, retirement and the stamps (`t_first`,
        TPOT, a request's close) happen here, at the read-back that
        delivered the token. A request that ended while the call ran (EOS in
        the call before, a deadline, a cancel) takes nothing: that was its
        one speculative window."""
        # THE one host roundtrip per decode window — EOS/retirement decisions are host-side, amortized over `win` tokens
        with self._read_back(call.id, call.out) as (toks, rec):
            self._book(self.gen.due(call.work, rec))
            self._hand_out(call, toks, rec, finished)

    def _hand_out(self, call, toks, rec, finished):
        """Emit what `call`'s read-back brought; `rec` is its record, whose
        stamps (launch to the read's return) the request tracer's spans and
        the token gaps take."""
        t0, t1 = rec.t_launch0, rec.t_wait1
        self._chunks_run(call.chunks)
        first, nxt = toks if call.mixed else ((), toks)
        nxt = np.asarray(nxt)                       # [S, win]
        tr_on = self.tracer.enabled
        if tr_on:
            for slot, start in call.riding:
                if slot.trace is not None:
                    self.tracer.record(
                        slot.trace, "prefill_chunk", t0, t1 - t0,
                        tid=self.trace_tid,
                        attrs={"start": start, "chunk": self.chunk,
                               "fused": True, "call": call.id})
        for slot, i in call.firsts:
            if slot.state != _FREE:
                slot.flying -= 1
                self._emit(slot, int(first[i]), finished)
        self.decode_steps += 1
        with self._phase("serving/emit"):
            for s in call.rows:
                if s.state == _FREE:
                    continue
                skip = call.skip.get(s.idx, 0)
                s.flying -= call.win - skip
                ctx = s.trace             # _retire closes the request
                anchor, j = s.t_prev, 0
                for t in nxt[s.idx][skip:]:
                    self._emit(s, int(t), finished)
                    j += 1
                    if s.state == _FREE:            # retired mid-window
                        break
                self._observe_tpot(s, anchor, j, t1)
                if tr_on and ctx is not None:
                    self.tracer.record(ctx, "decode_window", t0, t1 - t0,
                                       tid=self.trace_tid,
                                       attrs={"emitted": j, "call": call.id})

    def _decode_walk(self, dec, pos, win):
        """What the decode walks have to do in a call that advances the slots
        `dec` by `win`, a layer, as `StepRecord` fields, summed over the
        positions the generator counts a walk at (`walk_at`: a token's, or a
        forward's): the paged decode kernel's live (slot, block) pairs, its
        grid steps and the rows it moves (`paged_decode_walk_counts`; a
        latent pool's walk visits the same pairs), for a pool of two kinds
        the pairs a WINDOW layer's walk visits and the pairs it would visit
        with no window, both in the window kind's blocks, and the bytes of a
        state kind's state the call's tokens read + write. Booked whichever
        program walks: they count the call, and the gather oracle serves the
        same pairs."""
        from deepspeed_tpu.ops.pallas.decode_attention import \
            paged_decode_walk_counts
        at = self.gen.walk_at(pos[[s.idx for s in dec]], win)
        work = _lands(paged_decode_walk_counts(
            at, self.block_size, nb=self.nb, widths=self._walk_widths,
            selected=bool(self._index_topk)), _DECODE_FIELDS)
        if self._latent:
            work["latent_walk_blocks"] = work["decode_live_blocks"]
        if self._index_topk:
            work.update(self._sparse_work(at + 1))
        if self.window_kind is not None:
            wkind = self.window_kind
            work.update(_lands(paged_decode_walk_counts(
                at, wkind.block, wkind.window), _DECODE_WINDOW_FIELDS))
        if self.state_kind is not None:
            work["ssm_state_bytes"] = \
                len(dec) * win * self._state_token_bytes
        return work

    @contextlib.contextmanager
    def _read_back(self, call_id, out):
        """THE place a call is read, which every blocking read passes: the
        wait is a phase of its own (`serving/read_back`: the host does
        nothing else in it), the body hands out what came — (the tokens, the
        call's record with the wait's stamps) — and the record is closed
        with the tokens live requests took."""
        st = self.steptrace
        with self._phase("serving/read_back", call=call_id) as ph:
            toks = self._fetch(out)
            if self._pending is None:
                st.ready()      # else the next call is queued behind it
        rec = self.gen.close(st.read_call(call_id, ph.t0, ph.t1),
                             self._call_counts)
        tokens0 = self.tokens_generated
        yield toks, rec
        st.close_call(rec, self.tokens_generated - tokens0)

    def _fetch(self, out):
        """The `device_get` of a step program's `(tokens, counts)` (the mixed
        step's tokens: a pair, first tokens and window tokens). The counts,
        and those parked by programs whose tokens nobody read, come back in
        the same `device_get` and are added to this step's sums (a model
        without counters: empty pytrees, nothing to fetch or add)."""
        # dstpu: ignore[DT001]: the scheduler's one host roundtrip per device call (decode window, verify step, a prompt's first token) — retirement and acceptance are host-side; tokens and the model's counters in ONE device_get, no second sync
        toks, *counts = jax.device_get([*out] + self._parked_counts)
        self._parked_counts = []
        self._step_counts += np.sum(counts, axis=0, dtype=np.int64)
        self._call_counts = counts[0]       # this call's own
        return toks

    def _compiled_programs(self) -> int:
        """Compiled-program count over the persistent step functions: its
        growth during a step says that step recompiled."""
        return sum(self.compile_stats().values())

    # ------------------------------------------------------------------
    # batch front-end + introspection
    # ------------------------------------------------------------------

    @property
    def num_active(self):
        """Requests that are not finished: the slots in use, and the
        requests that left theirs at dispatch and whose last tokens the
        call in flight still holds."""
        return len(self._live())

    def run(self, requests: Sequence[Request]) -> Dict[Any, CompletedRequest]:
        """Submit a batch of requests and drain the engine."""
        for r in requests:
            self.submit(r)
        out: Dict[Any, CompletedRequest] = {}
        while self.queue or self.num_active:
            before = (self.prefill_chunks, self.decode_steps,
                      self.device_calls, len(self.queue))
            for done in self.step():
                out[done.uid] = done
            after = (self.prefill_chunks, self.decode_steps,
                     self.device_calls, len(self.queue))
            if after == before:                     # defensive: cannot happen
                raise RuntimeError(
                    f"serving scheduler made no progress: queue="
                    f"{len(self.queue)} active={self.num_active} "
                    f"free_blocks={self.allocator.num_free}")
        # a call nothing waits for (every row ended while it ran) is read,
        # not left: the engine rests with nothing in flight
        self._drain(self._early)
        # drained: flush the tail of the trace into the exporters (a run
        # shorter than export_interval would otherwise leave no files)
        if self.telemetry.enabled:
            self.telemetry.export(self.steps)
        return out

    def compile_stats(self) -> Dict[str, int]:
        """Compiled-program counts of the persistent step functions — the
        serving promise is that these stay at 1 each for the engine's
        lifetime, across any mix of request shapes (the draft programs join
        the promise when spec decode is on; the streamed mode's six
        per-phase programs replace the resident ones)."""
        out = self.programs.compile_counts()
        if self.spec_on:
            out.update(self.drafter.compile_stats())
        return out

    def kv_pool_writers(self) -> Dict[str, str]:
        """Step program -> how it writes its new K/V rows into the pool:
        `dstpu_kv_pool_write` (the in-place kernel on a carried pool) or
        `xla_scatter` (`ops/attention_dispatch.py::kv_pool_writer` decides;
        there is nothing to set). A program appears once it has been traced.
        Empty for a model that keeps no record (the streamed layers, the
        `moe_freq` >= 2 MoE stack: the scatter form only)."""
        return self._by_step_program("kv_pool_writers")

    def attention_programs(self) -> Dict[str, str]:
        """Step program -> the attention program its layers were traced with
        (`ops/attention_dispatch.py`'s registry names). As `kv_pool_writers`:
        nothing to set, a program appears once traced."""
        return self._by_step_program("paged_attn_programs")

    def _by_step_program(self, record) -> Dict[str, str]:
        traced = getattr(self.engine.model_spec, record, None) or {}
        out = {program: traced[phase] for program, phase in (
            ("decode_step", "paged_decode"), ("prefill_step", "prefill_chunk"),
            ("verify_step", "verify"), ("mixed_step", "mixed"))
            if phase in traced}
        if "mixed/prefill_chunk" in traced:     # its two groups' programs
            out["mixed_step"] = "+".join(
                traced["mixed/" + phase]
                for phase in ("prefill_chunk", "paged_decode"))
        return out

    def stats(self) -> Dict[str, Any]:
        out = {"steps": self.steps, "device_calls": self.device_calls,
               "overlapped_calls": self.overlapped_calls,
               "decode_steps": self.decode_steps,
               "prefill_chunks": self.prefill_chunks,
               "fused_chunks": self.fused_chunks,
               "chunk_groups": self.chunk_groups,
               "padded_chunks": self.padded_chunks,
               "tokens_generated": self.tokens_generated,
               "peak_active": self.peak_active,
               "cancelled": self.cancelled,
               "deadline_cancelled": self.deadline_cancelled,
               "handoffs_in": self.handoffs_in,
               "handoffs_out": self.handoffs_out,
               "queued": len(self.queue), "active": self.num_active,
               "free_blocks": self.allocator.num_free,
               "reclaimable_blocks": self.allocator.num_reclaimable,
               "available_blocks": self.allocator.available,
               "compiles": self.compile_stats(),
               "kv_pool_writer": self.kv_pool_writers(),
               "attention_program": self.attention_programs()}
        if self.cache_kinds is not None:
            # a kind of layer: its layers, its blocks (a window layer's are
            # the slots' rings and one trash block) and what they hold
            # (a state kind's "blocks": its rows, one a slot and the trash)
            out["kv_pool_kinds"] = {
                kind.name: {
                    "layers": kind.layers, "block": kind.block,
                    "window": kind.window,
                    "blocks": int(self.pool[kind.leaves[0]].shape[1]),
                    "bytes": int(sum(self.pool[leaf].nbytes
                                     for leaf in kind.leaves)),
                    # what a cached token costs, all the kind's layers (a
                    # state kind keeps no token): as STORED, and as the
                    # model's entry has it (a kind that does not say: 0)
                    "bytes_per_token": 0 if kind.state else int(sum(
                        self.pool[leaf].nbytes // (self.pool[leaf].shape[1]
                                                   * kind.block)
                        for leaf in kind.leaves)),
                    "model_bytes_per_token": int(
                        kind.layers * kind.entry_values
                        * self.pool[kind.leaves[0]].dtype.itemsize)}
                for kind in self.cache_kinds}
            if self.window_kind is not None:
                out["kv_pool_kinds"]["window"]["ring_blocks_per_slot"] = \
                    self.ring
        if self.step_counter_names:
            # the model's own counters (routed experts: calls, assignments,
            # active experts, the largest expert's load), summed over layers
            # and steps; `StepRecord.counters` has them a step
            out["step_counters"] = dict(zip(
                self.step_counter_names,
                (int(v) for v in self.step_counter_totals)),
                **self.walk_totals)
        entry = self.gen.stats(out.get("step_counters"))
        if entry is not None:
            out["generator"] = entry
        if self.spec_on:
            out["spec_decode"] = {
                "drafter": self.drafter.name,
                "draft_k": self.draft_k,
                "verify_steps": self.verify_calls,
                "drafted_tokens": self.drafted_tokens,
                "accepted_tokens": self.accepted_tokens,
                "emitted_tokens": self.spec_emitted_tokens,
                # accepted/proposed (the drafter's hit rate) and tokens
                # emitted per SEQUENCE per model step (the throughput
                # multiple: 1.0 = spec decode is pure overhead, draft_k+1
                # is the ceiling; the denominator is per-slot verify
                # participations, so batching doesn't inflate it)
                "acceptance_rate": (self.accepted_tokens /
                                    max(1, self.drafted_tokens)),
                "accepted_tokens_per_step": (self.spec_emitted_tokens /
                                             max(1, self.verify_slot_steps))}
        if self.kv_quant or self.weight_quant != "off":
            q = {"kv_cache_dtype": self.kv_cache_dtype,
                 "weights": self.weight_quant}
            if self.kv_quant:
                g = self.pool["k_scale"].shape[-1]
                q["kv_group_size"] = int(self.pool["k"].shape[-1] // g)
            if self.weight_quant_stats is not None:
                # the pytree-wide WOQ ratio (bytes_before/bytes_after incl.
                # scales) — the weight-memory saving actually realized
                q["weight_quant"] = dict(self.weight_quant_stats)
            out["quantization"] = q
        if self.audits_run:
            out["audit"] = {"runs": self.audits_run,
                            "violations": self.audit_violations_total,
                            "repairs": self.audit_repairs}
        if self.pressure is not None:
            out["degradation"] = self.pressure.stats()
        if self.prefix_cache is not None:
            out["prefix_cache"] = {
                "hit_blocks": self.prefix_hit_blocks,
                "hit_tokens": self.prefix_hit_tokens,
                "prefill_chunks_skipped": self.prefill_chunks_skipped,
                "cached_blocks": self.prefix_cache.num_cached,
                "evictions": self.allocator.evictions}
        if self.streamed:
            # staging-pool overlap counters (device-ward hits/stalls +
            # write-back accounting) — the streamed mode's "is the overlap
            # real" readout, available with telemetry off
            from deepspeed_tpu.telemetry.memscope import tree_bytes
            out["offload"] = {
                "staging": self.engine.streamer.stats(),
                "layer_bytes": self.engine.store.layer_bytes,
                "host_param_bytes": self.engine.store.host_bytes,
                # peak HBM of the streamed-layer staging window — distinct
                # from the always-resident (embed/norm/head) tree below
                "staged_peak_bytes": self.engine.peak_param_hbm_bytes,
                "resident_param_bytes": tree_bytes(self.engine.params)}
        if self.memscope is not None:
            out["memory"] = self.memscope.snapshot()
        if self.telemetry.enabled:
            out["latency"] = self.latency_snapshot()
            # compile watchdog: ONE warmup compile per program is the
            # contract; any recompile after that is named here (and in the
            # flight recorder, with the triggering shapes)
            out["watchdog"] = self.telemetry.watchdog.summary()
        return out

    def latency_snapshot(self) -> Dict[str, Dict[str, float]]:
        """Per-request latency histogram snapshots (ttft_ms / tpot_ms /
        queue_wait_ms / e2e_ms -> count/mean/p50/p90/p99/min/max). Empty
        when telemetry is disabled."""
        if not self.telemetry.enabled:
            return {}
        snap = self.telemetry.registry.snapshot()
        return {name.split("/", 1)[1]: m for name, m in snap.items()
                if m.get("type") == "histogram" and name.startswith("serving/")}

    def write_monitor_events(self, monitor):
        """Serving cache/pool observability through the experiment monitor
        (guarded, best-effort: `write_events_safe`), stepped by the
        scheduler iteration."""
        from deepspeed_tpu.monitor.monitor import write_events_safe
        write_events_safe(monitor, [
            ("Serving/prefix_hit_tokens", self.prefix_hit_tokens, self.steps),
            ("Serving/prefix_evictions", self.allocator.evictions, self.steps),
            ("Serving/pool_free_blocks", self.allocator.available, self.steps),
        ])
