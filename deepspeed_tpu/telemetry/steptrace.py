"""Step timeline: what each scheduler or train step did, and when.

One recorder a subsystem, fed by the `Span` the repo already has
(`telemetry/spans.py`). A `Phase` is a `Span` — it enters the same
`jax.profiler.TraceAnnotation` (free while no profiler session runs, on the
device trace's clock while one does) and writes the same chrome-trace event
when `telemetry.chrome_trace` is on — that ALSO stamps the engine's clock and
adds its seconds to the open step record: one span, three sinks.

A step is `begin_step()`, phases that tile it with no gap (each starts where
the one before ended, so one clock read a phase), `dispatched()` / `ready()`
marks around device work, and `end_step(**counts)`. Its record holds the
seconds per phase and the EXPOSED HOST SECONDS: the step's wall time less the
time a device call was in flight (from a `dispatched()` to the next blocking
read-back's `ready()`, carried across steps while no read-back came, or while
the caller read one call back with the next already dispatched behind it and
so did not call `ready()`) — the time the chip provably had nothing of this
step queued. Requests get a
record when they are admitted and a completed one, under the same `uid`, when
they retire. A DEVICE CALL whose output the host reads gets a record of its
own (`CallRecord`): opened where it is dispatched, completed under the same
`id` where it is read back, which is a step later where the serving loop runs
one call deep. The step is the host's unit and the call the device's: a
`StepRecord` holds what the host did between two stamps — some of it booked
at a call's DISPATCH (`prefill_chunks`, `fused_chunks`, `decoding`,
`device_calls`, `overlapped_calls`, the walk counts, `ssm_*`), some at the
READ-BACK of the call the step before dispatched (`emitted`, `counters`) —
and a `CallRecord` holds one call's work beside that call's four stamps: the
host entering and leaving its launch, and entering and leaving the blocking
read. The blocking read is a phase of its own (`serving/read_back`), so a
step's phases say how long the host WAITED; the rest of the step is its work.

Records are plain tuples of numbers and strings in bounded rings, selected by
stamp (`records(since, until)`, `requests(since, until)`, `calls(since,
until)`), so any window of a run can be read after the fact. The device's
side of a step is read the same way, after the fact and on demand:
`device_scopes()` gives, for every instruction of the engine's compiled step
programs, the `jax.named_scope` the program put it under
(`telemetry/device_scopes.py`), which a device trace's events are joined to.
A recorder holds the rings and never the engine: `latest("serving")` hands the rings of
an engine that is gone to whoever asks in the same process (the benchmark's
readers do).
"""

import collections
import itertools
import time

from deepspeed_tpu.telemetry.spans import Span

__all__ = ["StepTrace", "Phase", "StepRecord", "RequestRecord", "CallRecord",
           "latest", "DEFAULT_CAPACITY"]

DEFAULT_CAPACITY = 8192     # steps kept: about ten minutes of 76 ms steps

StepRecord = collections.namedtuple("StepRecord", [
    # Serving: a field is booked either at the DISPATCH of this step's call
    # or at the READ-BACK this step made, which one call deep is of the call
    # the step BEFORE dispatched. `CallRecord` has a call's work beside its
    # own time; the step numbers in it say which two records it touched.
    "step",             # 1-based index of the step in this recorder
    "t_start", "t_end",  # on the engine's clock (time.perf_counter by default)
    "phases",           # ((name, seconds), ...) in order of first entry;
                        # the seconds sum to t_end - t_start;
                        # "serving/read_back" is the host blocked on a read
    "exposed_s",        # wall time less the in-flight intervals
    "admitted",         # requests given a slot this step
    "prefill_chunks",   # prefill chunks dispatched this step (dispatch)
    "decoding",         # slots in the decode call (0 = no decode call)
                        # (dispatch)
    "emitted",          # tokens emitted this step (read-back)
    "queued",           # requests waiting as the step ended
    "free_blocks",      # pool blocks free or reclaimable as the step ended
    "blocked_on",       # "" | "pool" | "slots": why the head of a non-empty
                        # queue was not admitted
    "compiles",         # growth of the step programs' compile caches
    "counters",         # the model's own counters of the device calls READ
                        # in this step, in
                        # `ServingEngine.step_counter_names` order (routed
                        # experts: `parallel.moe.ROUTED_COUNTERS`, summed
                        # over layers); () for a model with none
    "decode_live_blocks",   # (this and every count below: dispatch) live
                        # (slot, logical block) pairs of the decode call:
                        # pos // block + 1 summed over its decoding slots
                        # and its tokens
    "decode_grid_steps",    # block-axis steps `dstpu_paged_decode`'s walk
                        # takes for those positions, a layer, summed over
                        # the call's tokens (KV heads folded out)
    "decode_walk_rows",     # rows of K (and of V) that walk moves for those
                        # pairs, a layer: `decode_live_blocks` x block, less
                        # the dead row tiles of a SHORT table's frontier
                        # blocks (`decode_attention._frontier_rows`)
    "prefill_live_blocks",  # logical blocks `dstpu_paged_prefill` walks for
                        # this step's chunks, a layer:
                        # (start + chunk - 1) // block + 1 a chunk ...
    "prefill_table_blocks",  # ... of the blocks in those chunks' tables
                        # (what the gather path attends); both 0 where the
                        # prefill program built is not that kernel
    "decode_window_live_blocks",   # a pool of two kinds (window layers
                        # beside full ones): the (slot, block) pairs a
                        # WINDOW layer's decode walk visits, in the window
                        # kind's blocks — from the block the window begins
                        # in to the one `pos` is in ...
    "decode_window_table_blocks",  # ... of the pairs the same slots' walk
                        # would visit with no window (pos // block + 1)
    "prefill_window_live_blocks",  # the same two for this step's prefill
    "prefill_window_table_blocks",  # chunks; all four 0 for a one-kind pool
    "latent_walk_blocks",   # a pool of the LATENT kind (MLA): the (slot,
                        # block) pairs `dstpu_mla_decode`'s walk visits, a
                        # layer (`decode_live_blocks` of such a pool) ...
    "latent_chunk_positions",  # ... and the cached positions this step's
                        # chunks attend, a layer: `start + chunk` a chunk,
                        # where `dstpu_mla_prefill` runs; both 0 elsewhere
    "prefill_kept_pairs",   # the (query, position) pairs the causal mask
                        # keeps of this step's chunk walks, a FULL layer:
                        # `chunk * start + chunk * (chunk + 1) / 2` a chunk,
                        # where a chunk-walk kernel runs ...
    "prefill_window_kept_pairs",  # ... and the pairs the mask AND the
                        # window keep, a WINDOW layer of a two-kind pool
                        # (0 for a one-kind pool): what the chunk walks'
                        # roofline counts as work
    "fused_chunks",     # of `prefill_chunks`, the chunks that rode the
                        # step's decode call (the scheduler's `mixed_step`:
                        # one device call, every weight read once)
    "ssm_state_bytes",  # a pool with a state kind (recurrent layers): bytes
                        # of state this step's decode tokens read + wrote —
                        # decoding slots x tokens x state layers x one
                        # slot's state, twice; what `dstpu_ssm_update` moves
    "ssm_chunk_tokens",  # ... and the positions this step's prefill chunks
                        # ran the chunked scan over; both 0 with no state kind
    "device_calls",     # calls this step dispatched whose tokens a blocking
                        # read fetches (a decode or mixed call, a verify
                        # call, a prompt's last chunk as a call of its own)
    "overlapped_calls",  # ... of them, the calls dispatched while the call
                        # before was unread: queued behind it on the device,
                        # so the chip never waited for this step's host work
    "chunk_groups",     # the chunk groups this step's mixed call carried: a
                        # window token takes up to G of `fused_chunks` through
                        # every weight with it (G = ceil(prefill budget /
                        # window) where the model's mixed program takes a
                        # group, else 1) ...
    "padded_chunks",    # ... and the absent chunks in them (rows of padding
                        # through the matmuls): groups * G - fused_chunks
    "index_scored_positions",   # a pool whose entry has an index key (a
                        # learned sparse-attention indexer,
                        # `models/sparse_attn.py`): the (query, cached
                        # position) pairs this step's chunks and decode
                        # tokens SCORED, a layer: t + 1 a query at t ...
    "selected_positions",   # ... of them, the pairs the selection kept:
                        # min(t + 1, topk) a query — the model's work ...
    "sparse_walk_positions",    # ... and the pairs the attention walks
                        # READ to attend them: every position under the
                        # frontier in the form kept (the selection rides the
                        # dense walks as a mask), so read / selected is the
                        # form's waste; all three 0 with no indexer
], defaults=(0, 0, 0, 0, 0, 0, "", 0, (), 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0,
             0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0))

RequestRecord = collections.namedtuple("RequestRecord", [
    "uid", "t_submit", "t_admit",
    "t_first_token", "t_finish",        # None until they happen
    "prompt_len", "emitted", "cached_prefix_tokens",
    "finish_reason",                    # "" while the request runs
    "step_admit", "step_first_token", "step_finish",   # StepRecord.step of
                                        # the step that caused each; 0 = not yet
])

CallRecord = collections.namedtuple("CallRecord", [
    "id",               # the scheduler's name for the call (`_Call.id`, what
                        # a slot's `feed` names): 1, 2, ... in dispatch order
    "program",          # "decode" | "mixed" | "prefill" (a prompt's last
                        # chunk as a call of its own) | "verify" | "decode_w1"
    "step_launch", "step_read",     # StepRecord.step of the step that
                        # dispatched it and of the one that read it (0 = not
                        # read yet; equal where the step is synchronous)
    "t_launch0", "t_launch1",   # the host enters and leaves the dispatch: the
                        # jitted call's arguments handed over, the program
                        # enqueued (the dispatch phase's own stamps)
    "t_wait0", "t_wait1",   # the host enters and leaves the blocking read of
                        # its output (`serving/read_back`'s stamps); None
                        # while the call is in flight
    "queued_behind",    # another call was unread at its dispatch: the device
                        # had work queued ahead of this one
    "rows",             # decoding slots in it ...
    "win",              # ... each of which it gives this many tokens
    "chunks",           # prefill chunks the device ran for it: those riding
                        # it (or the one of a "prefill" call) and those
                        # dispatched before it as calls nobody read
    "firsts",           # ... of them a prompt's last: first tokens it samples
    "emitted",          # tokens its read-back handed to live requests, first
                        # tokens included (<= rows * win + firsts: a tail past
                        # `max_new`, an EOS, a request that ended meanwhile)
    "forwards",         # a block-diffusion call (`DecodeModelSpec.generator`):
                        # its passes through the weights, by its own counters
                        # at the read-back: denoise + commit forwards less the
                        # fused ones, which are one of each, of ...
    "block_rows",       # ... this many rows a slot a denoise or commit
                        # forward (the block length); `win` is then (denoise
                        # + commit) * block_rows, the rows a slot ran through
                        # the model, and `emitted` the tokens COMMITTED and
                        # delivered. 0, 0 for every other call
], defaults=(0, 0))

_LATEST = {}        # subsystem -> the most recently created recorder


def latest(subsystem):
    """The most recently created recorder of `subsystem` ("serving",
    "train") in this process, or None. With several engines in one process
    (a router's in-process replicas) this is the NEWEST one only; hold
    `engine.steptrace` for a particular engine."""
    return _LATEST.get(subsystem)


class Phase(Span):
    """One phase of a step: `Span`'s trace annotation and chrome event, plus
    stamps on the engine's clock (`t0`, `t1`, kept for the request tracer)
    that go into `trace`'s open step record. Inside a step a phase starts
    where the one before it ended; outside one it only stamps."""

    __slots__ = ("trace", "t0", "t1")

    def __init__(self, name, trace, tid=0, **attrs):
        Span.__init__(self, name, sink=trace.sink, tid=tid, **attrs)
        self.trace = trace
        self.t0 = self.t1 = 0.0

    def __enter__(self):
        Span.__enter__(self)
        mark = self.trace._mark
        self.t0 = self.trace.clock() if mark is None else mark
        return self

    def __exit__(self, exc_type, exc, tb):
        trace = self.trace
        self.t1 = t1 = trace.clock()
        if trace._mark is not None:
            phases = trace._phases
            phases[self.name] = phases.get(self.name, 0.0) + (t1 - self.t0)
            trace._mark = t1
        return Span.__exit__(self, exc_type, exc, tb)


class StepTrace:
    """Rings of step records, request records and device-call records for
    one engine."""

    def __init__(self, subsystem, capacity=DEFAULT_CAPACITY,
                 clock=time.perf_counter, sink=None):
        if capacity <= 0:
            raise ValueError("a recorder needs a capacity > 0")
        self.subsystem = subsystem
        self.capacity = int(capacity)
        self.clock = clock
        self.sink = sink            # ChromeTraceSink or None
        self.step = 0
        self.facts = {}             # what holds for every step of the run
                                    # (training: "held_residuals", the plan
                                    # of what the blocks keep for the backward)
        self.scope_provider = None  # the engine's `device_scopes.
                                    # ProgramTable`: its jitted step programs
                                    # and the abstract shapes of their
                                    # arguments, called by `device_scopes()`
        self._steps = collections.deque(maxlen=self.capacity)
        self._requests = collections.deque(maxlen=self.capacity)
        self._calls = collections.deque(maxlen=self.capacity)
        self._open_calls = {}       # id -> CallRecord dispatched, not yet read
        self._mark = None           # end of the last phase; None = no open step
        self._t_start = 0.0
        self._phases = {}
        self._busy = 0.0            # in-flight seconds inside the open step
        self._inflight_since = None
        _LATEST[subsystem] = self

    # ---- one step ------------------------------------------------------

    def begin_step(self, device_idle=False):
        """Open a step. `device_idle=True` says the caller knows the device
        finished what earlier steps left in flight (training: the last
        step's loss is ready), so nothing is carried into this one."""
        self.step += 1
        self._t_start = self._mark = self.clock()
        self._phases = {}
        self._busy = 0.0
        if device_idle:
            self._inflight_since = None

    def phase(self, name, tid=0, **attrs):
        """`attrs` go to the trace annotation (`call=<id>` on a call's
        dispatch and read-back), formatted only while a profiler runs."""
        return Phase(name, self, tid=tid, **attrs)

    def dispatched(self):
        """A device call is about to be enqueued."""
        if self._inflight_since is None:
            self._inflight_since = self.clock()

    def ready(self):
        """A blocking read-back returned: nothing is in flight."""
        if self._inflight_since is not None:
            self._busy += self.clock() - max(self._inflight_since,
                                             self._t_start)
            self._inflight_since = None

    def end_step(self, **counts):
        """Close the step at the end of its last phase and keep its record
        (`counts`: the `StepRecord` fields after `exposed_s`)."""
        t_end = self._mark
        if t_end is None:
            return None
        if not self._phases:
            t_end = self.clock()
        busy = self._busy
        if self._inflight_since is not None:    # still running: carried over
            busy += t_end - max(self._inflight_since, self._t_start)
        rec = StepRecord(self.step, self._t_start, t_end,
                         tuple(self._phases.items()),
                         max(0.0, (t_end - self._t_start) - busy), **counts)
        self._steps.append(rec)
        self._mark = None
        return rec

    # ---- requests ------------------------------------------------------

    def open_request(self, uid, t_submit, t_admit, prompt_len,
                     cached_prefix_tokens=0, t_first_token=None,
                     step_first_token=0):
        """Record an admission (or the adoption of a handed-off request,
        which brings its first-token stamp with it); returns the record,
        which `close_request` takes back."""
        rec = RequestRecord(uid, t_submit, t_admit, t_first_token, None,
                            prompt_len, 0, cached_prefix_tokens, "",
                            self.step, step_first_token, 0)
        self._requests.append(rec)
        return rec

    def close_request(self, opened, t_first_token, step_first_token,
                      t_finish, emitted, finish_reason):
        rec = opened._replace(
            t_first_token=t_first_token, step_first_token=step_first_token,
            t_finish=t_finish, emitted=emitted, finish_reason=finish_reason,
            step_finish=self.step)
        self._requests.append(rec)
        return rec

    # ---- device calls --------------------------------------------------

    def open_call(self, id, program, t_launch0, t_launch1,
                  queued_behind=False, rows=0, win=0, chunks=0, firsts=0):
        """A call whose output the host will read has been dispatched, by
        the open step, between the two stamps (its dispatch phase's)."""
        rec = CallRecord(id, program, self.step, 0, t_launch0, t_launch1,
                         None, None, bool(queued_behind), rows, win, chunks,
                         firsts, 0)
        self._open_calls[id] = rec
        return rec

    def read_call(self, id, t_wait0, t_wait1):
        """The blocking read of call `id` returned, in the open step,
        between the two stamps; the record, which `close_call` takes back
        once the host has handed out what the read brought."""
        return self._open_calls.pop(id)._replace(
            step_read=self.step, t_wait0=t_wait0, t_wait1=t_wait1)

    def close_call(self, read, emitted):
        rec = read._replace(emitted=emitted)
        self._calls.append(rec)
        return rec

    # ---- reading -------------------------------------------------------

    def device_scopes(self):
        """The `device_scopes.ScopeRow`s of every step program the engine
        built: (program, instruction name, opcode, custom-call target,
        result type, scope, backward, straddles), read from each program's
        own compiled text. The first call lowers and compiles the programs
        through the AOT path (the persistent compilation cache serves it
        where it holds them; the jit call caches are not touched) and the
        provider keeps the rows; a run that never asks pays nothing. () for
        an engine that handed over no program (a streamed one: its steps
        are host loops)."""
        return self.scope_provider() if self.scope_provider else ()

    def records(self, since=None, until=None):
        """Step records with `since < t_end <= until` (None = unbounded)."""
        return [r for r in self._steps
                if (since is None or r.t_end > since)
                and (until is None or r.t_end <= until)]

    def tail(self, n):
        """The newest `n` step records (all of them while the ring holds
        fewer), oldest first."""
        return list(itertools.islice(reversed(self._steps), n))[::-1]

    def calls(self, since=None, until=None):
        """Call records with `since < t_wait1 <= until`: the calls READ in
        the window, in the order they were read."""
        return [c for c in self._calls
                if (since is None or c.t_wait1 > since)
                and (until is None or c.t_wait1 <= until)]

    def in_flight(self):
        """The calls dispatched and not yet read, oldest first."""
        return list(self._open_calls.values())

    def requests(self, since=None, until=None, stamp="t_admit"):
        """One record a request, its newest (completed if it has retired),
        for the requests whose `stamp` lies in (`since`, `until`]."""
        newest = {}
        for r in self._requests:
            newest[(r.uid, r.step_admit)] = r
        out = []
        for r in newest.values():
            t = getattr(r, stamp)
            if t is not None and (since is None or t > since) \
                    and (until is None or t <= until):
                out.append(r)
        return out
