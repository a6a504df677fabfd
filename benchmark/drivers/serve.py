"""Driver for configurations served through `init_inference(...).serving(...)`
(the paged continuous-batching scheduler, `inference/scheduler.py`).

One thread, one loop: submit what is due, call `step()`, stamp the clock
when it returns, note what the step did for every request. From the program
it takes the system under test, its `stats()` counters and the progress it
keeps per slot (`cursor`, `emitted`); every stamp is this file's own.

A run: weights on the device from the seed -> engine and pool -> warm both
step programs -> logits against the plain reference -> PRE-ROLL of the cell's
own traffic until occupancy is stationary (set-up the traffic needs, counted
in `setup_s`) -> the window, opened as a step returns and closed by the first
step to return `--seconds` later -> tail-out (open loop only: traffic goes on
until every request that was due inside the window has its first token).
"""

import time

import jax
import jax.numpy as jnp
import numpy as np

import deepspeed_tpu
from deepspeed_tpu.comm import mesh as mesh_mod
from deepspeed_tpu.config.core import MeshConfig
from deepspeed_tpu.inference.scheduler import Request
from deepspeed_tpu.models.gpt import make_gpt_decode_model

import estimators
import harness
import traffic_gen
from drivers import gpt_family

# Program logits against the float32 reference, over all compared positions:
# root-mean-square error <= LOGIT_RMS_TOLERANCE of the reference's
# root-mean-square logit, and the largest error <= LOGIT_MAX_TOLERANCE of the
# largest |logit|. The program computes in bfloat16 (8 bits of mantissa)
# through 16 layers: the chip gave 2.4-3.1% rms and 2.0-3.9% max over five
# seeds (PR 23), the CPU 0.5% at two tiny layers. The limits are twice the
# band measured; an 8-bit float (3 bits of mantissa, 6% a rounding) or a wrong
# position, table or mask lands several times over them.
LOGIT_RMS_TOLERANCE = 0.06
LOGIT_MAX_TOLERANCE = 0.10
CHECK_PROMPTS = (11 / 8, 1 / 4)  # of a prefill chunk: two chunks (the second
                                 # partial), and a part of one
CHECK_DECODE_STEPS = 2
CHECK_POOL_BLOCKS = 8
WARM_NEW = 3                    # a two-chunk prompt, then two decode steps
IDLE_POLL_S = 0.0005


class Recorder:
    """What each step did, stamped with the time the step returned."""

    def __init__(self):
        self.step_spans = []    # (t_start, t_end)
        self.events = []        # (t, uid, prompt tokens prefilled, emitted)
        self.progress = {}      # uid -> (prefilled, emitted) so far
        self.finished_at = {}   # uid -> t
        self.bad = set()        # uids that ended wrong
        self.samples = []       # (t, used blocks, decode steps so far,
                                #  decoding requests, their context tokens)

    def _advance(self, uid, prefilled, emitted, t):
        was = self.progress.get(uid, (0, 0))
        if (prefilled, emitted) != was:
            self.events.append((t, uid, prefilled - was[0], emitted - was[1]))
            self.progress[uid] = (prefilled, emitted)

    def observe(self, serving, finished, t_start, t_end, want, vocab):
        self.step_spans.append((t_start, t_end))
        live = ctx = 0
        for slot in serving.slots:
            if slot.uid is None:
                continue
            emitted = len(slot.emitted)
            self._advance(slot.uid, min(slot.cursor, slot.prompt_len),
                          emitted, t_end)
            if emitted:
                live += 1
                ctx += slot.prompt_len + emitted
        for done in finished:
            self._advance(done.uid, done.prompt_len, len(done.tokens), t_end)
            self.finished_at[done.uid] = t_end
            tokens = np.asarray(done.tokens)
            if done.finish_reason != "length" or len(tokens) != want[done.uid] \
                    or tokens.min() < 0 or tokens.max() >= vocab:
                self.bad.add(done.uid)
        alloc = serving.allocator
        self.samples.append((t_end, alloc.capacity - alloc.available,
                             serving.decode_steps, live, ctx))


def _build(cell, seed, device):
    cfg = cell["config_json"]
    knobs = dict(cfg["serving"])
    block = knobs.pop("kv_block_size")
    gcfg = gpt_family.gpt_config(cfg, max_seq_len=knobs["max_context"])
    mesh_mod.clear_mesh()
    mesh_mod.init_mesh(MeshConfig(data=1), devices=[device])
    t0 = time.perf_counter()
    params = gpt_family.device_weights(gcfg, seed, jnp.bfloat16, device)
    engine = deepspeed_tpu.init_inference(
        make_gpt_decode_model(cfg=gcfg, name=cell["config"], params=params),
        config={"dtype": "bfloat16", "kv_cache_dtype": "bfloat16",
                "greedy": True, "kv_block_size": block,
                "max_out_tokens": knobs["max_context"]})
    serving = engine.serving(**knobs)
    jax.block_until_ready((engine.params, serving.pool))
    return gcfg, engine, serving, time.perf_counter() - t0


def _request(req):
    return Request(uid=req["uid"], tokens=req["tokens"],
                   max_new_tokens=req["output_tokens"], stop_on_eos=False)


def _warm(serving, vocab, seed):
    """Both step programs once, through the scheduler itself."""
    rng = np.random.default_rng([seed, 0x3A23])
    t0 = time.perf_counter()
    done = serving.run([Request(
        uid="warm", tokens=rng.integers(0, vocab, (serving.chunk + 8,),
                                        np.int32),
        max_new_tokens=WARM_NEW, stop_on_eos=False)])
    assert len(done["warm"].tokens) == WARM_NEW
    return time.perf_counter() - t0


def _check_logits(cell, engine, serving, gcfg, seed):
    """Chunked prefill, then decoding through the paged cache, against the
    reference's full forward pass: LOGITS on a seeded sample, at the served
    widths, same table width and slot count as the served programs (so the
    dispatch picks the same attention programs) over a small pool of its own.
    Decoding is teacher-forced with the reference's argmax, so one rounding
    flip cannot send the two apart."""
    ref = harness.load_module("references", cell["config_json"]["reference"])
    arch = ref.arch_from_config(cell["config_json"])
    spec = engine.model_spec
    block, chunk, nb = serving.block_size, serving.chunk, serving.nb
    slots = serving.max_slots
    rng = np.random.default_rng([seed, 0xC4EC])
    prompts = [rng.integers(0, gcfg.vocab_size, (max(3, int(f * chunk)),),
                            np.int32) for f in CHECK_PROMPTS]
    pool = spec.init_paged_pool(CHECK_POOL_BLOCKS, block, jnp.bfloat16)
    prefill = jax.jit(spec.prefill_paged_fn, donate_argnums=(4,))
    decode = jax.jit(spec.decode_paged_fn, donate_argnums=(3,))
    tables = np.zeros((slots, nb), np.int32)        # 0 is the trash block
    rows = (0, slots - 1)
    free = iter(range(1, CHECK_POOL_BLOCKS))
    worst = scale = err2 = ref2 = 0.0
    same = cases = 0

    def compare(got, want):
        nonlocal worst, scale, err2, ref2, same, cases
        got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
        worst = max(worst, float(np.abs(got - want).max()))
        scale = max(scale, float(np.abs(want).max()))
        err2 += float(np.square(got - want).sum())
        ref2 += float(np.square(want).sum())
        same += int(got.argmax() == want.argmax())
        cases += 1

    history = []
    for row, prompt in zip(rows, prompts):
        need = -(-(len(prompt) + CHECK_DECODE_STEPS + 1) // block)
        tables[row, :need] = [next(free) for _ in range(need)]
        for start in range(0, len(prompt), chunk):
            seg = prompt[start:start + chunk]
            toks = np.zeros((1, chunk), np.int32)
            toks[0, :len(seg)] = seg
            out, pool = prefill(engine.params, toks,
                                np.asarray([start], np.int32),
                                np.asarray([len(seg) - 1], np.int32), pool,
                                tables[row][None])
        want = ref.logits(engine.params, jnp.asarray(prompt), arch)[-1]
        compare(out[0], want)
        history.append(list(prompt) + [int(np.asarray(want).argmax())])
    for _ in range(CHECK_DECODE_STEPS):
        tok = np.zeros((slots,), np.int32)
        pos = np.zeros((slots,), np.int32)
        for row, seq in zip(rows, history):
            tok[row], pos[row] = seq[-1], len(seq) - 1
        out, pool = decode(engine.params, tok, pos, pool, tables)
        for row, seq in zip(rows, history):
            want = ref.logits(engine.params,
                              jnp.asarray(np.asarray(seq, np.int32)), arch)[-1]
            compare(out[row], want)
            seq.append(int(np.asarray(want).argmax()))
    del pool
    rms = (err2 / ref2) ** 0.5
    ok = bool(np.isfinite(worst) and rms <= LOGIT_RMS_TOLERANCE
              and worst <= LOGIT_MAX_TOLERANCE * scale)
    return ok, {"rms_error_share": rms, "max_error_share": worst / scale,
                "max_abs_logit": scale, "argmax_equal": f"{same}/{cases}",
                "tolerances": [LOGIT_RMS_TOLERANCE, LOGIT_MAX_TOLERANCE]}


def run(cell, seconds, seed, devices, profiler, compiles, t_process):
    device = devices[0]
    gcfg, engine, serving, init_s = _build(cell, seed, device)
    traffic = cell["traffic_json"]
    vocab = gcfg.vocab_size
    compile_s = _warm(serving, vocab, seed)
    t0 = time.perf_counter()
    logits_ok, logits_note = _check_logits(cell, engine, serving, gcfg, seed)
    check_s = time.perf_counter() - t0

    open_loop = traffic["kind"] == "open_loop"
    if open_loop:
        plan = traffic_gen.open_loop_schedule(traffic, seconds)
    else:
        # prebuilt, and more than a program ten times faster could drain
        count = traffic["min_queue"] + int(
            (traffic["preroll_s"] + seconds + 10) * traffic["prebuilt_rps"])
        plan = traffic_gen.backlog_requests(traffic, count)
    traffic_gen.token_arrays(plan, vocab, seed)
    requests = [_request(r) for r in plan]
    want = {r["uid"]: r["output_tokens"] for r in plan}

    rec = Recorder()
    due, submitted = {}, {}
    nxt = 0
    traced_s = min(traffic.get("traced_seconds", seconds), seconds)
    opened = closed = stop_at = None
    stats_open = stats_close = compiles_open = compiles_close = None
    setup_s = None
    # the schedule's window stretch starts when the pre-roll has run its
    # length; the measured window opens as the first step after that returns
    t_zero = time.perf_counter() + traffic["preroll_s"]

    def feed(now):
        nonlocal nxt
        if open_loop:
            while nxt < len(plan) and t_zero + plan[nxt]["due"] <= now:
                due[plan[nxt]["uid"]] = t_zero + plan[nxt]["due"]
                submitted[plan[nxt]["uid"]] = now
                serving.submit(requests[nxt])
                nxt += 1
        else:
            while len(serving.queue) < traffic["min_queue"]:
                due[plan[nxt]["uid"]] = submitted[plan[nxt]["uid"]] = now
                serving.submit(requests[nxt])
                nxt += 1

    def window_requests_served():
        first = estimators.first_token_times(rec.events)
        return all(uid in first for uid, t in due.items()
                   if t_zero <= t < t_zero + seconds)

    with harness.quiet_host():
        while stop_at is None or time.perf_counter() < stop_at:
            with profiler.annotate("bench.submit"):
                feed(time.perf_counter())
            if not (serving.queue or serving.num_active):
                time.sleep(IDLE_POLL_S)
                continue
            t_start = time.perf_counter()
            with profiler.annotate("bench.step"):
                finished = serving.step()
            t_end = time.perf_counter()
            with profiler.annotate("bench.bookkeeping"):
                rec.observe(serving, finished, t_start, t_end, want, vocab)
            if opened is None:
                if t_end >= t_zero:
                    opened = t_end                  # the window opens here
                    setup_s = t_end - t_process
                    stats_open = serving.stats()
                    compiles_open = compiles.count
            elif closed is None:
                if t_end >= opened + seconds:
                    closed = t_end
                    stats_close = serving.stats()
                    compiles_close = compiles.count
                    profiler.close_window()
                    stop_at = t_end + (traffic["tailout_s"] if open_loop
                                       else 0.0)
                elif profiler.enabled and profiler.dir is None \
                        and t_end >= opened + seconds - traced_s:
                    profiler.start()
            elif open_loop and window_requests_served():
                break
    if profiler.running:
        profiler.stop()

    counters = {k: stats_close[k] - stats_open[k]
                for k in ("steps", "decode_steps", "prefill_chunks",
                          "tokens_generated")}
    intervals = estimators.emission_intervals(rec.events, opened, closed)
    counters["decode_tokens"] = sum(k for _, k in intervals)
    counters["decode_slot_steps"] = (counters["decode_steps"]
                                     * serving.max_slots * serving.window)

    if open_loop:
        measured = [uid for uid, t in due.items()
                    if t_zero <= t < t_zero + seconds]
        _, missing = estimators.ttft_ms(rec.events, due, t_zero, seconds)
    else:
        measured = [uid for uid, t in rec.finished_at.items()
                    if opened < t <= closed]
        missing = 0
    failed = missing + sum(1 for uid in measured if uid in rec.bad)
    in_window_compiles = compiles_close - compiles_open
    programs = serving.compile_stats()
    correct = bool(logits_ok and failed == 0 and in_window_compiles == 0
                   and all(v == 1 for v in programs.values())
                   and len(measured) > 0)

    memory = device.memory_stats() or {}
    obs = {
        "setup_s": setup_s, "init_s": init_s, "compile_s": compile_s,
        "events": rec.events, "step_spans": rec.step_spans,
        "finished_at": rec.finished_at, "due": due, "submitted": submitted,
        "due_from": t_zero, "opened": opened, "closed": closed,
        "seconds": seconds, "counters": counters,
        "series": {"pool_used_blocks": [
            used for t, used, _n, _live, _ctx in rec.samples
            if opened < t <= closed]},
        "pool_capacity_blocks": serving.allocator.capacity,
        "decode_samples": [(t, n, live, ctx)
                           for t, _used, n, live, ctx in rec.samples],
        "traced": (profiler.started_at, profiler.closed_at),
        "memory_peak_bytes": memory.get("peak_bytes_in_use", 0),
        "memory_limit_bytes": memory.get("bytes_limit", 0),
        "config": cell["config_json"],
    }
    notes = {"logits": logits_note, "compiles_in_window": in_window_compiles,
             "programs": programs, "requests_measured": len(measured),
             "window_s": closed - opened, "steps": counters["steps"],
             "seconds": {"init": init_s, "warm": compile_s, "reference": check_s,
                         "preroll": traffic["preroll_s"]},
             "tpot_per_finished_request_ms":
                 estimators.tpot_per_finished_request_ms(
                     rec.events, rec.finished_at, opened, closed),
             "finished_in_window": sum(1 for t in rec.finished_at.values()
                                       if opened < t <= closed),
             "queued_at_open_close": [stats_open["queued"],
                                      stats_close["queued"]],
             "active_at_open_close": [stats_open["active"],
                                      stats_close["active"]]}
    return {"correct": correct, "attempted": len(measured), "failed": failed,
            "obs": obs, "notes": notes}
