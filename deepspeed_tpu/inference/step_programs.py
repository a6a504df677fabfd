"""The serving step's compiled programs — built once, shapes pinned for the
engine's lifetime, and the ONE module that calls `jax.jit` on them.

`build_resident(...)` and `build_streamed(...)` each build a `StepPrograms`, the object
the scheduler's loop holds (`ServingEngine.programs`). What a builder needs
of the engine it takes as arguments; nothing here imports the scheduler.

ONE shape: every step program returns `((tokens...), counts), pool`.
`counts` is the model's own per-call counters (`DecodeModelSpec.
step_counters`, e.g. the routed experts'), summed over the call's forwards:
an int32 `[len(step_counters)]` where the model names some, the EMPTY pytree
`()` where it names none (`build_resident`'s `paged`). An empty pytree adds nothing to a
carry, an output or a `device_get`: the uncounted programs lower to the text
they lowered to when they returned bare tokens.

This is the DEVICE half of a generator; `inference/generators.py` is the
host half, one contract the scheduler's loop calls. Two instances fill the
tokens. One token a slot a forward: a decode or mixed call scans `window`
forwards of one row a slot and returns `[S, window]` tokens, its first input
`pick`ed on the device from the call before. DIFFUSION OVER BLOCKS
(`DecodeModelSpec.generator`, `_block_diffusion_steps`): a call commits
`blocks_per_call` whole blocks of B tokens a slot through denoise + commit
forwards of B rows a slot (a block that is not its last commits in the pass
that opens the next: a fused forward of 2B rows), returns `[S,
blocks_per_call * B]` committed tokens, counts its forwards
(`engine.BLOCK_DIFFUSION_COUNTERS`, after the model's own) and takes NO token
from the call before it. Both ride a group of chunks a forward through
`_ride_group`.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.inference.engine import (BLOCK_DIFFUSION_COUNTERS,
                                            sample_logits)

# the whole-step programs, as `compile_stats()` and the watchdog name them
_NAMES = ("decode_step", "prefill_step", "mixed_step", "verify_step",
          "decode_step_w1")


class StepPrograms:
    """One serving engine's step programs: the callables `decode`,
    `prefill`, `mixed` and `verify` (None where not built) and `decode_w1()`;
    `no_prev`, what `pick` takes where no call is in flight (the shapes,
    dtype and sharding of a mixed call's (first tokens, window tokens), so
    that a call has ONE signature whatever came before it); `group`, the
    chunks a token of a mixed call takes (G); each built
    program's name (`built`, `compile_counts`) and example arguments
    (`examples`). A test that swaps a program for a stub assigns the
    attribute: the loop reads it a call."""

    def __init__(self, decode, prefill, mixed, verify, no_prev, parts,
                 make_w1, example_args, group=1):
        self.decode, self.prefill = decode, prefill
        self.mixed, self.verify, self.no_prev = mixed, verify, no_prev
        self.group = group
        self.w1 = None          # `decode_w1()`'s, once built
        self._parts = parts     # name -> program: the streamed mode's six
                                # per-layer programs (its steps are host loops)
        self._make_w1, self._example_args = make_w1, example_args

    def decode_w1(self):
        """The 1-step decode program, built the first time a degraded path
        needs it: the spec-decode-disabled fallback (its blocks are sized
        for a k-draft overhang, not a window-rounding tail: a K-step window
        could write past them) and the pressure ladder's window-shrink
        rung. One warmup compile at first engagement (`decode_step_w1` from
        then on). Where the window is one token already, it is `decode`."""
        if self._make_w1 is None:
            return self.decode
        if self.w1 is None:
            self.w1 = self._make_w1()
        return self.w1

    def built(self):
        """[(name, program)] of the programs built so far, under the names
        `compile_stats()` and the compile watchdog report."""
        if self._parts:
            return list(self._parts.items())
        whole = (self.decode, self.prefill, self.mixed, self.verify, self.w1)
        return [(name, fn) for name, fn in zip(_NAMES, whole)
                if fn is not None]

    def compile_counts(self):
        """name -> compiled signatures (the serving promise: 1 each for the
        engine's lifetime). `mixed_step` appears once a chunk has ridden a
        decode call: until then it is a jit wrapper nothing has traced. A
        program replaced by a plain function (fault injection) has no cache
        and counts int() = 0."""
        counts = {name: int(getattr(fn, "_cache_size", int)())
                  for name, fn in self.built()}
        if not counts.get("mixed_step", 1):
            del counts["mixed_step"]
        return counts

    def examples(self, params, pool, tables, rng):
        """[(name, program, example arguments)] of the built whole-step
        programs, as the scheduler calls them: `tables` is a decode call's
        tables argument (a row a slot; a pair for a pool of two kinds), of
        which a chunk takes one row. Empty in the streamed mode: no
        whole-step executable exists to analyse."""
        args = self._example_args(params, pool, tables, rng)
        return [(name, fn, args[name]) for name, fn in self.built()
                if name in args]


def _sampler(cfg):
    draw = functools.partial(sample_logits, greedy=cfg.greedy,
                             temperature=cfg.temperature, top_k=cfg.top_k,
                             top_p=cfg.top_p)

    def sample(logits, rng):
        # (named for `telemetry/device_scopes.py`, as the model's halves are)
        with jax.named_scope("sample"):
            return draw(logits, rng)

    return sample


def _ride_group(mixed_paged, params, riding, i, G, tok, pos, pool, tables,
                **more):
    """Forward `i` of a mixed call with its chunk group riding: `riding` =
    (`chunks` [W, G, C], `starts` / `lasts` [W, G], `chunk_tables`
    [W, G, nb], `n` the chunks that are real), of which group `i` goes
    through the model with the slots' rows `tok` as one tensor
    (`DecodeModelSpec.mixed_paged_fn`, which takes `more` as keywords).
    Returns its (logits, pool, counts)."""
    chunks, starts, lasts, chunk_tables, n = riding

    def at(a):
        return jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False)
    count = () if G == 1 else (jnp.minimum(n - i * G, G),)
    return mixed_paged(
        params, at(chunks), at(starts), at(lasts),
        jax.tree_util.tree_map(at, chunk_tables), tok, pos, pool, tables,
        *count, **more)


def step_counter_names(spec):
    """What a resident engine's step programs count, in order: the model's
    own (`DecodeModelSpec.step_counters`), then a block-diffusion
    generator's forwards."""
    return tuple(getattr(spec, "step_counters", None) or ()) + (
        BLOCK_DIFFUSION_COUNTERS if getattr(spec, "generator", None) else ())


def build_resident(spec, cfg, transform, *, window, max_slots, chunk,
                   spec_on, draft_k, replicated, watchdog, group=1,
                   blocks_per_call=1, denoising_steps=0):
    """The whole-model programs of a resident engine: `cfg` its config (the
    sampler's settings), `transform` its dequantize-on-use wrapper of a model
    function, `replicated` its mesh's replicated sharding, `watchdog` its
    telemetry's compile watchdog, `group` the chunks a token of the mixed
    program takes (G; more than 1 only for a model whose
    `mixed_chunk_groups` says its mixed program runs a group). For a model
    with a block-diffusion `generator`: `blocks_per_call` blocks a slot a
    call, at most `denoising_steps` denoise forwards a block, and `window`
    is the forwards a call's chunks may ride (`_block_diffusion_steps`)."""
    G = group
    generator = getattr(spec, "generator", None)
    model_counters = tuple(getattr(spec, "step_counters", None) or ())
    counters = step_counter_names(spec)
    sample = _sampler(cfg)

    def paged(fn):
        """A spec's paged function under the ONE arity `(logits, pool,
        counts)`: a counted model's returns its counters already, the
        others get the empty pytree (a generator's counters follow the
        model's, zero until the block loop counts)."""
        fn = transform(fn)
        if not generator:
            return fn if counters else lambda *args: (*fn(*args), ())
        extra = jnp.zeros((len(BLOCK_DIFFUSION_COUNTERS),), jnp.int32)

        def counted(*args, **more):
            logits, pool, *counts = fn(*args, **more)
            return logits, pool, jnp.concatenate(
                [*counts[:bool(model_counters)], extra])
        return counted

    # what a window's sum of counts starts from: zeros, or the empty pytree
    no_counts = functools.partial(jnp.zeros, (len(counters),), jnp.int32) \
        if counters else tuple

    decode_paged = paged(spec.decode_paged_fn)
    prefill_paged = paged(spec.prefill_paged_fn)

    def pick(tok):
        """A call's input token a slot. A plain [S] array is the host's.
        Else ((first [W * G], nxt [S, win]) of the call BEFORE, still on
        the device, src [S], host [S]): per slot the host's value (src 0),
        the last token that call sampled for it (1), or the first token
        of the prompt whose last chunk rode that call as its chunk
        src - 2 — call k's tokens are call k-1's outputs, and never make
        the trip to the host and back between the two."""
        if not isinstance(tok, tuple):
            return tok
        (first, nxt), src, host = tok
        return jnp.where(
            src == 0, host, jnp.where(
                src == 1, nxt[:, -1], first[jnp.maximum(src - 2, 0)]))

    def make_decode_step(window):
        """The decode-WINDOW program: `window` tokens per sync inside one
        lax.scan, so one device call + one host roundtrip amortize over
        the window — the dispatch-latency lever. Emits tokens [S, window]:
        the successors of the input token, with the input's k/v (and each
        successor's but the last) written into the pool along the way. A
        builder: `decode_w1()` is the same program at one token."""

        def decode_step(params, tok, pos, pool, tables, rng):
            tok = pick(tok)
            if window == 1:  # no scan wrapper: keep the 1-step hot path
                logits, pool, counts = decode_paged(params, tok, pos, pool,
                                                    tables)
                return (sample(logits, rng)[:, None], counts), pool

            def body(carry, _):
                tok, pos, pool, rng, acc = carry
                rng, sub = jax.random.split(rng)
                logits, pool, counts = decode_paged(params, tok, pos, pool,
                                                    tables)
                nxt = sample(logits, sub)
                acc = jax.tree_util.tree_map(jnp.add, acc, counts)
                return (nxt, pos + 1, pool, rng, acc), nxt

            (_, _, pool, _, acc), toks = jax.lax.scan(
                body, (tok, pos, pool, rng, no_counts()), None, length=window)
            return (jnp.moveaxis(toks, 0, 1), acc), pool

        return decode_step

    def prefill_step(params, toks, start, last_idx, pool, table, rng):
        logits, pool, counts = prefill_paged(params, toks, start, last_idx,
                                             pool, table)
        return (sample(logits, rng), counts), pool

    mixed_paged = getattr(spec, "mixed_paged_fn", None)
    if mixed_paged is not None:
        mixed_paged = paged(mixed_paged)

    def mixed_step(params, chunks, starts, lasts, chunk_tables, n, tok,
                   pos, pool, tables, rng):
        """The MIXED program: a decode window whose first tokens each carry
        up to G prefill chunks through the model with them
        (`DecodeModelSpec.mixed_paged_fn`: the chunks' rows and the slots'
        rows as one tensor, every weight read once), the others plain decode
        tokens. `chunks` [W, G, C], `starts` / `lasts` [W, G] and
        `chunk_tables` [W, G, nb] hold a group of chunks a window position,
        of which the first `n` CHUNKS (traced, 1..W * G) are real, full
        groups first: token i takes chunks [i * G, (i + 1) * G), so the
        first ceil(n / G) tokens ride and only the last of them may carry
        fewer than G. Two loops with dynamic bounds over one carried pool,
        so ONE compile serves every count. Returns ((first tokens [W * G]:
        what each chunk's last row sampled, window tokens [S, W]), counts),
        pool."""
        tok = pick(tok)

        def ride(i, tok, pos, pool, rng):
            logits, pool, counts = _ride_group(
                mixed_paged, params, (chunks, starts, lasts, chunk_tables, n),
                i, G, tok, pos, pool, tables)
            sampled = sample(logits, rng)
            # (one chunk a token: the scalar, as it has always lowered)
            head = sampled[0] if G == 1 else sampled[:G]
            return head, sampled[G:], pool, counts

        if window == 1:     # as `decode_step`: no loop around one token
            first, nxt, pool, counts = ride(0, tok, pos, pool, rng)
            return ((first[None] if G == 1 else first, nxt[:, None]),
                    counts), pool

        def body(i, carry, riding):
            tok, pos, pool, rng, acc, first, toks = carry
            rng, sub = jax.random.split(rng)
            if riding:
                head, nxt, pool, counts = ride(i, tok, pos, pool, sub)
                first = first.at[i].set(head) if G == 1 else \
                    jax.lax.dynamic_update_slice(first, head, (i * G,))
            else:
                logits, pool, counts = decode_paged(params, tok, pos, pool,
                                                    tables)
                nxt = sample(logits, sub)
            acc = jax.tree_util.tree_map(jnp.add, acc, counts)
            return (nxt, pos + 1, pool, rng, acc, first,
                    toks.at[:, i].set(nxt))

        carry = (tok, pos, pool, rng, no_counts(),
                 jnp.zeros((window * G,), jnp.int32),
                 jnp.zeros((tok.shape[0], window), jnp.int32))
        riding = n if G == 1 else (n + G - 1) // G
        carry = jax.lax.fori_loop(
            0, riding, lambda i, c: body(i, c, True), carry)
        carry = jax.lax.fori_loop(
            riding, window, lambda i, c: body(i, c, False), carry)
        _, _, pool, _, acc, first, toks = carry
        return ((first, toks), acc), pool

    if generator:
        # a call commits blocks and `pick`s nothing: the two programs above
        # are replaced, the chunk program and the rest stay
        make_decode_step, mixed_step = _block_diffusion_steps(
            generator, paged(spec.denoise_paged_fn), mixed_paged,
            transform(spec.head_fn), no_counts,
            blocks=blocks_per_call, steps=denoising_steps, group=G)

    # the pool is donated: the update is in-place in HBM. The compile
    # watchdog (telemetry/flight_recorder.py) wraps each program when
    # telemetry is on: any cache miss after the ONE warmup compile is
    # recorded — with telemetry off, wrap() returns the jitted function.
    # The tokens of a decode or mixed call are the next call's input
    # (`pick`), so their sharding is part of that call's signature: it is
    # SAID (replicated, what `no_prev` is placed with) and not left to the
    # compiler's propagation, or the call after an empty engine and the
    # call behind another would be two signatures of one program.
    toks_at = (replicated, None)
    decode = watchdog.wrap(
        "decode_step", jax.jit(make_decode_step(window), donate_argnums=(3,),
                               out_shardings=toks_at))
    prefill = watchdog.wrap(
        "prefill_step", jax.jit(prefill_step, donate_argnums=(4,)))
    # a step's chunks ride its decode call where the model can run the
    # two as one (the scheduler's `_chunks_riding` says when); spec decode
    # has no decode call to ride
    mixed = None
    if mixed_paged is not None and not spec_on:
        mixed = watchdog.wrap(
            "mixed_step", jax.jit(mixed_step, donate_argnums=(8,),
                                  out_shardings=toks_at))

    verify = None
    K1 = draft_k + 1
    if spec_on:
        verify_paged = paged(spec.verify_paged_fn)

        def verify_step(params, toks, pos, pool, tables, rng):
            """Fixed-shape verify: score the k drafts of every slot in ONE
            call — tokens [S, k+1] (col 0 = last emitted token at the
            cursor, cols 1..k = drafts), positions pos..pos+k per row, all
            k+1 tokens' k/v written through the tables along the way.
            Returns the SAMPLED token per position [S, k+1]: the argmax
            under greedy config (the exact-match acceptance target), the
            target model's own draw otherwise — the conservative
            sample-and-match scheme (output distribution preserved; true
            rejection sampling would return probabilities here)."""
            logits, pool, counts = verify_paged(params, toks, pos, pool,
                                                tables)
            S, V = logits.shape[0], logits.shape[-1]
            tgt = sample(logits.reshape(S * K1, V), rng).reshape(S, K1)
            return (tgt, counts), pool

        verify = watchdog.wrap(
            "verify_step", jax.jit(verify_step, donate_argnums=(3,)))

    make_w1 = None if window == 1 or generator else lambda: watchdog.wrap(
        "decode_step_w1", jax.jit(make_decode_step(1), donate_argnums=(3,)))

    no_prev = None if generator else jax.device_put(
        (np.zeros((window * G,), np.int32),
         np.zeros((max_slots, window), np.int32)), replicated)

    def example_args(params, pool, tables, rng):
        S, W = max_slots, window

        def i32(*shape):
            return np.zeros(shape, np.int32)

        one = jax.tree_util.tree_map(lambda t: np.asarray(t)[:1], tables)
        # a call's input tokens as the scheduler hands them: the call
        # before's outputs (on the device), the source a slot, the host's
        tok = i32(S, generator.block_length) if generator \
            else (no_prev, i32(S), i32(S))
        decode_args = (params, tok, i32(S), pool, tables, rng)
        return {
            "decode_step": decode_args, "decode_step_w1": decode_args,
            "prefill_step": (params, i32(1, chunk), i32(1), i32(1), pool,
                             one, rng),
            "mixed_step": (
                params, i32(W, G, chunk), i32(W, G), i32(W, G),
                jax.tree_util.tree_map(
                    lambda t: np.tile(t[None], (W, G, 1)), one),
                np.int32(1), tok, i32(S), pool, tables, rng),
            "verify_step": (params, i32(S, K1), i32(S), pool, tables, rng)}

    return StepPrograms(decode, prefill, mixed, verify, no_prev, parts={},
                        make_w1=make_w1, example_args=example_args, group=G)


def _block_diffusion_steps(generator, denoise_paged, mixed_paged, head,
                           no_counts, *, blocks, steps, group):
    """The decode and mixed programs of a model that generates by diffusion
    over blocks (`engine.BlockDiffusion`): `(make_decode_step, mixed_step)`
    in `build_resident`'s places.

    A call runs `blocks` blocks a slot, all slots block-synchronous. ONE loop
    of forwards on the carried pool, each ONE pass through every weight
    (`denoise_paged`; with chunks riding, `mixed_paged`: up to G chunks and
    the slots' rows as one tensor). The carried state says what a forward is,
    and how wide. While a slot that runs still has a masked row it is a
    DENOISE forward of B rows a slot: `head` makes the rows' logits and the
    rule unmasks rows from them (`BlockDiffusion.unmask`; what the forward
    wrote into the pool is written over by the next forward of the block).
    A clean block is COMMITTED — its K/V stay, its tokens go to the output,
    and every running slot opens its next block as B mask tokens B positions
    on. A block that is not the call's last commits in a FUSED forward of 2B
    rows a slot: its clean tokens at `pos .. pos + B - 1` and the next
    block's mask rows behind them, each block attending to its own end, so
    the pass is that block's commit AND the next one's first denoise step
    and is counted as one of each (`denoise_forwards + commit_forwards`
    counts roles; passes are that less `fused_forwards`). The call's last
    block commits in a forward of its own, which samples nothing and runs no
    head. A block's step schedule is n_s of `steps` steps; the loop has no
    bound of its own because a block of B rows is clean after at most `steps`
    of them.

    `tok` [S, B]: a slot's first block as the host has it — mask ids where a
    slot goes on generating, a prompt's last `L mod B` tokens before mask ids
    where it begins, and NO mask id in the row of a slot that is not in the
    call (it runs against the trash block and keeps no forward waiting).
    Chunks ride the forwards of B rows in order, a group each (a fused
    forward carries none: its chunks wait one forward); the LAST block's
    commit waits (as denoise forwards that change nothing) until every group
    has ridden, so a call always runs the chunks it was given. Returns
    ((tokens [S, blocks * B]), counts), pool; the counters after the model's
    own are `BLOCK_DIFFUSION_COUNTERS`."""
    G, B = group, generator.block_length
    mask_id = generator.mask_token_id
    n_s = jnp.asarray(generator.transfers(steps) + [0], jnp.int32)
    extra = len(BLOCK_DIFFUSION_COUNTERS)
    RIDE, PLAIN, FUSED = range(3)      # the kinds of forward: `due`

    def run(params, tok, pos, pool, tables, riding=None):
        S = tok.shape[0]
        running = jnp.any(tok == mask_id, axis=1)              # [S]
        live = jnp.sum(running, dtype=jnp.int32)
        groups = 0 if riding is None else (riding[4] + G - 1) // G
        # a block as every running slot opens it
        fresh = jnp.where(running[:, None], mask_id, tok)
        opened = jnp.broadcast_to(running[:, None], tok.shape)

        def sampled(rows, x, masked, n):
            """The head and the rule over a forward's rows — in a forward
            that has a masked row: a commit forward of its own, and one
            that waits for chunks, sample nothing."""
            return jax.lax.cond(
                jnp.any(masked),
                lambda: generator.unmask(head(params, rows), x, masked, n),
                lambda: (x, masked, jnp.zeros((S,), jnp.int32)))

        def booked(acc, counts, moved, *roles):
            """`acc` with a forward's counters: the model's, and the block
            loop's for its `roles` (fused, denoise, commit: 0 or 1 each)."""
            fused, denoise, commit = (jnp.asarray(r, jnp.int32)
                                      for r in roles)
            return (acc + counts).at[-extra:].add(jnp.stack([
                fused, denoise, commit,
                jnp.sum(jnp.where(running, moved, 0)) * denoise,
                live * commit]))

        def forward(state, ride):
            """B rows a slot: a denoise step, or the last block's commit."""
            x, masked, pos, pool, b, s, f, out, acc = state
            if ride:
                rows, pool, counts = _ride_group(
                    mixed_paged, params, riding, f, G, x, pos, pool, tables,
                    hidden=True)
                f = f + 1
            else:
                rows, pool, counts = denoise_paged(params, x, pos, pool,
                                                   tables, hidden=True)
            commit = ~jnp.any(masked) & (f >= groups)
            x1, masked1, moved = sampled(
                rows, x, masked, n_s[jnp.minimum(s, steps)])
            with jax.named_scope("denoise/commit"):
                out = jnp.where(commit, jax.lax.dynamic_update_slice(
                    out, x, (0, b * B)), out)
                x = jnp.where(commit, fresh, x1)
                masked = jnp.where(commit, opened, masked1)
                pos = jnp.where(commit & running, pos + B, pos)
                acc = booked(acc, counts, moved, 0, ~commit, commit)
            return (x, masked, pos, pool, b + commit,
                    jnp.where(commit, 0, s + 1), f, out, acc)

        def fused(state):
            """2B rows a slot: block b's commit and block b + 1's first
            denoise step."""
            x, _, pos, pool, b, _, f, out, acc = state
            rows, pool, counts = denoise_paged(
                params, jnp.concatenate([x, fresh], axis=1), pos, pool,
                tables, hidden=True)
            x1, masked1, moved = sampled(rows, fresh, opened, n_s[0])
            with jax.named_scope("denoise/commit"):
                out = jax.lax.dynamic_update_slice(out, x, (0, b * B))
                pos = jnp.where(running, pos + B, pos)
                acc = booked(acc, counts, moved, 1, 1, 1)
            return (x1, masked1, pos, pool, b + 1, jnp.ones_like(b), f, out,
                    acc)

        def due(state):
            """Which forward the state asks for next."""
            _, masked, _, _, b, _, f, _, _ = state
            return jnp.where(
                ~jnp.any(masked) & (b < blocks - 1), FUSED,
                jnp.where(f < groups, RIDE, PLAIN))

        def run_while(kind, body, state):
            return jax.lax.while_loop(
                lambda st: (st[4] < blocks) & (due(st) == kind), body, state)

        # ONE traced body a kind of forward, whatever the call's blocks and
        # chunks: each a loop that runs while its kind is due, one behind the
        # other inside the call's loop. (Not a `lax.switch` a forward: a
        # conditional does not hand the carried pool through in place. Its
        # first branch copied a whole pool leaf into and out of every layer's
        # write: compiled for a described v5e at the cell's size, PR 59;
        # `tests/test_steptrace.py::test_block_diffusion_programs_hold_
        # nothing_of_the_pools_size` holds it.)
        def step(state):
            if riding is not None:
                state = run_while(RIDE, functools.partial(forward, ride=True),
                                  state)
            state = run_while(PLAIN, functools.partial(forward, ride=False),
                              state)
            return run_while(FUSED, fused, state) if blocks > 1 else state

        zero = jnp.zeros((), jnp.int32)
        state = (tok, tok == mask_id, pos, pool, zero, zero, zero,
                 jnp.zeros((S, blocks * B), jnp.int32), no_counts())
        if blocks == 1:
            # one block has no boundary to fuse: its riding forwards, then
            # its others, once — and no loop around them (one that XLA can
            # see runs once ABORTS the TPU compiler, jax 0.9 / libtpu 0.0.34:
            # `hlo_instruction.cc: operands_[i] != nullptr`;
            # `tests/test_steptrace.py` compiles both for a described v5e)
            state = step(state)
        else:
            state = jax.lax.while_loop(lambda st: st[4] < blocks, step,
                                       state)
        _, _, _, pool, _, _, _, out, acc = state
        return (out, acc), pool

    def make_decode_step(_window):
        def decode_step(params, tok, pos, pool, tables, rng):
            del rng         # greedy: x0 is the argmax
            return run(params, tok, pos, pool, tables)
        return decode_step

    def mixed_step(params, chunks, starts, lasts, chunk_tables, n, tok, pos,
                   pool, tables, rng):
        """`chunks` [W, G, C] (+ `starts`, `lasts`, `chunk_tables`): a group
        a forward of B rows, of which the first `n` CHUNKS (traced) are
        real."""
        del rng
        return run(params, tok, pos, pool, tables,
                   (chunks, starts, lasts, chunk_tables, n))

    return make_decode_step, mixed_step


def build_streamed(spec, cfg, *, num_layers, streamer, watchdog):
    """The offloaded-weights (streamed) mode: SIX single-signature jitted
    programs — {embed, layer, head} x {prefill, decode} — and a host loop
    that walks the layer program L times per call, weights fed by the
    engine's async staging pool `streamer` (layer i computes while layer
    i+1's upload and layer i+2's disk read are in flight). The layer index
    is TRACED (the pool's layer axis is dynamic-sliced and written back in
    place via donation), so every layer of the walk shares one compile: one
    compile per PROGRAM, six in all. The window is one token, there is no
    mixed and no verify program, and `decode` takes what the resident one
    takes and ignores the device half of its tokens."""
    sample = _sampler(cfg)
    L = num_layers

    def head(res, x, last_idx, rng):
        last = jnp.take_along_axis(x, last_idx[:, None, None], axis=1)
        return sample(spec.final_fn(res, last)[:, 0], rng)

    # a jit a role AND a phase: each program then has ONE call signature for
    # the engine's lifetime. Each jit wraps a `partial` of its own, a
    # DISTINCT function object — jax.jit wrappers over one function share a
    # compile cache, which would double every program's reported count.
    parts = {f"{role}_{phase}": watchdog.wrap(
        f"{role}_{phase}", jax.jit(functools.partial(fn),
                                   donate_argnums=donate))
        for phase in ("prefill", "decode")
        for role, fn, donate in (("embed", spec.embed_fn, ()),
                                 ("layer", spec.layer_paged_fn, (3,)),
                                 ("head", head, ()))}

    def walk(phase, params, toks, positions, last_idx, pool, tables, rng):
        embed, layer, head = (parts[f"{role}_{phase}"]
                              for role in ("embed", "layer", "head"))
        x = embed(params, toks, positions)
        for i in range(L):
            x, pool = layer(streamer.layer(i), x, np.int32(i), pool, tables,
                            positions)
        return head(params, x, last_idx, rng), pool

    def prefill_step(params, toks, start, last_idx, pool, table, rng):
        positions = np.asarray(start, np.int32)[:, None] + \
            np.arange(toks.shape[1], dtype=np.int32)[None]
        tok, pool = walk("prefill", params, toks, positions,
                         np.asarray(last_idx, np.int32), pool, table, rng)
        return (tok, ()), pool

    def decode_step(params, tok, pos, pool, tables, rng):
        if isinstance(tok, tuple):
            tok = tok[2]        # the host walk takes the host's tokens
        tok = np.asarray(tok, np.int32)[:, None]
        nxt, pool = walk("decode", params, tok,
                         np.asarray(pos, np.int32)[:, None],
                         np.zeros(len(tok), np.int32), pool, tables, rng)
        return (nxt[:, None], ()), pool

    return StepPrograms(decode_step, prefill_step, None, None,
                        no_prev=(None, None), parts=parts, make_w1=None,
                        example_args=lambda *live: {})
