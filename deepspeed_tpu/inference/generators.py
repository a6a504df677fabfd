"""The host half of a GENERATOR: what the serving loop (`scheduler.py`) asks
about the kind of decode call a model makes; `step_programs.py` builds the
device half. ONE contract, which the loop calls and never looks behind
(docs/inference.md has it as a table): the data — `window` / `ride_window`
(positions a slot advances a call; forwards of it a chunk group may ride),
`row_forwards` (rows a slot runs a position it advances), `block` (prefill
covers a prompt's whole blocks), `samples_first` (does a prompt's last chunk
sample a first token), `no_transplant` (why not, or None), `program_args`
(what `build_resident` takes beside the loop's settings) — and the hooks
`feed`, `opens`, `walk_at`, `due`, `close`, `stats`, documented on the plain
instance. What an instance cannot have yet its constructor refuses, by name.

Two instances: `Autoregressive` and `BlockDiffusionCalls` (whose data on the
spec is `engine.BlockDiffusion`). A third (spec decode's draft + verify,
ROADMAP R14) gives the same and nothing in the loop forks.
"""

import numpy as np

from deepspeed_tpu.inference.step_programs import step_counter_names


class Autoregressive:
    """One token a slot a forward: a call scans `window` forwards of one row
    a slot, and takes its first input from the call before it, still on the
    device (`step_programs.py`: `pick`)."""

    spec = None             # (no `DecodeModelSpec.generator`)
    block = row_forwards = 1
    samples_first = True
    blocks_per_call = denoising_steps = 0
    no_transplant = None
    program_args = {}

    def __init__(self, window, max_slots):
        self.window = self.ride_window = window
        self.max_slots = max_slots

    def feed(self, dec, prior, no_prev):
        """(The call's token argument, `skip`: slot index -> the leading
        tokens of its row that are not generated). Here ((the call before's
        (first tokens, window tokens), still on the device — `no_prev` where
        none is in flight —, src [S], host tokens [S]), none): a slot's input
        is the host's last emitted token, or stays on the device as the
        output `prior` samples for it (`src`: 1 = the window's last token,
        2 + i = first token i)."""
        tok = np.zeros((self.max_slots,), np.int32)
        src = np.zeros((self.max_slots,), np.int32)
        for s in dec:
            if prior is not None and s.feed is not None \
                    and s.feed[0] == prior.id:
                src[s.idx] = s.feed[1]      # still on the device
            else:
                tok[s.idx] = s.emitted[-1]
        return (no_prev if prior is None else prior.prev, src, tok), {}

    def opens(self, out, riding, no_prev):
        """Of a call's output `out`: (what the NEXT call picks its input
        from, whether first tokens came beside the window's)."""
        return (out[0] if riding else (no_prev[0], out[0])), bool(riding)

    def walk_at(self, pos, win):
        """The positions [n, slots] a call's walks are counted at: a
        token's."""
        return pos + np.arange(win)[:, None]

    def due(self, work, rec=None):
        """The part of a call's `work` (`StepRecord` fields) that joins the
        step's sums NOW — at dispatch (`rec` None) or at the read-back whose
        record is `rec`. A token a row: the host counts all at dispatch."""
        return work if rec is None else {}

    def close(self, rec, counts):
        """The call's record once its counters `counts` are read."""
        return rec

    def stats(self, counters):
        """Its entry in `ServingEngine.stats()`, or None."""
        return None


class BlockDiffusionCalls:
    """Diffusion over blocks (`engine.BlockDiffusion`, the model's
    `DecodeModelSpec.generator`): a call commits `blocks_per_call` whole
    blocks of B tokens a slot, all slots block-synchronous, so positions
    advance by `window` = blocks_per_call * B a call and the host still books
    them at dispatch. A block takes up to `steps` denoise forwards and a
    commit forward, of B rows a slot each — ROLES, which the device counts
    (`denoise_forwards`, `commit_forwards`); a block that is not the call's
    last commits in the pass that is the next block's first denoise step, a
    FUSED forward of 2B rows a slot (`fused_forwards`;
    `step_programs.py::_block_diffusion_steps`), so a call's PASSES through
    the weights are denoise + commit - fused, at most blocks_per_call *
    (steps + 1) - (blocks_per_call - 1). Its chunks ride the passes of B
    rows: `ride_window` = that less the blocks_per_call - 1 fused ones. Call
    k+1 takes NOTHING from call k, a prompt's last chunk samples no first
    token (its `L mod B` tail opens the first generated block as clean
    tokens), and the forwards a call took are known at its read-back only:
    its record and its walks are closed and booked there. What it cannot
    have yet is refused at build time, by name, as the pools of two kinds
    refuse theirs."""

    samples_first = False
    no_transplant = (
        "generates by diffusion over blocks: block transplant (prefill-only "
        "slots, handoff) is not built for it — a prompt's last block is not "
        "committed when its prefill ends, and no first token is sampled to "
        "hand over")

    def __init__(self, spec, scfg, config, *, chunk, block_size, spec_on,
                 max_slots):
        gen = self.spec = spec.generator
        self.block = B = gen.block_length
        kvd = str(scfg.quantization.kv_cache_dtype or "") \
            or str(config.kv_cache_dtype)
        asked = {
            "spec_decode": (
                spec_on,
                "a verify chunk is causal inside and scores drafts of one "
                "token a forward; a block's rows are generated together"),
            "kv_cache_dtype int8": (
                kvd == "int8",
                "a block's rows are written once a denoise forward and read "
                "by the walk at 8 x B query rows a KV head; the quantizing "
                "write and the dequantizing walk are not built for it"),
            "enable_prefix_caching": (
                scfg.enable_prefix_caching,
                "a block registers when its prompt chunk is dispatched, and "
                "the block a prompt ends in is committed later by the decode "
                "call that finishes it"),
            "degradation": (
                scfg.degradation.enabled,
                "the ladder's window-shrink rung runs a one-token decode "
                "program, and a call commits whole blocks"),
            "sampling (greedy false)": (
                not config.greedy,
                "confidence is the probability of the argmax; the sampled "
                "variants of the rule are not built"),
            f"prefill_chunk {chunk}": (
                chunk % B or block_size % B,
                f"chunks and pool blocks hold whole blocks of {B}"),
            "mixed_paged_fn / denoise_paged_fn": (
                spec.denoise_paged_fn is None,
                "the generator's forwards are the model's "
                "`denoise_paged_fn`")}
        for what, (wanted, why) in asked.items():
            if wanted:
                raise ValueError(
                    f"model spec '{spec.name}' generates by diffusion over "
                    f"blocks of {B}: {what} is not built for it — {why}")
        self.max_slots = max_slots
        self.blocks_per_call = max(1, int(scfg.blocks_per_call))
        self.denoising_steps = gen.steps
        self.window = self.blocks_per_call * B
        # (a block runs B rows through up to `steps` denoise forwards and its
        # commit; the read-back has the forwards it took: `close`)
        self.row_forwards = self.denoising_steps + 1
        self.ride_window = self.blocks_per_call * self.row_forwards \
            - 2 * (self.blocks_per_call - 1)
        self.program_args = dict(blocks_per_call=self.blocks_per_call,
                                 denoising_steps=self.denoising_steps)
        names = step_counter_names(spec)
        self._forwards = [names.index(name) for name in (
            "denoise_forwards", "commit_forwards", "fused_forwards")]

    def feed(self, dec, prior, no_prev):
        """(`tok` [S, B] — a slot's first block of the call: mask ids where
        it goes on generating, its prompt's last `L mod B` tokens before mask
        ids where it begins, and no mask id in the row of a slot that is not
        in the call —, slot index -> those prompt tokens' count). The host
        knows all of it at dispatch: nothing is taken from `prior`."""
        B, mask = self.block, self.spec.mask_token_id
        tok = np.full((self.max_slots, B), int(mask == 0), np.int32)
        skip = {}
        for s in dec:
            tok[s.idx] = mask
            whole = s.prompt_len - s.prompt_len % B
            if s.pos == whole and s.prompt_len > whole:
                tail = s.prompt[whole:]
                tok[s.idx, :len(tail)] = tail
                skip[s.idx] = len(tail)
        return tok, skip

    def opens(self, out, riding, no_prev):
        # its output is the committed tokens alone: no first token is
        # sampled, and the next call picks nothing from it
        return None, False

    def walk_at(self, pos, win):
        # a walk a FORWARD, at the block's last position: ONE forward of each
        # of the call's blocks (`due` books them by the forwards taken)
        return pos + np.arange(self.block - 1, win, self.block)[:, None]

    def due(self, work, rec=None):
        """At the read-back, by the walks the call took — one a denoise or
        commit forward, two a fused one: `rec.win` rows a slot, B a walk. Its
        counters say how many and not which block's they were, so each is
        booked as the call's mean one — exact where its blocks take the same
        number (flat logits: S + 1 each); its blocks lie B positions apart."""
        if rec is None:
            return {}
        walks = rec.win / self.block
        return {name: n * walks / self.blocks_per_call
                for name, n in work.items()}

    def close(self, rec, counts):
        """The forwards the call took, by its own counters: `forwards` its
        passes through the weights, `win` the rows a slot ran through the
        model (what the readers divide by) — B a denoise or commit forward,
        2B a fused one."""
        if not rec.win:
            return rec
        denoise, commit, fused = (int(n) for n in counts[self._forwards])
        return rec._replace(forwards=denoise + commit - fused,
                            block_rows=self.block,
                            win=(denoise + commit) * self.block)

    def stats(self, counters):
        # forwards and rows apart from tokens: `tokens_generated` are
        # committed AND delivered; the step counters have the forwards, by
        # ROLE — a fused forward is one pass that counts as two. `forwards`
        # (as `CallRecord.forwards`) and `passes_per_block` are passes
        # through the weights; `forwards_per_block` stays by role, what the
        # rule took of a block whatever the loop fused (S + 1 on flat logits)
        commits = max(1, counters["commit_forwards"])
        roles = counters["denoise_forwards"] + counters["commit_forwards"]
        forwards = roles - counters["fused_forwards"]
        return {"kind": "block_diffusion",
                "block_length": self.block,
                "denoising_steps": self.denoising_steps,
                "blocks_per_call": self.blocks_per_call,
                "remasking": self.spec.remasking,
                "forwards": forwards,
                "forwards_per_block": roles / commits,
                "passes_per_block": forwards / commits,
                "fused_forward_share": counters["fused_forwards"] / commits}


def build(spec, scfg, config, *, streamed, window, chunk, block_size,
          spec_on, max_slots):
    """The generator of a serving engine on `spec`: the model's own
    (`DecodeModelSpec.generator`; a streamed engine walks one token a call
    whatever the model) or the plain one at the settings' `window`."""
    if streamed or getattr(spec, "generator", None) is None:
        return Autoregressive(window, max_slots)
    return BlockDiffusionCalls(spec, scfg, config, chunk=chunk,
                               block_size=block_size, spec_on=spec_on,
                               max_slots=max_slots)
