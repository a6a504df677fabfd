"""Driver for the SDAR-MoE family (`models/sdar_moe.py`, `model_type:
sdar_moe`) served through `init_inference(...).serving(...)` by DIFFUSION OVER
BLOCKS: the SAME loop, recorder, window and estimators as `drivers/serve.py` —
that module's `run` is called as it is — with this file's set-up (`_build`),
warm-up (`_warm`: the mixed program too), request (`_request`: no prompt
holds the mask token) and reference check (`_check_logits`) in the places
where `serve.run` looks its own up by name.

What the check covers:

- IT RUNS THE STEP PROGRAMS THE WINDOW TIMES, ON THE SERVED POOL: two calls
  of the served executables — `serving.programs.mixed` with the riding
  prompts' chunks, then `serving.programs.decode` — handed what
  `ServingEngine._launch` hands them, all `max_slots` slots live (4 rows a
  slot), `blocks_per_call` blocks a call. Each call is then WALKED AGAIN on
  the host from the same pool and the same inputs, a forward at a time
  through the spec's `denoise_paged_fn` / `mixed_paged_fn` (the bodies of
  those programs' block loops) with the logits of the compared slots' rows
  and every row's experts kept (`routing=True`): a forward is a denoise
  forward while a running slot has a masked row (the PROGRAM'S rule,
  `BlockDiffusion.unmask`, on the device), else the block's commit.
- THE TIE: the served call and the host's walk of it must agree — the four
  block-loop counters exactly, the committed tokens of EVERY running slot
  `[S, blocks_per_call * 4]`, and the pool's rows of the compared slots'
  committed blocks and of the riding prompts' chunks. A served loop that
  committed a wrong token, advanced a position wrongly or dropped a riding
  chunk fails it (controls: PERF.md section 6).
- the REFERENCE REPLAYS THE TRAJECTORY: at every forward of the compared
  sequences it is given the block (tokens and mask ids) after the tokens
  committed so far, and the experts the program chose for every position,
  and computes the whole sequence under the block-causal mask with no
  cache: the logits of all B rows are compared, rms and largest. Prompts
  whose length is and is not a multiple of 4, of one chunk and of several;
  two of them prefilled by chunks RIDING the first call, so that their
  blocks read K/V a mixed call wrote.
- the COMMITTED K/V of each compared sequence's last block and of the riding
  prompts' chunks, as the SERVED calls left them in the pool, against the
  reference's keys and values of those positions.
- reported and not limited (with random weights confidences are ~1e-5 apart
  and an argmax flips on a rounding): the share of denoise forwards where
  the reference's OWN rule on its own logits would have unmasked another row
  or another token; limited: the share of (position, layer) pairs whose set
  of 8 experts differs from the one the reference would choose.

Four limits against the reference and the tie's three decide `correct`, each
limit between two readings (PERF.md section 6).
"""

import time

import jax
import jax.numpy as jnp
import numpy as np

import deepspeed_tpu
from deepspeed_tpu.comm import mesh as mesh_mod
from deepspeed_tpu.config.core import MeshConfig

import harness
import steprings
from drivers import gpt_family, serve

try:
    from deepspeed_tpu.models import sdar_moe
except ImportError:
    raise SystemExit("benchmark: this program has no SDAR-MoE family and no "
                     "block-diffusion generator (models/sdar_moe.py); the "
                     "cell cannot run on it")

# THE LIMITS. Each sits between two readings through this check as it is (my
# chip runs, PR 54, PERF.md section 6): the served programs' largest over
# their seeds, and this same verdict on the reference ITSELF computed through
# float8_e4m3 (weights and activations of every product; the nearest
# precision below the configuration's bfloat16), which must come out not
# correct.
#
# Logits of all 4 rows of every compared forward, routing held equal:
# root-mean-square error as a share of the reference's root-mean-square
# logit, and the largest error as a share of the largest |logit|.
LOGIT_RMS_LIMIT = 0.03
LOGIT_MAX_LIMIT = 0.05
# The committed keys and values of the last block and of the riding prompts'
# chunks as the SERVED calls left them, all layers: root-mean-square error
# as a share of the reference's root-mean-square entry (the cache is
# bfloat16: one rounding is 0.2-0.4%).
KV_RMS_LIMIT = 0.02
# Share of (position, layer) pairs whose SET of 8 experts differs from the
# one the reference would choose on the same stream: a ninth probability
# within bfloat16's rounding of the eighth.
EXPERT_SET_MISMATCH_LIMIT = 0.15

# THE TIE of the served step programs to the forwards compared above: each
# checked call is run by the SERVED `mixed_step` / `decode_step` and walked
# again on the host from the same pool and inputs (`program_trajectory`).
# The four block-loop counters must be EQUAL. Two limits, each between the
# served programs' reading over their seeds — 0 and 0: on this chip the two
# walks agree to the bit, every token of 508 (slot, block) pairs and every
# pool row — and the same verdict on a served call with a fault (my chip
# runs, PR 54, `_chip/control54.py`): `mixed_step` one riding chunk short
# (tokens 0, rows 44.3%; the reference's K/V limit reads 46.4%) and
# `decode_step` a block ahead of its positions (tokens 34.4%, rows 9.2%).
#
# Share of (running slot, block) pairs whose committed tokens differ: one
# pair of a call's 256 is 0.4%, a rounding's worth of room where a build of
# the two executables ever fuses them apart.
TIE_TOKEN_MISMATCH_LIMIT = 0.01
# The pool's rows of the compared slots' committed blocks and of the riding
# prompts' chunks, served call against host walk: root-mean-square difference
# as a share of the root-mean-square entry (one bfloat16 rounding of the
# cache is 0.2-0.4%; the faults read 9.2% and 44.3%).
TIE_KV_RMS_LIMIT = 0.005

# the compared sequences' prompts, in prefill chunks: parts of one chunk (a
# multiple of 4 and the three remainders), a chunk and a bit
CHECK_PROMPTS = (5 / 32, 13 / 32, 23 / 32, 15 / 16, 41 / 64, 9 / 8)
# ... and two that are prefilled by chunks RIDING the first forwards: several
# chunks, and one
RIDING_PROMPTS = (19 / 8, 11 / 16)
CHECK_CALLS = 2             # served calls the check runs and walks: the
                            # first `mixed_step` (the riding prompts'
                            # chunks), the second `decode_step`
REFERENCE_PAD = 128         # the reference's sequences run padded to this

_built = {}


def model_config(cfg, max_seq_len):
    """The program's configuration for the file's keys (the published
    `config.json`'s, cut as the file says). Every width is the file's."""
    return sdar_moe.sdar_moe_config(
        cfg, max_seq_len, cfg["generator"]["block_length"],
        # the table is shorter than the dispatch's automatic crossover; the
        # paged decode kernel is the deployment's choice (configuration file)
        use_flash_attention=True, dtype=jnp.bfloat16)


def _generator(cfg):
    g = cfg["generator"]
    return sdar_moe.generator(g["block_length"], g["mask_token_id"],
                              g["denoising_steps"], g["remasking"],
                              g["confidence_threshold"])


def _build(cell, seed, device):
    cfg = cell["config_json"]
    knobs = dict(cfg["serving"])
    block = knobs.pop("kv_block_size")
    gcfg = model_config(cfg, max_seq_len=knobs["max_context"])
    mesh_mod.clear_mesh()
    mesh_mod.init_mesh(MeshConfig(data=1), devices=[device])
    t0 = time.perf_counter()
    init = jax.jit(sdar_moe.sdar_moe_init_fn(
        gcfg, dtype=jnp.bfloat16, embedding_std=cfg["embedding_range"]),
                   out_shardings=jax.sharding.SingleDeviceSharding(device))
    params = init(gpt_family.seed_key(seed))
    engine = deepspeed_tpu.init_inference(
        sdar_moe.make_sdar_moe_decode_model(gcfg, _generator(cfg),
                                            params=params,
                                            name=cell["config"]),
        config={"dtype": "bfloat16", "kv_cache_dtype": "bfloat16",
                "greedy": True, "kv_block_size": block,
                "max_out_tokens": knobs["max_context"]})
    serving = engine.serving(**knobs)
    jax.block_until_ready((engine.params, serving.pool))
    _built["serving"] = serving
    _built["mask"] = cfg["generator"]["mask_token_id"]
    return gcfg, engine, serving, time.perf_counter() - t0


def _request(req):
    """`serve._request`, and no prompt holds the mask token (the generator
    knows a masked row by it): a drawn one becomes token 0."""
    tokens = np.where(req["tokens"] == _built["mask"], 0, req["tokens"])
    return serve.Request(uid=req["uid"], tokens=tokens,
                         max_new_tokens=req["output_tokens"],
                         stop_on_eos=False)


def _warm(serving, vocab, seed, serve_warm=serve._warm):
    """ALL THREE step programs once, through the scheduler itself, so that
    none is traced, lowered or compiled in the traffic's pre-roll
    (`serve_nemotron_h._warm`'s reason): `serve._warm`'s one request runs
    `prefill_step` and `decode_step`; then a second prompt arrives while a
    first request generates, so its chunks ride that slot's call:
    `mixed_step`, the program the window runs."""
    vocab = min(vocab, _built["mask"])      # (no mask token is drawn)
    seconds = serve_warm(serving, vocab, seed)
    rng = np.random.default_rng([seed, 0x3A24])
    t0 = time.perf_counter()
    first = serve.Request(
        uid="warm_generating", tokens=rng.integers(0, vocab, (9,), np.int32),
        max_new_tokens=4 * serving.window, stop_on_eos=False)
    rides = serve.Request(
        uid="warm_riding", max_new_tokens=serve.WARM_NEW, stop_on_eos=False,
        tokens=rng.integers(0, vocab, (serving.chunk + 9,), np.int32))
    serving.submit(first)
    serving.step()          # its chunk and its first call
    serving.submit(rides)
    fused = serving.fused_chunks
    while serving.queue or serving.num_active:
        serving.step()
    assert serving.fused_chunks > fused
    return seconds + time.perf_counter() - t0


def _forwards(spec, gen, keep, chunk_rows):
    """The check's two forwards, jitted: (`denoise`, `mixed`) — the spec's
    own paged functions with the experts kept, the PROGRAM'S rule applied to
    the logits, and only the rows of the slots `keep` handed back: (logits
    [keep, B, V], x and masked after the rule [S, B], the rows the rule
    unmasked [S], the slot rows' experts [L, keep, B, k], the chunks' rows'
    experts [L, G * C, k] (mixed), pool)."""
    keep = jnp.asarray(keep)

    def after(logits, sets, x, masked, n):
        B = x.shape[1]
        x1, m1, moved = gen.unmask(logits, x, masked, n)
        rows = (keep[:, None] * B + jnp.arange(B)[None]).reshape(-1)
        sets = sets[:, rows].reshape(sets.shape[0], len(keep), B, -1)
        return (logits[rows].astype(jnp.float32).reshape(len(keep), B, -1),
                x1, m1, moved, sets)

    def denoise(params, x, masked, n, pos, pool, tables):
        logits, pool, _counts, sets = spec.denoise_paged_fn(
            params, x, pos, pool, tables, routing=True)
        return (*after(logits, sets, x, masked, n), pool)

    def mixed(params, chunks, starts, lasts, chunk_tables, count, x, masked,
              n, pos, pool, tables):
        G = chunks.shape[0]
        more = () if G == 1 else (count,)
        logits, pool, _counts, sets = spec.mixed_paged_fn(
            params, chunks, starts, lasts, chunk_tables, x, pos, pool,
            tables, *more, routing=True)
        out = after(logits[G:], sets[:, chunk_rows:], x, masked, n)
        return (*out, sets[:, :chunk_rows], pool)

    return (jax.jit(denoise, donate_argnums=(5,)),
            jax.jit(mixed, donate_argnums=(10,)))


def program_trajectory(spec, params, serving, vocab, seed):
    """The check's sequences through `CHECK_CALLS` calls of the SERVED step
    programs (`serving.programs.mixed`, then `.decode`: the executables the
    window times, handed what `ServingEngine._launch` hands them) on the
    SERVED pool (borrowed: donated and handed back; the allocator has every
    block free here), and each call AGAIN, from the same pool and the same
    inputs, forward by forward on the host through the spec's own paged
    functions with the logits and the experts kept. Every slot is live: the
    compared sequences in the first slots, short prompts in the others; the
    riding prompts' chunks ride the first call and their sequences open
    their first block in the second, as a request's do.

    The host's walk repeats `_block_diffusion_steps`' loop (a forward is a
    denoise forward while a running slot has a masked row, else the block's
    commit; chunks ride forward 0, 1, ... a group each) and writes the rows
    the served call wrote, reading nothing else of what it left: a block's
    forwards read the cache below the block and the block itself. So the
    two must agree, and `tie` says how far: the committed tokens of every
    running slot, the four counters, and the pool's rows of the compared
    slots' committed blocks. The REFERENCE is then held against the host's
    forwards (logits, experts) and against the SERVED call's rows of the
    last block (K/V).

    Returns (sequences: one dict a compared sequence — `prompt`, `forwards`:
    [dict(start, x, masked, logits [B, V], sets [L, B, k], after: the rows
    after the program's rule (tokens, masked) or None for a commit
    forward)], `sets` [L, T, k]: the experts the program chose for every
    position of the final sequence, `kv`: the served call's rows of the last
    block [L, 2, Hkv, B, hd] —, forwards the host ran, `tie`)."""
    gen = spec.generator
    B, mask = gen.block_length, gen.mask_token_id
    chunk, slots, bs, nb = (serving.chunk, serving.max_slots,
                            serving.block_size, serving.nb)
    G, ride = serving.programs.group, serving.ride_window
    blocks, steps = serving.blocks_per_call, serving.denoising_steps
    plan = gen.transfers(steps) + [0]
    rng = np.random.default_rng([seed, 0xC4EC])
    n_cmp, n_ride = len(CHECK_PROMPTS), len(RIDING_PROMPTS)
    compared = list(range(n_cmp + n_ride))
    late = range(n_cmp, n_cmp + n_ride)     # live from the second call

    def draw(n):
        return rng.integers(0, min(vocab, mask), (n,)).astype(np.int32)

    prompts = [draw(max(B + 1, int(f * chunk)) | (i % B if i else 0))
               for i, f in enumerate(CHECK_PROMPTS + RIDING_PROMPTS)]
    # (the first a multiple of 4 where the chunk is; the others every
    # remainder)
    prompts[0] = prompts[0][:len(prompts[0]) - len(prompts[0]) % B]
    prompts += [draw(int(rng.integers(B + 1, max(B + 2, chunk // 4))))
                for _ in range(slots - len(prompts))]
    whole = [len(p) - len(p) % B for p in prompts]
    free = iter(range(1, serving.pool["k"].shape[1]))
    tables = np.zeros((slots, nb), np.int32)        # 0 is the trash block
    for s, p in enumerate(prompts):
        need = -(-(len(p) + (CHECK_CALLS * blocks + 1) * B) // bs)
        tables[s, :need] = [next(free) for _ in range(need)]

    prefill = jax.jit(
        lambda *a: spec.prefill_paged_fn(*a, routing=True),
        donate_argnums=(4,))
    pool = serving.pool
    sets_of = {s: [] for s in compared}     # per compared slot: [L, rows, k]
    riding = []                             # (slot, start): ride call 0
    for s, p in enumerate(prompts):
        for start in range(0, whole[s], chunk):
            if s in late:
                riding.append((s, start))
                continue
            seg = p[start:min(start + chunk, whole[s])]
            toks = np.zeros((1, chunk), np.int32)
            toks[0, :len(seg)] = seg
            _, pool, _counts, sets = prefill(
                params, toks, np.asarray([start], np.int32),
                np.asarray([len(seg) - 1], np.int32), pool, tables[s][None])
            if s in sets_of:
                sets_of[s].append(np.asarray(sets)[:, :len(seg)])
    assert len(riding) <= ride * G

    denoise, mixed = _forwards(spec, gen, np.asarray(compared), G * chunk)
    live = np.ones((slots,), bool)
    live[late] = False                      # until their chunks have ridden
    pos = np.asarray(whole, np.int32)
    out = {s: dict(prompt=prompts[s], forwards=[]) for s in compared}

    def rows_of(pool, s, lo, hi):
        """The pool's rows of slot `s`'s positions [lo, hi):
        [L, 2, Hkv, hi - lo, hd]."""
        cuts = sorted({lo, hi, *range(lo - lo % bs + bs, hi, bs)})
        return np.stack([np.concatenate([
            np.asarray(pool[leaf][:, tables[s, a // bs], :,
                                  a % bs:a % bs + b - a], np.float32)
            for a, b in zip(cuts, cuts[1:])], axis=2)
            for leaf in ("k", "v")], axis=1)

    def chunk_arrays(group):
        """`_launch`'s arrays of the chunks riding one call, `[ride, G,
        ...]`: chunk i is chunk i % G of forward i // G."""
        toks = np.zeros((ride * G, chunk), np.int32)
        starts = np.zeros((ride * G,), np.int32)
        lasts = np.zeros((ride * G,), np.int32)
        idx = [s for s, _ in group]
        idx += idx[-1:] * (ride * G - len(group))
        for i, (s, start) in enumerate(group):
            seg = prompts[s][start:min(start + chunk, whole[s])]
            toks[i, :len(seg)] = seg
            starts[i], lasts[i] = start, len(seg) - 1
        return (toks.reshape(ride, G, chunk), starts.reshape(ride, G),
                lasts.reshape(ride, G), tables[idx].reshape(ride, G, nb))

    tie = dict(token_blocks=0, token_blocks_differ=0, counters=[],
               kv_served=[], kv_host=[])
    last_kv = {}
    ran = 0
    for call in range(CHECK_CALLS):
        # the call's inputs, as `_block_input` / `_launch` build them
        tok = np.full((slots, B), int(mask == 0), np.int32)
        for s in np.flatnonzero(live):
            tok[s] = mask
            tail = prompts[s][whole[s]:] if pos[s] == whole[s] else ()
            tok[s, :len(tail)] = tail
        shown = np.where(live[:, None], tables, 0).astype(np.int32)
        group = riding if call == 0 else []
        pos0 = np.where(live, pos, 0).astype(np.int32)

        # (1) the SERVED program: one call
        if group:
            chunks = chunk_arrays(group)
            served, pool = serving.programs.mixed(
                params, *chunks, np.int32(len(group)), tok, pos0, pool,
                shown, serving._next_rng())
        else:
            served, pool = serving.programs.decode(
                params, tok, pos0, pool, shown, serving._next_rng())
        served_toks, served_counts = jax.device_get(served)
        served_rows = {(s, b): rows_of(pool, s, pos0[s] + b * B,
                                       pos0[s] + (b + 1) * B)
                       for s in compared if live[s] for b in range(blocks)}
        # ... and of the prompts whose chunks rode it
        served_chunks = {s: rows_of(pool, s, 0, whole[s])
                         for s in sorted({s for s, _ in group})}

        # (2) the same call on the host, a forward at a time
        running = (tok == mask).any(axis=1)
        x, masked, at = tok.copy(), tok == mask, pos0.copy()
        groups = -(-len(group) // G)
        host_toks = np.zeros((slots, blocks * B), np.int32)
        counts = np.zeros((4,), np.int64)
        b = step = f = 0
        while b < blocks:
            n = np.int32(plan[min(step, steps)])
            if f < groups:
                part = [a[f] for a in chunks]
                here = group[f * G:(f + 1) * G]
                logits, x1, m1, moved, sets, chunk_sets, pool = mixed(
                    params, *part, np.int32(len(here)), x, masked, n, at,
                    pool, shown)
                chunk_sets = np.asarray(chunk_sets)
                for i, (s, start) in enumerate(here):
                    seg_len = min(chunk, whole[s] - start)
                    sets_of[s].append(
                        chunk_sets[:, i * chunk:i * chunk + seg_len])
            else:
                logits, x1, m1, moved, sets, pool = denoise(
                    params, x, masked, n, at, pool, shown)
            ran += 1
            commit = not masked.any() and (b < blocks - 1 or f + 1 >= groups)
            logits, sets = np.asarray(logits), np.asarray(sets)
            x1, m1, moved = np.asarray(x1), np.asarray(m1), np.asarray(moved)
            for i, s in enumerate(compared):
                if running[s]:
                    out[s]["forwards"].append(dict(
                        start=int(at[s]), x=x[s].copy(),
                        masked=masked[s].copy(), logits=logits[i],
                        sets=sets[:, i],
                        after=None if commit else (x1[s].copy(),
                                                   m1[s].copy())))
            if commit:
                # every running slot's block stays, and its next opens
                for i, s in enumerate(compared):
                    if running[s]:
                        sets_of[s].append(sets[:, i])
                host_toks[:, b * B:(b + 1) * B] = x
                x = np.where(running[:, None], mask, tok).astype(np.int32)
                masked = np.broadcast_to(running[:, None], x.shape).copy()
                at = np.where(running, at + B, at).astype(np.int32)
                counts += (0, 1, 0, running.sum())
                b, step = b + 1, 0
            else:
                x, masked, step = x1, m1, step + 1
                counts += (1, 0, moved[running].sum(), 0)
            f += 1

        # (3) how far the two agree
        differ = (served_toks != host_toks).reshape(
            slots, blocks, B).any(-1)[running]
        tie["token_blocks"] += differ.size
        tie["token_blocks_differ"] += int(differ.sum())
        tie["counters"].append(
            ([int(v) for v in served_counts[-4:]], [int(v) for v in counts]))
        for (s, b), rows in served_rows.items():
            # (a block's rows say something where both committed the same
            # tokens into it and into the call's blocks before it)
            same = (served_toks[s, :(b + 1) * B]
                    == host_toks[s, :(b + 1) * B]).all()
            if same:
                tie["kv_served"].append(rows)
                tie["kv_host"].append(rows_of(pool, s, pos0[s] + b * B,
                                              pos0[s] + (b + 1) * B))
            if b == blocks - 1:
                last_kv[s] = rows
        for s, rows in served_chunks.items():
            tie["kv_served"].append(rows)
            tie["kv_host"].append(rows_of(pool, s, 0, whole[s]))
            out[s]["prompt_kv"] = rows
        pos = np.where(live, pos + blocks * B, pos).astype(np.int32)
        live[late] = True               # their chunks are in the pool
    for s in compared:
        out[s]["sets"] = np.concatenate(sets_of[s], axis=1)
        out[s]["kv"] = last_kv[s]
    serving.pool = pool
    return [out[s] for s in compared], ran, tie


def _flat(arrays):
    return np.concatenate([np.ravel(a) for a in arrays])


def _errors(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    err = got - want
    return (float(np.sqrt(np.square(err).sum() / np.square(want).sum())),
            float(np.abs(err).max() / np.abs(want).max()))


def compare(sequences, tie, ref, params, arch, sampler):
    """The limits on `program_trajectory`'s sequences and on its `tie` of the
    served calls to the host's walk -> (ok, note)."""
    B = sampler.block_length
    plan = ref.transfers(sampler) + [0]
    got, want, kv_got, kv_want = [], [], [], []
    differs = pairs = rule_rows = rule_tokens = denoised = 0
    per_sequence = []
    for seq in sequences:
        prompt = seq["prompt"]
        whole = len(prompt) - len(prompt) % B
        committed, step, mine, theirs = [], 0, [], []
        for f in seq["forwards"]:
            prefix = np.concatenate([prompt[:whole], *committed]) \
                if committed else prompt[:whole]
            assert len(prefix) == f["start"]
            tokens = np.concatenate([prefix, f["x"]]).astype(np.int32)
            forced = np.concatenate(
                [seq["sets"][:, :len(prefix)], f["sets"]], axis=1)
            kv = []
            logits, sets = ref.forward(
                params, jnp.asarray(tokens), arch, forced=forced, kv=kv,
                rows=slice(len(prefix), len(prefix) + B),
                pad_to=REFERENCE_PAD)
            logits = np.asarray(logits)
            got.append(f["logits"])
            want.append(logits)
            mine.append(f["logits"])
            theirs.append(logits)
            chose = np.sort(f["sets"], axis=-1)
            differs += int((chose != np.asarray(sets)[:, len(prefix):]
                            ).any(-1).sum())
            pairs += chose.shape[0] * chose.shape[1]
            if f["after"] is None:
                committed.append(f["x"])
                step = 0
                last_kv = kv
            else:
                # what the reference's OWN rule would do on its own logits
                x_ref, m_ref = ref.unmask_rule(logits, f["x"], f["masked"],
                                               plan[min(step, len(plan) - 1)],
                                               sampler)
                x_got, m_got = f["after"]
                denoised += 1
                rule_rows += int((m_ref != m_got).any())
                rule_tokens += int((m_ref == m_got).all()
                                   and (x_ref != x_got).any())
                step += 1
        # the last block's committed keys and values, every layer, and the
        # rows a riding prompt's chunks wrote: the SERVED calls' rows
        first = len(prompt) - len(prompt) % B + (len(committed) - 1) * B
        spans = [(seq["kv"], first, first + B)]
        if "prompt_kv" in seq:
            spans.append((seq["prompt_kv"], 0, whole))
        for layer, (k, v) in enumerate(last_kv):
            for i, ours in enumerate((k, v)):
                for rows, lo, hi in spans:
                    kv_got.append(rows[layer, i])
                    kv_want.append(np.moveaxis(np.asarray(ours)[lo:hi], 0, 1))
        per_sequence.append([len(prompt), len(seq["forwards"]),
                             *(round(e, 5) for e in _errors(
                                 np.stack(mine), np.stack(theirs)))])
    rms, worst = _errors(np.stack(got), np.stack(want))
    kv_rms, kv_worst = _errors(_flat(kv_got), _flat(kv_want))
    mismatch = differs / pairs
    # the served calls against the host's walk of the same calls
    tie_tokens = tie["token_blocks_differ"] / tie["token_blocks"]
    tie_counters = all(served == host for served, host in tie["counters"])
    tie_kv = _errors(_flat(tie["kv_served"]), _flat(tie["kv_host"]))[0] \
        if tie["kv_served"] else float("inf")
    ok = bool(np.isfinite(worst) and rms <= LOGIT_RMS_LIMIT
              and worst <= LOGIT_MAX_LIMIT and kv_rms <= KV_RMS_LIMIT
              and mismatch <= EXPERT_SET_MISMATCH_LIMIT
              and tie_counters and tie_tokens <= TIE_TOKEN_MISMATCH_LIMIT
              and tie_kv <= TIE_KV_RMS_LIMIT)
    return ok, {
        "served_call_token_blocks_differing_share": tie_tokens,
        "served_call_token_blocks": tie["token_blocks"],
        "served_call_token_limit": TIE_TOKEN_MISMATCH_LIMIT,
        "served_call_counters_equal": tie_counters,
        "served_call_counters": tie["counters"],
        "served_call_kv_rms_error_share": tie_kv,
        "served_call_kv_blocks_compared": len(tie["kv_served"]),
        "served_call_kv_rms_limit": TIE_KV_RMS_LIMIT,
        "rms_error_share": rms, "max_error_share": worst,
        "tolerances": [LOGIT_RMS_LIMIT, LOGIT_MAX_LIMIT],
        "kv_rms_error_share": kv_rms, "kv_max_error_share": kv_worst,
        "kv_rms_limit": KV_RMS_LIMIT, "routing": "held equal",
        "expert_set_mismatch_share": mismatch,
        "expert_set_mismatch_limit": EXPERT_SET_MISMATCH_LIMIT,
        "expert_set_pairs": pairs,
        "forwards_compared": len(got), "denoise_forwards_compared": denoised,
        "own_rule_other_rows_share": rule_rows / max(1, denoised),
        "own_rule_other_token_share": rule_tokens / max(1, denoised),
        "per_sequence": per_sequence}


def _check_logits(cell, engine, serving, gcfg, seed, round_to=None):
    """Two calls of the served step programs on the served pool, all slots
    live, two sequences prefilled by riding chunks; each call walked again
    on the host and tied to it; every denoise and commit forward of eight
    sequences against the reference's whole-sequence forward on that
    trajectory with the routing held equal.
    `round_to`: the lower-precision control's (PERF.md; no run uses it)."""
    cfg = cell["config_json"]
    ref = harness.load_module("references", cfg["reference"])
    arch = ref.arch_from_config(cfg, round_to=round_to)
    sampler = ref.sampler_from_config(cfg)
    sequences, ran, tie = program_trajectory(
        engine.model_spec, engine.params, serving, gcfg.vocab_size, seed)
    ok, note = compare(sequences, tie, ref, engine.params, arch, sampler)
    note["forwards_run"] = ran
    note["prompts_checked"] = [len(s["prompt"]) for s in sequences]
    note["served_calls_checked"] = CHECK_CALLS
    return ok, note


def run(cell, seconds, seed, devices, profiler, compiles, t_process):
    # `serve.run` finds its set-up, its warm-up, its request and its check
    # as module globals
    serve._build, serve._check_logits = _build, _check_logits
    serve._warm, serve._request = _warm, _request
    result = serve.run(cell, seconds, seed, devices, profiler, compiles,
                       t_process)
    serving = _built["serving"]
    stats = serving.stats()
    notes = result["notes"]
    notes["kv_pool_writer"] = stats["kv_pool_writer"]
    notes["attention_program"] = stats["attention_program"]
    notes["step_counters"] = stats["step_counters"]
    notes["generator"] = stats["generator"]
    # ... and of the calls READ inside the window, by the step ring's counters
    names = list(serving.step_counter_names)
    inside = [step.counters for step in steprings.steps(result["obs"],
                                                        "serving")
              if step.counters]
    notes["window_counters"] = {
        name: int(sum(c[names.index(name)] for c in inside))
        for name in ("denoise_forwards", "commit_forwards", "rows_unmasked",
                     "blocks_committed")}
    notes["blocks_per_call"] = serving.blocks_per_call
    memory = devices[0].memory_stats() or {}
    notes["memory_stats"] = {
        k: int(memory[k]) for k in ("bytes_in_use", "peak_bytes_in_use",
                                    "bytes_limit") if k in memory}
    return result
