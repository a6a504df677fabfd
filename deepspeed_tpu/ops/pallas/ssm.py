"""The per-slot recurrent state of state-space (Mamba-2) layers, touched in
place (Pallas) — and the chunked form of the recurrence a prefill chunk runs.

    S_t = a_t S_(t-1) + dt_t * x_t (outer) B_t        a_t = exp(dt_t * A_h)
    y_t = S_t C_t                                     (+ D_h x_t, the caller's)

per head h, `S` in R^(P x N) float32, `x_t` in R^P, `B_t`, `C_t` in R^N shared
by the heads of a group. A sequence keeps ONE `S` a layer whatever its
length, so the serving pool holds it per SLOT (`inference/kv_cache.py`:
`CacheKind(state=True)`): `[rows, H, P, N]` with `rows = layers * (1 + slots)`
flat, row 0 of a layer its trash row, CARRIED through the layer loop and
touched only by the calls here — the rule of `ops/pallas/kv_pool.py`: an XLA
gather or scatter on a carried buffer copies it whole.

- `ssm_update` (`dstpu_ssm_update`): a decode token of every row: ONE read
  and ONE write of the state a token a layer, where it lies
  (`input_output_aliases`); its roofline is HBM bandwidth
  (`benchmark/roofline_ssm.py`). What the chip taught (PERF.md, PR 45) is the
  kernel's shape. (1) A 4 MiB row read and written with both copies in
  flight TOGETHER takes 12.8-13.3 us, one after the other 5.6 + 6.4: the
  state stays in HBM (`pl.ANY`) and the kernel copies it itself, a grid
  step's rows (`_rows_per_step`: two at 128 x 64 x 128) read in one burst
  and written in another, never both at once — the step's arithmetic, in
  two halves, hides behind one burst each. (2) A head's `dt x` column
  broadcast along N and its `y = S C` summed along N both cross lanes;
  taking turns a head they stall each other (15.7 us a row against 4.2):
  every broadcast of a row first, then every sum, in loops over blocks of
  sixteen heads (`_heads_per_block`). The small per-row operands arrive
  with P on the sublanes and the heads on the lanes (`[B, P, H]`), a head's
  decay as a scalar (SMEM).
  THE SHELL IS NOT MAMBA-2'S: `stream_rows` (the bursts and the two halves)
  and `streamed_update` (the call: the aliased state, a step's rows, the
  scratch) take the recurrence as a BODY — this file's, and the gated delta
  rule's in `ops/pallas/gdn.py` (`dstpu_gdn_update`), which runs on the same
  state kind.
- `state_read` / `state_write` (`dstpu_ssm_state_read|write`): a few rows
  copied out of, or into, a carried buffer by index — what a prefill chunk
  does with its slot's state and what both groups do with the convolution's
  tail.
- `ssm_chunk_scan`: the same recurrence over a whole chunk in its chunked
  (matmul) form, plain `jax.numpy` under the caller's `ssm/scan` scope.
  `dt = 0` at a position leaves the state alone there (decay 1, no input): a
  chunk's padded tail.

Off the TPU every entry runs its `jax.numpy` twin (`*_reference`), which is
also the kernels' test oracle (`interpret=True` forces the interpreter). On
a TPU there is no twin: a state the update kernel does not address raises.
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.platform.device import pallas_interpret

KERNEL_NAME = "dstpu_ssm_update"
# `state_read` / `state_write`: a row in and out, double-buffered, 4 x 4 MiB
# at the published widths, past Mosaic's default scoped limit
_VMEM_LIMIT = 48 * 1024 * 1024
# `ssm_update`: a step's rows (`_BURST_BYTES` of state at most) twice in VMEM,
# and `_VMEM_SMALL` for the small operands' blocks and Mosaic's own scratch;
# a loop iteration unrolls `_BLOCK_HEADS` heads
_BURST_BYTES = 8 * 1024 * 1024
_BLOCK_HEADS = 16
_VMEM_SMALL = 8 * 1024 * 1024


def state_in_place_supported(state) -> bool:
    """Shapes the kernels address: float32 `[rows, H, P, N]` whose `(P, N)`
    face is whole native tiles."""
    return (state.ndim == 4 and state.dtype == jnp.float32
            and state.shape[2] % 8 == 0 and state.shape[3] % 128 == 0)


def _mode(interpret, state=None):
    """(run the kernel?, in the interpreter?): `interpret` given forces the
    kernel; else the kernel on a TPU and the `jax.numpy` twin everywhere
    else. On a TPU a `state` the update kernel does not address is refused:
    the twin there is a scatter that copies the whole carried state a
    token."""
    if interpret is not None:
        return True, bool(interpret)
    if pallas_interpret():
        return False, False
    if state is not None and not state_in_place_supported(state):
        raise ValueError(
            f"the in-place update (dstpu_ssm_update, dstpu_gdn_update) "
            f"addresses float32 [rows, H, P, N] state whose (P, N) face is "
            f"whole (8, 128) tiles, not {state.dtype}{list(state.shape)}: on "
            f"a TPU there is no other in-place path")
    return True, False


# ----------------------------------------------------------------------
# the decode token
# ----------------------------------------------------------------------


def state_token_bytes(state) -> int:
    """What ONE decode token of one slot moves of a state kind's state
    proper `[layers, rows, ...]`, all layers: its row of each, read and
    written."""
    return 2 * int(state.nbytes // state.shape[1])


def ssm_update_reference(state, rows, a, dtx, B, C):
    """The oracle and the off-TPU path, in `ssm_update`'s terms."""
    H = state.shape[1]
    per = H // B.shape[1]
    Bh = jnp.repeat(B.astype(jnp.float32), per, axis=1)        # [b, H, N]
    Ch = jnp.repeat(C.astype(jnp.float32), per, axis=1)
    new = a[:, :, None, None] * state[rows] \
        + dtx[:, :, :, None] * Bh[:, :, None, :]
    y = jnp.sum(new * Ch[:, :, None, :], axis=-1)
    return y, state.at[rows].set(new.astype(state.dtype))


def _rows_per_step(b, row_bytes):
    """Rows a grid step owns: the most that divide b within `_BURST_BYTES`
    of state."""
    most = max(1, _BURST_BYTES // row_bytes)
    return max(k for k in range(1, min(b, most) + 1) if b % k == 0)


def _heads_per_block(heads, groups):
    """Heads a loop iteration unrolls: `_BLOCK_HEADS` where the heads are
    whole lane tiles (a block's `dt x` columns come to the first lanes by one
    dynamic rotate) and a block is whole groups or a part of one; else all."""
    per = heads // groups
    hb = _BLOCK_HEADS
    if heads % 128 or heads % hb or (hb % per and per % hb):
        return heads
    return hb


def stream_rows(rows_ref, s_hbm, out_hbm, buf, read_sem, write_sem, update,
                blocks):
    """THE SHELL of an in-place decode update, whatever the recurrence: the
    state stays in HBM, a grid step's K rows (`buf`: `[2, K, ...]`) come in
    one burst and leave in another, never both at once, and the step's
    arithmetic, in two halves, hides behind one burst each. `update(tile, r,
    lo, hi)`: the body — blocks lo..hi (of `blocks`, the body's own unit) of
    the step's row r, where it lies (`tile`, the row in VMEM)."""
    step, steps = pl.program_id(0), pl.num_programs(0)
    K = buf.shape[1]
    slot = step % 2

    def reads(at, slot):
        """The copies of step `at`'s rows into half `slot` of the buffer."""
        return [pltpu.make_async_copy(s_hbm.at[rows_ref[at * K + r]],
                                      buf.at[slot, r], read_sem.at[slot])
                for r in range(K)]

    def writes(at, slot):
        return [pltpu.make_async_copy(buf.at[slot, r],
                                      out_hbm.at[rows_ref[at * K + r]],
                                      write_sem.at[slot])
                for r in range(K)]

    def half(k):
        """The step's work in two halves (k = 0, 1), one for each stream to
        hide behind: its rows' halves, or the halves of its one row's
        blocks."""
        if K > 1:
            lo, hi = (0, K // 2, K)[k:k + 2]
            jax.lax.fori_loop(
                lo, hi,
                lambda r, _: update(buf.at[slot, r], r, 0, blocks), None)
        else:
            update(buf.at[slot, 0], 0, *(0, blocks // 2, blocks)[k:k + 2])

    @pl.when(step == 0)
    def _():
        for copy in reads(0, 0):
            copy.start()

    for copy in reads(step, slot):
        copy.wait()

    @pl.when(step > 0)
    def _():
        for copy in writes(step - 1, 1 - slot):
            copy.start()

    half(0)

    @pl.when(step > 0)
    def _():
        for copy in writes(step - 1, 1 - slot):
            copy.wait()

    @pl.when(step + 1 < steps)
    def _():
        for copy in reads(step + 1, 1 - slot):
            copy.start()

    half(1)

    @pl.when(step + 1 == steps)
    def _():
        for copy in writes(step, slot):
            copy.start()
        for copy in writes(step, slot):
            copy.wait()


def streamed_update(kernel, name, state, rows, operands, result, interpret):
    """`kernel` on the shell: `kernel(rows_ref, *operand refs, s_hbm,
    result_ref, out_hbm, buf, read_sem, write_sem)`, a grid step the K rows
    `_rows_per_step` gives it. `operands`: `[(array [b, ...], scalars?)]`, a
    row's small inputs, blocked K rows a step — in SMEM where `scalars`;
    `result`: the shape a row of the small float32 result. Returns (result
    `[b, ...]`, the state, updated where it lay)."""
    b = rows.shape[0]
    row_bytes = math.prod(state.shape[1:]) * state.dtype.itemsize
    K = _rows_per_step(b, row_bytes)

    def rows_of(tail, scalars=False):
        at = lambda i, rows_ref: (i,) + (0,) * len(tail)
        return pl.BlockSpec((K,) + tuple(tail), at,
                            **(dict(memory_space=pltpu.SMEM) if scalars
                               else {}))

    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b // K,),
            in_specs=[rows_of(a.shape[1:], scalars)
                      for a, scalars in operands] + [in_hbm],
            out_specs=[rows_of(result), in_hbm],
            scratch_shapes=[pltpu.VMEM((2, K) + state.shape[1:], state.dtype),
                            pltpu.SemaphoreType.DMA((2,)),
                            pltpu.SemaphoreType.DMA((2,))]),
        out_shape=[jax.ShapeDtypeStruct((b,) + tuple(result), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operands: rows, the small ones, state
        input_output_aliases={1 + len(operands): 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=2 * K * row_bytes + _VMEM_SMALL),
        interpret=interpret,
        name=name,
    )(rows, *(a for a, _ in operands), state)


def _update_kernel(rows_ref, a_ref, dtx_ref, b_ref, c_ref, s_hbm,
                   y_ref, out_hbm, buf, read_sem, write_sem, *, groups):
    H, P = buf.shape[2:4]
    per = H // groups
    hb = _heads_per_block(H, groups)
    lane = jax.lax.broadcasted_iota(jnp.int32, (P, H), 1)

    def heads_of(j):
        """(first head, group of its head hh) of block j."""
        h0 = j * hb
        if hb >= per:
            return h0, lambda hh: h0 // per + hh // per
        return h0, lambda hh: h0 // per

    def update(tile, r, lo, hi):
        """Blocks lo..hi of the step's row r, where they lie: EVERY lane
        broadcast (a head's `dt x` column along N), then EVERY lane sum
        (`y`). Taking turns a head, the two kinds stall each other in the
        cross-lane units: 15.7 us a row against 4.2 (PERF.md, PR 45)."""

        def spread(j, _):
            h0, group = heads_of(j)
            dtx = dtx_ref[r]
            if H > hb:                  # the block's columns to lanes 0..hb-1
                dtx = pltpu.roll(dtx, (H - h0) % H, 1)
            for hh in range(hb):
                tile[h0 + hh] = a_ref[r, 0, h0 + hh] * tile[h0 + hh] \
                    + dtx[:, hh:hh + 1] * b_ref[r, pl.ds(group(hh), 1), :]
            return 0

        def gather(j, y):
            h0, group = heads_of(j)
            for hh in range(hb):
                col = jnp.sum(
                    tile[h0 + hh] * c_ref[r, pl.ds(group(hh), 1), :],
                    axis=-1, keepdims=True)
                y = jnp.where(lane == h0 + hh, col, y)
            return y

        jax.lax.fori_loop(lo, hi, spread, 0)
        y_ref[r] = jax.lax.fori_loop(
            lo, hi, gather,
            y_ref[r] if lo else jnp.zeros((P, H), jnp.float32))

    stream_rows(rows_ref, s_hbm, out_hbm, buf, read_sem, write_sem, update,
                H // hb)


def ssm_update(state, rows, a, dtx, B, C, interpret=None):
    """One token of the recurrence for b rows, the state updated IN PLACE.

    state: `[M, H, P, N]` float32 (one layer's, or every layer's flat);
    rows: `[b]` int32, row i's state is `state[rows[i]]` (rows that share an
    index — dead slots at a trash row — leave garbage there); a: `[b, H]`
    float32 decay `exp(dt * A)`; dtx: `[b, H, P]` float32 `dt * x`; B, C:
    `[b, G, N]`, head h reads group `h // (H / G)`. Returns (y `[b, H, P]`
    float32 `= S_new C`, state)."""
    use, interpret = _mode(interpret, state)
    rows = rows.astype(jnp.int32)
    a, dtx = a.astype(jnp.float32), dtx.astype(jnp.float32)
    if not use:
        return ssm_update_reference(state, rows, a, dtx, B, C)
    H, P = state.shape[1:3]
    # P on the sublanes, a head a lane: a head's column broadcasts along N;
    # a head's decay is a scalar
    y, state = streamed_update(
        functools.partial(_update_kernel, groups=B.shape[1]), KERNEL_NAME,
        state, rows,
        [(a[:, None, :], True), (jnp.swapaxes(dtx, 1, 2), False),
         (B.astype(jnp.float32), False), (C.astype(jnp.float32), False)],
        (P, H), interpret)
    return jnp.swapaxes(y, 1, 2), state


# ----------------------------------------------------------------------
# rows of a carried buffer, by index
# ----------------------------------------------------------------------


def _copy_kernel(rows_ref, src_ref, out_ref):
    del rows_ref
    out_ref[...] = src_ref[...]


def _write_kernel(rows_ref, new_ref, buf_ref, out_ref):
    del rows_ref, buf_ref
    out_ref[...] = new_ref[...]


def _row_block(buf):
    return (1,) + buf.shape[1:], (0,) * (buf.ndim - 1)


def state_read(buf, rows, interpret=None):
    """`buf[rows]` (`[b, ...]`) of a carried `[M, ...]` buffer, the named
    rows the only part of it that is touched."""
    use, interpret = _mode(interpret)
    if not use:
        return buf[rows]
    block, rest = _row_block(buf)
    return pl.pallas_call(
        _copy_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(rows.shape[0],),
            in_specs=[pl.BlockSpec(block, lambda i, r: (r[i],) + rest)],
            out_specs=pl.BlockSpec(block, lambda i, r: (i,) + rest)),
        out_shape=jax.ShapeDtypeStruct(rows.shape + buf.shape[1:], buf.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="dstpu_ssm_state_read",
    )(rows.astype(jnp.int32), buf)


def state_write(buf, rows, new, interpret=None):
    """`buf.at[rows].set(new)` IN PLACE on a carried `[M, ...]` buffer (rows
    that share an index: one of them wins)."""
    use, interpret = _mode(interpret)
    if not use:
        return buf.at[rows].set(new.astype(buf.dtype))
    block, rest = _row_block(buf)
    return pl.pallas_call(
        _write_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(rows.shape[0],),
            in_specs=[pl.BlockSpec(block, lambda i, r: (i,) + rest),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(block, lambda i, r: (r[i],) + rest)),
        out_shape=jax.ShapeDtypeStruct(buf.shape, buf.dtype),
        # operands: rows, new, buf
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="dstpu_ssm_state_write",
    )(rows.astype(jnp.int32), new.astype(buf.dtype), buf)


# ----------------------------------------------------------------------
# a whole chunk
# ----------------------------------------------------------------------


def ssm_scan_reference(x, dt, A, B, C, state):
    """The recurrence a position at a time (`lax.scan`), float32: the
    chunked form's oracle. Shapes as `ssm_chunk_scan`."""
    per = x.shape[2] // B.shape[2]
    f32 = lambda v: v.astype(jnp.float32)

    def step(S, inputs):
        x_t, dt_t, B_t, C_t = inputs
        Bh, Ch = (jnp.repeat(v, per, axis=1) for v in (B_t, C_t))
        S = jnp.exp(dt_t * A)[:, :, None, None] * S \
            + (dt_t[:, :, None] * x_t)[..., None] * Bh[:, :, None, :]
        return S, jnp.sum(S * Ch[:, :, None, :], axis=-1)

    state, y = jax.lax.scan(
        step, f32(state),
        tuple(jnp.moveaxis(f32(v), 1, 0) for v in (x, dt, B, C)))
    return jnp.moveaxis(y, 0, 1), state


def ssm_chunk_scan(x, dt, A, B, C, state, chunk):
    """The recurrence over T positions from a carried state, in its chunked
    form: within a chunk of `chunk` positions every pair (i >= j) at once as
    masked matrix products, between chunks one state a chunk.

    x: `[b, T, H, P]`; dt: `[b, T, H]` float32, 0 where a position must leave
    the state alone; A: `[H]` float32 (negative); B, C: `[b, T, G, N]`;
    state: `[b, H, P, N]` float32. Returns (y `[b, T, H, P]` float32, the
    state after position T - 1). T need not be a multiple of `chunk`.
    Products take their inputs in `x.dtype` and accumulate in float32; the
    decays are float32."""
    b, T, H, P = x.shape
    G, N = B.shape[2:]
    per = H // G
    pad = -T % chunk
    if pad:
        x, dt, B, C = (jnp.pad(v, [(0, 0), (0, pad)] + [(0, 0)] * (v.ndim - 2))
                       for v in (x, dt, B, C))
    c, Q = (T + pad) // chunk, chunk
    dtype = x.dtype
    dot = functools.partial(jnp.einsum, preferred_element_type=jnp.float32)
    dt = dt.astype(jnp.float32).reshape(b, c, Q, G, per)
    x = x.reshape(b, c, Q, G, per, P)
    B, C = (v.astype(dtype).reshape(b, c, Q, G, N) for v in (B, C))
    cs = jnp.cumsum(dt * A.reshape(G, per), axis=2)         # inclusive
    dtx = (dt[..., None] * x.astype(jnp.float32)).astype(dtype)
    # inside a chunk: position j reaches i >= j decayed by exp(cs_i - cs_j)
    i_ge_j = jnp.tril(jnp.ones((Q, Q), bool))[:, :, None, None]
    decay = jnp.exp(jnp.where(i_ge_j, cs[:, :, :, None] - cs[:, :, None],
                              -jnp.inf))                    # [b,c,i,j,G,per]
    scores = (decay * dot("bcign,bcjgn->bcijg", C, B)[..., None]).astype(dtype)
    y = dot("bcijgh,bcjghp->bcighp", scores, dtx)
    # what a chunk adds to the state, decayed to the chunk's end
    last = cs[:, :, -1]                                     # [b, c, G, per]
    to_end = jnp.exp(last[:, :, None] - cs)[..., None]
    added = dot("bcjgn,bcjghp->bcghpn", B,
                (dtx.astype(jnp.float32) * to_end).astype(dtype))

    def carry(S, inputs):
        keep, add = inputs
        return keep[..., None, None] * S + add, S

    state, before = jax.lax.scan(
        carry, state.astype(jnp.float32).reshape(b, G, per, P, N),
        (jnp.moveaxis(jnp.exp(last), 1, 0), jnp.moveaxis(added, 1, 0)))
    before = jnp.moveaxis(before, 0, 1)                     # [b,c,G,per,P,N]
    y = y + jnp.exp(cs)[..., None] * dot(
        "bcign,bcghpn->bcighp", C, before.astype(dtype))
    return (y.reshape(b, c * Q, H, P)[:, :T],
            state.reshape(b, H, P, N))
