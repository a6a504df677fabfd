"""The gated delta rule (Gated DeltaNet's recurrence) on the per-slot state
kind — the second body of `ops/pallas/ssm.py`'s in-place shell, and the
chunked form a prefill chunk and a training step run.

    S_t = a_t S_(t-1) + k_t (outer) u_t      u_t = beta_t (v_t - a_t S_(t-1)^T k_t)
    o_t = S_t^T q_t                           a_t = exp(g_t), g_t <= 0

per value head h, `S` in R^(K x V) float32 (key x value), `q_t`, `k_t` in R^K
shared by the value heads of a key head (head h reads key head `h // (H /
G)`), `v_t` in R^V. Where Mamba-2's write is its input alone (`dt x (outer)
B`), this one CONTRACTS the decayed state with the key before it can write:
what the state already holds for `k_t` is taken out of `v_t` first.

- `gdn_update` (`dstpu_gdn_update`): a decode token of every row on
  `ssm.stream_rows`, the shell PR 45's measurements shaped (the state in HBM,
  a step's rows in one burst and out in another, the arithmetic in two
  halves behind them). The body, from the OLD state in one pass over a
  head's K x V tile: `r = a S^T k`, `p = a S^T q`, `u = beta (v - r)`,
  `o = p + (k . q) u`, `S <- a S + k (outer) u` — two sums over the key axis
  (the sublanes: no lane crosses), two columns broadcast along the lanes
  (`k`, `q`), seven operations a state element, one read and one write of
  the state a token a layer. A head's `a`, `beta` and `k . q` are scalars
  (SMEM); `k` and `q` arrive with K on the sublanes and a key head a lane.
- `gdn_chunk_scan`: the same recurrence over a whole chunk in its chunked
  (matmul) form, plain `jax.numpy` under the caller's `gdn/scan` scope:
  inside a chunk of Q positions, with `gamma_i = sum_(j<=i) g_j` and
  `Gamma_ij = exp(gamma_i - gamma_j)`, the strictly-lower system `T = (I +
  strict_lower((beta k) k^T * Gamma))^-1` (by the block rule from blocks of
  one position up, `_inverse_of_unit_lower`), then matmuls against the
  carried state. `g = 0` AND `beta = 0` at a position leave the state alone
  there (decay 1, nothing written): a chunk's padded tail. The scan has a
  BACKWARD of its own under `jax.custom_vjp` (`_chunk_scan_backward`, PR
  56): a call keeps its inputs, makes a chunk's system and the chunks'
  starting states again, and runs the chunks in reverse with the state's
  cotangent carried — what a training step differentiates
  (`models/hybrid.py::hybrid_loss`), whose blocks may HOLD the scan's
  output under the name `SCAN_OUTPUT` and then never run it a second time.
  `gdn_update`, the decode token's kernel, has no gradient: nothing trains
  through a cache.

Off the TPU `gdn_update` runs its `jax.numpy` twin (`gdn_update_reference`),
which is also the kernel's test oracle; `gdn_scan_reference` (a position at
a time) is the chunked form's.
"""

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl

from deepspeed_tpu.ops.pallas import ssm

KERNEL_NAME = "dstpu_gdn_update"
# the triangular solve's float32 products (`_inverse_of_unit_lower`): three
# bfloat16 passes. Six (HIGHEST) read the same errors against the float32
# reference to three digits and cost 2.2% of Qwen3-Next's cell (PERF.md, PR 47)
_SOLVE_PRECISION = jax.lax.Precision.HIGH
_solve_dot = functools.partial(jnp.matmul, precision=_SOLVE_PRECISION)


def _by_head(x, heads):
    """`[.., G, n]` of the key heads -> `[.., H, n]`, a value head's own."""
    return jnp.repeat(x, heads // x.shape[-2], axis=-2)


def gdn_update_reference(state, rows, a, beta, q, k, v):
    """The oracle and the off-TPU path, in `gdn_update`'s terms."""
    H = state.shape[1]
    f32 = lambda x: x.astype(jnp.float32)
    qh, kh = _by_head(f32(q), H), _by_head(f32(k), H)          # [b, H, K]
    old = a[:, :, None, None] * state[rows]
    u = beta[..., None] * (f32(v) - jnp.einsum("bhkv,bhk->bhv", old, kh))
    new = old + kh[..., None] * u[:, :, None, :]
    return (jnp.einsum("bhkv,bhk->bhv", new, qh),
            state.at[rows].set(new.astype(state.dtype)))


def _update_kernel(rows_ref, s_ref, k_ref, q_ref, v_ref, s_hbm, o_ref,
                   out_hbm, buf, read_sem, write_sem, *, groups):
    H = buf.shape[2]
    per = H // groups

    def update(tile, r, lo, hi):
        """Heads lo..hi of the step's row r, where they lie, each from its
        OLD state in one pass."""
        k, q = k_ref[r], q_ref[r]                   # [K, G]: a key head a lane
        for h in range(lo, hi):
            g = h // per
            kb, qb = k[:, g:g + 1], q[:, g:g + 1]   # columns, along the lanes
            a, beta, kq = (s_ref[r, i, h] for i in range(3))
            S = tile[h]
            held = a * jnp.sum(S * kb, axis=0, keepdims=True)
            u = beta * (v_ref[r, pl.ds(h, 1), :] - held)
            o_ref[r, pl.ds(h, 1), :] = \
                a * jnp.sum(S * qb, axis=0, keepdims=True) + kq * u
            tile[h] = a * S + kb * u

    ssm.stream_rows(rows_ref, s_hbm, out_hbm, buf, read_sem, write_sem,
                    update, H)


def gdn_update(state, rows, a, beta, q, k, v, interpret=None):
    """One token of the gated delta rule for b rows, the state updated IN
    PLACE.

    state: `[M, H, K, V]` float32 (one layer's, or every layer's flat);
    rows: `[b]` int32, row i's state is `state[rows[i]]` (rows that share an
    index — dead slots at a trash row — leave garbage there); a: `[b, H]`
    float32 decay `exp(g)`; beta: `[b, H]` float32; q, k: `[b, G, K]`, value
    head h reads key head `h // (H / G)`; v: `[b, H, V]`. Returns (o
    `[b, H, V]` float32 `= S_new^T q`, state)."""
    use, interpret = ssm._mode(interpret, state)
    rows = rows.astype(jnp.int32)
    f32 = lambda x: x.astype(jnp.float32)
    a, beta, q, k, v = f32(a), f32(beta), f32(q), f32(k), f32(v)
    if not use:
        return gdn_update_reference(state, rows, a, beta, q, k, v)
    H, _, V = state.shape[1:]
    kq = _by_head(jnp.sum(k * q, axis=-1)[..., None], H)[..., 0]
    return ssm.streamed_update(
        functools.partial(_update_kernel, groups=q.shape[1]), KERNEL_NAME,
        state, rows,
        # a head's three scalars; K on the sublanes, a key head a lane
        [(jnp.stack([a, beta, kq], axis=1), True),
         (jnp.swapaxes(k, 1, 2), False), (jnp.swapaxes(q, 1, 2), False),
         (v, False)],
        (H, V), interpret)


# ----------------------------------------------------------------------
# a whole chunk
# ----------------------------------------------------------------------


def gdn_scan_reference(q, k, v, g, beta, state):
    """The recurrence a position at a time (`lax.scan`), float32: the
    chunked form's oracle. Shapes as `gdn_chunk_scan`."""
    H = v.shape[2]
    f32 = lambda x: x.astype(jnp.float32)

    def step(S, inputs):
        q_t, k_t, v_t, g_t, beta_t = inputs
        q_t, k_t = _by_head(q_t, H), _by_head(k_t, H)
        S = jnp.exp(g_t)[..., None, None] * S
        u = beta_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", S, k_t))
        S = S + k_t[..., None] * u[:, :, None, :]
        return S, jnp.einsum("bhkv,bhk->bhv", S, q_t)

    state, o = jax.lax.scan(
        step, f32(state),
        tuple(jnp.moveaxis(f32(x), 1, 0) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), state


def _inverse_of_unit_lower(L):
    """`(I + L)^-1` of strictly lower triangular L `[..., Q, Q]` float32, by
    the block rule `[[A, 0], [C, B]]^-1 = [[A^-1, 0], [-B^-1 C A^-1, B^-1]]`
    from blocks of one position up: with X the inverse of the diagonal
    blocks of s positions and L_s the part of L inside the blocks of 2 s but
    outside those of s, `X <- X - X L_s X` (ten products at Q = 64: at s =
    1, X is I and the step is `I - L_1`).
    Every factor is an inverse of a sub-block, as bounded as the whole. The
    series `(I - L)(I + L^2)(I + L^4) ...`, which served until PR 56 in ten
    products, is not: a power L^n has entries the size of `|L|^n C(Q, n)`
    that must cancel to the inverse's bounded ones, and float32 loses them
    once beta k_i . k_j passes ~0.2 over a chunk of 64 (keys that resemble
    each other, as trained ones do, or beta up to 2): it returned noise
    there. Float32 products above the default's one bfloat16 pass: T's
    entries cancel, and a chunk's every later product reads them."""
    # L is HELD: six masked parts read it, and left alone XLA makes its
    # producer (the decays' exponentials among them) again inside each
    # product's fusion at a prefill chunk's size: 25% of the whole scan's
    # estimated cycles (compiled for a v5e, PERF.md section 6, PR 56)
    L = jax.lax.optimization_barrier(L)
    Q = L.shape[-1]
    at = jnp.arange(Q)
    together = lambda size: at[:, None] // size == at[None, :] // size
    between = lambda size: jnp.where(
        together(2 * size) & ~together(size), L, 0.0)
    X = jnp.eye(Q, dtype=L.dtype) - between(1)
    size = 2
    while size < Q:
        X = X - _solve_dot(_solve_dot(X, between(size)), X)
        size *= 2
    return X


def _chunks(q, k, v, g, beta, chunk):
    """The inputs a chunk at a time, heads as (key head, value head of it) so
    that q and k are never repeated: q, k `[b, c, Q, G, K]` in `v.dtype`, v
    `[b, c, Q, G, per, V]`, g, beta `[b, c, Q, G, per]` float32; a ragged tail
    padded with positions that leave the state alone."""
    b, T, H, V = v.shape
    G, K = k.shape[2:]
    per = H // G
    pad = -T % chunk
    if pad:
        q, k, v, g, beta = (
            jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
            for x in (q, k, v, g, beta))
    c, Q = (T + pad) // chunk, chunk
    f32 = lambda x: x.astype(jnp.float32)
    q, k = (x.astype(v.dtype).reshape(b, c, Q, G, K) for x in (q, k))
    v = v.reshape(b, c, Q, G, per, V)
    g, beta = (f32(x).reshape(b, c, Q, G, per) for x in (g, beta))
    return q, k, v, g, beta


_dot = functools.partial(jnp.einsum, preferred_element_type=jnp.float32)
_f32 = lambda x: x.astype(jnp.float32)
_heads_first = lambda x: jnp.moveaxis(x, (2, 3), (-2, -1))  # [b,c,G,per,i,j]


def _decays(g):
    """(gamma, the inclusive sum of a chunk's log-decays; Gamma `[b, c, i, j,
    G, per]`: position j reaches i >= j decayed by exp(gamma_i - gamma_j))."""
    Q = g.shape[2]
    gamma = jnp.cumsum(g, axis=2)                           # inclusive
    i_ge_j = jnp.tril(jnp.ones((Q, Q), bool))[:, :, None, None]
    return gamma, jnp.exp(jnp.where(
        i_ge_j, gamma[:, :, :, None] - gamma[:, :, None], -jnp.inf))


def _system(k, beta, Gamma):
    """The chunk's strictly-lower system L `[b, c, G, per, i, j]` float32."""
    Q = k.shape[2]
    kk = _dot("bcign,bcjgn->bcijg", k, k)[..., None]
    return _heads_first(jnp.where(jnp.tril(jnp.ones((Q, Q), bool), -1)
                                  [:, :, None, None],
                                  beta[:, :, :, None] * kk * Gamma, 0.0))


def _writes(k, v, beta, gamma):
    """What each position writes, before the carried state is taken out:
    (beta v, beta exp(gamma) k) in `v.dtype`."""
    bv = (beta[..., None] * _f32(v)).astype(v.dtype)
    bk = (beta * jnp.exp(gamma))[..., None] * _f32(k)[:, :, :, :, None]
    return bv, bk.astype(v.dtype)


def _solved(T_inv, bv, bk):
    """... through the chunk's triangular system: (v_in `[b, c, G, per, i,
    V]`, k_in `[.., i, K]`) float32."""
    return (_dot("bcghij,bcjghp->bcghip", T_inv, bv),
            _dot("bcghij,bcjghn->bcghin", T_inv, bk))


def _reads(q, k, gamma, Gamma):
    """What reads the state inside the chunk, and what the chunk hands on:
    (reads `[b, c, G, per, i, j]`, q_in, k_out `[b, c, Q, G, per, K]`, in
    `q.dtype`; the chunk's whole log-decay `[b, c, G, per]` float32)."""
    dtype = q.dtype
    reads = _heads_first(_dot("bcign,bcjgn->bcijg", q, k)[..., None]
                         * Gamma).astype(dtype)
    q_in = (jnp.exp(gamma)[..., None]
            * _f32(q)[:, :, :, :, None]).astype(dtype)
    last = gamma[:, :, -1]                                  # [b, c, G, per]
    k_out = (jnp.exp(last[:, :, None] - gamma)[..., None]
             * _f32(k)[:, :, :, :, None]).astype(dtype)
    return reads, q_in, k_out, last


def _by_chunk(xs):
    return tuple(jnp.moveaxis(x, 1, 0) for x in xs)


def _chunk_scan(q, k, v, g, beta, state, chunk):
    """`gdn_chunk_scan`, the function itself."""
    b, T, H, V = v.shape
    G, K = k.shape[2:]
    dtype = v.dtype
    q, k, v, g, beta = _chunks(q, k, v, g, beta, chunk)
    gamma, Gamma = _decays(g)
    T_inv = _inverse_of_unit_lower(_system(k, beta, Gamma)).astype(dtype)
    v_in, k_in = _solved(T_inv, *_writes(k, v, beta, gamma))
    reads, q_in, k_out, last = _reads(q, k, gamma, Gamma)

    def carry(S, inputs):
        v_in, k_in, reads, q_in, k_out, keep = inputs
        held = S.astype(dtype)
        new = v_in - _dot("bghin,bghnp->bghip", k_in.astype(dtype), held)
        o = _dot("bighn,bghnp->bghip", q_in, held) \
            + _dot("bghij,bghjp->bghip", reads, new.astype(dtype))
        S = keep[..., None, None] * S \
            + _dot("bjghn,bghjp->bghnp", k_out, new.astype(dtype))
        return S, o

    state, o = jax.lax.scan(
        carry, _f32(state).reshape(b, G, H // G, K, V),
        _by_chunk((v_in, k_in, reads, q_in, k_out, jnp.exp(last))))
    # [c, b, G, per, Q, V] -> [b, T, H, V]
    o = jnp.moveaxis(o, (0, 4), (1, 2)).reshape(b, -1, H, V)
    return o[:, :T], state.reshape(b, H, K, V)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def gdn_chunk_scan(q, k, v, g, beta, state, chunk):
    """The recurrence over T positions from a carried state, in its chunked
    form: within a chunk of `chunk` positions the delta rule's triangular
    system solved at once, between chunks one state a chunk.

    q, k: `[b, T, G, K]` (the caller's normalisation and scale applied);
    v: `[b, T, H, V]`; g: `[b, T, H]` float32 log-decay (<= 0), beta:
    `[b, T, H]` float32 — BOTH 0 where a position must leave the state
    alone; state: `[b, H, K, V]` float32. Returns (o `[b, T, H, V]` float32,
    the state after position T - 1). T need not be a multiple of `chunk`.
    Products take their inputs in `v.dtype` and accumulate in float32; the
    decays and the triangular solve are float32 (`_SOLVE_PRECISION`).

    Differentiable in everything but `chunk`, by a backward of
    its own (`_chunk_scan_backward`): a call keeps its INPUTS for it and
    nothing it made of them, so a long sequence is best run a segment of
    chunks a call on the carried state (`models/hybrid.py::_in_segments`).
    Where it is differentiated `o` carries the name `SCAN_OUTPUT`
    (`jax.ad_checkpoint.checkpoint_name`, in the forward rule): a
    `jax.checkpoint` that holds the name runs the scan once forward and
    never again, its backward reading the held `o` and the inputs made
    again. A call nobody differentiates lowers as it always has."""
    return _chunk_scan(q, k, v, g, beta, state, chunk)


# The name of the scan's `o` where it is differentiated: the rule keeps its
# inputs alone, so under a `jax.checkpoint` whose policy holds this name
# nothing of the forward scan has a reader when the block is made again, and
# the backward runs `_chunk_scan_backward` without it
# (`models/hybrid.py::held_candidates`). Float32, the width it is computed at.
SCAN_OUTPUT = "gdn_scan_output"


def _chunk_scan_forward(q, k, v, g, beta, state, chunk):
    o, after = _chunk_scan(q, k, v, g, beta, state, chunk)
    return (checkpoint_name(o, SCAN_OUTPUT), after), (q, k, v, g, beta, state)


def _chunk_scan_backward(chunk, kept, cotangents):
    """(dq, dk, dv, dg, dbeta, dstate) from (do, dstate after). The forward
    keeps its inputs alone; everything a chunk's system is made of — the
    decays, L, T = (I + L)^-1, what the positions write and read
    — is made AGAIN here, and the carried state at each chunk's start by
    running the state's recurrence forward once more (two of the forward's
    four products a chunk), so no `[chunks, heads, Q, Q]` or `[chunks,
    heads, K, V]` array outlives the forward. The chunks then run in REVERSE
    with the state's cotangent carried; what is elementwise or a product
    over one chunk is transposed by `jax.vjp` of the forward's own pieces,
    and the inverse by its identity, `dL = -T^T dT T^T`, not through the
    products that made it."""
    q, k, v, g, beta, state = kept
    do, dafter = cotangents
    b, T, H, V = v.shape
    G, K = k.shape[2:]
    dtype = v.dtype

    def system(q, k, v, g, beta):
        q, k, v, g, beta = _chunks(q, k, v, g, beta, chunk)
        gamma, Gamma = _decays(g)
        reads, q_in, k_out, last = _reads(q, k, gamma, Gamma)
        return (_system(k, beta, Gamma), *_writes(k, v, beta, gamma),
                reads, q_in, k_out, jnp.exp(last))

    (L, bv, bk, reads, q_in, k_out, keep), system_vjp = jax.vjp(
        system, q, k, v, g, beta)
    T_inv = _inverse_of_unit_lower(L)
    (v_in, k_in), solved_vjp = jax.vjp(_solved, T_inv.astype(dtype), bv, bk)
    by_chunk = _by_chunk((v_in, k_in, reads, q_in, k_out, keep))

    def start(S, inputs):           # the state alone, forward: S at a start
        v_in, k_in, _, _, k_out, keep = inputs
        new = v_in - _dot("bghin,bghnp->bghip", k_in.astype(dtype),
                          S.astype(dtype))
        return keep[..., None, None] * S + _dot(
            "bjghn,bghjp->bghnp", k_out, new.astype(dtype)), S

    _, starts = jax.lax.scan(
        start, _f32(state).reshape(b, G, H // G, K, V), by_chunk)
    c = starts.shape[0]
    do = jnp.pad(do, [(0, 0), (0, c * chunk - T), (0, 0), (0, 0)])
    do = jnp.moveaxis(do.reshape(b, c, chunk, G, H // G, V), (1, 2), (0, 4))

    def carry(dS, inputs):
        v_in, k_in, reads, q_in, k_out, keep, S, do = inputs
        held, k_in, do = S.astype(dtype), k_in.astype(dtype), do.astype(dtype)
        new = (v_in - _dot("bghin,bghnp->bghip", k_in, held)).astype(dtype)
        after = dS.astype(dtype)
        dnew = _dot("bghij,bghip->bghjp", reads, do) \
            + _dot("bjghn,bghnp->bghjp", k_out, after)
        write = dnew.astype(dtype)
        grads = (dnew, -_dot("bghip,bghnp->bghin", write, held),
                 _dot("bghip,bghjp->bghij", do, new).astype(dtype),
                 _dot("bghip,bghnp->bighn", do, held).astype(dtype),
                 _dot("bghjp,bghnp->bjghn", new, after).astype(dtype),
                 jnp.sum(dS * S, axis=(-2, -1)))
        dS = keep[..., None, None] * dS \
            + _dot("bighn,bghip->bghnp", q_in, do) \
            - _dot("bghin,bghip->bghnp", k_in, write)
        return dS, grads

    dstate, grads = jax.lax.scan(
        carry, _f32(dafter).reshape(b, G, H // G, K, V),
        by_chunk + (starts, do), reverse=True)
    dv_in, dk_in, dreads, dq_in, dk_out, dkeep = (
        jnp.moveaxis(x, 0, 1) for x in grads)
    dT, dbv, dbk = solved_vjp((dv_in, dk_in))
    T_t = jnp.swapaxes(T_inv, -1, -2)
    dL = -_solve_dot(_solve_dot(T_t, _f32(dT)), T_t)
    return (*system_vjp((dL, dbv, dbk, dreads, dq_in, dk_out, dkeep)),
            dstate.reshape(b, H, K, V).astype(state.dtype))


gdn_chunk_scan.defvjp(_chunk_scan_forward, _chunk_scan_backward)
