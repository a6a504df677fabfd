"""The gated delta rule (Gated DeltaNet's recurrence) on the per-slot state
kind — the second body of `ops/pallas/ssm.py`'s in-place shell, and the
chunked form a prefill chunk runs.

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
  strict_lower((beta k) k^T * Gamma))^-1` (L is nilpotent: `(I - L)(I +
  L^2)(I + L^4) ...`, five squarings at Q = 64), then matmuls against the
  carried state. `g = 0` AND `beta = 0` at a position leave the state alone
  there (decay 1, nothing written): a chunk's padded tail.

Off the TPU `gdn_update` runs its `jax.numpy` twin (`gdn_update_reference`),
which is also the kernel's test oracle; `gdn_scan_reference` (a position at
a time) is the chunked form's.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from deepspeed_tpu.ops.pallas import ssm

KERNEL_NAME = "dstpu_gdn_update"
# the triangular solve's float32 products (`_inverse_of_unit_lower`): three
# bfloat16 passes. Six (HIGHEST) read the same errors against the float32
# reference to three digits and cost 2.2% of Qwen3-Next's cell (PERF.md, PR 47)
_SOLVE_PRECISION = jax.lax.Precision.HIGH


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
    """`(I + L)^-1` of strictly lower triangular L `[..., Q, Q]` float32:
    `sum_n (-L)^n = (I - L)(I + L^2)(I + L^4) ...`, the powers by squaring
    until they vanish (L^Q = 0). Float32 products above the default's one
    bfloat16 pass: T's entries cancel, and a chunk's every later product
    reads them."""
    dot = functools.partial(jnp.matmul, precision=_SOLVE_PRECISION)
    T = jnp.eye(L.shape[-1], dtype=L.dtype) - L
    power, reach = L, 2                 # T is exact up to L^(reach - 1)
    while reach < L.shape[-1]:
        power = dot(power, power)
        T = T + dot(T, power)
        reach *= 2
    return T


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
    decays and the triangular solve are float32 (`_SOLVE_PRECISION`)."""
    b, T, H, V = v.shape
    G, K = k.shape[2:]
    per = H // G
    pad = -T % chunk
    if pad:
        q, k, v, g, beta = (
            jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
            for x in (q, k, v, g, beta))
    c, Q = (T + pad) // chunk, chunk
    dtype = v.dtype
    dot = functools.partial(jnp.einsum, preferred_element_type=jnp.float32)
    f32 = lambda x: x.astype(jnp.float32)
    # heads as (key head, value head of it): q and k are never repeated
    q, k = (x.astype(dtype).reshape(b, c, Q, G, K) for x in (q, k))
    v = v.reshape(b, c, Q, G, per, V)
    g, beta = (f32(x).reshape(b, c, Q, G, per) for x in (g, beta))
    gamma = jnp.cumsum(g, axis=2)                           # inclusive
    # position j reaches i >= j decayed by exp(gamma_i - gamma_j)
    i_ge_j = jnp.tril(jnp.ones((Q, Q), bool))[:, :, None, None]
    Gamma = jnp.exp(jnp.where(i_ge_j, gamma[:, :, :, None]
                              - gamma[:, :, None], -jnp.inf))  # [b,c,i,j,G,per]
    heads_first = lambda x: jnp.moveaxis(x, (2, 3), (-2, -1))  # [b,c,G,per,i,j]
    kk = dot("bcign,bcjgn->bcijg", k, k)[..., None]
    L = heads_first(jnp.where(jnp.tril(jnp.ones((Q, Q), bool), -1)
                              [:, :, None, None],
                              beta[:, :, :, None] * kk * Gamma, 0.0))
    T_inv = _inverse_of_unit_lower(L).astype(dtype)
    # what each position writes, before the carried state is taken out ...
    bv = (beta[..., None] * f32(v)).astype(dtype)
    bk = (beta * jnp.exp(gamma))[..., None] * f32(k)[:, :, :, :, None]
    v_in = dot("bcghij,bcjghp->bcghip", T_inv, bv)
    k_in = dot("bcghij,bcjghn->bcghin", T_inv, bk.astype(dtype))
    # ... what reads it inside the chunk, and what the chunk hands on
    reads = heads_first(dot("bcign,bcjgn->bcijg", q, k)[..., None]
                        * Gamma).astype(dtype)
    q_in = (jnp.exp(gamma)[..., None] * f32(q)[:, :, :, :, None]).astype(dtype)
    last = gamma[:, :, -1]                                  # [b, c, G, per]
    k_out = (jnp.exp(last[:, :, None] - gamma)[..., None]
             * f32(k)[:, :, :, :, None]).astype(dtype)

    def carry(S, inputs):
        v_in, k_in, reads, q_in, k_out, keep = inputs
        held = S.astype(dtype)
        new = v_in - dot("bghin,bghnp->bghip", k_in.astype(dtype), held)
        o = dot("bighn,bghnp->bghip", q_in, held) \
            + dot("bghij,bghjp->bghip", reads, new.astype(dtype))
        S = keep[..., None, None] * S \
            + dot("bjghn,bghjp->bghnp", k_out, new.astype(dtype))
        return S, o

    state, o = jax.lax.scan(
        carry, f32(state).reshape(b, G, per, K, V),
        tuple(jnp.moveaxis(x, 1, 0) for x in (
            v_in, k_in, reads, q_in, k_out, jnp.exp(last))))
    # [c, b, G, per, Q, V] -> [b, T, H, V]
    o = jnp.moveaxis(o, (0, 4), (1, 2)).reshape(b, c * Q, H, V)
    return o[:, :T], state.reshape(b, H, K, V)
