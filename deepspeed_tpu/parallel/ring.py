"""Ring attention — context parallelism for long sequences.

The reference snapshot has NO context parallelism (SURVEY §2.3: "Ring attention /
context parallel — absent"); its long-sequence story is Ulysses + block-sparse
attention. On TPU, ring attention is the idiomatic long-context mechanism: each
sequence rank holds a KV shard, KV blocks rotate around the `sequence` ICI ring via
`ppermute` while every rank accumulates online-softmax partials of its Q shard —
compute and transfer overlap, memory stays O(T/sp).

PRIMARY path (`ring_flash_attention` / `use_flash=True`): each ring step runs
the HBM-streaming Pallas flash kernel (`ops/pallas/flash_attention.py`,
`flash_attention_with_lse`) on the whole held K/V shard; partials merge by
(o, lse), so the online-softmax state carries across ring steps in the
forward AND — via the lse cotangent threaded through the kernel's custom
VJP — the backward. Causal rings SKIP future-only steps entirely (the held
shard's owner is later in token order than every local query: no compute,
no HBM traffic — the step contributes (o=0, lse=-inf)), the diagonal step
runs the kernel's masked form, and past steps run unmasked, so causal ring
work is ~half of full ((sp+1)/2sp of the steps compute on average).

ORACLE/fallback (`ring_attention_blockwise` / `use_flash=False`): the same
ring schedule from differentiable lax pieces (blockwise einsum + running
(m, l, acc) merge), numerically the dense-softmax identity. It keeps the
flash path parity-testable on the CPU harness (interpret-mode Pallas is
orders slower than einsum there) and carries the shapes the kernel cannot
(local shards that are not 128-multiples).

COMPOSITION (`ring_ulysses_attention`): DeepSpeed-Ulysses' head-scatter
all-to-all composed with the ring — sp = ulysses_degree × ring_degree, as in
the reference's hybrid. The `sequence` mesh axis is factored into
(`seq_ring`, `seq_ulysses`) sub-axes; inside the shard_map each rank trades
its T/sp token shard for an H/ulysses head shard over `seq_ulysses`
(tokens gather to T/ring_degree, contiguous in ring order), runs the ring
over `seq_ring`, and trades back. Per-chip attention memory is
O(T/(ring·ulysses)) for K/V residency with ulysses-fold fewer heads per
ring step.

All three register in the attention dispatch layer
(`ops/attention_dispatch.py`) — the GPT zoo engages them via
`GPTConfig.attention_backend` rather than per-call-site wiring.
"""

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from deepspeed_tpu.comm import mesh as mesh_mod
from deepspeed_tpu.comm.mesh import BATCH_AXES, SEQ_AXIS, TENSOR_AXIS

NEG_INF = -1e30

# factored sub-axes of SEQ_AXIS for the ring∘Ulysses hybrid
RING_SUBAXIS = "seq_ring"
ULYSSES_SUBAXIS = "seq_ulysses"


def _block_attn_partial(q, k, v, q_offset, k_offset, causal, sm_scale):
    """Unnormalized block attention with running-max bookkeeping.
    q: [B, Tq, H, hd]; k,v: [B, Tk, H, hd] → (scores_max [B,H,Tq],
    exp-sum [B,H,Tq], weighted values [B,Tq,H,hd])."""
    s = jnp.einsum("bthd,bshd->bhts", q.astype(jnp.float32), k.astype(jnp.float32)) * sm_scale
    if causal:
        Tq, Tk = q.shape[1], k.shape[1]
        q_pos = q_offset + jnp.arange(Tq)[:, None]
        k_pos = k_offset + jnp.arange(Tk)[None, :]
        s = jnp.where((q_pos >= k_pos)[None, None], s, NEG_INF)
    m = jnp.max(s, axis=-1)                                   # [B,H,Tq]
    p = jnp.exp(s - m[..., None])
    # fully-masked rows: m = -inf → p = exp(-inf - -inf) = nan; guard
    p = jnp.where(jnp.isfinite(m)[..., None], p, 0.0)
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bhts,bshd->bthd", p, v.astype(jnp.float32))
    return m, l, o


def _can_use_flash(q, causal):
    """Flash inner blocks: long-enough kernel-tileable local shards on a
    real backend (interpret-mode pallas on CPU is orders slower than
    einsum). Causal and non-causal rings both qualify — the non-causal
    ring runs the unmasked kernel every step."""
    from deepspeed_tpu.ops.pallas.flash_attention import flash_seq_tileable
    from deepspeed_tpu.platform.device import pallas_interpret
    del causal
    Tl = q.shape[1]
    return (flash_seq_tileable(Tl) and Tl >= 1024
            and not pallas_interpret())


def _ring_attention_local(q, k, v, axis_name, sp, causal, sm_scale,
                          use_flash=False):
    """Runs inside shard_map. q,k,v local: [B, Tl, H, hd].

    `use_flash=True` routes each ring step's block attention through the
    Pallas flash kernel (ops/pallas/flash_attention.py): ring blocks are
    whole contiguous shards, so under a causal mask every (q_shard, k_shard)
    pair is exactly one of three cases — DIAGONAL (src == mine: standard
    causal), PAST (src < mine: no mask), FUTURE (fully masked: skip, no
    compute, lse = -inf) — which avoids offset-aware masking inside the
    kernel entirely; a non-causal ring runs the unmasked kernel every step.
    Partials merge by (o, lse): out = Σ_i o_i · exp(lse_i − lse_total) —
    the online-softmax carry across ring steps, fwd and (via the kernel's
    lse cotangent) bwd.

    The einsum path applies the SAME causal step-skipping: future-only
    steps return the empty partial (m=-inf, l=0, o=0) through a lax.cond
    instead of computing a fully-masked block — causal ring work is ~half
    of full on both paths."""
    B, Tl, H, hd = q.shape
    my_idx = jax.lax.axis_index(axis_name)
    perm = [(j, (j + 1) % sp) for j in range(sp)]

    if use_flash:
        from deepspeed_tpu.ops.pallas.flash_attention import \
            flash_attention_with_lse
        qt = jnp.swapaxes(q, 1, 2)                       # [B, H, Tl, hd]

        def step(carry, i):
            acc, lse_run, kv = carry
            k_blk, v_blk = kv
            src = (my_idx - i) % sp

            def diagonal():
                o, lse = flash_attention_with_lse(
                    qt, jnp.swapaxes(k_blk, 1, 2), jnp.swapaxes(v_blk, 1, 2),
                    causal=True, sm_scale=sm_scale)
                return o.astype(jnp.float32), lse

            def past():
                o, lse = flash_attention_with_lse(
                    qt, jnp.swapaxes(k_blk, 1, 2), jnp.swapaxes(v_blk, 1, 2),
                    causal=False, sm_scale=sm_scale)
                return o.astype(jnp.float32), lse

            def future():
                return (jnp.zeros((B, H, Tl, hd), jnp.float32),
                        jnp.full((B, H, Tl), NEG_INF, jnp.float32))

            if causal:
                o_blk, lse_blk = jax.lax.cond(
                    src == my_idx, diagonal,
                    lambda: jax.lax.cond(src < my_idx, past, future))
            else:
                o_blk, lse_blk = past()
            lse_new = jnp.logaddexp(lse_run, lse_blk)
            safe = jnp.where(jnp.isfinite(lse_new), lse_new, 0.0)
            alpha = jnp.where(jnp.isfinite(lse_run),
                              jnp.exp(lse_run - safe), 0.0)
            beta = jnp.where(jnp.isfinite(lse_blk),
                             jnp.exp(lse_blk - safe), 0.0)
            acc = acc * alpha[..., None] + o_blk * beta[..., None]
            kv = jax.tree_util.tree_map(
                lambda x: jax.lax.ppermute(x, axis_name, perm), kv)
            return (acc, lse_new, kv), None

        acc0 = jnp.zeros((B, H, Tl, hd), jnp.float32)
        lse0 = jnp.full((B, H, Tl), NEG_INF, jnp.float32)
        (acc, _, _), _ = jax.lax.scan(step, (acc0, lse0, (k, v)),
                                      jnp.arange(sp))
        return jnp.swapaxes(acc, 1, 2).astype(q.dtype)

    def step(carry, i):
        acc, m_run, l_run, kv = carry
        k_blk, v_blk = kv
        src = (my_idx - i) % sp       # owner of the block we currently hold

        def live():
            return _block_attn_partial(
                q, k_blk, v_blk, my_idx * Tl, src * Tl, causal, sm_scale)

        if causal:
            def future():
                # fully-masked shard: skip the einsum entirely — the empty
                # partial merges as a no-op through the finite-mass guards
                return (jnp.full((B, H, Tl), NEG_INF, jnp.float32),
                        jnp.zeros((B, H, Tl), jnp.float32),
                        jnp.zeros((B, Tl, H, hd), jnp.float32))

            m_blk, l_blk, o_blk = jax.lax.cond(src <= my_idx, live, future)
        else:
            m_blk, l_blk, o_blk = live()
        m_new = jnp.maximum(m_run, m_blk)
        # guard: rows where both are -inf stay -inf
        safe_m = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        alpha = jnp.where(jnp.isfinite(m_run), jnp.exp(m_run - safe_m), 0.0)
        beta = jnp.where(jnp.isfinite(m_blk), jnp.exp(m_blk - safe_m), 0.0)
        l_new = l_run * alpha + l_blk * beta
        acc = acc * alpha.transpose(0, 2, 1)[..., None] + \
            o_blk * beta.transpose(0, 2, 1)[..., None]
        kv = jax.tree_util.tree_map(
            lambda x: jax.lax.ppermute(x, axis_name, perm), kv)
        return (acc, m_new, l_new, kv), None

    acc0 = jnp.zeros((B, Tl, H, hd), jnp.float32)
    m0 = jnp.full((B, H, Tl), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, Tl), jnp.float32)
    (acc, m, l, _), _ = jax.lax.scan(step, (acc0, m0, l0, (k, v)), jnp.arange(sp))
    l_safe = jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
    return (acc / l_safe).astype(q.dtype)


def _check_flash_shard(Tl, sp, what="ring"):
    """use_flash=True demands kernel-tileable local shards; surface the
    contract instead of the flash kernel's deep block-divisibility assert."""
    from deepspeed_tpu.ops.pallas.flash_attention import flash_seq_tileable
    if not flash_seq_tileable(Tl):
        raise ValueError(
            f"{what} flash attention: local shard T/sp = {Tl} (sp={sp}) is "
            f"not a 128-multiple — the Pallas kernel tiles 128-lane blocks. "
            f"Pad T to a multiple of sp*128, or drop use_flash to run the "
            f"blockwise oracle path")


def ring_attention(q, k, v, causal=True, sm_scale=None, axis_name=SEQ_AXIS,
                   mesh=None, use_flash=None):
    """Global-array entry: q,k,v [B, T, H, hd] sharded (data, sequence, tensor).
    Returns attention output with the same layout/sharding.

    use_flash: None = auto — per-step block attention runs the Pallas flash
    kernel when the LOCAL shard is a 128-multiple >= 1024 tokens on a real
    TPU backend (measured r4: the kernel beats materialized attention 1.6x
    at 1k, 2.3x at 2k, 3.4x at 4k fwd+bwd; interpret mode on CPU would be
    orders slower, so the einsum oracle is kept there). True forces the
    kernel (128-multiple local shards required — clear ValueError
    otherwise); False forces the blockwise oracle."""
    mesh = mesh or mesh_mod.get_mesh()
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    sp = sizes.get(axis_name, 1)
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    if sp == 1:
        # degenerate ring: honor the use_flash contract anyway — True must
        # run (and shape-check) the kernel, not silently fall to einsum
        if use_flash:
            _check_flash_shard(q.shape[1], 1)
            from deepspeed_tpu.ops.pallas.flash_attention import \
                flash_attention
            return flash_attention(q, k, v, causal=causal,
                                   sm_scale=sm_scale)
        m, l, o = _block_attn_partial(q, k, v, 0, 0, causal, sm_scale)
        return (o / jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]).astype(q.dtype)

    if q.shape[1] % sp != 0:
        raise ValueError(
            f"ring attention: T = {q.shape[1]} does not divide over the "
            f"{sp}-way `{axis_name}` mesh axis")
    local_q_shape = (q.shape[0], q.shape[1] // sp, *q.shape[2:])
    if use_flash is None:
        use_flash = _can_use_flash(
            jax.ShapeDtypeStruct(local_q_shape, q.dtype), causal)
    if use_flash:
        _check_flash_shard(local_q_shape[1], sp)

    spec = P(BATCH_AXES, axis_name, TENSOR_AXIS, None)
    fn = shard_map(
        partial(_ring_attention_local, axis_name=axis_name, sp=sp, causal=causal,
                sm_scale=sm_scale, use_flash=use_flash),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec, check_vma=False)
    return fn(q, k, v)


def ring_flash_attention(q, k, v, causal=True, sm_scale=None,
                         axis_name=SEQ_AXIS, mesh=None):
    """The PRIMARY long-context path: ring attention with the Pallas flash
    kernel forced for every ring step (see `_ring_attention_local`)."""
    return ring_attention(q, k, v, causal=causal, sm_scale=sm_scale,
                          axis_name=axis_name, mesh=mesh, use_flash=True)


def ring_attention_blockwise(q, k, v, causal=True, sm_scale=None,
                             axis_name=SEQ_AXIS, mesh=None):
    """The lax-level blockwise ORACLE: same ring schedule, einsum block
    attention — the parity reference for the flash path and the fallback
    for shard shapes the kernel cannot tile."""
    return ring_attention(q, k, v, causal=causal, sm_scale=sm_scale,
                          axis_name=axis_name, mesh=mesh, use_flash=False)


# ----------------------------------------------------------------------
# ring ∘ Ulysses composition (the reference hybrid: sp = ulysses × ring)
# ----------------------------------------------------------------------


def factored_sequence_mesh(mesh, ulysses_degree):
    """Split `mesh`'s `sequence` axis into (seq_ring, seq_ulysses) sub-axes
    of sizes (sp // ulysses_degree, ulysses_degree). Device order is
    preserved: seq_ulysses is the INNER factor, so Ulysses' all-to-all —
    the bandwidth-hungry collective of the pair — rides adjacent ICI
    neighbors while the ring's ppermute spans the outer stride, mirroring
    the mesh module's slow-outer/fast-inner axis convention."""
    names = list(mesh.axis_names)
    i = names.index(SEQ_AXIS)
    shape = mesh.devices.shape
    sp = shape[i]
    if sp % ulysses_degree != 0:
        raise ValueError(
            f"ring∘Ulysses: ulysses_degree {ulysses_degree} does not divide "
            f"the `sequence` axis size {sp}")
    ring_degree = sp // ulysses_degree
    devices = mesh.devices.reshape(
        shape[:i] + (ring_degree, ulysses_degree) + shape[i + 1:])
    new_names = names[:i] + [RING_SUBAXIS, ULYSSES_SUBAXIS] + names[i + 1:]
    return Mesh(devices, tuple(new_names)), ring_degree


def ring_ulysses_attention(q, k, v, causal=True, sm_scale=None,
                           ulysses_degree=None, mesh=None, use_flash=None):
    """Context parallelism composed with Ulysses head parallelism over ONE
    `sequence` mesh axis: sp = ulysses_degree × ring_degree.

    q,k,v: [B, T, H, hd] global arrays (matched q/kv head counts — GQA
    callers repeat K/V first, as for every external attention program).
    Inside the factored mesh's shard_map, each rank:

      1. all-to-alls over `seq_ulysses`: trades its T/sp token shard for an
         H/ulysses head shard — tokens gather CONTIGUOUSLY in ring order
         (seq_ulysses is the inner factor of the T sharding), so ring rank
         r then holds tokens [r·T/ring, (r+1)·T/ring);
      2. runs the ring over `seq_ring` (flash kernel per step when
         engaged — same auto rule as `ring_attention`, on the post-
         all-to-all local shape);
      3. all-to-alls back to the [B, T/sp, H, hd] layout.

    `ulysses_degree=None` auto-picks the largest divisor of sp that also
    divides the per-tensor-shard head count — all heads busy, remainder of
    sp goes to the ring. Degenerate ends are exact: ulysses_degree == sp is
    pure Ulysses, ulysses_degree == 1 is pure ring."""
    mesh = mesh or mesh_mod.get_mesh()
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    sp = sizes.get(SEQ_AXIS, 1)
    tp = sizes.get(TENSOR_AXIS, 1)
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    if sp == 1:
        # degenerate hybrid = degenerate ring (which honors use_flash)
        return ring_attention(q, k, v, causal=causal, sm_scale=sm_scale,
                              mesh=mesh, use_flash=use_flash)

    B, T, H, hd = q.shape
    if H % tp != 0:
        raise ValueError(f"ring∘Ulysses: {H} heads do not divide over the "
                         f"{tp}-way `tensor` axis")
    local_h = H // tp
    if ulysses_degree is None:
        ulysses_degree = 1
        for d in range(min(sp, local_h), 0, -1):
            if sp % d == 0 and local_h % d == 0:
                ulysses_degree = d
                break
    if local_h % ulysses_degree != 0:
        raise ValueError(
            f"ring∘Ulysses: ulysses_degree {ulysses_degree} does not divide "
            f"the per-tensor-shard head count {local_h} (H={H}, tp={tp}) — "
            f"the head-scatter all-to-all needs whole heads per rank. "
            f"Lower ulysses_degree (its factor of sp moves to the ring)")
    if k.shape[2] != H or v.shape[2] != H:
        raise ValueError(
            f"ring∘Ulysses: k/v head count {k.shape[2]} != q head count {H} "
            f"— repeat GQA K/V heads before the all-to-all (the zoo's "
            f"dispatch layer does this for external programs)")
    if T % sp != 0:
        raise ValueError(f"ring∘Ulysses: T = {T} does not divide over the "
                         f"{sp}-way `sequence` axis")

    fmesh, ring_degree = factored_sequence_mesh(mesh, ulysses_degree)
    if use_flash is None:
        use_flash = _can_use_flash(
            jax.ShapeDtypeStruct(
                (B, T // ring_degree, local_h // ulysses_degree, hd),
                q.dtype), causal)
    if use_flash:
        _check_flash_shard(T // ring_degree, ring_degree, what="ring∘Ulysses")

    spec = P(BATCH_AXES, (RING_SUBAXIS, ULYSSES_SUBAXIS), TENSOR_AXIS, None)

    def local(q, k, v):
        # [b, T/sp, h_tp, hd] → head-scatter / token-gather over ulysses
        a2a = partial(jax.lax.all_to_all, axis_name=ULYSSES_SUBAXIS,
                      tiled=True)
        q, k, v = (a2a(x, split_axis=2, concat_axis=1) for x in (q, k, v))
        o = _ring_attention_local(q, k, v, axis_name=RING_SUBAXIS,
                                  sp=ring_degree, causal=causal,
                                  sm_scale=sm_scale, use_flash=use_flash)
        return a2a(o, split_axis=1, concat_axis=2)

    fn = shard_map(local, mesh=fmesh, in_specs=(spec, spec, spec),
                   out_specs=spec, check_vma=False)
    return fn(q, k, v)
