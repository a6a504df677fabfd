"""Unified attention dispatch — ONE decision layer for every attention call.

The model zoo has five attention entry points (training flash/dense,
chunked paged prefill, paged single-token decode, paged spec-decode verify,
contiguous-cache decode) and until PR 14 each call site carried its own copy
of the engage predicate: the training `use_flash_attention` check lived at
`models/gpt.py::_attention` while the decode-kernel check lived 400 lines
away in `_decode_kernel_wanted`, and every new variant (the PR 12 quantized
kernels, ring context parallelism) had to be special-cased at each site.

This module is the single home for those decisions. A call site builds an
`AttnSite` — the dispatch KEY: (phase, q/kv length, mesh axes, kv dtype)
plus the masking flags that disqualify kernels — and `select()` walks the
PROGRAM REGISTRY (highest priority first) to name the program that runs.
Variants register once here instead of branching at five call sites:

  * a KERNEL program (flash, the ring family, the paged and latent walks,
    the contiguous decode kernel) registers with a `runner`, and its site
    invokes it through the registry without knowing its internals;
  * a program WITHOUT a runner is the site's own dense / gather oracle,
    which reads what the pool holds (its int8 twin from the pool's scale
    leaves), never a program's name — so adding a program is an entry
    here and no edit of a caller.

Every predicate reads only TRACE-TIME-STATIC inputs (shapes, config
fields, the installed mesh spec), so dispatch can never cause a recompile:
the serving tier's ≤1-compile-per-program invariant is untouched, and
`dstpu_lint` DT004 treats `register_program` as a once-per-lifetime
construction context (programs built at registration time are persistent,
exactly like the scheduler's `_build_*` programs).

`dispatch_table()` renders the live registry — the reference table in
docs/kernels.md is generated from the same data the dispatcher walks.
"""

import dataclasses
from typing import Callable, Dict, Optional, Tuple

# ----------------------------------------------------------------------
# engage predicates — the ONE home of the measured crossovers
# ----------------------------------------------------------------------

# Training auto-dispatch crossover (measured r4, bf16 dots + 512-blocks:
# XLA materialized attention wins <= 512, flash wins 1.6x/2.3x/3.4x at
# 1k/2k/4k fwd+bwd) — see GPTConfig.use_flash_attention.
FLASH_MIN_SEQ = 1024
# Decode auto-dispatch: the blocked streaming kernel reads only the live
# cache prefix while the XLA einsum reads the whole allocated M every step;
# below this the einsum already sits at the bandwidth floor (r5: 174-204us
# vs kernel 189us vs floor 164us at ctx 8k) — see docs/kernels.md.
DECODE_KERNEL_MIN_CTX = 8192


def flash_wanted(force_flash: Optional[bool], T: int) -> bool:
    """THE training-attention flash predicate (single definition — the two
    historical copies at models/gpt.py:436 and :855 both resolve here).
    `force_flash` is `GPTConfig.use_flash_attention`: True forces, False
    forbids, None auto-engages from FLASH_MIN_SEQ."""
    return force_flash is True or (force_flash is None and T >= FLASH_MIN_SEQ)


def decode_kernel_wanted(force_flash: Optional[bool], M: int) -> bool:
    """THE decode-kernel predicate: explicit True forces, auto engages from
    DECODE_KERNEL_MIN_CTX with a block-tileable length (contiguous path:
    M = allocated cache length; paged path: M = table_width * block = the
    effective context)."""
    return (force_flash is True
            or (force_flash is None
                and M >= DECODE_KERNEL_MIN_CTX and M % 128 == 0))


def active_mesh_axes() -> Tuple[str, ...]:
    """Mesh axes with size > 1 on the installed global mesh (() when no
    mesh) — the `mesh_axes` component of the dispatch key."""
    from deepspeed_tpu.comm import mesh as mesh_mod
    if not mesh_mod.has_mesh():
        return ()
    sizes = mesh_mod.get_spec().axis_sizes()
    return tuple(name for name, n in sizes.items() if n > 1)


# ----------------------------------------------------------------------
# the dispatch key
# ----------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class AttnSite:
    """One attention call site's dispatch key. Everything here is known at
    trace time; nothing data-dependent may enter (that would make program
    selection a recompile hazard)."""
    phase: str                    # "train" | "decode" | "paged_decode" |
                                  # "prefill_chunk" | "verify"
    q_len: int                    # query length (T; chunk C; 1 for decode)
    kv_len: int                   # key/context length (T, M, or nb*block)
    causal: bool = True
    has_bias: bool = False        # additive bias (alibi) present
    has_window: bool = False      # a window mask no kernel applies: any
                                  # window outside the paged phases, and the
                                  # per-layer local flag (traced) in them
    window: int = 0               # paged phases: the site's STATIC sliding
                                  # window (every call of it windowed), which
                                  # the paged walks take as a lower bound and
                                  # a mask; 0 = none
    scale_attn: bool = True       # False = unscaled scores (GPT-Neo)
    sink: bool = False            # a learned logit a head joins the softmax's
                                  # denominator (`GPTConfig.attn_sink`): the
                                  # paged walks take it as the online
                                  # softmax's initial state, flash does not
    head_dim: int = 0             # a head's query-key width and its value
    value_dim: int = 0            # width (0 / 0: a site that does not say
                                  # has one width): the paged walks take
                                  # values of their own width, flash does not
    kv_dtype: str = "bfloat16"    # KV storage dtype ("int8" = quantized pool)
    block_size: int = 0           # paged pool physical block (paged phases)
    pool_in_place: bool = False   # paged phases: the pool is the carried,
                                  # flat, Mosaic-only form (`kv_pool_writer`
                                  # named KV_POOL_WRITE_KERNEL for it)
    latent: bool = False          # paged phases: the pool holds ONE latent
                                  # entry a position for all heads (MLA,
                                  # `models/mla.py`): only the `mla_*`
                                  # programs read it
    mesh_axes: Tuple[str, ...] = ()  # active (size>1) mesh axes
    force_flash: Optional[bool] = None  # GPTConfig.use_flash_attention
    backend: Optional[str] = None       # GPTConfig.attention_backend request
    external_fn: bool = False     # caller supplied its own attn_fn — only
                                  # the "external" pseudo-program may match

    @property
    def square(self) -> bool:
        return self.q_len == self.kv_len


# ----------------------------------------------------------------------
# the program registry
# ----------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class AttentionProgram:
    """One registered attention implementation.

    `matches` decides eligibility from the AttnSite alone. A program is run
    by its `runner`, never by its name: where `runner` is set, the site
    invokes it without knowing the program; where it is None, the site runs
    its own dense / gather oracle, which picks its dequantizing twin from
    the pool's leaves. One signature a kind of site:

      * train: `(q, k, v, *, causal, sm_scale)`, zoo layout [B, T, H, hd],
        matched heads -> [B, T, H, hd];
      * a paged phase: `(q, pool_l, block_tables, start, *, sm_scale,
        window, work=None, rank=None)` with q [B, C, H, hd], the pool's
        leaves WHOLE, the tables in the pool's numbering, each row's first
        position, the site's static window (None = none), the decode
        walks' work list where the caller built it outside its layer loop,
        and for a latent pool the latent rank (q then `[q~ | q_r | 0]`, the
        result the un-absorbed values) -> [B, C, H * hd] ([B, C, H * rank];
        H * the values' width where that is their own); a K/V walk also
        takes `sink=` [H], given only at a site with a sink logit, and
        `selected=`, given only by a sparse layer (`models/sparse_attn.py`:
        the positions each row attends, in the walk's own layout);
      * contiguous decode: `(q, cache_k, cache_v, pos, *, sm_scale)` with q
        [B, H, hd] and the head-major cache -> [B, H, hd].

    `work` counts, on the host, what ONE call of the program does a layer
    that a dense attend over the whole table would not: for a chunk walk
    `(start, chunk, block, table_blocks, window=None)` -> {`live_blocks`,
    `table_blocks`, `kept_pairs`, ...} by the kernel's own names (the
    scheduler alone knows which fields of its step record they land in). None
    where the program walks nothing of the kind: the gather oracles.

    `when` is the human-readable engage condition for `dispatch_table()`
    and docs/kernels.md."""
    name: str
    phases: Tuple[str, ...]
    priority: int                 # higher wins among eligible programs
    matches: Callable[[AttnSite], bool]
    when: str = ""
    runner: Optional[Callable] = None
    work: Optional[Callable] = None


_REGISTRY: Dict[str, AttentionProgram] = {}


def register_program(program: AttentionProgram) -> AttentionProgram:
    """Add (or replace) a program in the dispatch registry. Registration is
    a once-per-lifetime construction context: a program whose runner closes
    over jitted callables builds them HERE, not per call."""
    _REGISTRY[program.name] = program
    return program


def get_program(name: str) -> AttentionProgram:
    return _REGISTRY[name]


def registered_programs(phase: Optional[str] = None):
    """Programs (highest priority first, name-tiebroken) — the order
    `select` walks."""
    progs = [p for p in _REGISTRY.values()
             if phase is None or phase in p.phases]
    return sorted(progs, key=lambda p: (-p.priority, p.name))


def select(site: AttnSite) -> str:
    """Name the program this site runs: the highest-priority registered
    program whose `matches(site)` holds. Every phase registers a priority-0
    fallback that always matches, so selection is total.

    An explicit ring-family `backend` request on a live `sequence` mesh
    that resolves to a NON-ring program (the site carries alibi/window
    bias or non-square attention — outside the kernel contract) raises
    instead of silently materializing dense attention: at the 128k+
    contexts context parallelism exists for, the dense fallback is an
    HBM OOM far from its cause. (A request with NO `sequence` axis still
    falls through to auto — that degenerate case is exact and documented
    on `GPTConfig.attention_backend`.) A backend string naming NO
    registered program is a config typo and raises immediately — silently
    ignoring "ring-ulysses" would hand a 128k run to single-chip dense."""
    if site.phase == "train" and site.backend is not None \
            and site.backend not in _REGISTRY:
        raise ValueError(
            f"unknown attention_backend {site.backend!r}: no program of "
            f"that name is registered (registered: {sorted(_REGISTRY)})")
    for prog in registered_programs(site.phase):
        if prog.matches(site):
            if (site.phase == "train"
                    and site.backend in ("ring", "ring_ulysses")
                    and "sequence" in site.mesh_axes
                    and prog.name not in ("ring", "ring_ulysses",
                                          "external")):
                raise ValueError(
                    f"attention_backend={site.backend!r} was requested on "
                    f"a `sequence` mesh but this site is ineligible for "
                    f"the ring programs (alibi/sliding-window bias or "
                    f"non-square attention — the plain-causal kernel "
                    f"contract) — resolved program would be "
                    f"{prog.name!r}. Drop the backend request or the "
                    f"arch flag")
            return prog.name
    raise LookupError(
        f"no attention program registered for phase {site.phase!r} "
        f"(registry: {sorted(_REGISTRY)})")


def dispatch_table() -> Dict[str, list]:
    """phase -> [(program, when)] in selection order — the reference table
    (docs/kernels.md renders this)."""
    phases = ("train", "prefill_chunk", "decode", "paged_decode", "verify")
    return {ph: [(p.name, p.when) for p in registered_programs(ph)]
            for ph in phases}


# ----------------------------------------------------------------------
# built-in programs
# ----------------------------------------------------------------------
# Priorities: 100s = explicit backend requests (ring family), 50s =
# kernel engagement, 0 = the always-eligible dense fallback.


def _kernel_shape_ok(site: AttnSite) -> bool:
    """Kernel-path disqualifiers shared by flash/ring: the Pallas
    contract is plain (un-biased, un-windowed, scaled) square causal-or-not
    attention on 128-multiple sequences."""
    return (not site.has_bias and not site.has_window and site.square
            and site.q_len % 128 == 0)


def _train_external(site):
    return site.external_fn


def _train_ring(site):
    return (site.backend in ("ring", "ring_ulysses")
            and "sequence" in site.mesh_axes
            and not site.has_bias and not site.has_window and site.square)


def _train_flash(site):
    return (site.phase == "train" and _kernel_shape_ok(site)
            and site.scale_attn and site.causal
            and not site.sink and site.head_dim == site.value_dim
            and flash_wanted(site.force_flash, site.q_len))


def _run_ring(q, k, v, *, causal=True, sm_scale=None):
    from deepspeed_tpu.parallel.ring import ring_attention
    return ring_attention(q, k, v, causal=causal, sm_scale=sm_scale)


def _run_ring_ulysses(q, k, v, *, causal=True, sm_scale=None):
    from deepspeed_tpu.parallel.ring import ring_ulysses_attention
    return ring_ulysses_attention(q, k, v, causal=causal, sm_scale=sm_scale)


def _per_shard(kernel, q, v):
    """Run a zoo-layout attention kernel (q, k `[B, T, H, hd]`; v and the
    result like them, or `[B, T, H*hd]`) on each device's own batch/head
    shard.

    A compiled `pallas_call` is an opaque custom call: GSPMD cannot
    partition it, so inside the engine's partitioned `jit` it all-gathers
    q/k/v over `data` and runs the WHOLE batch on every chip (the CPU
    interpreter lowers the kernel to ordinary HLO, which GSPMD does
    partition — so virtual-device runs never showed this). `shard_map`
    over the batch axes and the head axis hands the kernel the per-shard
    operands instead (merged heads split as their columns do: a shard's
    heads are one run of them). The sequence dim stays whole: this kernel
    attends over all of T (context parallelism is the ring programs' job).

    Left bare when there is nothing to split: no mesh, batch and tensor
    axes of size 1, a trace already inside a shard_map body
    (`constraints_disabled`), or a batch/head count the axes do not divide
    (XLA replicates such an array anyway)."""
    import jax
    from jax.sharding import PartitionSpec as P

    from deepspeed_tpu.comm import mesh as mesh_mod
    if not mesh_mod.has_mesh() or mesh_mod._CONSTRAINTS_DISABLED:
        return kernel
    B, _, H, _ = q.shape
    n_batch = mesh_mod.axis_size(mesh_mod.BATCH_AXES)
    n_heads = mesh_mod.axis_size(mesh_mod.TENSOR_AXIS)
    batch = mesh_mod.BATCH_AXES if n_batch > 1 and B % n_batch == 0 else None
    heads = mesh_mod.TENSOR_AXIS if n_heads > 1 and H % n_heads == 0 else None
    if batch is None and heads is None:
        return kernel
    spec = P(batch, None, heads, None)
    v_spec = spec if v.ndim == 4 else P(batch, None, heads)
    return jax.shard_map(kernel, mesh=mesh_mod.get_mesh(),
                         in_specs=(spec, spec, v_spec), out_specs=v_spec,
                         check_vma=False)


def _run_flash(q, k, v, *, causal=True, sm_scale=None):
    import functools

    from deepspeed_tpu.ops.pallas.flash_attention import (
        flash_attention, flash_heads_in_place)
    kernel = functools.partial(flash_attention, causal=causal,
                               sm_scale=sm_scale)
    form = v.shape
    if flash_heads_in_place(q.shape[-1]):
        # the values and the result cross the shard_map as the projections'
        # own columns: a reshape on either side of that boundary does not
        # cancel against the model's, and XLA then lays the 4-D tensor out
        # for it (PERF.md section 7: `[B, T, H, D] <-> [B, T, H*D]` is a
        # relayout on the TPU)
        v = v.reshape(v.shape[:2] + (-1,))
    return _per_shard(kernel, q, v)(q, k, v).reshape(form)


register_program(AttentionProgram(
    name="external", phases=("train",), priority=1000,
    matches=_train_external,
    when="caller passed an explicit attn_fn (sparse/Ulysses wrappers)"))

register_program(AttentionProgram(
    name="ring_ulysses", phases=("train",), priority=110,
    matches=lambda s: _train_ring(s) and s.backend == "ring_ulysses",
    when="attention_backend='ring_ulysses', `sequence` mesh axis active; "
         "sp = ulysses_degree x ring_degree (head all-to-all around the "
         "K/V ring)",
    runner=_run_ring_ulysses))

register_program(AttentionProgram(
    name="ring", phases=("train",), priority=100,
    matches=lambda s: _train_ring(s) and s.backend == "ring",
    when="attention_backend='ring', `sequence` mesh axis active; K/V "
         "shards rotate via ppermute, flash kernel per ring step",
    runner=_run_ring))

register_program(AttentionProgram(
    name="flash", phases=("train",), priority=50,
    matches=_train_flash,
    when=f"T >= {FLASH_MIN_SEQ} (auto) or use_flash_attention=True; "
         "plain scaled causal, T % 128 == 0, no sink logit, values as wide "
         "as the keys",
    runner=_run_flash))

register_program(AttentionProgram(
    name="dense", phases=("train",), priority=0,
    matches=lambda s: True,
    when="fallback: XLA materialized attention (GQA grouped einsum, "
         "alibi/window masks, short T)"))


# -- contiguous-cache decode ------------------------------------------------


def _run_decode(q, cache_k, cache_v, pos, *, sm_scale=None):
    from deepspeed_tpu.ops.pallas.decode_attention import decode_attention
    return decode_attention(q, cache_k, cache_v, pos, sm_scale=sm_scale)


register_program(AttentionProgram(
    name="decode_kernel", phases=("decode",), priority=50,
    matches=lambda s: (not s.has_bias and not s.has_window
                       and decode_kernel_wanted(s.force_flash, s.kv_len)),
    when=f"M >= {DECODE_KERNEL_MIN_CTX} and M % 128 == 0 (auto) or "
         "use_flash_attention=True; no alibi/window",
    runner=_run_decode))

register_program(AttentionProgram(
    name="decode_dense", phases=("decode",), priority=0,
    matches=lambda s: True,
    when="fallback: XLA einsum over the whole allocated cache"))


# -- paged pool (serving) ---------------------------------------------------


def _paged_kernel_ok(site):
    return (site.phase == "paged_decode" and site.q_len == 1
            and not site.has_bias and not site.has_window
            and site.block_size % 128 == 0
            and decode_kernel_wanted(site.force_flash, site.kv_len))


# How a step program writes its new K/V rows into the pool. THE rule, and
# the one place it lives: `models/gpt.py::_scan_paged` asks it once per
# program and builds the whole layer loop in the form it names;
# `ServingEngine.stats()["kv_pool_writer"]` reports what was built.
#
# The invariant it protects: NEVER AN XLA SCATTER, UPDATE-SLICE OR GATHER ON
# A CARRIED POOL THAT A MOSAIC CALL ALSO TOUCHES. The scatter wants the pool
# with the heads next to `hd`, the Mosaic calls are pinned to the default
# layout, and XLA reconciles the two with a copy of the WHOLE pool inside
# the layer loop (compiled for a v5e, PR 25: 16 whole-pool copies a step);
# a gather of a few blocks is rewritten into slices of the whole pool
# (measured on the chip, PR 25: slower than the form it replaced). So the
# two forms are: pool carried through the scan + every write and read a
# Mosaic call (`ops/pallas/kv_pool.py`, `dstpu_paged_decode`,
# `dstpu_paged_prefill`), or pool
# sliced by the scan as xs/ys + XLA scatter and gather on the slice (a copy
# of the pool per program call, but each layer's slice is re-laid-out
# alone). Nothing in between.
KV_POOL_WRITE_KERNEL = "dstpu_kv_pool_write"
KV_POOL_WRITE_SCATTER = "xla_scatter"


def kv_pool_writer(pool) -> str:
    """Name the writer for `pool` (the `[L, N, Hkv, block, hd]` pytree of
    `init_paged_kv_pool` — `k`/`v`, each leaf with its own width, and `kr`
    where the keys' half tile is kept apart, `kv_pool.py::kv_leaf_shapes`,
    or `ik` where a sparse layer's indexer keeps its key
    (`models/sparse_attn.py`) — or a latent kind's one leaf `{"ckv": [L, N,
    1, block, width]}`) from what can be seen at trace time: the in-place
    kernel for a float pool made of whole native tiles, on a TPU, in a
    single-device program (a bare Mosaic call cannot be partitioned — the
    same limit as `dstpu_paged_decode`); the scatter everywhere else: the
    CPU, the int8 pool with its narrow scale leaves, head widths under a
    lane tile, a multi-device mesh."""
    from deepspeed_tpu.ops.pallas.kv_pool import pool_in_place_supported
    from deepspeed_tpu.platform.device import pallas_interpret
    if (set(pool) in ({"k", "v"}, {"k", "kr", "v"}, {"k", "v", "ik"},
                      {"ckv"})
            and not pallas_interpret() and not active_mesh_axes()
            and all(pool_in_place_supported(x.dtype, x.shape[-2], x.shape[-1])
                    for x in pool.values())):
        return KV_POOL_WRITE_KERNEL
    return KV_POOL_WRITE_SCATTER


# The paged runners, ONE signature (`AttentionProgram`): a decode walk takes
# the one query row a slot and hands back the chunk layout.


def _walk_args(q, pool_l, sm_scale, sink):
    """(q, sm_scale, the K/V walks' further keywords) of a site: for a pool
    that keeps the keys' half tile apart (`kv_pool.py::kv_leaf_shapes`) the
    query laid out against `[k | kr]`, the scale the MODEL's head width's and
    `kr_pool=`; `sink=` where the site has a sink logit."""
    more = {} if sink is None else dict(sink=sink)
    if "kr" not in pool_l:
        return q, sm_scale, more
    import math

    from deepspeed_tpu.ops.pallas.kv_pool import split_query
    return (split_query(q, pool_l["k"].shape[1]),
            sm_scale or 1.0 / math.sqrt(q.shape[-1]),
            dict(more, kr_pool=pool_l["kr"]))


def _run_paged_decode(q, pool_l, block_tables, start, *, sm_scale=None,
                      window=None, work=None, rank=None, sink=None,
                      selected=None):
    from deepspeed_tpu.ops.pallas.decode_attention import \
        paged_decode_attention
    B = q.shape[0]
    q, sm_scale, more = _walk_args(q, pool_l, sm_scale, sink)
    if selected is not None:    # a sparse layer's selection, as a bias
        more["selected"] = selected
    return paged_decode_attention(
        q[:, 0], pool_l["k"], pool_l["v"], block_tables, start,
        sm_scale=sm_scale, work=work, window=window,
        **more).reshape(B, 1, -1)


def _run_paged_decode_quant(q, pool_l, block_tables, start, *, sm_scale=None,
                            window=None, work=None, rank=None):
    from deepspeed_tpu.ops.pallas.decode_attention import \
        paged_decode_attention_quant
    B = q.shape[0]
    return paged_decode_attention_quant(
        q[:, 0], pool_l["k"], pool_l["v"], pool_l["k_scale"],
        pool_l["v_scale"], block_tables, start, sm_scale=sm_scale, work=work,
        window=window).reshape(B, 1, -1)


def _run_paged_prefill(q, pool_l, block_tables, start, *, sm_scale=None,
                       window=None, work=None, rank=None, sink=None,
                       block_length=1, selected=None):
    from deepspeed_tpu.ops.pallas.prefill_attention import \
        paged_prefill_attention
    q, sm_scale, more = _walk_args(q, pool_l, sm_scale, sink)
    if selected is not None:    # a sparse layer's selection, as a mask
        more["selected"] = selected
    if block_length > 1:    # a diffusion generator's block-causal mask
        more["block_length"] = block_length
    return paged_prefill_attention(q, pool_l["k"], pool_l["v"], block_tables,
                                   start, sm_scale=sm_scale, window=window,
                                   **more)


def _run_mla_decode(q, pool_l, block_tables, start, *, sm_scale=None,
                    window=None, work=None, rank=None):
    from deepspeed_tpu.ops.pallas.mla_attention import mla_decode_attention
    B = q.shape[0]
    return mla_decode_attention(q[:, 0], pool_l["ckv"], block_tables, start,
                                rank, sm_scale, work=work).reshape(B, 1, -1)


def _run_mla_prefill(q, pool_l, block_tables, start, *, sm_scale=None,
                     window=None, work=None, rank=None):
    from deepspeed_tpu.ops.pallas.mla_attention import mla_prefill_attention
    return mla_prefill_attention(q, pool_l["ckv"], block_tables, start, rank,
                                 sm_scale)


# A chunk walk's `work` (`AttentionProgram`), its module imported where it is
# asked for, as a runner's is.


def _paged_prefill_work(start, chunk, block, table_blocks, window=None):
    from deepspeed_tpu.ops.pallas.prefill_attention import \
        paged_prefill_walk_counts
    return paged_prefill_walk_counts(start, chunk, block, table_blocks,
                                     window)


def _mla_prefill_work(start, chunk, block, table_blocks, window=None):
    from deepspeed_tpu.ops.pallas.mla_attention import mla_prefill_walk_counts
    return mla_prefill_walk_counts(start, chunk, block, table_blocks, window)


# A latent pool (MLA): the absorbed walks of `ops/pallas/mla_attention.py`.
# They outrank every program above and match latent sites only, so a latent
# site never selects a K/V program and no other site selects these.
register_program(AttentionProgram(
    name="mla_decode_kernel", phases=("paged_decode",), priority=90,
    matches=lambda s: s.latent and _paged_kernel_ok(s),
    when="latent pool + the paged kernel's conditions: absorbed walk over "
         "the live (slot, block) pairs, a block read once for scores and "
         "values (dstpu_mla_decode)",
    runner=_run_mla_decode))

register_program(AttentionProgram(
    name="mla_prefill_kernel", phases=("prefill_chunk",), priority=90,
    matches=lambda s: s.latent and _paged_prefill_ok(s),
    when="latent pool in the in-place form, C % 128 == 0, block % 128 == "
         "0: absorbed flash walk over the blocks under the chunk's frontier "
         "(dstpu_mla_prefill)",
    runner=_run_mla_prefill, work=_mla_prefill_work))

register_program(AttentionProgram(
    name="mla_gather", phases=("paged_decode", "prefill_chunk"), priority=80,
    matches=lambda s: s.latent,
    when="latent pool, fallback: table gather + dense ABSORBED attend over "
         "the whole table (the kernels' oracle; the CPU, a mesh, a chunk "
         "or block off the lane tile)"))


register_program(AttentionProgram(
    name="paged_kernel_quant", phases=("paged_decode",), priority=60,
    matches=lambda s: (_paged_kernel_ok(s) and s.kv_dtype == "int8"
                       and not s.sink),
    when="int8 pool + kernel conditions, no sink logit: streamed tiles "
         "dequantize in-kernel (paged_decode_attention_quant)",
    runner=_run_paged_decode_quant))

register_program(AttentionProgram(
    name="paged_kernel", phases=("paged_decode",), priority=50,
    matches=_paged_kernel_ok,
    when="C == 1, block % 128 == 0, effective context nb*block past the "
         "decode crossover; no alibi, no per-layer local flag (a static "
         "window is the walk's lower bound and a mask; a sink logit its "
         "softmax's initial state; values of their own width and keys in "
         "two leaves as the pool holds them)",
    runner=_run_paged_decode))


def _paged_prefill_ok(site):
    # every disqualifier is a shape, a dtype or a mesh, all in the key: the
    # in-place pool form (a float pool of whole tiles, on a TPU, in a
    # single-device program: head widths of whole lane tiles with it) and
    # lane-tile multiples for the chunk and the block. No crossover: the
    # walk costs what the blocks under the frontier cost.
    return (site.pool_in_place and not site.has_bias and not site.has_window
            and site.block_size % 128 == 0 and site.q_len % 128 == 0)


register_program(AttentionProgram(
    name="paged_prefill_kernel", phases=("prefill_chunk",), priority=50,
    matches=_paged_prefill_ok,
    when="in-place pool form (float pool of whole tiles, TPU, single "
         "device), C % 128 == 0, block % 128 == 0, no alibi, no per-layer "
         "local flag: flash walk over the blocks under the chunk's frontier "
         "and, with a static window, from the block the window begins in; "
         "a sink logit, values of their own width and keys in two leaves "
         "as the decode walk takes them (dstpu_paged_prefill)",
    runner=_run_paged_prefill, work=_paged_prefill_work))

register_program(AttentionProgram(
    name="paged_gather_quant",
    phases=("paged_decode", "prefill_chunk", "verify"), priority=10,
    matches=lambda s: s.kv_dtype == "int8",
    when="int8 pool on the gather path: dequantizing gather oracle "
         "(chunked prefill, verify, CPU/arch-flag fallbacks)"))

register_program(AttentionProgram(
    name="paged_gather",
    phases=("paged_decode", "prefill_chunk", "verify"), priority=0,
    matches=lambda s: True,
    when="fallback: table gather + dense attend over the whole table "
         "(the oracle; spec-decode verify, and chunked prefill wherever "
         "paged_prefill_kernel does not apply: the CPU, a mesh, hd 64, "
         "alibi, a per-layer local flag, a chunk or block off the lane "
         "tile)"))
