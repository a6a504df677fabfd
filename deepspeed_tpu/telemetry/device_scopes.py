"""The device-scope table: which `jax.named_scope` each instruction of a
compiled step program stands under, read from the program's own text.

A device trace names an operation by its HLO instruction (`fusion.474
bf16[24,2048,8192]`), and the name is the compiler's. What the PROGRAM called
the region the instruction came from is in the compiled text and nowhere in
the trace: every instruction of `compiled.as_text()` carries
`metadata={op_name="jit(mixed_step)/while/body/closed_call/mlp/moe/combine/
reduce_sum" ...}` under the instruction name the trace prints. `rows_of`
parses that text into `ScopeRow`s; whoever holds a trace joins them to its
events by (instruction name, opcode, result type) and sums the seconds by
scope (`benchmark/readers/scope_time_share.py` does).

An engine hands its recorder (`StepTrace.scope_provider`) a `ProgramTable`:
the jitted step programs and the ABSTRACT shapes of their arguments — never
an array, never the engine. Nothing is lowered, compiled or parsed until
`StepTrace.device_scopes()` is asked for the rows, and then through the AOT
path (`aot_compile`), which leaves the jit CALL caches alone:
`compile_stats()` reads the same before and after.

`SCOPES` is the ONE list of the scopes the step programs declare, each with
the layer of `PERF.md`'s map it belongs to. `python -m
deepspeed_tpu.telemetry.device_scopes` prints it as the table of
`docs/profiling.md`; `tests/test_device_scopes.py` holds the programs and
the document to it.
"""

import collections
import re
import time

__all__ = ["ScopeRow", "ProgramTable", "SCOPES", "abstract", "aot_compile",
           "scope_of", "rows_of", "segments", "top"]

ScopeRow = collections.namedtuple("ScopeRow", [
    "program",      # the step program, as `compile_stats()` names it
    "name",         # the HLO instruction's name, as a device trace prints it
    "opcode",       # fusion | custom-call | copy | all-gather | ...
    "target",       # a custom call's target ("" otherwise)
    "result",       # the result type, layouts dropped: "bf16[640,4096]"
    "scope",        # the `jax.named_scope` path ("" = the program named none)
    "backward",     # the instruction is part of a transpose (a backward pass)
    "straddles",    # a fusion whose fused instructions come from more than
                    # one top-level scope: its time is booked to `scope`, its
                    # OWN metadata's (XLA gives a fusion its root's), whole
])

# scope -> (layer of PERF.md section 3, what stands under it). A name is what
# ONE `jax.named_scope` pushes; a row's `scope` is a path of them, outermost
# first (`attn_full/kv_pool_write`, `mlp/moe/router`).
SCOPES = {
    # the halves of a layer, and what is around the layers
    "embed": ("model step programs",
              "token (and position) embedding, its multiplier and norm"),
    "attn": ("model step programs",
             "a dense family's attention half: norm, QKV, rope, the write, "
             "the walk, the gate, the out-projection (training: the same "
             "half, flash inside)"),
    "attn_full": ("model step programs",
                  "the attention half of a FULL layer (two-kind pools, the "
                  "hybrid families)"),
    "attn_window": ("model step programs",
                    "the attention half of a WINDOW layer (two-kind pools)"),
    "attn_latent": ("model step programs",
                    "the attention half of a LATENT layer (MLA)"),
    "attn_sparse": ("sparse attention indexer",
                    "the attention half of a SPARSE layer: the full layer's "
                    "half with the indexer's projections, score walk and "
                    "selection between the write and the walk"),
    "ssm": ("recurrent state",
            "a Mamba-2 half: norm, in-projection, convolution, scan or "
            "update, gate norm, out-projection, residual"),
    "gdn": ("recurrent state",
            "a Gated DeltaNet half, as `ssm`"),
    "mlp": ("model step programs",
            "the second half of a layer: residual, norm, the dense MLP or "
            "the experts (`mlp/moe/*`)"),
    "head": ("model step programs",
             "final norm and LM head (serving: of the sampled rows)"),
    "head_loss": ("train step program",
                  "training: the LM head's logits and the cross entropy"),
    "sample": ("model step programs", "the sampler on a call's logits"),
    # a block-diffusion generator's part of a forward (`inference/engine.py::
    # BlockDiffusion`, `step_programs.py::_block_diffusion_steps`)
    "denoise/confidence": ("model step programs",
                           "x0 = argmax and its probability, every row of a "
                           "block forward's logits"),
    "denoise/unmask": ("model step programs",
                       "the unmask rule: ranks, threshold, the rows that "
                       "take x0"),
    "denoise/commit": ("model step programs",
                       "the block loop's state: a committed block to the "
                       "output, the next block's mask tokens, the counters"),
    "optimizer": ("train step program",
                  "unscale, overflow check, gradient norm and clip, the "
                  "update, the loss scale"),
    # inside an attention half
    "qkv": ("model step programs",
            "norm, the fused QKV matmul, q/k norm, rope"),
    "out": ("model step programs", "the attention out-projection"),
    "gate": ("model step programs", "attn * sigmoid(gate) (gated attention)"),
    "walk": ("Pallas kernels",
             "the attention program itself: `dstpu_paged_decode` / "
             "`dstpu_paged_prefill` / `dstpu_mla_*`, or the gather path's "
             "dense attend"),
    "kv_pool_write": ("paged KV pool", "the new rows into the pool"),
    "kv_pool_read": ("paged KV pool",
                     "the gather path's read of a row's whole table"),
    "paged_decode_work": ("Pallas kernels",
                          "the decode walk's work list, once a token"),
    "index_proj": ("sparse attention indexer",
                   "the indexer's query heads, its one key (LayerNorm, "
                   "rope) and its head weights"),
    "index_scores": ("sparse attention indexer",
                     "the score walk over a sequence's cached index keys: "
                     "`dstpu_sparse_index_scores[_decode]`, or the gather "
                     "path's dense scores"),
    "select": ("sparse attention indexer",
               "the exact top-k of a query's scores: `dstpu_sparse_select`, "
               "or its `jax.numpy` twin"),
    "mla/q_proj": ("model step programs", "MLA: q down, norm, up, rope"),
    "mla/kv_down": ("model step programs", "MLA: the latent, norm, rope"),
    "mla/expand": ("model step programs",
                   "MLA whole-sequence form: keys and values from the latent"),
    "mla/absorb": ("model step programs",
                   "MLA paged form: W_kb into the query, W_vb out of the "
                   "result"),
    "mla/out": ("model step programs", "MLA out-projection"),
    # inside a recurrent half
    "in_proj": ("recurrent state", "the half's in-projection(s)"),
    "conv": ("recurrent state",
             "the causal convolution, its tail's read and write, the scan's "
             "inputs"),
    "scan": ("recurrent state", "a chunk: the chunked scan"),
    "update": ("recurrent state",
               "a decode token: `dstpu_ssm_update` / `dstpu_gdn_update`"),
    "out_proj": ("recurrent state", "gate, gate norm, out-projection"),
    # inside `mlp`
    "moe/router": ("routed experts", "scores, top-k, weights"),
    "moe/dispatch": ("routed experts", "sort by expert, the group table"),
    "moe/experts": ("routed experts", "the grouped matmuls `dstpu_moe_gmm`"),
    "moe/combine": ("routed experts", "unsort, the weighted sum over k"),
    "moe/shared_expert": ("routed experts", "the shared expert's SwiGLU"),
    "moe/latent_down": ("routed experts", "LatentMoE: tokens to the latent"),
    "moe/latent_up": ("routed experts", "LatentMoE: the latent back"),
    # the train step's ZeRO collectives where they are the program's own calls
    # (the explicit-collective paths: qwZ / qgZ, the compressed wires). The
    # partitioner's collectives carry the name of the instruction they were
    # made for (`optimizer`, `attn/out`), a sharding constraint's none.
    "zero/param_gather": ("ZeRO sharding and collectives",
                          "stage-3 shards gathered before use"),
    "zero/grad_reduce": ("ZeRO sharding and collectives",
                         "the gradients' reduce over the data domain"),
}

# name-stack entries that are JAX's and not the program's
_STRUCTURAL = frozenset((
    "while", "body", "cond", "closed_call", "core_call", "checkpoint",
    "remat", "rematted_computation", "custom_vjp_call",
    "custom_vjp_call_jaxpr", "custom_jvp_call", "custom_lin", "pjit",
    "shard_map", "xla_call"))
_BRANCH = re.compile(r"^branch_\d+_fun$")
_WRAPPED = re.compile(r"^([A-Za-z_][\w.]*)\((.*)\)$", re.S)


def _split(path):
    """`path` at the slashes outside parentheses: a transform wraps a whole
    name, slashes and all (`transpose(jvp(ssm/in_proj))`)."""
    parts, depth, start = [], 0, 0
    for i, ch in enumerate(path):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
        elif ch == "/" and depth == 0:
            parts.append(path[start:i])
            start = i + 1
    parts.append(path[start:])
    return parts


def _unwrapped(part):
    """(`part` less the transforms that wrap it, whether one of them is a
    `jit(...)`: then what is left is the callee's name)."""
    while True:
        found = _WRAPPED.match(part)
        if not found:
            return part, False
        wrapper, part = found.groups()
        if wrapper in ("jit", "pjit"):
            return part, True


def scope_of(op_name):
    """(scope, backward) of an instruction's `op_name`: the
    `jax.named_scope` path and nothing else. The last component (the
    primitive, or a call's callee) goes; the program's own `jit(...)` at the
    head goes, and a later one ends the path (what follows is the callee's
    own name stack: `ssm/scan/jit(cumsum)/ssm_chunk_scan/...`); the
    control-flow and checkpoint wrappers (`while`, `body`, `cond`,
    `closed_call`, `checkpoint`, `rematted_computation`, `custom_vjp_call*`,
    `branch_<n>_fun`) and an einsum's formula go; `jvp(...)`, `transpose(...)`
    and `vmap(...)` are unwrapped, and a `transpose(` says the instruction is
    of a backward pass. XLA joins the names of instructions it merged with
    `;`: the first speaks."""
    op_name = op_name.split(";")[0]
    kept = []
    for at, part in enumerate(_split(op_name)[:-1]):
        part, called = _unwrapped(part)
        if called:
            if at == 0 or part == "main":
                continue    # the program itself
            break           # a callee's own name stack follows: not ours
        # a transform wraps ONE name of the stack, which may hold structure
        # of its own (`transpose(jvp(while))`)
        for name in _split(part):
            if name and name not in _STRUCTURAL and "->" not in name \
                    and not _BRANCH.match(name):
                kept.append(name)
    return "/".join(kept), "transpose(" in op_name


def top(scope):
    """The outermost scope of a path ("" of none)."""
    return scope.split("/", 1)[0]


def segments(scope, names=SCOPES):
    """`scope` cut into declared names, outermost first, or None where it
    cannot be: a declared name may hold a slash of its own (`moe/router`), so
    the cut is searched. A kernel's own name in the stack (`dstpu_*`, what
    `pallas_call(name=...)` pushes) passes as it is."""
    if not scope:
        return []
    parts = scope.split("/")
    for n in range(len(parts), 0, -1):
        first = "/".join(parts[:n])
        if first in names or (n == 1 and first.startswith("dstpu_")):
            rest = segments("/".join(parts[n:]), names)
            if rest is not None:
                return [first] + rest
    return None


# ----------------------------------------------------------------------
# the compiled text
# ----------------------------------------------------------------------

_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .*\{\s*$")
_INSTRUCTION = re.compile(
    r"^\s+(?:ROOT )?%?([\w.\-]+) = (.+?) ([a-z][a-z0-9\-]*)\(")
_LAYOUT = re.compile(r"\{[^{}]*\}")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_TARGET = re.compile(r'custom_call_target="([^"]+)"')
# what an instruction fuses, applies or wraps (a loop's body and condition and
# a conditional's branches are named by other keys: they, and what a `call`
# calls, run as instructions of their own)
_CALLED = re.compile(
    r"\b(?:calls|to_apply|called_computations)="
    r"\{?(%?[\w.\-]+(?:, ?%?[\w.\-]+)*)")
_OPERAND = re.compile(r"\(%?([\w.\-]+)[,)]")


def _common(scopes):
    """The scope the paths in `scopes` share, outermost first ("" where they
    share none or there is none)."""
    paths = [s.split("/") for s in scopes]
    if not paths:
        return ""
    shared = []
    for names in zip(*paths):
        if len(set(names)) > 1:
            break
        shared.append(names[0])
    return "/".join(shared)


def rows_of(program, text):
    """The `ScopeRow`s of one compiled program's text
    (`compiled.as_text()`): one a top-level instruction — of the entry
    computation, of a loop's body or condition, of a branch — and none for
    what is fused into another (its time is its fusion's) or applied by one
    (a reduction's scalar function).

    An instruction is booked to what its OWN metadata names (XLA gives a
    fusion its root's). One the COMPILER made and gave no metadata at all (a
    multi-output fusion, a layout copy) takes the scope its fused
    instructions share, or else the scope of what made its first operand:
    the region whose values it holds or re-lays."""
    computations, inner = {}, set()
    current = None
    for line in text.splitlines():
        if current is None:
            head = _COMPUTATION.match(line)
            if head:
                current = computations.setdefault(head.group(1), {})
            continue
        if line.startswith("}"):
            current = None
            continue
        found = _INSTRUCTION.match(_LAYOUT.sub("", line.split(
            ", metadata={", 1)[0]))
        if not found:
            continue
        name, result, opcode = found.groups()
        op_name = _OP_NAME.search(line)
        target = _TARGET.search(line) if opcode == "custom-call" else None
        operand = _OPERAND.search(line, max(line.find(f" {opcode}("), 0))
        called = [n.strip().lstrip("%") for names in _CALLED.findall(line)
                  for n in names.split(",")]
        if opcode != "call":
            inner.update(called)
        current[name] = (opcode, target.group(1) if target else "", result,
                         op_name.group(1) if op_name else None,
                         called if opcode == "fusion" else (),
                         operand.group(1) if operand else None)

    def fused(computation, seen):
        """(scope, backward) of every named instruction fused in."""
        for _o, _t, _r, op_name, called, _a in computations.get(
                computation, {}).values():
            if op_name is not None:
                scope = scope_of(op_name)
                if scope[0]:
                    seen.append(scope)
            for c in called:
                fused(c, seen)
        return seen

    def resolve(instructions, name, depth=0):
        """(scope, backward, straddles) of one top-level instruction."""
        opcode, _t, _r, op_name, called, operand = instructions[name]
        inside = []
        for c in called:
            fused(c, inside)
        straddles = len({top(scope) for scope, _b in inside}) > 1
        if op_name is not None:
            return scope_of(op_name) + (straddles,)
        if inside:
            return (_common([scope for scope, _b in inside]),
                    any(b for _s, b in inside), straddles)
        if operand in instructions and depth < 8:
            return resolve(instructions, operand, depth + 1)[:2] + (False,)
        return "", False, False

    rows = []
    for computation, instructions in computations.items():
        if computation in inner:
            continue
        for name, (opcode, target, result, *_rest) in instructions.items():
            rows.append(ScopeRow(program, name, opcode, target, result,
                                 *resolve(instructions, name)))
    return rows


# ----------------------------------------------------------------------
# ONE way to a step program's compiled object
# ----------------------------------------------------------------------

def abstract(args):
    """`args` with every array replaced by its `ShapeDtypeStruct`; what is
    no array becomes one's shape through numpy. The sharding is kept where
    the example is COMMITTED to one: an uncommitted array (a fresh PRNG key)
    is placed by default, a jitted call leaves its sharding unspecified, and
    a lowering that states it is another module — it would miss the call's
    own lowering and executable and compile a second time."""
    import jax

    def sds(x):
        try:
            sharding = getattr(x, "sharding", None) \
                if getattr(x, "committed", True) else None
            return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)
        except Exception:
            import numpy as np
            a = np.asarray(x)
            return jax.ShapeDtypeStruct(a.shape, a.dtype)

    return jax.tree_util.tree_map(sds, args)


def unwatched(fn):
    """The jitted callable under the compile watchdog's `_WatchedProgram`
    (or `fn` itself); None where nothing can be lowered."""
    if not hasattr(fn, "lower"):
        fn = getattr(fn, "fn", fn)
    return fn if hasattr(fn, "lower") else None


def aot_compile(fn, *args):
    """`fn` compiled for the SHAPES of `args`, through the AOT
    `lower().compile()` path: nothing executes, no buffer materializes, and
    the jit CALL cache is untouched (`compile_stats()` reads the same before
    and after). The persistent compilation cache serves it where it holds
    the program. None where `fn` cannot be lowered."""
    fn = unwatched(fn)
    return None if fn is None else fn.lower(*abstract(args)).compile()


class ProgramTable:
    """What a recorder keeps of its engine's step programs: name -> (the
    jitted callable, the abstract shapes of its arguments). Calling it gives
    every program's `ScopeRow`s; each program is lowered, compiled and parsed
    the first time that is asked, and `seconds` says what that took.

    The rows are those of the executable the calls run: a lowering for the
    call's own shapes meets the call's own executable. So after a change of
    SCOPES ALONE a persistent compilation cache that an older source filled
    gives the old names (its key leaves metadata out; rows and trace still
    join, the device runs that same executable): start from an empty cache
    to read the new ones."""

    def __init__(self):
        self._programs = {}     # name -> (fn, abstract args)
        self._rows = {}         # name -> its rows, once asked for
        self.seconds = {}       # name -> lower + compile + parse, seconds

    def add(self, name, fn, args):
        """Keep `fn` (unwrapped) under `name` with the shapes of `args`;
        a program added again (a recompile for other shapes) replaces the
        one before."""
        fn = unwatched(fn)
        if fn is not None:
            self._programs[name] = (fn, abstract(args))
            self._rows.pop(name, None)

    def names(self):
        return list(self._programs)

    def __call__(self):
        rows = []
        for name, (fn, args) in self._programs.items():
            if name not in self._rows:
                t0 = time.perf_counter()
                self._rows[name] = rows_of(
                    name, aot_compile(fn, *args).as_text())
                self.seconds[name] = time.perf_counter() - t0
            rows += self._rows[name]
        return rows


def _markdown():
    lines = ["| scope | layer | what stands under it |", "|---|---|---|"]
    lines += [f"| `{name}` | {layer} | {what} |"
              for name, (layer, what) in SCOPES.items()]
    return "\n".join(lines)


if __name__ == "__main__":
    print(_markdown())
