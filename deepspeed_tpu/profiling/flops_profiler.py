"""Flops profiler.

Reference: `profiling/flops_profiler/profiler.py:28` — module hooks + patched
torch.nn.functional counting MACs/latency per module, tree report, auto-invoked
from the engine at `flops_profiler_profile_step`.

TPU-native: XLA already knows the exact flop count of the compiled program —
`jitted.lower(...).compile().cost_analysis()` exposes `flops`,
`bytes accessed`, and `optimal_seconds`. The profiler wraps any jitted callable
(or the engine's train step) and reports program-level numbers plus derived
utilization against the chip's peak.

Per-module tree (the reference's `print_model_profile` MACs/latency tree):
`ModuleProfile` cost-analyzes each submodule function separately (lowered
with abstract ShapeDtypeStructs — no weights materialize) and assembles a
depth-limited tree with flops/MACs/params and the share of the whole model;
`gpt_module_profile` wires the GPT zoo's block structure (embed / N x
{attn, mlp} / lm_head) into it.
"""

import time

import numpy as np

from deepspeed_tpu.utils.logging import logger


def cost_analysis(fn, *args, **kwargs):
    """Compile `fn` for the given args and return XLA's cost analysis dict."""
    import jax
    # dstpu: ignore[DT004]: the profiler's job is a fresh lower+compile — it MEASURES compilation, it doesn't serve from it
    jitted = fn if hasattr(fn, "lower") else jax.jit(fn)
    compiled = jitted.lower(*args, **kwargs).compile()
    try:
        analyses = compiled.cost_analysis()
        analysis = analyses[0] if isinstance(analyses, (list, tuple)) else analyses
    except Exception as e:
        logger.warning(f"cost_analysis unavailable: {e}")
        analysis = {}
    return dict(analysis or {})


class FlopsProfiler:
    """Program-level flops/latency profiler (reference class name/API subset:
    start_profile / stop_profile / get_total_flops / print_model_profile)."""

    def __init__(self, model=None, ds_engine=None):
        self.engine = ds_engine
        self.analysis = {}
        self.measured_seconds = None
        self.started = False
        self.module_tree = None    # ModuleProfile root (set_module_tree)

    def set_module_tree(self, tree):
        """Attach a ModuleProfile tree (e.g. `gpt_module_profile(cfg)`) so
        print_model_profile renders the reference's per-module breakdown."""
        self.module_tree = tree

    def start_profile(self, ignore_list=None):
        self.started = True
        self._t0 = time.perf_counter()

    def stop_profile(self):
        if self.started:
            self.measured_seconds = time.perf_counter() - self._t0
            self.started = False

    def profile_fn(self, fn, *args, n_timing_runs=3, **kwargs):
        """Cost-analyze + wall-clock a jitted callable."""
        import jax
        self.analysis = cost_analysis(fn, *args, **kwargs)
        # dstpu: ignore[DT004]: one-shot profiling wrapper — lives for exactly n_timing_runs calls
        jitted = fn if callable(getattr(fn, "lower", None)) else jax.jit(fn)
        out = jitted(*args, **kwargs)          # compile+warm
        jax.tree_util.tree_map(lambda x: None, out)
        t0 = time.perf_counter()
        for _ in range(n_timing_runs):
            out = jitted(*args, **kwargs)
        flat = jax.tree_util.tree_leaves(out)
        if flat:
            np.asarray(jax.device_get(flat[0])).sum()  # completion fence
        self.measured_seconds = (time.perf_counter() - t0) / n_timing_runs
        return out

    def get_total_flops(self, as_string=False):
        f = self.analysis.get("flops", 0.0)
        return _num_to_string(f) + "FLOPS" if as_string else f

    def get_total_macs(self, as_string=False):
        f = self.get_total_flops() / 2
        return _num_to_string(f) + "MACs" if as_string else f

    def get_total_duration(self, as_string=False):
        d = self.measured_seconds or self.analysis.get("optimal_seconds", 0.0)
        return f"{d*1e3:.2f} ms" if as_string else d

    def get_total_params(self, as_string=False):
        n = 0
        if self.engine is not None:
            from deepspeed_tpu.utils.tree import tree_num_params
            n = tree_num_params(self.engine.state.params)
        return _num_to_string(n) if as_string else n

    def print_model_profile(self, profile_step=1, module_depth=-1, top_modules=1,
                            detailed=True, output_file=None):
        flops = self.get_total_flops()
        dur = self.get_total_duration()
        from deepspeed_tpu.platform.device import DEVICE_PEAKS, device_kind
        peaks = DEVICE_PEAKS.get(device_kind())
        achieved = flops / dur if dur else 0.0
        of_peak = (f"{100 * achieved / (peaks.bf16_tflops * 1e12):.1f}% of "
                   f"{device_kind()} peak" if peaks is not None else
                   f"no published peak on file for {device_kind()!r}")
        lines = [
            "-------------------------- DeepSpeed-TPU Flops Profiler --------------------------",
            f"profile step:                   {profile_step}",
            f"params:                         {self.get_total_params(as_string=True)}",
            f"flops per step:                 {_num_to_string(flops)}FLOPS",
            f"step latency:                   {dur*1e3:.2f} ms",
            f"achieved:                       {achieved/1e12:.2f} TFLOPS "
            f"({of_peak})",
            f"bytes accessed:                 {_num_to_string(self.analysis.get('bytes accessed', 0))}B",
        ]
        if detailed and self.module_tree is not None:
            tree_secs = None
            if dur and flops:
                # attribute the measured step time to the fwd tree by its
                # share of the program's total flops (bwd+update included in
                # `flops`, so the fwd tree gets its proportional slice)
                tree_secs = dur * self.module_tree.total_flops / flops
            lines.append("per-module (fwd flops, est. latency):")
            lines.extend(self.module_tree.render(module_depth=module_depth,
                                                 total_seconds=tree_secs))
        lines.append(
            "----------------------------------------------------------------------------------")
        report = "\n".join(lines)
        if output_file:
            with open(output_file, "w") as f:
                f.write(report)
        else:
            logger.info("\n" + report)
        return report

    def end_profile(self):
        self.stop_profile()


def get_model_profile(model, input_shape=None, args=(), kwargs=None, print_profile=True,
                      detailed=True, module_depth=-1, top_modules=1, warm_up=1,
                      as_string=True, output_file=None, ignore_modules=None):
    """Reference `get_model_profile` — profile a callable outside the engine.
    `model` is a jittable fn; `args` its example inputs."""
    prof = FlopsProfiler()
    prof.profile_fn(model, *args, **(kwargs or {}))
    if print_profile:
        prof.print_model_profile(detailed=detailed, module_depth=module_depth,
                                 top_modules=top_modules, output_file=output_file)
    flops = prof.get_total_flops(as_string=as_string)
    macs = prof.get_total_macs(as_string=as_string)
    params = prof.get_total_params(as_string=as_string)
    return flops, macs, params


class ModuleProfile:
    """One node of the per-module profile tree (reference
    `flops_profiler/profiler.py:28` prints this per torch module; here each
    node is a jittable submodule function cost-analyzed in isolation)."""

    def __init__(self, name, flops=0.0, params=0, multiplier=1, children=()):
        self.name = name
        self.flops = float(flops)        # per instance
        self.params = int(params)        # per instance
        self.multiplier = multiplier     # e.g. n_layer for a block node
        self.children = list(children)

    @classmethod
    def of(cls, name, fn, abstract_args, multiplier=1, params=0):
        """Cost-analyze `fn` lowered against ShapeDtypeStructs."""
        import jax
        # dstpu: ignore[DT004]: abstract cost analysis — lowered against ShapeDtypeStructs once, never executed
        analysis = cost_analysis(jax.jit(fn), *abstract_args)
        return cls(name, analysis.get("flops", 0.0), params, multiplier)

    @property
    def total_flops(self):
        return self.multiplier * (self.flops +
                                  sum(c.total_flops for c in self.children))

    @property
    def total_params(self):
        return self.multiplier * (self.params +
                                  sum(c.total_params for c in self.children))

    def render(self, total=None, depth=0, module_depth=-1, total_seconds=None):
        """Depth-limited lines; with `total_seconds` (a measured fwd walltime)
        each node also shows its flops-proportional latency estimate — the
        reference profiler's per-module latency column (`profiler.py:28`),
        attributed by share instead of per-hook timers."""
        total = total or self.total_flops or 1.0
        pct = 100.0 * self.total_flops / total
        mult = f" x{self.multiplier}" if self.multiplier > 1 else ""
        lat = ""
        if total_seconds:
            lat = f", ~{1e3 * total_seconds * self.total_flops / total:.2f} ms"
        lines = [f"{'  ' * depth}{self.name}{mult}: "
                 f"{_num_to_string(self.total_flops)}FLOPS "
                 f"({_num_to_string(self.total_flops / 2)}MACs, {pct:.1f}%)"
                 + (f", {_num_to_string(self.total_params)}params"
                    if self.total_params else "") + lat]
        if module_depth < 0 or depth < module_depth:
            for c in self.children:
                lines.extend(c.render(total, depth + 1, module_depth,
                                      total_seconds))
        return lines


def gpt_module_profile(cfg, batch_size=1, seq_len=None):
    """Per-module flops tree for a GPT-zoo config: embed / blocks x L
    {attn, mlp} / lm_head — the reference's per-module report for its
    injected transformer. Everything lowers abstractly (no weights)."""
    import jax
    import jax.numpy as jnp
    from jax import ShapeDtypeStruct as S
    from deepspeed_tpu.models import gpt as G

    T = seq_len or min(cfg.max_seq_len, 512)
    B, D, L = batch_size, cfg.d_model, cfg.n_layer
    shapes = jax.eval_shape(G.gpt_init_fn(cfg, dtype=jnp.dtype(cfg.dtype)),
                            jax.random.PRNGKey(0))
    blocks = shapes["blocks"]
    layer = jax.tree_util.tree_map(lambda s: S(s.shape[1:], s.dtype), blocks)
    resident = {k: S(v.shape, v.dtype) for k, v in shapes.items()
                if k != "blocks"}
    x = S((B, T, D), jnp.dtype(cfg.dtype))
    toks = S((B, T), jnp.int32)
    pos = S((B, T), jnp.int32)

    def nparams(tree):
        import numpy as _np
        return sum(int(_np.prod(s.shape))
                   for s in jax.tree_util.tree_leaves(tree))

    def attn_fn(x, p, positions):
        return G._attn_half(x, p, cfg, positions)[0]

    def mlp_fn(x, p):
        return G._mlp(x, p, cfg)

    def embed_fn(res, toks, pos):
        return G._embed(res, toks, pos, cfg)

    def head_fn(res, x):
        return G._lm_head(res, x, cfg)

    attn_keys = [k for k in layer if k.startswith(("attn_", "ln1"))]
    mlp_keys = [k for k in layer if k.startswith(("mlp_", "ln2"))]
    block_node = ModuleProfile(
        "block", multiplier=L,
        children=[
            ModuleProfile.of("attn", attn_fn, (x, layer, pos),
                             params=nparams({k: layer[k] for k in attn_keys})),
            ModuleProfile.of("mlp", mlp_fn, (x, layer),
                             params=nparams({k: layer[k] for k in mlp_keys})),
        ])
    # param attribution: the head weight (untied) and final norm belong to the
    # lm_head node, everything else resident (wte/wpe/emb norms) to embed
    head_keys = [k for k in resident if k.startswith(("lm_head", "lnf"))]
    embed_params = nparams({k: v for k, v in resident.items()
                            if k not in head_keys})
    root = ModuleProfile(getattr(cfg, "name", "gpt"), children=[
        ModuleProfile.of("embed", embed_fn, (resident, toks, pos),
                         params=embed_params),
        block_node,
        ModuleProfile.of("lm_head", head_fn, (resident, x),
                         params=nparams({k: resident[k] for k in head_keys})),
    ])
    return root


def _num_to_string(num, precision=2):
    if num >= 1e12:
        return f"{num/1e12:.{precision}f} T"
    if num >= 1e9:
        return f"{num/1e9:.{precision}f} G"
    if num >= 1e6:
        return f"{num/1e6:.{precision}f} M"
    if num >= 1e3:
        return f"{num/1e3:.{precision}f} K"
    return f"{num:.{precision}f} "
