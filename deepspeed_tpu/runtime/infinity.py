"""ZeRO-Infinity training: train models whose parameters exceed HBM.

Reference: `runtime/swap_tensor/partitioned_param_swapper.py:36` +
`zero/stage3.py` NVMe integration — in training, ZeRO-Infinity keeps the
fp16 parameters AND the fp32 optimizer state on host RAM / NVMe; each layer's
weights stream into device memory right before use (forward and again in
backward), gradients stream out, and the optimizer step runs on host CPU
while the accelerator computes.

TPU-native shape:
  * bit16 working weights live in a `LayerParamStore` (host or NVMe tier);
    `LayerStreamer` double-buffers layer uploads through the forward loop
    and again (reversed) through the backward loop;
  * HBM holds: resident leaves (embed/norms/head), `lookahead+1` layer
    blocks, and the layer-boundary activations [L, B, T, D] — NOT the model;
  * backward is layer-at-a-time `jax.vjp` with in-layer recomputation (the
    boundary activation is the only saved tensor per layer — same memory
    shape as `jax.checkpoint` full remat);
  * each layer's gradient is fetched to host and fed to a per-layer
    `HostOffloadOptimizer` (the C++ OpenMP Adam, `csrc/cpu_optim`) whose
    fp32 master + moments never touch the device; the updated bit16 layer
    is written straight back to the store (the reference's swap-out);
  * one jitted block fn + one jitted block-vjp serve every layer.

This is the capability the reference's "train/serve models 10-100x beyond
device memory" claims rest on; the inference half lives in
`inference/zero_inference.py`.
"""

import types

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.runtime.cpu_optimizer import HostOffloadOptimizer
from deepspeed_tpu.runtime.offload_staging import HostwardPipe
from deepspeed_tpu.runtime.param_swap import LayerParamStore, LayerStreamer
from deepspeed_tpu.utils.logging import log_dist
from deepspeed_tpu.utils.tree import tree_cast


class InfinityEngine:
    """Layer-streaming trainer over a LayeredModelSpec (train fns required).

    `offload_device`: "cpu" | "nvme" for the bit16 weights;
    `optimizer_nvme_path`: optionally push the per-layer Adam moments to
    NVMe too (the full ZeRO-Infinity tier);
    `lookahead`: staging depth of the async double-buffered upload pool
    (0 = the blocking baseline — every layer acquisition stalls);
    `landing_depth`: how many layers' grad flats may be in device->host
    flight at once (the backward-direction half of the overlap);
    `telemetry`: a TelemetryConfig — enables the `offload/*` staging
    metrics (stage-wait, occupancy, in-flight bytes) and per-step export;
    `checkpoint`: a CheckpointConfig for `save_checkpoint` (engine,
    keep_last_n, checksum verification — checkpoint/saver.py)."""

    def __init__(self, spec, lr=1e-3, betas=(0.9, 0.999), eps=1e-8,
                 weight_decay=0.0, dtype=jnp.bfloat16, offload_device="cpu",
                 nvme_path=None, optimizer_nvme_path=None, lookahead=1,
                 optimizer="adam", adamw_mode=True, lr_schedule=None,
                 micro_batch_size=None, gradient_accumulation_steps=1,
                 gradient_clipping=0.0, training_data=None, collate_fn=None,
                 seed=1234, fp16=False, static_loss_scale=None,
                 initial_scale_power=16, loss_scale_window=1000,
                 min_loss_scale=1.0, hysteresis=2,
                 consecutive_hysteresis=False, landing_depth=None,
                 max_write_bytes=None, telemetry=None, checkpoint=None):
        assert spec.layer_train_fn is not None and spec.train_loss_fn is not None, \
            "InfinityEngine needs a LayeredModelSpec with train fns " \
            "(models.gpt.make_gpt_layered_model provides them)"
        self.spec = spec
        self.micro_batch_size = micro_batch_size
        self.gas = max(1, int(gradient_accumulation_steps))
        self.dtype = jnp.dtype(dtype)
        from deepspeed_tpu.telemetry import Telemetry
        self.telemetry = Telemetry(telemetry, subsystem="infinity")
        # minimal config surface for checkpoint/saver.py's free functions
        # (engine.config.checkpoint drives the checkpoint-engine choice;
        # this tier's state is a host-side numpy pytree, so default to the
        # npz engine rather than orbax)
        self.config = types.SimpleNamespace(
            checkpoint=(checkpoint if checkpoint is not None else
                        types.SimpleNamespace(engine="numpy",
                                              async_save=False)),
            telemetry=telemetry)
        self.monitor = None
        self.resident = jax.device_put(tree_cast(spec.resident, self.dtype))
        self.store = LayerParamStore(tree_cast(spec.blocks, self.dtype),
                                     device=offload_device,
                                     swap_folder=nvme_path,
                                     max_write_bytes=max_write_bytes)
        self.store.telemetry = self.telemetry
        self.streamer = LayerStreamer(self.store, lookahead=lookahead,
                                      telemetry=self.telemetry)
        self.landing_depth = max(1, int(landing_depth
                                        if landing_depth is not None
                                        else max(1, lookahead)))
        # hostward (grad-landing) stall accounting across the per-pass
        # pipes, so a stall share can count BOTH directions
        self.hostward_wait_ms_total = 0.0
        self.hostward_bytes_total = 0
        self.L = self.store.num_layers

        # fp32 masters + moments on host, one optimizer per layer + resident.
        # Masters come straight from spec.blocks (full init precision, no
        # store round-trip — on the nvme tier that would be a whole-model
        # write-then-read before step 0, and fp32(bit16(w)) would lose the
        # init's low bits).
        opt_kw = dict(lr=lr, betas=betas, eps=eps, weight_decay=weight_decay,
                      optimizer=optimizer, adamw_mode=adamw_mode,
                      lr_schedule=lr_schedule)
        # per-layer slicing INSIDE the loop: at most one extra layer of fp32
        # exists transiently (the tier exists because the model exceeds
        # memory; a list of all slices would peak at ~2x whole-model fp32
        # on top of the optimizers' own master copies)
        block_leaves = jax.tree_util.tree_leaves(spec.blocks)
        self.layer_opts = []
        for i in range(self.L):
            layer_i = jax.tree_util.tree_unflatten(
                self.store.treedef,
                [np.asarray(l[i], np.float32) for l in block_leaves])
            self.layer_opts.append(HostOffloadOptimizer(
                layer_i,
                nvme_folder=(f"{optimizer_nvme_path}/layer{i}"
                             if optimizer_nvme_path else None), **opt_kw))
            del layer_i
        self.resident_opt = HostOffloadOptimizer(
            # dstpu: ignore[DT001]: tier build, runs once — the resident host master starts from a device pull
            jax.device_get(tree_cast(spec.resident, jnp.float32)),
            nvme_folder=(f"{optimizer_nvme_path}/resident"
                         if optimizer_nvme_path else None), **opt_kw)

        # fp16 dynamic loss scaling (VERDICT r4 item 6 — reference supports
        # stage-3 + offload with dynamic scaling, `zero/stage3.py:1999`).
        # The scale rides the head-VJP seed (grads leave the device
        # pre-multiplied; the returned loss stays unscaled), the host divides
        # it back out of the grad flats, and the all-finite check runs on the
        # host flats BEFORE any layer's optimizer steps — fp16 therefore
        # forces the two-phase (accumulate-then-step) schedule, trading the
        # backward/step overlap for skip-step correctness, exactly like
        # gradient clipping does. The schedule itself is the shared
        # `precision.LossScaler` (hysteresis, window, min scale — one
        # implementation for both tiers), driven eagerly here.
        from deepspeed_tpu.runtime.precision import LossScaler
        self.fp16 = bool(fp16)
        self._scaler = LossScaler(static_scale=static_loss_scale,
                                  initial_scale_power=initial_scale_power,
                                  loss_scale_window=loss_scale_window,
                                  hysteresis=hysteresis,
                                  consecutive_hysteresis=consecutive_hysteresis,
                                  min_loss_scale=min_loss_scale,
                                  enabled=self.fp16)
        self._scale_state = self._scaler.init()  # scale == 1.0 when disabled

        layer_fn = spec.layer_train_fn
        loss_fn = spec.train_loss_fn

        self._block = jax.jit(layer_fn)

        def block_vjp(p, x_in, positions, g_out):
            _, pull = jax.vjp(lambda p_, x_: layer_fn(p_, x_, positions),
                              p, x_in)
            g_p, g_x = pull(g_out)
            return g_p, g_x

        self._block_vjp = jax.jit(block_vjp)

        def head(res, x, labels, seed):
            loss, pull = jax.vjp(lambda r, x_: loss_fn(r, x_, labels), res, x)
            # the loss-scale rides the VJP seed: grads leave pre-multiplied,
            # the RETURNED loss stays unscaled
            g_res, g_x = pull(jnp.asarray(seed, loss.dtype))
            return loss, g_res, g_x

        self._head = jax.jit(head)

        def embed_vjp(res, toks, positions, g_x0):
            _, pull = jax.vjp(lambda r: spec.embed_fn(r, toks, positions), res)
            (g_res,) = pull(g_x0)
            return g_res

        self._embed = jax.jit(spec.embed_fn)
        self._embed_vjp = jax.jit(embed_vjp)
        self._add = jax.jit(lambda a, b: jax.tree_util.tree_map(
            lambda x, y: x + y, a, b))
        # grads leave the device as ONE fused fp32 vector per tree: one
        # large device->host transfer per layer instead of one per leaf
        self._flatten = jax.jit(lambda tree: jnp.concatenate(
            [jnp.ravel(l).astype(jnp.float32)
             for l in jax.tree_util.tree_leaves(tree)]))
        self.gradient_clipping = float(gradient_clipping or 0.0)
        self.last_grad_norm = None
        # dataloader (reference engine training_data contract): batches of
        # micro_batch x gas rows per train_batch() call
        self.training_dataloader = None
        self._data_iterator = None
        if training_data is not None:
            from deepspeed_tpu.runtime.dataloader import TpuDataLoader
            bs = (micro_batch_size or 1) * self.gas
            self.training_dataloader = TpuDataLoader(
                training_data, bs, collate_fn=collate_fn, shuffle=True,
                seed=seed)
        self.step_count = 0
        log_dist(f"infinity engine: {spec.name} L={self.L} "
                 f"layer_mb={self.store.layer_bytes/1e6:.1f} "
                 f"weights={offload_device} "
                 f"opt={'nvme' if optimizer_nvme_path else 'host'}", ranks=[0])

    @property
    def cur_scale(self):
        """Current loss scale (reference `engine.cur_scale` spelling)."""
        return float(self._scale_state.scale)

    @cur_scale.setter
    def cur_scale(self, value):
        self._scale_state = self._scale_state._replace(
            scale=jnp.asarray(float(value), jnp.float32))

    @property
    def skipped_steps(self):
        return int(self._scale_state.overflows)

    @staticmethod
    def _unflatten_host(flat, shapes):
        out, off = [], 0
        for shape in shapes:
            n = int(np.prod(shape)) if shape else 1
            out.append(np.asarray(flat[off:off + n]).reshape(shape))
            off += n
        return out

    def _layer_step_host(self, i, flat):
        """Host optimizer step for layer i from a host fp32 grad flat; bit16
        write-back to the store (async under the store's write budget — the
        disk write of layer i overlaps layer i-1's backward)."""
        g_host = self._unflatten_host(flat, [s for s, _ in self.store.leaf_meta])
        g_tree = jax.tree_util.tree_unflatten(self.store.treedef, g_host)
        new_master = self.layer_opts[i].step(g_tree)
        self.store.put(i, [np.asarray(l).astype(self.store.leaf_meta[j][1])
                           for j, l in enumerate(
                               jax.tree_util.tree_leaves(new_master))])

    def _micro_pass(self, inputs, labels, acc, res_acc, mode):
        """One micro-batch forward+backward. `mode`:
        "apply"      — gas==1: each layer's host Adam runs overlapped inside
                       the backward loop;
        "accumulate" — non-final gas micro: host grad flats accumulate into
                       `acc`/`res_acc` (weights stay constant, as
                       accumulation semantics require);
        "finalize"   — FINAL gas micro: each layer's mean grad
                       (acc[i]+flat)/gas steps the host Adam inside the same
                       overlapped pipeline, and acc[i] is freed as consumed —
                       overlap is preserved and accumulator memory falls
                       layer by layer through the last backward."""
        B, T = inputs.shape
        positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None],
                                     (B, T))
        x = self._embed(self.resident, inputs, positions)
        boundaries = []
        for i in range(self.L):
            boundaries.append(x)
            x = self._block(self.streamer.layer(i), x, positions)

        loss, g_res, g_x = self._head(self.resident, x, labels,
                                      jnp.asarray(self.cur_scale, jnp.float32))

        # backward: stream layers in reverse. No reset first: layer L-1's
        # device copy from the forward is exactly what the backward needs;
        # the direction-aware eviction window handles the turn-around.
        # Layer i's grad flat is submitted to the hostward pipe the moment
        # its vjp is enqueued — copy_to_host_async dispatches the D2H copy
        # behind it — and lands `landing_depth` layers later, so the host
        # optimizer (and the write-back) overlaps the device backward while
        # the transfer itself overlaps the NEXT layer's vjp (the tier's
        # raison d'etre; a late transfer's stall is measured in
        # offload/hostward_wait_ms, not hidden).
        pipe = HostwardPipe(depth=self.landing_depth,
                            telemetry=self.telemetry)
        for i in reversed(range(self.L)):
            p = self.streamer.layer(i, direction=-1)
            g_p, g_x = self._block_vjp(p, boundaries[i], positions, g_x)
            for k, flat in pipe.submit(i, self._flatten(g_p)):
                self._consume(acc, mode, k, flat)
        for k, flat in pipe.drain():
            self._consume(acc, mode, k, flat)
        self.hostward_wait_ms_total += pipe.wait_ms_total
        self.hostward_bytes_total += pipe.bytes_total

        g_res = self._add(g_res, self._embed_vjp(self.resident, inputs,
                                                 positions, g_x))
        # dstpu: ignore[DT001]: ZeRO-Infinity tier — the resident grad flat accumulates in host RAM by design
        res_flat = np.asarray(jax.device_get(self._flatten(g_res)))
        if res_acc is None:
            res_acc = res_flat.copy()  # device_get arrays are read-only
        else:
            res_acc += res_flat
        return float(loss), res_acc

    def _consume(self, acc, mode, i, flat):
        """Consume layer i's LANDED host grad flat (the hostward pipe did
        the device->host transfer asynchronously)."""
        if mode == "apply":
            self._layer_step_host(i, flat)
            return
        if mode == "finalize":
            mean = (acc[i] + flat) / self.gas
            acc[i] = None  # accumulator memory falls as the backward proceeds
            self._layer_step_host(i, mean)
        elif acc[i] is None:
            acc[i] = flat.copy()  # landed arrays are read-only views
        else:
            acc[i] += flat

    def train_batch(self, batch=None, data_iter=None):
        """One full step over the GLOBAL batch (micro_batch x gas rows, like
        the main engine): streamed forward/backward per micro-batch, host
        optimizer steps on the mean gradient at the gas boundary, bit16
        write-back, resident update last. Returns the mean loss.

        With `gradient_clipping` set, the step runs in two phases: grads
        accumulate on host through every micro-pass; once the backward
        completes, the per-layer norms² are summed into the GLOBAL norm and
        the host Adam steps apply the clip scale layer by layer. The cost: the
        optimizer work no longer overlaps the device backward (the scale
        depends on every layer's grad) — correctness over overlap when
        clipping is requested (reference stage-3 + offload clips the same
        global norm)."""
        if batch is None:
            it = data_iter
            if it is None and self.training_dataloader is not None:
                if self._data_iterator is None:
                    from deepspeed_tpu.runtime.dataloader import RepeatingLoader
                    self._data_iterator = iter(
                        RepeatingLoader(self.training_dataloader))
                it = self._data_iterator
            assert it is not None, \
                "train_batch needs a batch or data_iter/training_data"
            batch = next(it)
        tokens = np.asarray(batch.get("tokens", batch.get("input_ids")))
        labels = batch.get("labels")
        if labels is None:
            inputs, labels = tokens[:, :-1], tokens[:, 1:]
        else:
            inputs = tokens
        inputs = jnp.asarray(inputs, jnp.int32)
        labels = jnp.asarray(labels, jnp.int32)
        B, T = inputs.shape
        assert B % self.gas == 0, (
            f"global batch {B} not divisible by "
            f"gradient_accumulation_steps={self.gas}")
        mbs = B // self.gas
        if self.micro_batch_size is not None:
            assert mbs == self.micro_batch_size, (
                f"global batch of {B} with gas={self.gas} implies micro "
                f"batch {mbs}, engine configured for {self.micro_batch_size}")

        clip = self.gradient_clipping
        # two-phase (accumulate, then step): needed whenever NO update may
        # run before a whole-model property of the grads is known — the
        # global norm for clipping, all-finiteness for the fp16 skip-step
        two_phase = clip > 0 or self.fp16
        acc = [None] * self.L
        res_acc = None
        losses = []
        for m in range(self.gas):
            sl = slice(m * mbs, (m + 1) * mbs)
            if two_phase:
                mode = "accumulate"
            elif self.gas == 1:
                mode = "apply"
            else:
                mode = "finalize" if m == self.gas - 1 else "accumulate"
            loss, res_acc = self._micro_pass(inputs[sl], labels[sl], acc,
                                             res_acc, mode)
            losses.append(loss)
        loss = float(np.mean(losses))

        # the scale the micro-passes SEEDED their VJPs with — snapshot before
        # the scaler update mutates it (unscaling with a grown scale would
        # silently shrink one update per window)
        used_scale = self.cur_scale
        if self.fp16:
            # host-side all-finite check on the (still scale-multiplied) grad
            # flats BEFORE any optimizer state or stored weight changes —
            # reference FP16_Optimizer.step overflow semantics; the halve /
            # hysteresis / window-grow schedule is the shared LossScaler
            finite = bool(np.isfinite(res_acc).all()) and all(
                bool(np.isfinite(a).all()) for a in acc)
            self._scale_state = self._scaler.update(
                self._scale_state, jnp.asarray(finite))
            if not finite:
                log_dist(f"fp16 overflow: step skipped, "
                         f"loss scale -> {self.cur_scale:.1f}", ranks=[0])
                self.streamer.reset()
                return float(loss)

        # mean grads carry gas micro-passes AND the fp16 loss scale
        denom = self.gas * used_scale
        g_res_flat = res_acc / denom

        scale = 1.0
        if two_phase:
            if clip > 0:
                sq = float(np.dot(g_res_flat, g_res_flat))
                for i in range(self.L):
                    mean_i = acc[i] / denom
                    sq += float(np.dot(mean_i, mean_i))
                total_norm = float(np.sqrt(sq))
                self.last_grad_norm = total_norm
                scale = min(1.0, clip / max(total_norm, 1e-12))
            for i in range(self.L):
                self._layer_step_host(i, acc[i] * (scale / denom))
                acc[i] = None
            g_res_flat = g_res_flat * scale

        self.streamer.reset()  # device copies are stale after write-back
        self.store.flush_writes()  # one barrier per step, not per layer

        res_leaves = jax.tree_util.tree_leaves(self.resident)
        g_res_host = jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(self.resident),
            self._unflatten_host(g_res_flat,
                                 [l.shape for l in res_leaves]))
        new_res_master = self.resident_opt.step(g_res_host)
        self.resident = jax.device_put(tree_cast(new_res_master, self.dtype))
        self.step_count += 1
        self.telemetry.maybe_export(self.step_count)
        return float(loss)

    @property
    def peak_param_hbm_bytes(self):
        return self.streamer.peak_live_layers * self.store.layer_bytes

    def offload_stats(self):
        """Host-side overlap counters,
        available with telemetry off. The two directions are reported
        SEPARATELY on purpose: `staging.stall_ms_total` (device-ward) is
        a pure transfer-lateness signal — acquiring a layer never waits
        on compute — while `hostward_wait_ms_total` is measured at the
        host's one sync point with the device stream per layer, so it
        includes the producing vjp's in-flight compute by construction;
        summing them into one "stall" would double-count compute as
        transfer."""
        return {"staging": self.streamer.stats(),
                "hostward_wait_ms_total": round(self.hostward_wait_ms_total,
                                                3),
                "hostward_bytes_total": self.hostward_bytes_total,
                "write_flushes": self.store.write_flushes,
                "landing_depth": self.landing_depth,
                "lookahead": self.streamer.lookahead}

    def memory_plan(self, capacity_bytes=0):
        """The memscope training plan priced from THE LIVE TIER: the host
        params column is byte-identical to the `LayerParamStore`, the
        device staging column to the streamer's `lookahead+1` window
        (telemetry/memscope.py `plan_training_from_infinity`)."""
        from deepspeed_tpu.telemetry.memscope import plan_training_from_infinity
        return plan_training_from_infinity(self, capacity_bytes=capacity_bytes)

    # ---- checkpointing (checkpoint/saver.py free functions; the commit
    # protocol, validated rollback-walking loads, retention and the fault
    # hooks all come from there — this tier only defines what "state" is) --

    @property
    def global_steps(self):
        return self.step_count

    @property
    def state(self):
        """Host snapshot pytree: fp32 masters + moments + loss-scale
        bookkeeping. The bit16 store is DERIVED state (bit16(master)) —
        rebuilt by the setter on load, so a checkpoint holds one copy of
        the truth and never needs to read the (possibly disk-resident)
        store."""
        return {"layer_opts": [o.state_dict() for o in self.layer_opts],
                "resident_opt": self.resident_opt.state_dict(),
                "step": int(self.step_count),
                "scale": float(self.cur_scale),
                "good_steps": int(self._scale_state.good_steps),
                "overflows": int(self._scale_state.overflows),
                "hysteresis_left": int(self._scale_state.hysteresis_left)}

    @state.setter
    def state(self, s):
        for i, sd in enumerate(s["layer_opts"]):
            opt = self.layer_opts[i]
            opt.load_state_dict(sd)
            # bit16 write-back: the store content is derived from the master
            self.store.put(i, [np.asarray(l).astype(self.store.leaf_meta[j][1])
                               for j, l in enumerate(opt.master)])
        self.store.flush_writes()
        self.resident_opt.load_state_dict(s["resident_opt"])
        res_master = jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(self.resident),
            self.resident_opt.master)
        self.resident = jax.device_put(tree_cast(res_master, self.dtype))
        self.streamer.reset()           # device copies are stale
        self.step_count = int(np.asarray(s["step"]))
        from deepspeed_tpu.runtime.precision import LossScaleState
        self._scale_state = LossScaleState(
            scale=jnp.asarray(float(np.asarray(s["scale"])), jnp.float32),
            good_steps=jnp.asarray(int(np.asarray(s["good_steps"])), jnp.int32),
            overflows=jnp.asarray(int(np.asarray(s["overflows"])), jnp.int32),
            hysteresis_left=jnp.asarray(
                int(np.asarray(s["hysteresis_left"])), jnp.int32))

    def save_checkpoint(self, save_dir, tag=None, client_state=None,
                        save_latest=True):
        """Atomic-commit checkpoint of the tier's host state (PR 2
        protocol: stage -> manifest -> rename-commit -> latest). The async
        write-back queue is flushed FIRST: a snapshot must never race its
        own in-flight disk writes — that ordering is what keeps a mid-step
        crash during async write-back recoverable (the manifest only ever
        describes a quiesced store)."""
        self.store.flush_writes()
        from deepspeed_tpu.checkpoint import saver
        client = dict(client_state or {})
        client.setdefault("global_steps", int(self.step_count))
        return saver.save_checkpoint(self, save_dir, tag=tag,
                                     client_state=client,
                                     save_latest=save_latest)

    def load_checkpoint(self, load_dir, tag=None):
        """Validated restore with the corruption rollback walk
        (checkpoint/saver.py): checksum-verified manifest, newest good tag
        wins. Full-state loads only — this tier's masters/moments ARE the
        model, partial loads have nothing to stand on."""
        from deepspeed_tpu.checkpoint import saver
        return saver.load_checkpoint(self, load_dir, tag=tag)

    def release(self):
        self.telemetry.close()
        self.store.release()
