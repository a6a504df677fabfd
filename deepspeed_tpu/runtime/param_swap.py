"""Parameter spill tier — ZeRO-Infinity params / ZeRO-Inference.

Reference: `runtime/swap_tensor/partitioned_param_swapper.py:36`
(`AsyncPartitionedParameterSwapper`) and the ZeRO-Inference recipe
(`docs/_posts/2022-09-10-zero-inference.md:35`): model weights live on
host RAM or NVMe and stream through device memory layer by layer, so the
servable model size is bounded by disk, not HBM.

TPU-native shape of the same idea:

  * the transformer stack is homogeneous — ONE compiled per-layer function
    is reused for every layer (weights are arguments, not constants);
  * `LayerParamStore` owns the per-layer host copies — "cpu" backend keeps
    them as numpy trees, "nvme" keeps them on disk via the AIO library
    (O_DIRECT, threaded) with a ring of staging slots. Each slot owns its
    OWN aio handle, so waiting for layer i's read to land never barriers
    the deeper read-ahead queued on other slots — that per-slot wait
    granularity is what makes the disk tier genuinely double-buffered.
  * `LayerStreamer` double-buffers host->HBM uploads: while layer i
    computes, layer i+1's `jax.device_put` is already in flight (uploads
    are async under JAX's dispatch model), and the NVMe read for layer i+2
    is queued behind it. HBM never holds more than `lookahead+1` layers of
    weights + the resident (embedding/norm/head) leaves.

The streamer measures the overlap instead of asserting it: every layer
acquisition that finds its buffer already staged records a ~0
`offload/stage_wait_ms`; a genuinely late buffer records the real host
stall. `offload/staging_occupancy` / `offload/inflight_bytes` gauges and
the `stats()` counters (hits, stall_ms_total) give the stall fraction of
docs/offload.md.

The reference needs ~1.8k LoC of swap machinery because every torch param
object must be rewired in place; here a layer's weights are just pytree
arguments to a jitted function, so the whole tier is this file.
"""

import pathlib
import time

import jax
import numpy as np

from deepspeed_tpu.utils.logging import logger


def _tree_bytes(tree):
    return sum(int(np.prod(l.shape)) * np.dtype(l.dtype).itemsize
               for l in jax.tree_util.tree_leaves(tree))


class _StageSlot:
    """One ring slot of the NVMe staging pool: its own aio handle (so its
    completion barrier covers only its own reads), the layer it holds, and
    the aligned host buffers the reads land in."""

    __slots__ = ("swapper", "layer", "bufs", "inflight")

    def __init__(self, swap_folder, threads):
        from deepspeed_tpu.runtime.swap_tensor import AsyncTensorSwapper
        self.swapper = AsyncTensorSwapper(swap_folder, num_threads=threads)
        self.layer = None       # layer index staged (or being read) here
        self.bufs = None        # host leaf buffers for that layer
        self.inflight = False   # read submitted, completion not yet waited

    def wait(self):
        if self.inflight:
            self.swapper.wait()
            self.inflight = False

    def release(self):
        self.swapper.release()


class LayerParamStore:
    """Host/NVMe store of L structurally-identical per-layer param trees.

    `stacked` is a pytree whose leaves carry a leading layer dimension L
    (the model zoo's `params["blocks"]` layout). device="cpu" keeps all L
    trees in host RAM; device="nvme" writes each layer to one file under
    `swap_folder` and serves reads through `staging` ring slots, each with
    its own aio handle and reusable aligned buffers (reference
    `partitioned_param_swapper.py` double-buffering — here with per-slot
    completion, so read-ahead on other slots keeps flowing while one layer
    lands).

    `max_write_bytes` bounds the async write-back queue (`put(blocking=
    False)`): submitted-but-unflushed write bytes past the budget force a
    flush, so the disk tier cannot pin unbounded host RAM behind a slow
    NVMe queue. None = 8 layers' worth; 0 = unbounded (flush per step via
    `flush_writes`)."""

    def __init__(self, stacked, device="cpu", swap_folder=None, staging=3,
                 aio_threads=4, dtype=None, max_write_bytes=None):
        leaves, self.treedef = jax.tree_util.tree_flatten(stacked)
        self.num_layers = int(leaves[0].shape[0])
        assert all(int(l.shape[0]) == self.num_layers for l in leaves), \
            "every stacked leaf must share the leading layer dimension"
        self.device = device
        self.telemetry = None       # optional Telemetry, set by the owner
        cast = (lambda a: a) if dtype is None else (
            lambda a: np.asarray(a).astype(dtype))

        host_layers = []
        for i in range(self.num_layers):
            host_layers.append([cast(np.asarray(l[i])) for l in leaves])
        self.leaf_meta = [(l.shape, l.dtype) for l in host_layers[0]]
        self.layer_bytes = sum(int(np.prod(s)) * np.dtype(d).itemsize
                               for s, d in self.leaf_meta)

        # async-write accounting (both tiers expose the counters so the
        # streamer's inflight gauge has one spelling)
        self.pending_write_bytes = 0
        self.inflight_read_bytes = 0
        self.write_flushes = 0
        if max_write_bytes is None:
            max_write_bytes = 8 * self.layer_bytes
        self.max_write_bytes = int(max_write_bytes)

        if device == "cpu":
            self._layers = host_layers
            self._ring = None
            self._wswapper = None
        elif device == "nvme":
            from deepspeed_tpu.runtime.swap_tensor import AsyncTensorSwapper
            assert swap_folder is not None, "nvme offload needs a swap_folder"
            self._swap_folder = swap_folder
            # initial spill through a throwaway bulk writer
            spill = AsyncTensorSwapper(swap_folder, num_threads=aio_threads)
            for i, layer in enumerate(host_layers):
                for j, arr in enumerate(layer):
                    spill.swap_out(f"layer{i}_leaf{j}", arr)
            spill.wait()
            spill.release()
            self._layers = None
            self._wswapper = None  # created lazily on first put()
            # staging ring: per-slot aio handles split the thread budget so
            # total aio threads stay ~aio_threads regardless of depth
            n_slots = max(2, int(staging))
            per_slot = max(1, aio_threads // n_slots)
            self._ring = [_StageSlot(swap_folder, per_slot)
                          for _ in range(n_slots)]
            logger.info(f"LayerParamStore: {self.num_layers} layers x "
                        f"{self.layer_bytes / 1e6:.1f} MB spilled to "
                        f"{pathlib.Path(swap_folder)} "
                        f"({n_slots} staging slots)")
        else:
            raise ValueError(f"unknown spill device {device!r} (cpu|nvme)")

    @property
    def host_bytes(self):
        """Total host/disk-resident bytes of the spilled tier — the number
        memscope's host column must match EXACTLY (plan_training_from_
        infinity compares against this)."""
        return self.layer_bytes * self.num_layers

    @property
    def inflight_bytes(self):
        """Bytes currently in asynchronous flight through this store:
        queued NVMe reads + submitted-but-unflushed write-back."""
        return self.inflight_read_bytes + self.pending_write_bytes

    # ---- nvme staging ----

    def _slot_for(self, i):
        return self._ring[i % len(self._ring)]

    def prefetch(self, i):
        """Queue the async NVMe read for layer i (no-op on the cpu tier or if
        already staged/in flight). Only the target slot's previous read is
        waited (its buffers are about to be reused); reads on other slots
        stay in flight — the per-slot handles are what make this a
        prefetch, not a barrier."""
        if self._ring is None or not (0 <= i < self.num_layers):
            return
        slot = self._slot_for(i)
        if slot.layer == i:
            return
        if slot.inflight:
            # the slot's previous occupant still has a read in flight — let
            # it land before its buffers are dropped (otherwise the AIO
            # threads would write into freed memory)
            slot.wait()
            self.inflight_read_bytes = max(
                0, self.inflight_read_bytes - self.layer_bytes)
        slot.bufs = [slot.swapper.swap_in(f"layer{i}_leaf{j}", shape, dt)
                     for j, (shape, dt) in enumerate(self.leaf_meta)]
        slot.layer = i
        slot.inflight = True
        self.inflight_read_bytes += self.layer_bytes

    def get(self, i):
        """Host leaf list for layer i. Blocks only on layer i's OWN slot:
        read-ahead queued on other slots keeps flowing while this one
        lands (the old single-handle design paid a global completion
        barrier here, serializing the very overlap prefetch() created)."""
        if self._layers is not None:
            return self._layers[i]
        slot = self._slot_for(i)
        if slot.layer != i:
            self.prefetch(i)
        if slot.inflight:
            slot.wait()
            self.inflight_read_bytes = max(
                0, self.inflight_read_bytes - self.layer_bytes)
        assert slot.layer == i, f"staging ring lost layer {i} (holds {slot.layer})"
        return slot.bufs

    def get_tree(self, i):
        return jax.tree_util.tree_unflatten(self.treedef, self.get(i))

    def put(self, i, leaves, blocking=False):
        """Write layer i's (updated) host leaves back to the store — the
        training-side swap-out (reference `AsyncPartitionedParameterSwapper`
        writes updated fp16 partitions back after the optimizer step).

        Writes go through a SEPARATE swapper so queued read-ahead stays in
        flight (a shared queue would make every put a full barrier). The
        layer's leaves are submitted as ONE batch and budget-checked once
        per layer (not per leaf): with `blocking=False` (default) they
        accumulate against `max_write_bytes` — past the budget the put
        itself flushes, so a slow disk cannot queue unbounded host RAM.
        The caller still runs `flush_writes()` before the next read of this
        layer — the training loop does it once per step, not per layer."""
        leaves = [np.asarray(l) for l in leaves]
        if self._layers is not None:
            self._layers[i] = leaves
            return
        slot = self._slot_for(i)
        if slot.layer == i:
            if slot.inflight:
                # a read of the OLD content is mid-flight into ring buffers
                # under the same names — let it land before the overwrite
                slot.wait()
                self.inflight_read_bytes = max(
                    0, self.inflight_read_bytes - self.layer_bytes)
            slot.layer = slot.bufs = None      # staged copy is now stale
        if self._wswapper is None:
            from deepspeed_tpu.runtime.swap_tensor import AsyncTensorSwapper
            self._wswapper = AsyncTensorSwapper(self._swap_folder)
        for j, arr in enumerate(leaves):
            self._wswapper.swap_out(f"layer{i}_leaf{j}", arr)
        self.pending_write_bytes += self.layer_bytes
        if blocking or (self.max_write_bytes and
                        self.pending_write_bytes > self.max_write_bytes):
            self.flush_writes()

    def flush_writes(self):
        """Barrier on outstanding put() writes (reads are unaffected)."""
        if getattr(self, "_wswapper", None) is not None and \
                self.pending_write_bytes:
            t0 = time.perf_counter()
            self._wswapper.wait()
            self.pending_write_bytes = 0
            self.write_flushes += 1
            tel = self.telemetry
            if tel is not None and getattr(tel, "enabled", False):
                tel.observe("offload/write_flush_ms",
                            (time.perf_counter() - t0) * 1e3)

    def release(self):
        if self._ring is not None:
            for slot in self._ring:
                slot.release()
        if getattr(self, "_wswapper", None) is not None:
            self._wswapper.release()


class LayerStreamer:
    """Async double-buffered host->device staging of `LayerParamStore`
    layers.

    `layer(i)` returns layer i's params on device, having already issued
    the (async) upload of layers i+1..i+lookahead and queued NVMe prefetch
    one step deeper — layer i computes while layer i+1's `jax.device_put`
    and layer i+2's disk read are in flight, so the step never blocks
    except on a genuinely late buffer. `lookahead=0` is the blocking
    baseline (every acquisition is a miss), the arm to compare against.

    `cyclic=True` pins the look-ahead to the scan order of a repeating
    layer walk (decode: L-1 wraps to 0), so the first layer of the next
    pass is already staged when the current pass finishes — without it the
    wrap evicts everything and every pass restarts cold.

    `peak_live_layers` records the high-water mark of simultaneously
    device-resident layers — the HBM working set of the spill tier — for
    tests and memory accounting. With `telemetry` set (any object with the
    Telemetry facade), every acquisition records `offload/stage_wait_ms`
    (0 for a staged hit, the measured host stall otherwise) and refreshes
    the `offload/staging_occupancy` / `offload/inflight_bytes` gauges."""

    def __init__(self, store: LayerParamStore, shardings=None, lookahead=1,
                 cyclic=False, telemetry=None, clock=None):
        self.store = store
        self.lookahead = max(0, int(lookahead))
        self.cyclic = bool(cyclic)
        self.telemetry = telemetry
        self._clock = clock if clock is not None else time.perf_counter
        self._shardings = (None if shardings is None
                           else jax.tree_util.tree_leaves(shardings))
        self._live = {}          # layer idx -> device leaf list
        self.peak_live_layers = 0
        self.uploads = 0
        self.acquires = 0
        self.hits = 0            # layer() calls served from the live window
        self.stall_ms_total = 0.0  # host time blocked making a layer live

    @property
    def depth(self):
        """Staging depth alias: lookahead+1 device buffers in rotation."""
        return self.lookahead + 1

    def _wrap(self, i):
        return i % self.store.num_layers if self.cyclic else i

    def _upload(self, i):
        if i in self._live or not (0 <= i < self.store.num_layers):
            return
        host = self.store.get(i)
        # jax.device_put dispatches asynchronously: the H2D copy overlaps
        # whatever compute is already enqueued — nothing here blocks on it
        if self._shardings is None:
            dev = [jax.device_put(h) for h in host]
        else:
            dev = [jax.device_put(h, s) for h, s in zip(host, self._shardings)]
        self._live[i] = dev
        self.uploads += 1
        self.peak_live_layers = max(self.peak_live_layers, len(self._live))

    def layer(self, i, direction=1):
        """Device param tree for layer i; evicts layers outside the
        look-ahead window and uploads ahead in `direction` (+1 for the
        forward pass, -1 for the reversed backward pass of the Infinity
        trainer). The stall measurement covers ONLY making layer i itself
        available — the deeper uploads/prefetch run after it, unmeasured,
        because they are the overlap machinery, not the stall."""
        self.acquires += 1
        step = 1 if direction >= 0 else -1
        window = {self._wrap(i + d * step) for d in range(self.lookahead + 1)}
        for j in list(self._live):
            # frees the HBM buffers (no other reference remains); the out-
            # of-window check also catches the turn-around between passes
            if j not in window:
                del self._live[j]
        hit = i in self._live
        if hit:
            self.hits += 1
            wait_ms = 0.0
        else:
            t0 = self._clock()
            self._upload(i)
            wait_ms = (self._clock() - t0) * 1e3
            self.stall_ms_total += wait_ms
        # look-ahead uploads (their get() may take a slot's completion
        # barrier), THEN the next NVMe read-ahead so it stays truly async
        for d in range(1, self.lookahead + 1):
            self._upload(self._wrap(i + d * step))
        self.store.prefetch(self._wrap(i + (self.lookahead + 1) * step))
        tel = self.telemetry
        if tel is not None and getattr(tel, "enabled", False):
            tel.observe("offload/stage_wait_ms", wait_ms)
            tel.set_gauge("offload/staging_occupancy", len(self._live))
            tel.set_gauge("offload/inflight_bytes", self.store.inflight_bytes)
        return jax.tree_util.tree_unflatten(self.store.treedef, self._live[i])

    def stats(self):
        """Host-side overlap counters (available
        with telemetry off): acquisitions, staged hits, and the total host
        stall — stall_ms_total / step wall time is the stall fraction."""
        return {"acquires": self.acquires, "hits": self.hits,
                "uploads": self.uploads,
                "hit_rate": self.hits / max(1, self.acquires),
                "stall_ms_total": round(self.stall_ms_total, 3),
                "peak_live_layers": self.peak_live_layers}

    def reset(self):
        self._live.clear()
