"""Checkpoint save/load with the reference's directory semantics, made
crash-safe.

Reference: `runtime/engine.py:2982` (`save_checkpoint`: tag dirs, `latest` file,
tag-consistency validation) and `:2653` (`load_checkpoint`), with the pluggable
`CheckpointEngine` ABC (`runtime/checkpoint_engine/checkpoint_engine.py:9`).

Layout:
    <save_dir>/<tag>/state/         — orbax (or npz) sharded TrainState
    <save_dir>/<tag>/client.json    — client_state (step counts, scheduler, user keys)
    <save_dir>/<tag>/manifest.json  — integrity manifest (commit marker)
    <save_dir>/latest               — text file with the most recent tag

Crash-safety contract (checkpoint/manifest.py holds the primitives):

  1. state is saved into a `<tag>.tmp` staging dir,
  2. `client.json` + `manifest.json` (per-leaf shapes/dtypes, per-file crc32,
     step, world/mesh shape, framework version) are written and fsynced there,
  3. the staging dir is rename-committed to `<tag>` (atomic on POSIX),
  4. only then does `latest` advance — itself via tempfile+rename.

A kill at ANY point leaves either a committed tag or an orphaned `.tmp` dir
(GC'd by the next save / the doctor CLI); `latest` always names a fully
committed tag. `load_checkpoint` verifies the manifest and walks back through
retained tags to the newest good one on corruption.

The sharded save/restore rides orbax (async-capable, multi-host aware) — the
TPU-native answer to per-rank `zero_pp_rank_*` shard files: the array metadata
carries the sharding, so load-time resharding to a different mesh is native
(what `ds_to_universal.py` needs offline, orbax does on the fly).
"""

import json
import os
import pathlib
import shutil
import threading
import time

import jax

from deepspeed_tpu.checkpoint import manifest as manifest_mod
from deepspeed_tpu.checkpoint.manifest import (CheckpointCorruptionError,
                                               LATEST_FILE, TMP_SUFFIX)
from deepspeed_tpu.utils.logging import logger, log_dist


# Fault-injection points (deepspeed_tpu/testing/faults.py installs hooks here
# to simulate kills at precise moments of the commit protocol):
#   after_state_save — state durable in the staging dir, metadata not yet
#   before_commit    — manifest written, rename-commit not yet executed
#   after_commit     — tag committed, `latest` not yet advanced
_FAULT_HOOKS = {}


def _fire_fault_hook(point, **ctx):
    hook = _FAULT_HOOKS.get(point)
    if hook is not None:
        hook(point=point, **ctx)


class CheckpointEngine:
    """Pluggable engine ABC (reference `checkpoint_engine.py:9`)."""

    def save(self, state, path):
        raise NotImplementedError

    def load(self, path, template):
        raise NotImplementedError

    def commit(self, tag):
        return True


class OrbaxCheckpointEngine(CheckpointEngine):
    """Default: orbax StandardCheckpointer (async-capable, sharding-aware).

    `async_save=True` lets `save()` return as soon as the device arrays are
    snapshotted — serialization runs on orbax's background thread and
    `commit()` (`wait_until_finished`) is the only blocking point, which the
    atomic-commit protocol invokes right before writing the manifest.
    """

    def __init__(self, async_save=False):
        import orbax.checkpoint as ocp
        self._ocp = ocp
        self.async_save = bool(async_save)
        self.checkpointer = ocp.StandardCheckpointer()

    def save(self, state, path):
        self.checkpointer.save(os.path.abspath(path), state, force=True)
        if not self.async_save:
            self.checkpointer.wait_until_finished()

    def load(self, path, template):
        self.checkpointer.wait_until_finished()
        restored = self.checkpointer.restore(os.path.abspath(path), template)
        return restored

    def commit(self, tag):
        self.checkpointer.wait_until_finished()
        return True


def _key_path_str(path):
    """Key path → "params/blocks/attn_qkv_w"-style name (same convention as
    checkpoint/universal.py's _flatten: dict keys and sequence indices as
    path segments, NamedTuple fields by name)."""
    parts = []
    for e in path:
        if hasattr(e, "name"):        # GetAttrKey (NamedTuple / dataclass)
            parts.append(str(e.name))
        elif hasattr(e, "key"):       # DictKey / FlattenedIndexKey
            parts.append(str(e.key))
        elif hasattr(e, "idx"):       # SequenceKey
            parts.append(str(e.idx))
        else:
            parts.append(str(e))
    return "/".join(parts)


def tree_entries(state):
    """Per-leaf {key, shape, dtype} manifest entries (metadata only — reads
    no device buffers)."""
    flat, _ = jax.tree_util.tree_flatten_with_path(state)
    entries = []
    for path, leaf in flat:
        entries.append({
            "key": _key_path_str(path),
            "shape": [int(d) for d in getattr(leaf, "shape", ()) or ()],
            "dtype": str(getattr(leaf, "dtype", type(leaf).__name__)),
        })
    return entries


class NumpyCheckpointEngine(CheckpointEngine):
    """Simple single-host .npz fallback (role of TorchCheckpointEngine).

    Leaves are stored positionally (`arr_i`) for exact template round-trips,
    plus a `keys.json` recording each leaf's key path — that's what lets the
    offline universal converter recover the params/master split from an npz
    checkpoint with no engine or treedef at hand."""

    def save(self, state, path):
        import numpy as np
        flat, treedef = jax.tree_util.tree_flatten_with_path(state)
        arrays = {}
        for i, (_, x) in enumerate(flat):
            arr = np.asarray(jax.device_get(x))
            if arr.dtype.kind == "V":
                # ml_dtypes leaves (bfloat16, fp8) round-trip through npz as
                # raw void — upcast to f32 (exact) and restore the template
                # dtype on load
                arr = arr.astype(np.float32)
            arrays[f"arr_{i}"] = arr
        pathlib.Path(path).mkdir(parents=True, exist_ok=True)
        np.savez(os.path.join(path, "state.npz"), **arrays)
        with open(os.path.join(path, "keys.json"), "w") as f:
            json.dump([_key_path_str(p) for p, _ in flat], f, indent=1)

    def load(self, path, template):
        import numpy as np
        flat_t, treedef = jax.tree_util.tree_flatten(template)
        flat = []
        with np.load(os.path.join(path, "state.npz")) as data:
            for i, t in enumerate(flat_t):
                arr = data[f"arr_{i}"]
                tdt = getattr(t, "dtype", None)
                if tdt is not None and arr.dtype != tdt and arr.dtype.kind != "V":
                    arr = arr.astype(tdt)
                flat.append(arr)
        return jax.tree_util.tree_unflatten(treedef, flat)


class AsyncCheckpointEngine(CheckpointEngine):
    """Async tiered save (reference `NebulaCheckpointEngine`,
    `nebula_checkpoint_engine.py:20`: snapshot fast, persist in background).

    The host copy of the state is taken synchronously (so training can mutate /
    donate device buffers immediately); serialization runs on a worker thread.
    `commit(tag)` blocks until the pending save is durable — the engine-level
    `save_checkpoint` calls it before writing `latest`, preserving the
    reference's "latest is only advanced after persist" semantics.
    """

    def __init__(self, inner: CheckpointEngine):
        self.inner = inner
        self._thread = None
        self._error = None
        self._completions = []

    def add_completion(self, fn):
        """Run `fn()` in the worker after the pending save persists — used for
        metadata whose ordering contract is "only after the state is durable"
        (manifest + rename-commit + the `latest` file)."""
        self._completions.append(fn)

    def save(self, state, path):
        host_state = jax.tree_util.tree_map(
            lambda x: jax.device_get(x) if hasattr(x, "devices") else x, state)
        self.wait()
        completions, self._completions = self._completions, []

        def worker():
            try:
                self.inner.save(host_state, path)
                for fn in completions:
                    fn()
            except Exception as e:  # surfaced on commit/wait
                self._error = e

        self._thread = threading.Thread(target=worker, daemon=True)
        self._thread.start()

    def load(self, path, template):
        self.wait()
        return self.inner.load(path, template)

    def wait(self):
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        if self._error is not None:
            err, self._error = self._error, None
            raise err

    def commit(self, tag):
        self.wait()
        return True


def _make_engine(config):
    name = getattr(config.checkpoint, "engine", "orbax")
    async_save = bool(getattr(config.checkpoint, "async_save", False))
    if name == "numpy":
        eng = NumpyCheckpointEngine()
    else:
        try:
            eng = OrbaxCheckpointEngine(async_save=async_save)
        except ImportError as e:
            logger.warning(f"orbax not importable ({e}); falling back to the "
                           "numpy checkpoint engine")
            eng = NumpyCheckpointEngine()
        except Exception as e:
            logger.warning(f"orbax unavailable ({e}); falling back to numpy engine")
            eng = NumpyCheckpointEngine()
    # orbax has its own async machinery (wired above); thread-wrap only the
    # numpy engine (whether requested or reached via fallback)
    if async_save and isinstance(eng, NumpyCheckpointEngine):
        eng = AsyncCheckpointEngine(eng)
    return eng


def _engine_for(engine):
    """One checkpoint engine per training engine, so async saves overlap
    training and cross-call wait() semantics hold."""
    ck = getattr(engine, "_ckpt_engine", None)
    if ck is None:
        ck = _make_engine(engine.config)
        engine._ckpt_engine = ck
    return ck


def _register_exit_drain(engine):
    """A clean interpreter exit must not abandon an in-flight async save:
    drain it at atexit (registered after orbax/concurrent.futures' own hooks,
    so it runs before them in LIFO order). A failed final save only logs —
    `latest` still names the previous committed tag by construction."""
    if getattr(engine, "_ckpt_exit_drain", None) is not None:
        return
    import atexit
    import weakref
    ref = weakref.ref(engine)

    def _drain():
        e = ref()
        if e is None:
            return
        try:
            wait_pending_save(e)
        except Exception as ex:
            logger.warning(f"final async checkpoint save failed at exit "
                           f"({ex!r}); `latest` still names the previous "
                           "committed tag")

    atexit.register(_drain)
    engine._ckpt_exit_drain = _drain


def get_latest_tag(load_dir):
    """The newest resumable tag: `latest` when it names a committed tag, else
    a scan of tag dirs (newest committed manifest wins) — a missing, empty or
    stale `latest` no longer strands an otherwise-healthy checkpoint root."""
    return manifest_mod.resolve_latest_tag(load_dir)


def wait_pending_save(engine):
    """Block until any in-flight async save (orbax background commit or the
    thread-wrapped numpy engine) is durable AND finalized (manifest written,
    tag committed, `latest` advanced). Re-raises a failed save's error."""
    t = getattr(engine, "_ckpt_pending", None)
    if t is not None:
        t.join()
        engine._ckpt_pending = None
        err = getattr(engine, "_ckpt_pending_error", None)
        engine._ckpt_pending_error = None
        if err is not None:
            raise err
    ck = getattr(engine, "_ckpt_engine", None)
    if isinstance(ck, AsyncCheckpointEngine):
        ck.wait()


def _world_info(engine):
    info = {"process_count": jax.process_count(),
            "device_count": jax.device_count()}
    mesh = getattr(engine, "mesh", None)
    if mesh is not None:
        try:
            info["mesh_shape"] = {str(a): int(s) for a, s in
                                  zip(mesh.axis_names, mesh.devices.shape)}
        except Exception:
            pass
    return info


def _emit_ckpt_events(engine, events):
    # route through the telemetry registry first (when enabled): save
    # latency becomes a `Checkpoint/save_ms` HISTOGRAM with percentiles
    # instead of a last-write-wins scalar
    telem = getattr(engine, "telemetry", None)
    if telem is not None:
        try:
            telem.record_events(events)
        except Exception as e:
            logger.warning(f"checkpoint telemetry events not recorded: {e}")
    mon = getattr(engine, "monitor", None)
    try:
        from deepspeed_tpu.monitor.monitor import write_events_safe
        write_events_safe(mon, events)
    except Exception as e:
        logger.warning(f"checkpoint monitor events not written: {e}")


def save_checkpoint(engine, save_dir, tag=None, client_state=None, save_latest=True):
    tag = tag if tag is not None else f"global_step{engine.global_steps}"
    tag = str(tag)
    save_dir = pathlib.Path(save_dir)
    save_dir.mkdir(parents=True, exist_ok=True)
    wait_pending_save(engine)

    stage_name = tag + TMP_SUFFIX
    if jax.process_index() == 0:
        removed = manifest_mod.gc_orphaned_tmp(save_dir, keep=None)
        if removed:
            logger.warning(f"checkpoint GC: removed orphaned staging dirs "
                           f"{removed} (crashed saves)")
    stage_dir = save_dir / stage_name
    final_dir = save_dir / tag
    stage_dir.mkdir(parents=True, exist_ok=True)

    ck_engine = _engine_for(engine)
    state_path = stage_dir / "state"
    entries = tree_entries(engine.state)
    world = _world_info(engine)
    step = int(engine.global_steps)
    engine_name = type(getattr(ck_engine, "inner", ck_engine)).__name__
    client = dict(client_state or {})
    t0 = time.monotonic()
    ckpt_cfg = getattr(engine.config, "checkpoint", None)
    keep_last_n = int(getattr(ckpt_cfg, "keep_last_n", 0) or 0)

    def finalize():
        """Runs once the state is durable in the staging dir. Order matters:
        metadata -> manifest -> rename-commit -> latest -> retention."""
        total_bytes = 0
        if jax.process_index() == 0:
            _fire_fault_hook("after_state_save", tag=tag, stage_dir=str(stage_dir))
            with open(stage_dir / "client.json", "w") as f:
                json.dump(client, f, indent=2, default=str)
            m = manifest_mod.write_manifest(
                stage_dir, tag=tag, step=step, tree=entries, world=world,
                engine=engine_name,
                extra={"framework_version": _framework_version()})
            total_bytes = m["total_bytes"]
            _fire_fault_hook("before_commit", tag=tag, stage_dir=str(stage_dir))
            aside = None
            if final_dir.exists():
                # re-save under an existing tag: rename the committed copy
                # aside (atomic) rather than rmtree'ing it — a kill between
                # the two renames leaves the old copy recoverable as a .tmp
                # orphan instead of destroying the only committed tag
                aside = save_dir / (tag + ".old" + TMP_SUFFIX)
                if aside.exists():
                    shutil.rmtree(aside)
                os.replace(final_dir, aside)
            os.replace(stage_dir, final_dir)       # COMMIT point
            manifest_mod.fsync_dir(save_dir)
            if aside is not None:
                shutil.rmtree(aside, ignore_errors=True)
            _fire_fault_hook("after_commit", tag=tag, ckpt_dir=str(final_dir))
            # ship the consolidation script next to `latest` at the save_dir
            # root (reference engine.py:3366 copies zero_to_fp32.py into the
            # save dir so `python zero_to_fp32.py . out` works in place)
            try:
                from deepspeed_tpu.checkpoint import zero_to_fp32 as _z2f
                shutil.copyfile(_z2f.__file__, save_dir / "zero_to_fp32.py")
            except Exception as e:
                logger.warning(f"could not ship zero_to_fp32.py: {e}")
            if save_latest:
                # ordering contract: `latest` only advances after the commit
                manifest_mod.atomic_write_text(save_dir / LATEST_FILE, tag)
            if keep_last_n > 0:
                latest_tag = tag if save_latest else get_latest_tag(save_dir)
                dropped = manifest_mod.retention_gc(
                    save_dir, keep_last_n, protect=(tag, latest_tag))
                if dropped:
                    log_dist(f"checkpoint retention (keep_last_n="
                             f"{keep_last_n}): removed {dropped}", ranks=[0])
        engine._last_ckpt_dir = str(save_dir)
        save_ms = (time.monotonic() - t0) * 1000.0
        _emit_ckpt_events(engine, [
            ("Checkpoint/save_ms", save_ms, step),
            ("Checkpoint/bytes", float(total_bytes), step),
            ("Checkpoint/last_good_step", float(step), step),
        ])
        log_dist(f"saved checkpoint {tag} to {final_dir} "
                 f"({total_bytes / 2**20:.1f} MiB, {save_ms:.0f} ms)", ranks=[0])

    if isinstance(ck_engine, AsyncCheckpointEngine):
        # finalization (incl. commit + `latest`) runs on the worker after
        # persist; save() returns as soon as the host snapshot is taken
        _register_exit_drain(engine)
        ck_engine.add_completion(finalize)
        ck_engine.save(engine.state, str(state_path))
    elif getattr(ck_engine, "async_save", False):
        # orbax async: the device snapshot is taken synchronously inside
        # save(); a finalizer thread blocks on orbax's background commit
        # (`wait_until_finished` — only at commit time) and then finalizes
        _register_exit_drain(engine)
        ck_engine.save(engine.state, str(state_path))

        def _commit_and_finalize():
            try:
                ck_engine.commit(tag)
                finalize()
            except Exception as e:
                engine._ckpt_pending_error = e

        engine._ckpt_pending_error = None
        engine._ckpt_pending = threading.Thread(target=_commit_and_finalize,
                                                daemon=True)
        engine._ckpt_pending.start()
    else:
        ck_engine.save(engine.state, str(state_path))
        ck_engine.commit(tag)
        finalize()
    return str(final_dir)


def _framework_version():
    try:
        import deepspeed_tpu
        return deepspeed_tpu.__version__
    except Exception:
        return "unknown"


def _load_prefixes(load_optimizer_states, load_module_only):
    """Which manifest-tree key prefixes must match the restore template: a
    partial load only consumes a subset of the state, so only that subset
    gates validation."""
    if load_module_only:
        return ("params", "master")
    if not load_optimizer_states:
        return ("params", "master", "step", "scaler")
    return None  # full structural match


def _candidate_tags(load_dir, tag):
    """Requested (or latest) tag first, then every other committed tag newest
    first — the rollback-on-corruption walk order."""
    cands = []
    if tag is not None:
        cands.append(str(tag))
    else:
        lt = get_latest_tag(load_dir)
        if lt is not None:
            cands.append(lt)
    for t, _step in manifest_mod.committed_tags(load_dir):
        if t not in cands:
            cands.append(t)
    return cands


def load_checkpoint(engine, load_dir, tag=None, load_optimizer_states=True,
                    load_module_only=False):
    wait_pending_save(engine)
    load_dir = pathlib.Path(load_dir)
    candidates = _candidate_tags(load_dir, tag)
    if not candidates:
        logger.warning(f"no checkpoint found in {load_dir} (no '{LATEST_FILE}' "
                       "file and no committed tag dirs)")
        return None, None

    ck_engine = _engine_for(engine)
    ckpt_cfg = getattr(engine.config, "checkpoint", None)
    deep = bool(getattr(ckpt_cfg, "verify_checksums", True))
    template_tree = tree_entries(engine.state)
    prefixes = _load_prefixes(load_optimizer_states, load_module_only)
    discarded = []

    for cand in candidates:
        ckpt_dir = load_dir / cand
        if not ckpt_dir.exists():
            if tag is not None and cand == str(tag):
                # an explicitly requested tag that simply isn't there is a
                # caller error, not corruption — substituting a different
                # tag here would silently load state the caller never asked
                # for (the corruption walk below only covers tags that
                # EXIST but fail validation)
                logger.warning(f"checkpoint dir {ckpt_dir} does not exist")
                return None, None
            discarded.append((cand, ["directory does not exist"]))
            continue
        m = manifest_mod.read_manifest(ckpt_dir)
        if m is None:
            # legacy pre-manifest checkpoint: accept, but only as the
            # primary candidate (never walk back INTO an unverifiable dir)
            if cand is not candidates[0]:
                discarded.append((cand, ["no manifest (legacy layout)"]))
                continue
            logger.warning(f"checkpoint {ckpt_dir} has no manifest (legacy "
                           "layout): loading without integrity verification")
        else:
            ok, errors = manifest_mod.verify_manifest(
                ckpt_dir, template_tree=template_tree, deep=deep,
                template_prefixes=prefixes)
            if not ok:
                discarded.append((cand, errors))
                logger.warning(
                    f"checkpoint {ckpt_dir} failed integrity verification "
                    f"({len(errors)} error(s): {errors[:3]}...); walking back "
                    "to an older tag")
                continue
        try:
            restored = ck_engine.load(str(ckpt_dir / "state"), engine.state)
        except Exception as e:
            discarded.append((cand, [f"restore failed: {e!r}"]))
            logger.warning(f"checkpoint {ckpt_dir} failed to restore "
                           f"({e!r}); walking back to an older tag")
            continue

        if load_module_only:
            engine.state = engine.state._replace(params=restored.params,
                                                 master=restored.master)
        elif not load_optimizer_states:
            engine.state = engine.state._replace(params=restored.params,
                                                 master=restored.master,
                                                 step=restored.step,
                                                 scaler=restored.scaler)
        else:
            engine.state = restored

        client_state = {}
        client_file = ckpt_dir / "client.json"
        if client_file.exists():
            with open(client_file) as f:
                client_state = json.load(f)
        if m is not None and client_state.get("global_steps") is not None \
                and int(client_state["global_steps"]) != int(m.get("step", -1)):
            logger.warning(
                f"checkpoint {cand}: manifest step {m.get('step')} != "
                f"client_state global_steps {client_state['global_steps']}")
        if discarded:
            names = [c for c, _ in discarded]
            logger.warning(f"recovered from {cand} after discarding corrupted/"
                           f"unusable tag(s) {names}")
            _emit_ckpt_events(engine, [
                ("Recovery/discarded_tags", float(len(discarded)),
                 int(engine.global_steps)),
            ])
        engine._last_ckpt_dir = str(load_dir)
        log_dist(f"loaded checkpoint {cand} from {ckpt_dir}", ranks=[0])
        return str(ckpt_dir), client_state

    detail = "; ".join(f"{c}: {errs[0]}" for c, errs in discarded[:5])
    raise CheckpointCorruptionError(
        f"no loadable checkpoint in {load_dir}: every retained tag failed "
        f"validation ({detail})")
