"""Experiment monitors — TensorBoard / WandB / CSV behind one interface.

Reference: `deepspeed/monitor/monitor.py:29` (`MonitorMaster` fanning out to
TensorBoardMonitor/WandbMonitor/csvMonitor, configs `monitor/config.py:15-63`).
Events are `(tag, value, step)` tuples, written only from process 0.
"""

import csv
import os
import pathlib

from deepspeed_tpu.utils.logging import logger


def _rank():
    try:
        import jax
        return jax.process_index()
    except Exception:
        return 0


class Monitor:
    def __init__(self, config):
        self.enabled = bool(getattr(config, "enabled", False))

    def write_events(self, event_list):
        raise NotImplementedError

    def close(self):
        """Release writer resources; safe to call more than once."""


class TensorBoardMonitor(Monitor):
    def __init__(self, config):
        super().__init__(config)
        self.summary_writer = None
        if self.enabled and _rank() == 0:
            try:
                from torch.utils.tensorboard import SummaryWriter
                log_dir = os.path.join(config.output_path or "./runs", config.job_name)
                self.summary_writer = SummaryWriter(log_dir=log_dir)
            except Exception as e:
                logger.warning(f"tensorboard unavailable: {e}")
                self.enabled = False

    def write_events(self, event_list, flush=True):
        if self.summary_writer is None:
            return
        for name, value, step in event_list:
            self.summary_writer.add_scalar(name, value, step)
        if flush:
            self.summary_writer.flush()

    def close(self):
        if self.summary_writer is not None:
            try:
                self.summary_writer.close()
            except Exception as e:
                logger.warning(f"tensorboard close failed: {e}")
            self.summary_writer = None


class WandbMonitor(Monitor):
    def __init__(self, config):
        super().__init__(config)
        self.run = None
        if self.enabled and _rank() == 0:
            try:
                import wandb
                self.run = wandb.init(project=config.project, group=config.group,
                                      entity=config.team)
            except Exception as e:
                logger.warning(f"wandb unavailable: {e}")
                self.enabled = False

    def write_events(self, event_list):
        if self.run is None:
            return
        import wandb
        for i, (name, value, step) in enumerate(event_list):
            # never-die: a dropped network must not crash the caller (same
            # contract write_events_safe documents — but wandb is the only
            # backend that talks to a REMOTE service per event, so it guards
            # its own loop too: callers going through MonitorMaster directly
            # are just as exposed)
            try:
                wandb.log({name: value}, step=step)
            except Exception as e:
                logger.warning(f"wandb log failed ({e}); dropping the "
                               f"remaining {len(event_list) - i} events")
                break

    def close(self):
        if self.run is not None:
            try:
                self.run.finish()
            except Exception as e:
                logger.warning(f"wandb finish failed: {e}")
            self.run = None


class CsvMonitor(Monitor):
    def __init__(self, config):
        super().__init__(config)
        self._files = {}    # tag -> (handle, csv.writer): opened once per tag
        if self.enabled and _rank() == 0:
            self.output_path = pathlib.Path(config.output_path or "./csv_monitor") / config.job_name
            self.output_path.mkdir(parents=True, exist_ok=True)
        else:
            self.enabled = False

    def write_events(self, event_list):
        if not self.enabled:
            return
        for name, value, step in event_list:
            entry = self._files.get(name)
            if entry is None:
                fname = self.output_path / (name.replace("/", "_") + ".csv")
                new = not fname.exists()
                f = open(fname, "a", newline="")
                w = csv.writer(f)
                if new:
                    w.writerow(["step", name])
                entry = self._files[name] = (f, w)
            f, w = entry
            w.writerow([step, value])
            f.flush()

    def close(self):
        for f, _w in self._files.values():
            try:
                f.close()
            except Exception:
                pass
        self._files = {}

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


def write_events_safe(monitor, event_list):
    """Best-effort event emission: the ONE guarded entry point for every
    caller that must never die on a monitoring failure — checkpoint/recovery
    paths (Checkpoint/save_ms, Recovery/restarts_total by cause, ...), the
    serving scheduler (Serving/*), and the telemetry monitor bridge. These
    run from contexts where no monitor may exist at all (async save
    finalizer threads, the elastic agent supervisor), so both the lookup and
    the write are guarded, unlike MonitorMaster.write_events."""
    if monitor is None or not getattr(monitor, "enabled", False):
        return
    try:
        monitor.write_events(list(event_list))
    except Exception as e:
        logger.warning(f"monitor event emission failed: {e}")


class MonitorMaster(Monitor):
    """Fans events out to every enabled monitor (reference same name)."""

    def __init__(self, ds_config):
        self.tb_monitor = TensorBoardMonitor(ds_config.tensorboard)
        self.wandb_monitor = WandbMonitor(ds_config.wandb)
        self.csv_monitor = CsvMonitor(ds_config.csv_monitor)
        self.enabled = (self.tb_monitor.enabled or self.wandb_monitor.enabled
                        or self.csv_monitor.enabled)

    def write_events(self, event_list):
        if _rank() != 0:
            return
        for m in (self.tb_monitor, self.wandb_monitor, self.csv_monitor):
            if m.enabled:
                m.write_events(event_list)

    def close(self):
        for m in (self.tb_monitor, self.wandb_monitor, self.csv_monitor):
            try:
                m.close()
            except Exception:
                pass
