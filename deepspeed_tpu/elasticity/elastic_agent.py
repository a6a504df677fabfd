"""Elastic agent — restart-on-membership-change supervision with a
restart-cause taxonomy, per-cause budgets, backoff, and resume-tag
negotiation.

Reference: `elasticity/elastic_agent.py:28` (`DSElasticAgent`, a torch-elastic
agent subclass that restarts worker groups when the rendezvous membership
changes, injecting DeepSpeed env).

TPU analog: there is no torch-elastic; recovery is supervised restart. The agent
runs a training callable (or subprocess) in a loop; when it exits with a
membership-change/failure condition, the agent re-reads the resource view,
validates the new world size against the elastic config
(`compute_elastic_config`, elasticity.py), negotiates the resume tag (newest
COMMITTED checkpoint — a mid-save crash leaves `latest` at the previous good
tag, see checkpoint/saver.py), and restarts — orbax restores the reshardable
checkpoint onto whatever mesh now exists.

Restart causes are classified so budgets can differ: a flapping pod slice
(membership) deserves more patience than a training loop that keeps producing
NaNs (bad_state) — the latter restarting forever would burn the pod on a bug.
"""

import inspect
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional

from deepspeed_tpu.elasticity.elasticity import (compute_elastic_config,
                                                 ElasticityIncompatibleWorldSize)
from deepspeed_tpu.elasticity.restart_policy import RestartBudget, RestartPolicy
from deepspeed_tpu.runtime.sentinel import BadStateError
from deepspeed_tpu.utils.logging import logger


class MembershipChanged(Exception):
    """Raised by a worker (or watcher) when the device/host membership changed."""


class RestartCause:
    """Why the previous attempt ended — the agent's restart taxonomy."""
    MEMBERSHIP = "membership_change"
    BAD_STATE = "bad_state"
    CRASH = "crash"
    INADMISSIBLE = "inadmissible_world"
    ALL = (MEMBERSHIP, BAD_STATE, CRASH, INADMISSIBLE)


def classify_failure(exc) -> str:
    if isinstance(exc, MembershipChanged):
        return RestartCause.MEMBERSHIP
    if isinstance(exc, BadStateError):
        return RestartCause.BAD_STATE
    return RestartCause.CRASH


@dataclass
class AgentSpec:
    """What the agent supervises.

    `run_fn(world_size, micro_batch[, resume_tag])` — the training entry; must
    resume from the negotiated checkpoint tag itself (engine.load_checkpoint).
    The third parameter is optional: the agent passes the negotiated tag only
    when the callable accepts it.
    `world_size_fn()` — current resource view (e.g. len of reachable hosts ×
    chips/host); re-queried before every (re)start.
    `checkpoint_dir` — checkpoint root for resume-tag negotiation (None: the
    run_fn manages resume on its own).
    `max_restarts` — global budget; `max_restarts_per_cause` overrides per
    RestartCause key (unlisted causes fall back to the global budget).
    Backoff between restarts is exponential (`restart_backoff_s` base,
    `backoff_factor` growth, capped at `max_backoff_s`) with proportional
    jitter so a pod of agents doesn't stampede the scheduler in lockstep.
    """
    run_fn: Callable
    world_size_fn: Callable[[], int]
    ds_config: dict
    max_restarts: int = 100
    restart_backoff_s: float = 5.0
    backoff_factor: float = 2.0
    max_backoff_s: float = 300.0
    backoff_jitter: float = 0.1
    max_restarts_per_cause: Dict[str, int] = field(default_factory=dict)
    checkpoint_dir: Optional[str] = None
    monitor: Any = None
    on_restart: Optional[Callable[[int], None]] = None


class ElasticAgent:
    """Supervises one training job with elastic world-size revalidation."""

    def __init__(self, spec: AgentSpec):
        self.spec = spec
        # budget/backoff live in the shared RestartBudget (restart_policy.py);
        # the agent keeps its historical surface (`restarts`,
        # `restart_causes`, `last_cause`) as views onto it
        self.budget = RestartBudget(RestartPolicy(
            max_restarts=spec.max_restarts,
            base_backoff_s=spec.restart_backoff_s,
            backoff_factor=spec.backoff_factor,
            max_backoff_s=spec.max_backoff_s,
            jitter=spec.backoff_jitter,
            per_cause=dict(spec.max_restarts_per_cause)))
        self.budget.causes.update({c: 0 for c in RestartCause.ALL})
        self.last_resume_tag: Optional[str] = None
        self._run_fn_takes_tag = self._accepts_resume_tag(spec.run_fn)

    @property
    def restarts(self) -> int:
        return self.budget.restarts

    @restarts.setter
    def restarts(self, n: int):
        self.budget.restarts = n

    @property
    def restart_causes(self) -> Dict[str, int]:
        return self.budget.causes

    @property
    def last_cause(self) -> Optional[str]:
        return self.budget.last_cause

    @staticmethod
    def _accepts_resume_tag(fn):
        try:
            params = list(inspect.signature(fn).parameters.values())
        except (TypeError, ValueError):
            return False
        if any(p.kind == inspect.Parameter.VAR_POSITIONAL for p in params):
            return True
        positional = [p for p in params if p.kind in
                      (inspect.Parameter.POSITIONAL_ONLY,
                       inspect.Parameter.POSITIONAL_OR_KEYWORD)]
        return len(positional) >= 3

    def _admissible(self, world_size):
        """(final_batch, micro_batch) for this world size, or raises."""
        final_batch, _valid, micro = compute_elastic_config(
            self.spec.ds_config, world_size=world_size, return_microbatch=True)
        return final_batch, micro

    def _negotiate_resume_tag(self):
        """Newest committed (manifest-carrying) tag in the checkpoint root —
        NOT simply the `latest` pointer, which a crash may have left stale or
        missing. Validation of content happens at load; this picks the tag
        every restarting worker will agree on."""
        if self.spec.checkpoint_dir is None:
            return None
        try:
            from deepspeed_tpu.checkpoint.saver import get_latest_tag
            tag = get_latest_tag(self.spec.checkpoint_dir)
        except Exception as e:
            logger.warning(f"elastic agent: resume-tag negotiation failed "
                           f"({e}); run_fn must resolve resume itself")
            return None
        self.last_resume_tag = tag
        return tag

    def _backoff_delay(self):
        return self.budget.next_delay()

    def _consume_restart(self, cause):
        ok = self.budget.consume(cause)
        self._emit_restart_events()
        if not ok:
            cap = self.spec.max_restarts_per_cause.get(cause)
            if cap is not None and self.restart_causes[cause] > cap:
                logger.error(f"elastic agent: restart budget for cause "
                             f"'{cause}' exhausted ({cap})")
            else:
                logger.error("elastic agent: global restart budget exhausted")
        return ok

    def _emit_restart_events(self):
        from deepspeed_tpu.monitor.monitor import write_events_safe
        events = [("Recovery/restarts_total", float(self.restarts), self.restarts)]
        events += [(f"Recovery/restarts/{c}", float(n), self.restarts)
                   for c, n in self.restart_causes.items() if n]
        write_events_safe(self.spec.monitor, events)

    def _pause_then_continue(self, cause):
        """Account the restart against its cause's budget; back off. Returns
        False when budgets are exhausted (the run loop then gives up)."""
        if not self._consume_restart(cause):
            return False
        if self.spec.on_restart is not None:
            self.spec.on_restart(self.restarts)
        delay = self._backoff_delay()
        if delay > 0:
            logger.info(f"elastic agent: backing off {delay:.1f}s before "
                        f"restart #{self.restarts} (cause: {cause})")
        time.sleep(delay)
        return True

    def run(self):
        """Run until clean exit or restart budget exhausted. Returns True on
        clean completion."""
        while True:
            world = self.spec.world_size_fn()
            try:
                final_batch, micro = self._admissible(world)
            except ElasticityIncompatibleWorldSize:
                # wait for the resource view to move into the valid set
                logger.warning(f"elastic agent: world size {world} inadmissible; "
                               "waiting for an admissible resource view")
                if not self._pause_then_continue(RestartCause.INADMISSIBLE):
                    return False
                continue

            resume_tag = self._negotiate_resume_tag()
            logger.info(f"elastic agent: starting run | world={world} "
                        f"batch={final_batch} micro={micro} "
                        f"resume_tag={resume_tag} restart #{self.restarts}")
            try:
                if self._run_fn_takes_tag:
                    self.spec.run_fn(world, micro, resume_tag)
                else:
                    self.spec.run_fn(world, micro)
                return True
            except Exception as e:
                cause = classify_failure(e)
                logger.warning(f"elastic agent: worker ended ({e!r}); "
                               f"cause={cause}; restarting from checkpoint")
            if not self._pause_then_continue(cause):
                return False
