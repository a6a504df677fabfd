"""The program's own step and request rings
(`deepspeed_tpu/telemetry/steptrace.py`), cut to the window a run measured.

The drivers hand the readers no engine, so the rings are found through the
program's module-level accessor, in the same process, after the run: the
newest recorder of a subsystem is the engine the driver built last. A program
that has no such module or no recorder (the parent of the PR that added
them) gives empty lists, and the readers leave their metric out. Stamps are `time.perf_counter`, the clock the drivers stamp with.
"""


def _ring(subsystem):
    try:
        from deepspeed_tpu.telemetry import steptrace
    except ImportError:
        return None
    return steptrace.latest(subsystem)


def window(obs):
    """(since, until]: the serving window as the driver opened and closed
    it; in training, from its opening to the end of its last step."""
    if "closed" in obs:
        return obs["opened"], obs["closed"]
    return obs["opened"], obs["step_spans"][-1][1]


def steps(obs, subsystem):
    """Step records that ended inside the window."""
    ring = _ring(subsystem)
    return [] if ring is None else ring.records(*window(obs))


def requests(obs, subsystem, stamp):
    """One record a request whose `stamp` lies inside the window."""
    ring = _ring(subsystem)
    return [] if ring is None else ring.requests(*window(obs), stamp=stamp)
