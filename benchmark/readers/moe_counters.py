"""Ratios of the routed experts' counters (the program's step ring, over the
measured window). `what`:

  load_max_over_mean   the largest expert's load over the mean load, averaged
                       over router calls by their assignments:
                       E * sum(max load) / sum(assignments); 1 = even
  idle_expert_share    100 * (1 - sum(active experts) / (E * router calls)):
                       experts with no row, over experts there are
"""
import steprings


def sums(obs, subsystem, since=None, until=None):
    """(router calls, assignments, active experts, largest loads) — the
    order of `parallel.moe.ROUTED_COUNTERS` — summed over the window's steps
    that ended in (since, until]; None for a program whose steps carry no
    counters."""
    steps = [s for s in steprings.steps(obs, subsystem)
             if getattr(s, "counters", ())
             and (since is None or since < s.t_end <= until)]
    if not steps:
        return None
    return tuple(sum(s.counters[i] for s in steps) for i in range(4))


def read(obs, trace, args):
    counted = sums(obs, args["subsystem"])
    if counted is None:
        return None
    calls, assignments, active, largest = counted
    experts = obs["config"]["num_experts"]
    if not calls or not assignments:
        return None
    if args["what"] == "load_max_over_mean":
        return experts * largest / assignments
    if args["what"] == "idle_expert_share":
        return 100.0 * (1.0 - active / (experts * calls))
    raise ValueError(f"unknown ratio {args['what']!r}")
