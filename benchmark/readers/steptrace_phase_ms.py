"""Time the program's step ring books under the named `phases` — or, with
`complement`, the steps' wall time LESS it — over the steps that ended inside
the window: `per: "step"` the mean a step in milliseconds, `per: "wall"` 100 x
its share of those steps' wall time. With `phases: ["serving/read_back"]`
(the host blocked on a device call's output, and nothing else) the complement
is the host's own work. A program whose steps never enter a named phase (the
parent of the PR that split the wait from the work) gives None."""
import steprings


def read(obs, trace, args):
    steps = steprings.steps(obs, args["subsystem"])
    names = set(args["phases"])
    if not any(name in names for s in steps for name, _ in s.phases):
        return None
    wall = sum(s.t_end - s.t_start for s in steps)
    total = sum(sec for s in steps for name, sec in s.phases if name in names)
    if args.get("complement"):
        total = wall - total
    if args["per"] == "step":
        return 1e3 * total / len(steps)
    return 100.0 * total / wall if wall else None
