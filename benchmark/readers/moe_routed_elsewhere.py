"""100 * the router's assignments whose expert is NOT held on this chip, over
all its assignments (the program's step ring, over the measured window): the
fifth routed counter, `moe_routed_elsewhere`, beside `moe_assignments`, which
counts the held experts' rows. A program whose steps carry fewer than five
counters (it holds every expert) gives None."""
import steprings

HELD, ELSEWHERE = 1, 4      # places in `parallel.moe.HELD_ROUTED_COUNTERS`


def read(obs, trace, args):
    steps = [s for s in steprings.steps(obs, args["subsystem"])
             if len(getattr(s, "counters", ())) > ELSEWHERE]
    held = sum(s.counters[HELD] for s in steps)
    elsewhere = sum(s.counters[ELSEWHERE] for s in steps)
    if not held + elsewhere:
        return None
    return 100.0 * elsewhere / (held + elsewhere)
