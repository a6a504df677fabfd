"""The part of decoding requests' token gap that is other requests' prefill:
100 * (1 - what the decode calls of the window would have taken at the pace of
a step that ran no prefill chunk, over what they took). Each step weighs by
the slots that decoded in it, since every one of them waited for it."""
import statistics

import steprings


def read(obs, trace, args):
    steps = [s for s in steprings.steps(obs, args["subsystem"]) if s.decoding]
    alone = [s.t_end - s.t_start for s in steps if not s.prefill_chunks]
    waited = sum((s.t_end - s.t_start) * s.decoding for s in steps)
    if not alone or not waited:
        return None
    return 100.0 * (1.0 - statistics.median(alone)
                    * sum(s.decoding for s in steps) / waited)
