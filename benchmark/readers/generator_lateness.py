"""How late the load generator ran: submit() time less due time, over the
requests due inside the window (one thread submits between blocking steps, so
this is bounded by a step's length; a starved generator shows here)."""
import estimators


def read(obs, trace, args):
    late = [1e3 * (obs["submitted"][uid] - t) for uid, t in obs["due"].items()
            if obs["opened"] <= t < obs["closed"]]
    return estimators.percentile(late, args["percentile"])
