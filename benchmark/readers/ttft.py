"""Time to first token from the instant a request was due, over the requests
due inside the window's stretch of the schedule. A request that never got a
first token is a failure of the run, not a sample."""
import estimators


def read(obs, trace, args):
    got, _missing = estimators.ttft_ms(obs["events"], obs["due"],
                                       obs["due_from"], obs["seconds"])
    return estimators.percentile(got, args["percentile"])
