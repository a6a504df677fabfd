"""Gap between output tokens over every emission inside the window:
`stat` is "mean" (interval time over tokens) or a percentile over tokens."""
import estimators


def read(obs, trace, args):
    intervals = estimators.emission_intervals(obs["events"], obs["opened"],
                                              obs["closed"])
    if args["stat"] == "mean":
        return estimators.tpot_mean_ms(intervals)
    return estimators.tpot_percentile_ms(intervals, args["percentile"])
