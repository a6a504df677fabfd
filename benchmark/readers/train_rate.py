"""Training tokens per second per chip: whole steps completed inside the
window, over the time from the first starting to the last ending."""
import estimators


def read(obs, trace, args):
    rate, _steps = estimators.train_tokens_per_s(
        obs["step_spans"], obs["tokens_per_step"], obs["opened"],
        obs["seconds"])
    return None if rate is None else rate / obs["chips"]
