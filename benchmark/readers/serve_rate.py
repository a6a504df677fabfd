"""Serving tokens per second: prompt tokens whose prefill chunk completed
inside the window plus tokens emitted inside it, over the window."""
import estimators


def read(obs, trace, args):
    return estimators.tokens_per_s(obs["events"], obs["opened"], obs["closed"])
