"""100 * one sum over the program's call records over another, both over the
calls read inside the window. A sum is given as its terms, each a list of
fields MULTIPLIED a record: `[["rows", "win"], ["firsts"]]` is the tokens a
call could hand out (`rows` slots x `win` tokens, and a first token a prompt
whose last chunk it carried), `[["emitted"]]` what it handed to live
requests."""
import math

import callring


def read(obs, trace, args):
    calls = callring.calls(obs, args["subsystem"])
    if not calls:
        return None

    def total(terms):
        return sum(math.prod(getattr(c, f) for f in term)
                   for c in calls for term in terms)

    den = total(args["den"])
    if not den:
        return None
    return 100.0 * total(args["num"]) / den
