"""The one traffic generator. A traffic mix is a JSON file of parameters under
`benchmark/traffic/`; this module turns it and a seed into the requests a
driver sends.

What is fixed for every seed, and why: the driver runs every cell under
several seeds and holds the spread of its runs against a bound. A free draw
of ~70 Poisson arrivals differs by 13% in its count from run to run, and
free draws from a heavy tail differ as much in total tokens, so the OFFERED
LOAD would be the loudest thing the benchmark measures. Here the count, the
multiset of lengths and the multiset of gaps are quantiles of the stated
distributions on an even grid, and the order is one fixed order (`ORDER`
below), the same for every seed: the seed makes the token ids and the
weights, never the work. Heavy tails and short gaps (bursts) are all there,
in every run. With the same multisets in a seeded order, six seeds still
spread `serve_tpot_mean_ms` by 1.1-1.5% where one seed repeated to 0.3%
(my chip runs, PR 23): which request meets which in the batch is work too.

`stratum`: the fixed order is shuffled inside groups, not over the whole
list. The sorted quantiles are dealt round-robin into groups of `stratum`
values, so each group spans the whole distribution; the groups and the order
inside each are shuffled once. Every run of ~`stratum` consecutive requests
then offers about the same tokens, while a long prompt may still land beside
another one.
"""

import math
import statistics

import numpy as np

_NORMAL = statistics.NormalDist()
ORDER = 0x0BE7      # seeds the ONE order of an open loop's lengths and gaps


def _grid(n):
    return [(i + 0.5) / n for i in range(n)]


def quantiles(spec, n):
    """`n` values: the quantiles of `spec`'s distribution at (i + 0.5) / n,
    ascending. Lengths are whole tokens clipped to [min, max]; exponential
    gaps are left as reals with mean 1 (the caller scales them)."""
    dist = spec["dist"]
    if dist == "exponential":
        return [-math.log(1.0 - u) for u in _grid(n)]
    lo, hi = spec["min"], spec["max"]
    if dist == "lognormal":
        vals = [spec["median"] * math.exp(spec["sigma"] * _NORMAL.inv_cdf(u))
                for u in _grid(n)]
    elif dist == "loguniform":
        vals = [lo * (hi / lo) ** u for u in _grid(n)]
    elif dist == "uniform":
        vals = [lo + (hi - lo) * u for u in _grid(n)]
    else:
        raise ValueError(f"unknown distribution {dist!r}")
    return [int(min(max(round(v), lo), hi)) for v in vals]


def stratified_order(values, stratum, rng):
    """`values` (ascending) in `rng`'s order: dealt round-robin into
    ceil(n / stratum) groups, the groups and each group's members shuffled."""
    n = len(values)
    groups = max(1, math.ceil(n / max(1, stratum)))
    dealt = [list(values[g::groups]) for g in range(groups)]
    rng.shuffle(dealt)
    out = []
    for group in dealt:
        rng.shuffle(group)
        out.extend(group)
    return out


def _lengths(traffic, n, rng):
    stratum = traffic.get("stratum", n)
    prompts = stratified_order(quantiles(traffic["prompt_tokens"], n),
                               stratum, rng)
    outputs = stratified_order(quantiles(traffic["output_tokens"], n),
                               stratum, rng)
    return prompts, outputs


def open_loop_segment(traffic, seconds, rng):
    """One stretch of an open loop lasting `seconds`: round(rate * seconds)
    requests as (offset from the stretch's start, prompt tokens, output
    tokens). The gaps are exponential quantiles scaled to sum to `seconds`,
    so the stretch's last gap ends as the stretch does."""
    n = max(1, round(traffic["rate_rps"] * seconds))
    gaps = quantiles(traffic["gaps"], n)
    scale = seconds / sum(gaps)
    gaps = stratified_order([g * scale for g in gaps],
                            traffic.get("stratum", n), rng)
    prompts, outputs = _lengths(traffic, n, rng)
    due = np.cumsum(gaps) - gaps[0]          # the first is due as the stretch starts
    return [(float(t), p, o) for t, p, o in zip(due, prompts, outputs)]


def open_loop_schedule(traffic, window_s):
    """The whole schedule of an open-loop cell, times relative to the
    window's nominal opening: a pre-roll stretch (negative times), the window
    stretch, and a tail-out stretch after it. Each stretch has its own fixed
    count, multisets and order. Returns a list of dicts sorted by `due`."""
    rng = np.random.default_rng(ORDER)
    pre_s, tail_s = traffic["preroll_s"], traffic["tailout_s"]
    parts = [(-pre_s, open_loop_segment(traffic, pre_s, rng), "preroll"),
             (0.0, open_loop_segment(traffic, window_s, rng), "window"),
             (window_s, open_loop_segment(traffic, tail_s, rng), "tailout")]
    out = []
    for start, segment, part in parts:
        for offset, prompt, output in segment:
            out.append({"uid": len(out), "due": start + offset, "part": part,
                        "prompt_tokens": prompt, "output_tokens": output})
    return out


def _spread_out(n):
    """0..n-1 in the order of their bit-reversed values (van der Corput), so
    that neighbours in the order are far apart in rank."""
    bits = max(1, (n - 1).bit_length())
    return sorted(range(n), key=lambda i: int(format(i, f"0{bits}b")[::-1], 2))


def backlog_requests(traffic, count):
    """`count` requests of a closed backlog, in submission order: a fixed
    cycle of `grid` requests, repeated. The same for every seed; the seed
    makes the token ids (and the weights).

    The cycle is the `grid`-point multiset with long and short prompts
    interleaved (ranks in bit-reversed order) and the outputs paired off at
    another stride. A backlog's throughput depends on how the queue's order
    packs the pool (a long prompt at the head holds back the short ones
    behind it) and a window holds only ~20 requests, so the order is not the
    seed's to choose: with a free shuffle the seeds differed by 7.8%, with
    one cycle entered at a seeded point by 3.8%, while one order repeats to
    0.04% (my chip runs, PR 23)."""
    grid = traffic["grid"]
    prompts = quantiles(traffic["prompt_tokens"], grid)
    outputs = quantiles(traffic["output_tokens"], grid)
    cycle = [(prompts[rank], outputs[(5 * rank + 3) % grid])
             for rank in _spread_out(grid)]
    return [{"uid": i, "prompt_tokens": cycle[i % grid][0],
             "output_tokens": cycle[i % grid][1]} for i in range(count)]


def token_arrays(requests, vocab_size, seed):
    """Seeded random token ids for every request, built before any timing."""
    rng = np.random.default_rng([seed, 0x70C5])
    for req in requests:
        req["tokens"] = rng.integers(0, vocab_size, (req["prompt_tokens"],),
                                     dtype=np.int32)
    return requests


def train_batch(traffic, vocab_size, chips, seed):
    """One global batch of seeded random tokens, with explicit labels so the
    model's sequence length is exactly `seq_len`."""
    rng = np.random.default_rng([seed, 0x7A11])
    rows = traffic["sequences_per_chip_per_step"] * chips
    tokens = rng.integers(0, vocab_size, (rows, traffic["seq_len"] + 1),
                          dtype=np.int32)
    return {"tokens": tokens[:, :-1], "labels": tokens[:, 1:]}
