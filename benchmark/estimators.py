"""Windowed estimators: from the benchmark's own stamps to its metrics.

Everything here is arithmetic on lists the drivers record with the host's
clock; nothing is read from the program. The grain is tokens and steps, not
finished requests: a window of <= 51 s holds three or four request
lifetimes, so an estimator that waits for a request to finish is quantised
by whole requests at both edges of the window (PR 22's spread, see PERF.md).

Records:
  step spans   [(t_start, t_end), ...]         one per scheduler/train step
  events       [(t, uid, prefilled, emitted)]  progress a step made for one
               request, stamped with the time that step returned: prompt
               tokens whose chunk it completed, output tokens it emitted
"""

import math


def percentile(values, q):
    """The q-th percentile (0-100), linear between closest ranks."""
    data = sorted(values)
    if not data:
        return None
    if len(data) == 1:
        return float(data[0])
    rank = (len(data) - 1) * q / 100.0
    lo = math.floor(rank)
    hi = min(lo + 1, len(data) - 1)
    return float(data[lo] + (data[hi] - data[lo]) * (rank - lo))


def weighted_percentile(pairs, q):
    """q-th percentile of values with whole-number weights: the value at
    which the cumulative weight first reaches q% of the total."""
    data = sorted(pairs)
    total = sum(w for _, w in data)
    if total <= 0:
        return None
    need = total * q / 100.0
    run = 0.0
    for value, weight in data:
        run += weight
        if run >= need:
            return float(value)
    return float(data[-1][0])


def window_tokens(events, opened, closed):
    """(prompt tokens prefilled, output tokens emitted) by steps that
    returned inside (opened, closed]. A request cut by either edge
    contributes the part that fell inside."""
    prefilled = emitted = 0
    for t, _uid, n_pre, n_out in events:
        if opened < t <= closed:
            prefilled += n_pre
            emitted += n_out
    return prefilled, emitted


def tokens_per_s(events, opened, closed):
    prefilled, emitted = window_tokens(events, opened, closed)
    return (prefilled + emitted) / (closed - opened)


def emission_intervals(events, opened, closed):
    """[(interval seconds, tokens)] for every emission inside the window
    whose predecessor for the same request is inside it too: the time since
    that request's previous emission, and the tokens this emission carried
    (a decode window of k tokens gives its interval once, with k tokens).
    A request's first token has no predecessor; it is TTFT's."""
    last = {}
    out = []
    for t, uid, _n_pre, n_out in events:
        if n_out <= 0:
            continue
        prev = last.get(uid)
        last[uid] = t
        if prev is not None and prev >= opened and t <= closed:
            out.append((t - prev, n_out))
    return out


def tpot_mean_ms(intervals):
    """Token-weighted mean gap: all interval time over all tokens."""
    tokens = sum(k for _, k in intervals)
    if tokens == 0:
        return None
    return 1e3 * sum(dt for dt, _ in intervals) / tokens


def tpot_percentile_ms(intervals, q):
    """Percentile over TOKENS: each of an emission's k tokens waited
    interval / k."""
    value = weighted_percentile([(dt / k, k) for dt, k in intervals], q)
    return None if value is None else 1e3 * value


def first_token_times(events):
    """uid -> the time of its first emission."""
    first = {}
    for t, uid, _n_pre, n_out in events:
        if n_out > 0 and uid not in first:
            first[uid] = t
    return first


def ttft_ms(events, due, opened, seconds):
    """Time to first token, from the instant each request was DUE, for the
    requests due inside [opened, opened + seconds). Returns (list of ms,
    number of those requests that never got a first token)."""
    first = first_token_times(events)
    got, missing = [], 0
    for uid, t_due in due.items():
        if opened <= t_due < opened + seconds:
            if uid in first:
                got.append(1e3 * (first[uid] - t_due))
            else:
                missing += 1
    return got, missing


def tpot_per_finished_request_ms(events, finished_at, opened, closed):
    """PR 22's estimator, kept for the diagnosis in PERF.md only: for every
    request that FINISHED inside the window, (last emission - first
    emission) / (tokens - 1); the mean of those per-request means."""
    first, last, count = {}, {}, {}
    for t, uid, _n_pre, n_out in events:
        if n_out > 0:
            first.setdefault(uid, t)
            last[uid] = t
            count[uid] = count.get(uid, 0) + n_out
    means = [1e3 * (last[uid] - first[uid]) / (count[uid] - 1)
             for uid, t_done in finished_at.items()
             if opened < t_done <= closed and count.get(uid, 0) > 1]
    return (sum(means) / len(means)) if means else None


def train_tokens_per_s(step_spans, tokens_per_step, opened, seconds):
    """Whole steps started at or after `opened`, up to and including the one
    that ends at or after `opened + seconds`: (tokens per second from the
    first of them starting to the last ending, number of steps)."""
    inside = []
    for t0, t1 in step_spans:
        if t0 >= opened:
            inside.append((t0, t1))
            if t1 >= opened + seconds:
                break
    if not inside:
        return None, 0
    elapsed = inside[-1][1] - inside[0][0]
    return len(inside) * tokens_per_step / elapsed, len(inside)
