"""Operations and bytes a kernel's algorithm needs, from shapes alone, and
the least time a chip could take for them. Kept with the benchmark so that
no later PR can move the yardstick. Recomputed calls count, because each
call is timed: this is a kernel's share of its roofline, not model
utilisation."""

BF16 = 2


def least_seconds(flops, nbytes, peaks):
    """(seconds, which bound): the larger of compute and memory time."""
    compute = flops / peaks["bf16_flops_per_s"]
    memory = nbytes / peaks["hbm_bytes_per_s"]
    return (compute, "compute") if compute >= memory else (memory, "memory")


def share(flops, nbytes, kernel_seconds, device_kind):
    """100 * least time over measured time, against the published peaks of
    `device_kind` in `benchmark/peaks.json`; an unknown device is an error."""
    import harness
    peaks = harness.load_json("peaks.json")[device_kind]
    return 100.0 * least_seconds(flops, nbytes, peaks)[0] / kernel_seconds


def paged_decode(context_tokens, rows, layers, heads, kv_heads, head_dim):
    """One token per row attending to its live context through the paged
    pool, over `layers` layers: `context_tokens` is the sum of the rows'
    context lengths. Bytes: every live K and V vector once, the query in and
    the output out. Operations: q.K and p.V, two a multiply-add."""
    flops = layers * 4 * context_tokens * heads * head_dim
    nbytes = layers * BF16 * (2 * context_tokens * kv_heads * head_dim
                              + 2 * rows * heads * head_dim)
    return flops, nbytes


def flash_causal(calls, batch, heads, seq, head_dim):
    """Causal flash attention over [batch, heads, seq, head_dim], by kernel:
    `calls` = {"fwd": n, "dq": n, "dkv": n} kernel calls. A call multiplies
    matrices over the lower triangle (half of seq x seq): the forward two
    (q.K, p.V), the dq kernel three (q.K again, dO.V, dS.K), the dkv kernel
    four (q.K again, dO.V, P.dO, dS.Q). Bytes: each call reads or writes
    about 4, 5 and 6 arrays of the size of q."""
    tri = batch * heads * seq * seq * head_dim        # one product, causal: 2*tri/2 flops
    products = {"fwd": 2, "dq": 3, "dkv": 4}
    arrays = {"fwd": 4, "dq": 5, "dkv": 6}
    one = batch * heads * seq * head_dim * BF16
    flops = sum(calls.get(k, 0) * products[k] * tri for k in products)
    nbytes = sum(calls.get(k, 0) * arrays[k] * one for k in arrays)
    return flops, nbytes
