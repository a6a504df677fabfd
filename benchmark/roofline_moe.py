"""Operations and bytes of the routed experts' grouped matmul
(`dstpu_moe_gmm`), from the program's own counters and the configuration's
widths, beside `roofline.py` (whose `share` turns them into a share of the
chip's published peaks). Kept with the benchmark so that no later PR can
move the yardstick.

A routed layer makes two calls over its M sorted assignment rows: gate and
up together, `[M, D] x [E, D, 2F]`, and down, `[M, F] x [E, F, D]`. A call
needs 2 * M * K * N operations, and at least these bytes: the weights of
every expert that HAS rows, once (an idle expert's are never needed), the
rows in and the rows out. Rows that are padding (an idle slot's token, the
tail of a short prompt's chunk) are routed and multiplied like any other, so
they count."""

BF16 = 2


def gmm(assignments, active_experts, hidden, expert_width):
    """(operations, bytes) of all `dstpu_moe_gmm` calls that the counters
    cover: `assignments` = sum over (layer, program call) of M,
    `active_experts` = sum over the same of the experts with rows."""
    D, F = hidden, expert_width
    flops = 2 * assignments * (D * 2 * F + F * D)
    weights = active_experts * (D * 2 * F + F * D)
    rows = assignments * ((D + 2 * F) + (F + D))
    return flops, BF16 * (weights + rows)
