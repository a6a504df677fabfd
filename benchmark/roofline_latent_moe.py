"""Operations and bytes of the grouped matmul (`dstpu_moe_gmm`) of PLAIN
experts in a LATENT space (the Nemotron-H family's LatentMoE), from the
program's own counters and the configuration's widths, beside `roofline.py`
and the gated count `roofline_moe.py` (which this file does not replace).
Kept with the benchmark so that no later PR can move the yardstick.

A routed layer makes two calls over its M sorted assignment rows, both in the
latent width L: up, `[M, L] x [E, L, F]`, and down, `[M, F] x [E, F, L]` (no
gate: the activation is `relu(x)^2` of one product). A call needs 2 * M * K *
N operations, and at least these bytes: the weights of every expert that HAS
rows, once, the rows in and the rows out. Rows that are padding are routed
and multiplied like any other, so they count."""

BF16 = 2


def gmm(assignments, active_experts, latent, expert_width):
    """(operations, bytes) of all `dstpu_moe_gmm` calls that the counters
    cover: `assignments` = sum over (layer, program call) of the rows M,
    `active_experts` = sum over the same of the experts with rows."""
    L, F = latent, expert_width
    flops = 2 * assignments * (L * F + F * L)
    weights = active_experts * 2 * L * F
    rows = assignments * ((L + F) + (F + L))
    return flops, BF16 * (weights + rows)
