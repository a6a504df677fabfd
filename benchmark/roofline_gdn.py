"""Operations and bytes of the gated delta rule's decode update
(`dstpu_gdn_update`), from the BYTES of state the program's step ring counts,
beside `roofline.py` (whose `share` turns them into a share of the chip's
published peaks) and `roofline_ssm.py` (Mamba-2's update on the same state
kind). Kept with the benchmark so that no later PR can move the yardstick:
the count is of the state's bytes, whatever implements the update.

A decode token of one slot reads one layer's state `[value heads, key width,
value width]` float32 whole and writes it whole: `S <- a S + k (outer) beta (v -
a S^T k)`, `o = S^T q`. The step ring's `ssm_state_bytes` is exactly that,
read + write, summed over the decoding slots, the call's tokens and the
state layers. The rows of a call that belong to no sequence (a dead slot's)
go to a trash row and are not counted: what they cost is the kernel's loss.
Operations: a state element takes one multiply for the decay, a multiply and
an add each for `S^T k`, for `S^T q` and for the rank-one write: 7. The
small operands (a head's three scalars, q, k, v, o) are ~25 KiB a row beside
4 MiB and are left out."""

FLOAT32 = 4


def update(state_bytes):
    """`state_bytes`: state read + written by the calls the counters cover.
    -> (operations, bytes)."""
    elements = state_bytes // (2 * FLOAT32)
    return 7 * elements, state_bytes
