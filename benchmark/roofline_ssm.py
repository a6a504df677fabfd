"""Operations and bytes of the recurrent state's decode update
(`dstpu_ssm_update`), from the BYTES of state the program's step ring counts,
beside `roofline.py` (whose `share` turns them into a share of the chip's
published peaks). Kept with the benchmark so that no later PR can move the
yardstick.

A decode token of one slot reads one layer's state `[heads, head width,
state size]` float32 whole and writes it whole: `S <- a S + dt x (outer) B`,
`y = S C`. The step ring's `ssm_state_bytes` is exactly that, read + write,
summed over the decoding slots, the call's tokens and the state layers. The
rows of a call that belong to no sequence (a dead slot's) go to a trash row
and are not counted: what they cost is the kernel's loss. Operations: a state
element takes two multiplies and an add for the update, a multiply and an add
for `y`. The small operands (a decay a head, `dt x`, B, C, y) are a few KiB a
row beside 8 MiB and are left out."""

FLOAT32 = 4


def update(state_bytes):
    """`state_bytes`: state read + written by the calls the counters cover.
    -> (operations, bytes)."""
    elements = state_bytes // (2 * FLOAT32)
    return 5 * elements, state_bytes
