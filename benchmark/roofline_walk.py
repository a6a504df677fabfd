"""Operations and bytes of the paged decode walk (`dstpu_paged_decode`) over a
pool of two kinds, from the BLOCKS the walk visits — the program's step ring
counts them a kind — beside `roofline.py` (whose `share` turns them into a
share of the chip's published peaks). Kept with the benchmark so that no
later PR can move the yardstick.

A grid step of the walk reads ONE block of K and one of V, every KV head of
it, whole — the rows past a sequence's last position and, on a window layer,
the rows before its window included: the bytes are the blocks', not the live
rows'. (`roofline.paged_decode` counts live rows: right for one kind of
layer whose walks start at block 0, and the metric `paged_decode_roofline`
keeps it.) Operations: q.K and p.V over every row read, two a multiply-add.
The queries in and the results out are left out: a few KiB a pair."""

BF16 = 2


def paged_walk(pairs_by_kind, heads, kv_heads, head_dim):
    """`pairs_by_kind`: [(layers, block tokens, (slot, block) pairs a layer
    visited)], one entry a kind of layer. -> (operations, bytes)."""
    rows = sum(layers * block * pairs for layers, block, pairs in pairs_by_kind)
    return (4 * rows * heads * head_dim,
            BF16 * 2 * rows * kv_heads * head_dim)
