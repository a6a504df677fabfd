"""Operations and bytes of the paged walks — the decode walk
`dstpu_paged_decode` and the chunk's walk `dstpu_paged_prefill` — over a pool
whose KINDS of layer differ in their KV heads and whose keys and values
differ in width, from what the program's step ring counts a kind, beside
`roofline.py` (whose `share` turns them into a share of the chip's published
peaks). Kept with the benchmark so that no later PR can move the yardstick.
(`roofline_walk.py` stays as it is: one `kv_heads` and one `head_dim` for
both kinds, which is K-EXAONE's pool.)

A kind is `(layers, block tokens, query heads, KV heads, key width, value
width)`, and the MODEL's entry is what is counted: `KV heads x (key width +
value width)` values a cached position (MiMo-V2-Flash: 4 x 320 in a full
layer, 8 x 320 in a window layer). A pool that stores the entry wider, or a
kernel that contracts the keys padded to whole lane tiles, reads and
multiplies more than these and shows it as a lower share, not as a larger
denominator (the rule `roofline_mla.py` states).

The decode walk reads whole blocks: every row of a (slot, block) pair's
block is read and multiplied — the rows past a sequence's last position and,
on a window layer, the rows before its window included. Operations: q.K over
the key width and p.V over the value width, two a multiply-add, every query
head.

The chunk's walk computes in tiles under its frontier; what is COUNTED for it
is the (query, position) pairs the causal mask keeps and, on a window layer,
the window keeps — the step ring's `prefill_kept_pairs` /
`prefill_window_kept_pairs` — so the tiles it computes and masks away read as
a lower share. Bytes of the chunk: the blocks under its frontier (from the
block its window begins in) once, whole, the queries in and the results out
(the kernel re-reads a block for every query tile and head group: also a
lower share)."""

BF16 = 2


def decode_walk(kinds):
    """`kinds`: [((layers, block, heads, kv_heads, key_dim, value_dim),
    (slot, block) pairs a layer's walk visited)] -> (operations, bytes)."""
    flops = nbytes = 0
    for (layers, block, heads, kv_heads, key_dim, value_dim), pairs in kinds:
        rows = layers * block * pairs
        flops += 2 * rows * heads * (key_dim + value_dim)
        nbytes += BF16 * rows * kv_heads * (key_dim + value_dim)
    return flops, nbytes


def chunk_walk(kinds, chunks, chunk):
    """`kinds`: [(the kind as above, the (query, position) pairs a layer's
    masks kept, the blocks a layer's walk visited)] over `chunks` prefill
    chunks of `chunk` queries each -> (operations, bytes)."""
    flops = nbytes = 0
    for (layers, block, heads, kv_heads, key_dim, value_dim), kept, blocks \
            in kinds:
        flops += 2 * layers * kept * heads * (key_dim + value_dim)
        q_and_out = chunks * chunk * heads * (key_dim + value_dim)
        nbytes += BF16 * layers * (
            blocks * block * kv_heads * (key_dim + value_dim) + q_and_out)
    return flops, nbytes
