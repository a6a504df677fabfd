"""Operations and bytes of latent attention (MLA) over the paged pool in the
ABSORBED form — the decode walk `dstpu_mla_decode` and the chunk's walk
`dstpu_mla_prefill` — from what the program's step ring counts, beside
`roofline.py` (whose `share` turns them into a share of the chip's published
peaks). Kept with the benchmark so that no later PR can move the yardstick.

A cached position is ONE entry of `rank + rope` values for all heads (GLM-4.7-
Flash: 512 + 64 = 576, 1152 bytes in bfloat16), and the MODEL's entry is what
is counted: a pool that stores the entry wider (640 columns, whole lane
tiles) reads more bytes than these and shows it as a lower share, not as a
larger denominator. A head's score against a position is a dot over the
entry (`rank + rope` multiply-adds), its value the entry's first `rank`
columns (`rank` multiply-adds): 2 x (rank + rope + rank) operations a head a
(query, position) pair — 43,520 at 20 heads.

The decode walk reads whole blocks: every row of a (slot, block) pair's block
is read and multiplied, the rows past the sequence's last position included.
The chunk's walk computes in tiles under its frontier; what is COUNTED for it
is the (query, position) pairs the causal mask keeps — `chunk x start +
chunk x (chunk + 1) / 2` a chunk — so the tiles it computes and masks away
on the diagonal read as a lower share. Bytes of the chunk: each position
under the frontier once, the queries in and the results out (the kernel
re-reads a block for every query tile and head group: also a lower share).
"""

BF16 = 2


def pair_ops(heads, rank, rope):
    """Operations of one (query, cached position) pair, all heads."""
    return 2 * heads * (rank + rope + rank)


def decode_walk(pairs, layers, block, heads, rank, rope):
    """`pairs`: (slot, block) pairs a layer's walk visited -> (operations,
    bytes) over `layers` layers."""
    rows = layers * block * pairs
    return rows * pair_ops(heads, rank, rope), rows * BF16 * (rank + rope)


def chunk_walk(chunks, positions, layers, chunk, heads, rank, rope):
    """`chunks` prefill chunks of `chunk` queries each, `positions` the sum
    over them of the cached positions under a chunk's frontier (`start +
    chunk`) -> (operations, bytes) over `layers` layers."""
    pairs = chunk * positions - chunks * chunk * (chunk - 1) // 2
    q_and_out = chunks * chunk * heads * (rank + rope + rank)
    return (layers * pairs * pair_ops(heads, rank, rope),
            layers * BF16 * (positions * (rank + rope) + q_and_out))
