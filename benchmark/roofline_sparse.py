"""Operations and bytes of a learned sparse-attention indexer over the paged
pool — the score walks (`dstpu_sparse_index_scores`, `..._decode`), the exact
selection (`dstpu_sparse_select`) and the attention over the SELECTED
positions (`dstpu_paged_prefill_sparse`, `dstpu_paged_decode_sparse`) — from
what the program's step ring counts (`index_scored_positions`,
`selected_positions`, a layer), beside `roofline.py` (whose `share` turns
them into a share of the chip's published peaks). Kept with the benchmark so
that no later PR can move the yardstick.

What is counted is the MODEL's work, not the form's:

- the score walk: a (query, cached position) pair is `heads` dots of `dim`
  (2 x heads x dim operations; Keye-VL-2.0: 2 x 16 x 64 = 2048), and a CALL
  reads the index keys under its frontier once — `dim` values a position
  (128 B in bfloat16): a chunk's 1024 rows share them, a decode token reads
  its slot's. A pool that stores the key wider (a whole lane tile, 256 B)
  reads more and shows it as a lower share, not as a larger denominator;
- the selection has no matrix product and no model bytes to speak of: its
  yardstick is ELEMENT PASSES — 32 counting passes over the scores' bit
  pattern, the tie rule's passes over the position's bits, and three more
  (the keys, the count above the threshold, the result);
- the sparse walk: the two products over the SELECTED pairs and the bytes of
  the entries a call has to read for them, whatever form reads them: a
  decode token's selected entries (K and V of every key-value head), a
  chunk's positions under its frontier once (its rows' selections cover
  them between them). A walk that computes every pair under the frontier
  and masks the rest away does `index_scored_positions /
  selected_positions` times the operations and scores that much lower where
  it is compute-bound (a chunk); a decode walk that reads every position
  for the 2048 it attends reads that many times the bytes.
"""


def index_scores(pairs, keys_read, layers, heads, dim, itemsize=2):
    """`pairs`: (query, position) pairs scored a layer; `keys_read`: cached
    positions under the calls' frontiers, a call each (what the walks read,
    in whole blocks) -> (operations, bytes) over `layers` layers."""
    return (layers * pairs * 2 * heads * dim,
            layers * keys_read * dim * itemsize)


def select_passes(table_positions):
    """Element passes the exact selection makes over a row's live scores: 32
    over the key's bits, one a bit of the largest position, and three."""
    return 32 + max(int(table_positions) - 1, 1).bit_length() + 3


def sparse_walk(pairs, entries_read, layers, heads, kv_heads, head_dim,
                itemsize=2):
    """`pairs`: (query, position) pairs the selection kept a layer;
    `entries_read`: the cached entries the calls have to read for them (a
    decode token's selected ones, a chunk's positions under its frontier) ->
    (operations, bytes) over `layers` layers: q.K and p.V a pair a head, K
    and V of every key-value head an entry."""
    return (layers * pairs * 2 * 2 * heads * head_dim,
            layers * entries_read * 2 * kv_heads * head_dim * itemsize)
