"""The walks' host twins where they live, beside their kernels
(`ops/pallas/*::*_walk_counts`, `ssm.py::state_token_bytes`), against hand
counts made position by position: what the serving loop books on the step
ring comes from them (the chunk walks' through the registered attention
program's `work`). Pure numpy: nothing is compiled."""

import numpy as np
import pytest

from deepspeed_tpu.ops.attention_dispatch import get_program
from deepspeed_tpu.ops.pallas.decode_attention import paged_decode_walk_counts
from deepspeed_tpu.ops.pallas.ssm import state_token_bytes

pytestmark = pytest.mark.serving


def _blocks_seen(pos, block, window=None):
    """Logical blocks holding a position that a query at `pos` sees."""
    first = 0 if not window else max(pos - window + 1, 0)
    return len({p // block for p in range(first, pos + 1)})


def _rows_moved(pos, block, tile):
    """Rows of the blocks under `pos` that a walk moves: every row of a
    block below the frontier, the frontier block's up to the end of the
    tile of `tile` rows that holds `pos`."""
    return len({p for p in range((pos // block + 1) * block)
                if p // block < pos // block or p // tile <= pos // tile})


def _decode(at, block, window=None, tile=None):
    whole = [[_blocks_seen(p, block) for p in row] for row in at]
    if window:
        live = sum(_blocks_seen(p, block, window) for row in at for p in row)
        return {"live_blocks": live, "table_blocks": sum(map(sum, whole)),
                "rows": live * block}       # a window walk: whole blocks
    return {"live_blocks": sum(map(sum, whole)),
            # a token's walk is launched with its live pairs (one step where
            # there are none: an empty call still runs)
            "grid_steps": sum(max(sum(row), 1) for row in whole),
            "rows": sum(_rows_moved(p, block, tile or block)
                        for row in at for p in row)}


def _chunk(start, C, block, table, window=None):
    rows = range(start, start + C)

    def blocks(window):
        return min(table, len({
            p // block for q in rows
            for p in range(0 if not window else max(q - window + 1, 0),
                           q + 1)}))
    return {"live_blocks": blocks(window),
            # of the table's blocks; for a windowed walk, of what it would
            # visit with no window
            "table_blocks": table if not window else blocks(None),
            "kept_pairs": sum(min(q + 1, window) if window else q + 1
                              for q in rows)}


CASES = [
    # the decode walk: tokens x slots positions, blocks of 16
    ("decode", lambda: paged_decode_walk_counts(
        np.array([[0, 15, 16], [1, 16, 17], [2, 17, 18]]), 16),
     lambda: _decode([[0, 15, 16], [1, 16, 17], [2, 17, 18]], 16)),
    ("decode", lambda: paged_decode_walk_counts(np.array([[511], [512]]), 512),
     lambda: _decode([[511], [512]], 512)),
    ("decode", lambda: paged_decode_walk_counts(np.zeros((2, 0), int), 16),
     lambda: {"live_blocks": 0, "grid_steps": 2, "rows": 0}),
    # a SHORT table's walk moves its frontier block in tiles of 128 rows
    # (`_frontier_rows`): positions at both ends of a tile and of a block
    ("decode", lambda: paged_decode_walk_counts(
        np.array([[0, 127, 128, 511], [512, 700, 1023, 1535]]), 512, nb=3,
        widths=(128, 128)),
     lambda: _decode([[0, 127, 128, 511], [512, 700, 1023, 1535]], 512,
                     tile=128)),
    # ... and whole where the table is long, a leaf is narrower than a lane
    # tile, a sparse layer's selection rides the walk, or the block is a tile
    *[("decode", lambda block=block, kw=kw: paged_decode_walk_counts(
        np.array([[0, 130, 600]]), block, **kw),
       lambda block=block: _decode([[0, 130, 600]], block))
      for block, kw in [(512, dict(nb=9, widths=(128, 128))),
                        (512, dict(nb=3, widths=(128, 4))),
                        (512, dict(nb=3, widths=(128,), selected=True)),
                        (128, dict(nb=8, widths=(128,))), (512, {})]],
    # a window layer's: window 8 in blocks of 8, window 128 in blocks of 128
    ("window", lambda: paged_decode_walk_counts(
        np.array([[3, 8, 40], [4, 9, 41]]), 8, 8),
     lambda: _decode([[3, 8, 40], [4, 9, 41]], 8, 8)),
    ("window", lambda: paged_decode_walk_counts(
        np.array([[127, 128, 1000]]), 128, 128),
     lambda: _decode([[127, 128, 1000]], 128, 128)),
    # the chunk walk and the pairs its mask keeps, full and windowed
    *[("chunk", lambda a=a: get_program("paged_prefill_kernel").work(*a),
       lambda a=a: _chunk(*a))
      for a in [(0, 16, 16, 8), (48, 16, 16, 8), (256, 128, 128, 3),
                (0, 16, 8, 32, 8), (48, 16, 8, 32, 8), (3, 16, 8, 32, 12)]],
    # a latent pool's: the same walk, and the positions the chunk attends
    *[("latent", lambda a=a: get_program("mla_prefill_kernel").work(*a),
       lambda a=a: dict(_chunk(*a), latent_positions=a[0] + a[1]))
      for a in [(0, 128, 128, 8), (128, 128, 128, 8), (384, 256, 128, 4)]],
    # a state kind's bytes a decode token: its row of every layer, twice
    ("state", lambda: state_token_bytes(np.zeros((3, 5, 8, 8, 16),
                                                 np.float32)),
     lambda: 2 * 3 * 8 * 8 * 16 * 4),
    ("state", lambda: state_token_bytes(np.zeros((2, 9, 4, 16, 8),
                                                 np.float16)),
     lambda: 2 * 2 * 4 * 16 * 8 * 2),
]


@pytest.mark.parametrize("kind,counted,by_hand", CASES,
                         ids=[f"{c[0]}{i}" for i, c in enumerate(CASES)])
def test_a_walks_counts_are_the_hand_count(kind, counted, by_hand):
    assert counted() == by_hand()


def test_the_gather_oracles_count_no_walk():
    for name in ("paged_gather", "paged_gather_quant", "mla_gather"):
        assert get_program(name).work is None
