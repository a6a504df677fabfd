"""The per-layer table of BENCHMARK.json, an entry a case, on the CPU:

    python3 -m pytest benchmark/checks -q

A cell's per-layer metrics are the entries whose `workloads` list names it,
and an entry's file is its reader and the reader's arguments
(`test_benchmark.py` holds the files to that). Two entries that a cell would
be read with alike are one entry with a longer list: a later cell JOINS a
folded metric by its name in that list and brings entries of its own only for
what is matched by shape or by a width key.
"""

import json
import os
import sys

import pytest

sys.path[:0] = [os.path.dirname(os.path.abspath(__file__))]

from test_benchmark import BENCH, BENCHMARK, PINNED, _json  # noqa: E402

CAP = 128           # the contract's; the driver refuses a longer table
ENTRIES = BENCHMARK["per_layer"]
CELLS = [w["name"] for w in BENCHMARK["workloads"]]
NAMES = [m["name"] for m in ENTRIES]
READ_AS = {}        # name -> (reader, args): what a cell is read with
for _name in NAMES:
    _spec = _json(BENCH, "layer_metrics", _name + ".json")
    READ_AS[_name] = (_spec["reader"],
                      json.dumps(_spec["args"], sort_keys=True))


def test_the_table_is_under_its_cap():
    assert len(ENTRIES) <= CAP
    assert PINNED <= set(NAMES)


@pytest.mark.parametrize("entry", ENTRIES, ids=NAMES)
def test_no_cell_is_read_twice_alike_and_no_copy_comes_back(entry):
    """An entry lists a cell once; no other entry has this one's (reader,
    args) in a cell this one lists; and none has its (reader, args, moves)
    at all, except a pinned file's copy, which waits for the test that pins
    it (`spec_keys_pinned.json`)."""
    cells = entry.get("workloads", CELLS)
    assert len(set(cells)) == len(cells)
    for other in ENTRIES:
        if other is entry or READ_AS[other["name"]] != READ_AS[entry["name"]]:
            continue
        assert not set(other.get("workloads", CELLS)) & set(cells), \
            other["name"]
        if other["moves"] == entry["moves"]:
            assert PINNED & {entry["name"], other["name"]}, other["name"]
