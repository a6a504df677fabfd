#!/usr/bin/env python3
"""`benchmark/run.py --rehearsal` for cells whose tiny sizes are not in
`rehearsal.json`: the same command line, the same `main()`, with every
`rehearsal_*.json` beside this file laid over `rehearsal.json` as run.py
loads it. (A `model_config` PR adds files to the benchmark and edits none;
its cell's rehearsal sizes arrive in a file of their own.)

    python3 benchmark/checks/rehearse_cell.py --workload <name> --seed <n> \\
        --seconds <s> --trace <0|1>
"""

import glob
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(os.path.dirname(HERE)), os.path.dirname(HERE)]

import harness  # noqa: E402
import run  # noqa: E402

_load_json = harness.load_json


def load_json(*parts):
    data = _load_json(*parts)
    if parts == ("checks", "rehearsal.json"):
        for path in sorted(glob.glob(os.path.join(HERE, "rehearsal_*.json"))):
            with open(path) as f:
                extra = json.load(f)
            for key in ("configs", "traffic"):
                data[key].update(extra.get(key, {}))
    return data


if __name__ == "__main__":
    harness.load_json = load_json
    sys.argv.append("--rehearsal")
    sys.exit(run.main())
