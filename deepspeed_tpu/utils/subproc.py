"""One subprocess recipe: env knobs in, JSON result out.

The parent sets environment knobs, the child runs one trial and prints its
result as a JSON object on the LAST line of stdout (progress chatter above
it is fine). The autotuner's measured-trial runner (`autotuning/measure.py`)
is the caller: in a child, a crash, a device OOM or a hang costs one
record and not the parent. The recipe — env filtering, spawn,
last-JSON-line parse, stderr salvage — exists here once.
"""

import json
import os
import subprocess
from typing import Dict, Optional, Sequence, Tuple


def last_json_line(text: str, key: Optional[str] = None) -> Optional[dict]:
    """The last stdout line that parses as a JSON object (optionally
    required to carry `key`), or None. Children print progress freely;
    only the final JSON object is the result."""
    for line in reversed((text or "").strip().splitlines()):
        try:
            cand = json.loads(line)
        except json.JSONDecodeError:
            continue
        if isinstance(cand, dict) and (key is None or key in cand):
            return cand
    return None


def child_env(overrides: Dict[str, str],
              clear_prefixes: Sequence[str] = (),
              base: Optional[Dict[str, str]] = None) -> Dict[str, str]:
    """The child's environment: the parent's, minus every variable whose
    name starts with a `clear_prefixes` entry (stray knobs meant for the
    parent must not silently reshape a pinned child config), plus
    `overrides` (stringified)."""
    env = {k: v for k, v in (base if base is not None else os.environ).items()
           if not any(k.startswith(p) for p in clear_prefixes)}
    env.update({k: str(v) for k, v in overrides.items()})
    return env


def run_json_child(argv: Sequence[str], overrides: Dict[str, str],
                   clear_prefixes: Sequence[str] = (), key: Optional[str] = None,
                   timeout: Optional[float] = None,
                   ) -> Tuple[Optional[dict], "subprocess.CompletedProcess"]:
    """Spawn `argv` with env knobs, return (last JSON result line, proc).

    The result is None when the child produced no parseable JSON line
    (crash, OOM, import error) — the caller decides whether that is a
    recorded failure or fatal; `proc.stderr` carries the evidence either
    way."""
    proc = subprocess.run(list(argv), env=child_env(overrides, clear_prefixes),
                          capture_output=True, text=True, timeout=timeout)
    return last_json_line(proc.stdout, key=key), proc
