"""Trial child entry — `python -m deepspeed_tpu.autotuning.trial`.

The measured stage's child half of the subprocess recipe
(`utils/subproc.py`): the parent (`measure.run_trial_child`) puts a JSON
trial spec in `DSTPU_TUNE_TRIAL`, this module reconstructs the model,
runs ONE measurement, and prints the result record as the last stdout
line. A crash, a real device OOM, or an import error in here costs the
tuner one recorded failure, never the session.

Only models this module can rebuild from JSON are supported — the
built-in demo zoo (`"model": {"kind": "tiny_gpt", "cfg": {...}}`, a
`GPTConfig` built from plain fields). Arbitrary model factories tune
in-process instead (`TuneSession` with a bound `measure_fn`).
"""

import json
import os
import sys


def _build_spec(model: dict):
    import jax.numpy as jnp
    from deepspeed_tpu.models.gpt import GPTConfig, make_gpt_decode_model
    kind = model.get("kind", "tiny_gpt")
    if kind != "tiny_gpt":
        raise ValueError(f"trial child cannot rebuild model kind {kind!r} "
                         f"— tune in-process with a bound measure_fn")
    cfg_d = dict(model.get("cfg", {}))
    cfg_d["dtype"] = jnp.dtype(cfg_d.get("dtype", "float32"))
    cfg_d.setdefault("remat", False)
    cfg = GPTConfig(**cfg_d)
    return make_gpt_decode_model(cfg=cfg, name=model.get("name", "tuned"))


def main() -> int:
    from deepspeed_tpu.autotuning.measure import (TRIAL_ENV,
                                                  measure_serving)
    raw = os.environ.get(TRIAL_ENV)
    if not raw:
        print(json.dumps({"ok": False,
                          "error": f"no {TRIAL_ENV} in the environment"}))
        return 2
    spec = json.loads(raw)
    if spec.get("kind", "serving") != "serving":
        print(json.dumps({"ok": False,
                          "error": "trial child runs serving trials only"}))
        return 2
    from deepspeed_tpu.comm import mesh as mesh_mod
    from deepspeed_tpu.config.core import MeshConfig
    mesh_mod._CURRENT_MESH = None
    mesh_mod._CURRENT_SPEC = None
    mesh_mod.init_mesh(MeshConfig(data=1, tensor=1, sequence=1, expert=1,
                                  pipe=1))
    rec = measure_serving(lambda: _build_spec(spec.get("model", {})),
                          spec.get("base_config", {}),
                          spec.get("overrides", {}),
                          spec["trace"],
                          clock=spec.get("clock", "virtual"))
    print(json.dumps(rec, sort_keys=True, default=str))
    return 0 if rec.get("ok") else 1


if __name__ == "__main__":
    sys.exit(main())
