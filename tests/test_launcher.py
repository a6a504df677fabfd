"""Launcher + elasticity tests (reference: tests/unit/launcher/, elasticity/)."""

import base64
import json
import types

import pytest

from deepspeed_tpu.launcher import runner as runner_mod
from deepspeed_tpu.launcher import launch as launch_mod
from deepspeed_tpu.launcher.multinode_runner import (make_runner, PDSHRunner,
                                                     SlurmRunner, OpenMPIRunner,
                                                     MPICHRunner, IMPIRunner,
                                                     MVAPICHRunner)
from deepspeed_tpu.elasticity import (ElasticAgent, AgentSpec, MembershipChanged,
                                      compute_elastic_config,
                                      ElasticityIncompatibleWorldSize)


def _args(**kw):
    base = dict(user_script="train.py", user_args=["--foo", "1"],
                master_addr="node0", master_port=29500, hostfile="/tmp/hf",
                launcher_args="", include="", exclude="")
    base.update(kw)
    return types.SimpleNamespace(**base)


RESOURCES = {"node0": 4, "node1": 4}
WORLD_B64 = base64.urlsafe_b64encode(json.dumps(RESOURCES).encode()).decode()


class TestHostfile:
    def test_parse(self, tmp_path):
        hf = tmp_path / "hostfile"
        hf.write_text("# comment\nnode0 slots=4\nnode1 slots=8\n\n")
        res = runner_mod.fetch_hostfile(str(hf))
        assert res == {"node0": 4, "node1": 8}

    def test_filters(self):
        res = {"a": 1, "b": 2, "c": 3}
        assert runner_mod.filter_resources(res, "a,b", "") == {"a": 1, "b": 2}
        assert runner_mod.filter_resources(res, "", "b") == {"a": 1, "c": 3}


class TestMultinodeRunners:
    @pytest.mark.parametrize("name,cls", [
        ("pdsh", PDSHRunner), ("slurm", SlurmRunner), ("openmpi", OpenMPIRunner),
        ("mpich", MPICHRunner), ("impi", IMPIRunner), ("mvapich", MVAPICHRunner),
    ])
    def test_make_runner(self, name, cls):
        r = make_runner(name, _args(), WORLD_B64, RESOURCES)
        assert isinstance(r, cls)
        assert r.name

    def test_pdsh_cmd(self):
        r = make_runner("pdsh", _args(), WORLD_B64, RESOURCES)
        r.add_export("JAX_PLATFORMS", "tpu")
        cmd, env = r.get_cmd({}, RESOURCES)
        joined = " ".join(map(str, cmd))
        assert cmd[0] == "pdsh"
        assert "node0,node1" in cmd
        assert "deepspeed_tpu.launcher.launch" in joined
        assert "--node_rank=%n" in joined
        assert "export JAX_PLATFORMS=tpu" in joined
        assert "train.py" in joined and "--foo" in joined
        assert env["PDSH_RCMD_TYPE"] == "ssh"

    def test_slurm_cmd(self):
        r = make_runner("slurm", _args(), WORLD_B64, RESOURCES)
        r.add_export("XLA_FLAGS", "--xla_foo")
        cmd, _ = r.get_cmd({}, RESOURCES)
        assert cmd[0] == "srun"
        assert "--ntasks-per-node=1" in cmd
        assert any(c.startswith("--export=ALL,XLA_FLAGS=") for c in cmd)
        assert "--node_rank=SLURM_NODEID" in cmd

    def test_openmpi_cmd(self):
        r = make_runner("openmpi", _args(), WORLD_B64, RESOURCES)
        cmd, _ = r.get_cmd({}, RESOURCES)
        assert cmd[0] == "mpirun"
        assert "ppr:1:node" in cmd
        i = cmd.index("-n")
        assert cmd[i + 1] == "2"

    def test_impi_per_host_blocks(self):
        r = make_runner("impi", _args(), WORLD_B64, RESOURCES)
        cmd, _ = r.get_cmd({}, RESOURCES)
        assert cmd.count("-host") == 2
        assert cmd.count(":") == 1


class TestNodeLauncher:
    def test_resolve_node_rank(self):
        assert launch_mod.resolve_node_rank("3") == 3
        assert launch_mod.resolve_node_rank("MY_RANK", {"MY_RANK": "5"}) == 5
        with pytest.raises(ValueError):
            launch_mod.resolve_node_rank("NOT_SET", {})

    def test_build_rank_env(self):
        env = launch_mod.build_rank_env(RESOURCES, node_rank=1, local_rank=2,
                                        procs_per_node=4, master_addr="node0",
                                        master_port=29500, base_env={})
        assert env["RANK"] == "6"
        assert env["LOCAL_RANK"] == "2"
        assert env["WORLD_SIZE"] == "8"
        assert env["CROSS_RANK"] == "1"
        assert env["COORDINATOR_ADDRESS"] == "node0:29500"
        assert env["PROCESS_ID"] == "6"

    def test_launch_spawns_and_propagates_rc(self, tmp_path):
        script = tmp_path / "worker.py"
        script.write_text(
            "import os, sys\n"
            "print(os.environ['RANK'], os.environ['WORLD_SIZE'])\n"
            "sys.exit(0 if os.environ['RANK'] != '1' else 3)\n")
        rc = launch_mod.main([
            f"--world_info={base64.urlsafe_b64encode(json.dumps({'localhost': 2}).encode()).decode()}",
            "--node_rank=0", "--procs_per_node=2", str(script)])
        assert rc == 3


class TestElasticAgent:
    DS_CONFIG = {"elasticity": {"enabled": True, "max_train_batch_size": 64,
                                "micro_batch_sizes": [2, 4], "min_gpus": 1,
                                "max_gpus": 32}}

    def test_restart_on_membership_change(self):
        calls = []

        def run_fn(world, micro):
            calls.append((world, micro))
            if len(calls) == 1:
                raise MembershipChanged("host lost")

        _, valid = compute_elastic_config(self.DS_CONFIG)
        w0, w1 = valid[-1], valid[-2]
        worlds = iter([w0, w1])
        spec = AgentSpec(run_fn=run_fn, world_size_fn=lambda: next(worlds),
                         ds_config=self.DS_CONFIG, restart_backoff_s=0.0)
        assert ElasticAgent(spec).run()
        assert len(calls) == 2
        assert calls[0][0] == w0 and calls[1][0] == w1

    def test_restart_budget(self):
        def run_fn(world, micro):
            raise RuntimeError("boom")

        spec = AgentSpec(run_fn=run_fn, world_size_fn=lambda: 4,
                         ds_config=self.DS_CONFIG, max_restarts=2,
                         restart_backoff_s=0.0)
        assert not ElasticAgent(spec).run()

    def test_inadmissible_world_size(self):
        final_batch, valid = compute_elastic_config(self.DS_CONFIG)
        bad = max(valid) + 1
        while bad in valid:
            bad += 1
        with pytest.raises(ElasticityIncompatibleWorldSize):
            compute_elastic_config(self.DS_CONFIG, world_size=bad)


class TestCliSuite:
    """bin/ CLI suite (reference: bin/ds_elastic, bin/ds_ssh, bin/ds_report)."""

    def test_ds_elastic_cli(self, tmp_path, capsys):
        from deepspeed_tpu.elasticity.cli import main
        cfg = tmp_path / "ds.json"
        cfg.write_text(json.dumps({
            "elasticity": {"enabled": True, "max_train_batch_size": 64,
                           "micro_batch_sizes": [2, 4], "min_gpus": 1,
                           "max_gpus": 32}}))
        assert main(["-c", str(cfg), "-w", "2"]) == 0
        out = capsys.readouterr().out
        assert "final_batch_size" in out and "micro_batch_size" in out

    def test_ds_ssh_hostfile_missing(self, tmp_path, capsys):
        from deepspeed_tpu.launcher.ds_ssh import main
        assert main(["-f", str(tmp_path / "nope"), "echo", "hi"]) == 1

    def test_bin_scripts_exist_and_shim(self):
        import pathlib
        bin_dir = pathlib.Path(__file__).parent.parent / "bin"
        for name in ("dstpu", "dstpu_report", "dstpu_bench", "dstpu_elastic",
                     "dstpu_ssh"):
            script = bin_dir / name
            assert script.exists(), name
            assert "main" in script.read_text()

    def test_pyproject_entry_points_resolve(self):
        import importlib
        import pathlib
        try:
            import tomllib            # stdlib from 3.11
        except ModuleNotFoundError:
            import tomli as tomllib   # 3.10 harness
        root = pathlib.Path(__file__).parent.parent
        with open(root / "pyproject.toml", "rb") as f:
            proj = tomllib.load(f)
        for target in proj["project"]["scripts"].values():
            mod_name, func = target.split(":")
            mod = importlib.import_module(mod_name)
            assert callable(getattr(mod, func))


import jax as _jax


@pytest.mark.skipif(
    _jax.__version_info__ < (0, 5),
    reason="this jaxlib's CPU backend cannot run cross-process computations "
           "(XlaRuntimeError: 'Multiprocess computations aren't implemented "
           "on the CPU backend') — the launcher wire itself is covered by "
           "the single-process launcher tests above")
class TestTwoProcessDistributed:
    def test_launcher_spawns_two_process_psum(self, tmp_path):
        """End-to-end multi-process path: the node-local launcher spawns two
        workers, each calls init_distributed (coordinator env from the
        launcher), builds a 2-device global mesh across processes, and a
        jitted cross-process reduction returns the right value — the real
        multi-host wire, minus the second host."""
        import textwrap
        worker = tmp_path / "worker.py"
        import os as _os
        repo = _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__)))
        worker.write_text(textwrap.dedent(f"""
            import sys, os, re
            sys.path.insert(0, {repo!r})
            # one device per process: strip the CPU-harness 8-device flag the
            # pytest parent exported
            _flags = re.sub(r"--xla_force_host_platform_device_count=\\d+", "",
                            os.environ.get("XLA_FLAGS", "")).strip()
            if _flags:
                os.environ["XLA_FLAGS"] = _flags
            else:
                os.environ.pop("XLA_FLAGS", None)
        """) + textwrap.dedent("""
            import jax
            jax.config.update("jax_platforms", "cpu")
            import numpy as np
            import jax.numpy as jnp
            from jax.sharding import NamedSharding, PartitionSpec as P
            import deepspeed_tpu
            from deepspeed_tpu.comm import mesh as mesh_mod
            from deepspeed_tpu.config.core import MeshConfig

            deepspeed_tpu.init_distributed()           # RANK/WORLD_SIZE/MASTER_* env
            assert jax.process_count() == 2, jax.process_count()
            assert jax.device_count() == 2, jax.device_count()
            mesh_mod.init_mesh(MeshConfig(data=2))
            mesh = mesh_mod.get_mesh()
            sh = NamedSharding(mesh, P(("data", "zero")))
            # each process contributes its rank+1 as its local shard
            x = jax.make_array_from_callback(
                (2,), sh, lambda idx: np.full((1,), jax.process_index() + 1.0))
            total = jax.jit(jnp.sum, out_shardings=NamedSharding(mesh, P()))(x)
            assert float(total) == 3.0, float(total)   # 1 + 2 across processes
            print("PSUM_OK", float(total))
        """))
        from deepspeed_tpu.launcher import launch as launch_mod
        from deepspeed_tpu.launcher.runner import encode_world_info
        import os
        env_backup = dict(os.environ)
        try:
            rc = launch_mod.main([
                "--world_info", encode_world_info({"localhost": [0, 1]}),
                "--node_rank", "0", "--procs_per_node", "2",
                "--master_addr", "127.0.0.1", "--master_port", "29517",
                str(worker)])
        finally:
            os.environ.clear()
            os.environ.update(env_backup)
        assert rc == 0

    def test_two_process_distributed_checkpoint_roundtrip(self, tmp_path):
        """Multi-host checkpoint story beyond a single psum (reference engine
        save/load `runtime/engine.py:2982,2653`): two processes form a global
        mesh, train a ZeRO-2 engine (optimizer state sharded ACROSS the
        processes), save an orbax checkpoint, train further, restore, and the
        post-restore eval must equal the post-save eval exactly — then one
        more step proves training continues."""
        import textwrap
        worker = tmp_path / "ckpt_worker.py"
        import os as _os
        repo = _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__)))
        ckdir = str(tmp_path / "ck")
        worker.write_text(textwrap.dedent(f"""
            import sys, os, re
            sys.path.insert(0, {repo!r})
            _flags = re.sub(r"--xla_force_host_platform_device_count=\\d+", "",
                            os.environ.get("XLA_FLAGS", "")).strip()
            if _flags:
                os.environ["XLA_FLAGS"] = _flags
            else:
                os.environ.pop("XLA_FLAGS", None)
            CKDIR = {ckdir!r}
        """) + textwrap.dedent("""
            import jax
            jax.config.update("jax_platforms", "cpu")
            import numpy as np
            import jax.numpy as jnp
            import deepspeed_tpu

            deepspeed_tpu.init_distributed()
            assert jax.process_count() == 2

            params = {"w": jnp.zeros((32, 32), jnp.float32)}
            def loss_fn(p, b):
                return jnp.mean((jnp.tanh(b["x"] @ p["w"]) - b["y"]) ** 2)
            e, *_ = deepspeed_tpu.initialize(model=loss_fn, model_parameters=params,
                config={"train_micro_batch_size_per_gpu": 4,
                        "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
                        "zero_optimization": {"stage": 2},
                        "mesh": {"data": 2}, "steps_per_print": 10**9})
            rng = np.random.default_rng(0)
            b = {"x": rng.normal(0, 1, (8, 32)).astype(np.float32),
                 "y": rng.normal(0, 1, (8, 32)).astype(np.float32)}
            for _ in range(3):
                e.train_batch(b)
            ev_saved = float(e.eval_batch(b))
            e.save_checkpoint(CKDIR, tag="t3")
            for _ in range(2):
                e.train_batch(b)
            assert float(e.eval_batch(b)) != ev_saved  # moved on
            e.load_checkpoint(CKDIR, tag="t3")
            ev_restored = float(e.eval_batch(b))
            assert ev_restored == ev_saved, (ev_restored, ev_saved)
            after = float(e.train_batch(b))
            assert np.isfinite(after)
            print("CKPT_ROUNDTRIP_OK", ev_restored)
        """))
        from deepspeed_tpu.launcher import launch as launch_mod
        from deepspeed_tpu.launcher.runner import encode_world_info
        import os
        env_backup = dict(os.environ)
        try:
            rc = launch_mod.main([
                "--world_info", encode_world_info({"localhost": [0, 1]}),
                "--node_rank", "0", "--procs_per_node", "2",
                "--master_addr", "127.0.0.1", "--master_port", "29531",
                str(worker)])
        finally:
            os.environ.clear()
            os.environ.update(env_backup)
        assert rc == 0
