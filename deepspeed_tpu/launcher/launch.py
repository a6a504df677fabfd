"""Node-local launcher.

Reference: `launcher/launch.py:132` — decodes the world-info blob, sets
RANK/LOCAL_RANK/WORLD_SIZE/MASTER_* env per spawned process, handles signals and
kills the process tree on exit.

TPU model: the default is ONE process per host (that process drives every local
chip through jax); `--procs_per_node > 1` spawns N processes with distinct
RANK/LOCAL_RANK for CPU-simulation of multi-process jax.distributed (the analog
of the reference's per-GPU fork, used by tests and by hosts exposing chips as
separate processes).
"""

import argparse
import base64
import json
import os
import signal
import subprocess
import sys
import time

from deepspeed_tpu.utils.logging import logger

PID_FILE_BASEPATH = "/tmp"


def parse_args(args=None):
    parser = argparse.ArgumentParser(description="deepspeed-tpu node-local launcher")
    parser.add_argument("--world_info", type=str, required=True,
                        help="base64 json {hostname: slots}")
    parser.add_argument("--node_rank", type=str, default="0",
                        help="this node's rank, or the NAME of an env var holding it "
                             "(e.g. SLURM_NODEID, OMPI_COMM_WORLD_RANK)")
    parser.add_argument("--master_addr", type=str, default="127.0.0.1")
    parser.add_argument("--master_port", type=int, default=29500)
    parser.add_argument("--procs_per_node", type=int, default=1,
                        help="processes to fork on this node (1 = one process "
                             "drives all chips; >1 = per-process jax.distributed)")
    parser.add_argument("--module", action="store_true",
                        help="interpret the script as a python module (python -m)")
    parser.add_argument("--no_python", action="store_true",
                        help="exec the script directly without the interpreter")
    parser.add_argument("--save_pid", type=str, default="")
    parser.add_argument("training_script", type=str)
    parser.add_argument("training_script_args", nargs=argparse.REMAINDER)
    return parser.parse_args(args)


def resolve_node_rank(value, env=None):
    """`--node_rank` is either an int literal or an env-var name (the MPI/SLURM
    runners can't template the rank into argv, so they pass the var name)."""
    env = env if env is not None else os.environ
    try:
        return int(value)
    except ValueError:
        if value in env:
            return int(env[value])
        raise ValueError(f"node_rank '{value}' is neither an int nor a set env var")


def build_rank_env(world_info, node_rank, local_rank, procs_per_node,
                   master_addr, master_port, base_env=None):
    """Env block for one spawned process (reference launch.py:168-175)."""
    env = dict(base_env if base_env is not None else os.environ)
    hosts = list(world_info.keys())
    nnodes = len(hosts)
    world_size = nnodes * procs_per_node
    rank = node_rank * procs_per_node + local_rank
    env.update({
        "RANK": str(rank),
        "LOCAL_RANK": str(local_rank),
        "WORLD_SIZE": str(world_size),
        "LOCAL_SIZE": str(procs_per_node),
        "CROSS_RANK": str(node_rank),
        "CROSS_SIZE": str(nnodes),
        "MASTER_ADDR": master_addr,
        "MASTER_PORT": str(master_port),
        # jax.distributed contract (comm.init_distributed reads these)
        "COORDINATOR_ADDRESS": f"{master_addr}:{master_port}",
        "NUM_PROCESSES": str(world_size),
        "PROCESS_ID": str(rank),
    })
    return env


def _signal_child(p, sig):
    """Signal a child's process group — but NEVER our own group. If the child
    shares our group (spawned without start_new_session, or its pid was
    recycled), killpg would TERM the caller and every sibling — in an
    in-process harness that detonates unrelated work."""
    try:
        pgid = os.getpgid(p.pid)
        if pgid == os.getpgid(0):
            p.send_signal(sig)
        else:
            os.killpg(pgid, sig)
    except (ProcessLookupError, PermissionError):
        pass


def terminate_process_tree(procs, timeout=30):
    """SIGTERM then SIGKILL the spawned processes (children ride the process
    group — each child is started in its own session)."""
    for p in procs:
        if p.poll() is None:
            _signal_child(p, signal.SIGTERM)
    deadline = time.time() + timeout
    for p in procs:
        remaining = max(0.1, deadline - time.time())
        try:
            p.wait(timeout=remaining)
        except subprocess.TimeoutExpired:
            _signal_child(p, signal.SIGKILL)


def main(args=None):
    args = parse_args(args)
    world_info = json.loads(base64.urlsafe_b64decode(args.world_info))
    if not world_info:
        raise ValueError("world_info must not be empty")
    node_rank = resolve_node_rank(args.node_rank)
    logger.info(f"launch: node_rank={node_rank} nnodes={len(world_info)} "
                f"procs_per_node={args.procs_per_node}")

    if args.save_pid:
        pid_file = os.path.join(PID_FILE_BASEPATH, f"{args.save_pid}.dstpu")
        with open(pid_file, "w") as fd:
            fd.write(str(os.getpid()))

    if args.no_python:
        cmd_head = []
    elif args.module:
        cmd_head = [sys.executable, "-u", "-m"]
    else:
        cmd_head = [sys.executable, "-u"]
    cmd = cmd_head + [args.training_script] + args.training_script_args

    if args.procs_per_node > 1:
        # no per-process chip assignment exists here: on TPU hardware every
        # rank would initialise the same chips and all but the first die
        # at start-up ("The TPU is already in use"). One process drives all
        # of a host's chips; >1 is the multi-controller CPU harness
        logger.warning(
            f"launch: --procs_per_node={args.procs_per_node} starts that "
            f"many processes with the SAME device visibility; on a TPU "
            f"host only one process can own the chips")
    from deepspeed_tpu.platform.device import \
        refuse_spawn_if_holding_accelerator
    refuse_spawn_if_holding_accelerator("launcher/launch.py")
    procs = []
    for local_rank in range(args.procs_per_node):
        env = build_rank_env(world_info, node_rank, local_rank,
                             args.procs_per_node, args.master_addr,
                             args.master_port)
        procs.append(subprocess.Popen(cmd, env=env, start_new_session=True))

    def handler(signum, frame):
        logger.info(f"launch: got signal {signum}, terminating children")
        terminate_process_tree(procs)
        sys.exit(128 + signum)

    saved = {sig: signal.signal(sig, handler)
             for sig in (signal.SIGINT, signal.SIGTERM)}

    rc = 0
    try:
        for p in procs:
            p_rc = p.wait()
            if p_rc != 0 and rc == 0:
                # keep the ORIGINATING failure code; siblings killed below exit
                # with signal statuses that would mask it
                rc = p_rc
                # one rank died → bring the node down (reference kills siblings)
                terminate_process_tree(procs)
    finally:
        terminate_process_tree(procs, timeout=5)
        # restore: leaving our handler installed poisons in-process callers
        # (a stray signal later would run it with dead procs and sys.exit)
        for sig, old in saved.items():
            signal.signal(sig, old)
    return rc


if __name__ == "__main__":
    sys.exit(main())
