"""Multi-process / multi-host job launcher.

Reference: tools/launch.py (dmlc-tracker ssh/mpi/local/yarn submission
of ps-lite worker+server processes). TPU-native redesign: there are no
parameter servers — every process is a jax.distributed peer — so the
launcher's job is the coordinator rendezvous the reference did with
DMLC_PS_ROOT_URI env plumbing:

  python -m mxnet_tpu.tools.launch -n 8 --launcher local python train.py
  python -m mxnet_tpu.tools.launch -n 2 -H hosts.txt --launcher ssh \
      python train.py

Each spawned process receives MXNET_COORDINATOR / MXNET_NUM_PROCESSES /
MXNET_PROCESS_ID (+ the jax.distributed equivalents), which
``mxnet_tpu.tools.launch.init()`` (call it at the top of the training
script) feeds into ``jax.distributed.initialize`` so the global mesh
spans all hosts.
"""
from __future__ import annotations

import argparse
import os
import shlex
import subprocess
import sys

__all__ = ["main", "init"]


def init():
    """Join the jax.distributed cluster from launcher-provided env.

    ``import mxnet_tpu`` already does this automatically when the
    launcher env is present (mxnet_tpu/__init__.py
    _maybe_init_distributed — the import touches the XLA backend, so
    the rendezvous must happen before/with it). Calling this explicitly
    is supported for scripts that import bare jax first. Returns True
    when the launcher env was present."""
    from .. import env as _env

    coord = _env.get_str("MXNET_COORDINATOR")
    if not coord:
        return False
    import jax

    if not jax.distributed.is_initialized():
        # rendezvous failures propagate — never run un-joined, and never
        # guess the rank (see mxnet_tpu.__init__._maybe_init_distributed)
        nproc = _env.get_str("MXNET_NUM_PROCESSES")
        pid = _env.get_str("MXNET_PROCESS_ID")
        if nproc is None or pid is None:
            raise RuntimeError(
                "MXNET_COORDINATOR is set but MXNET_NUM_PROCESSES/"
                "MXNET_PROCESS_ID are not — launch env is incomplete")
        jax.distributed.initialize(
            coordinator_address=coord,
            num_processes=int(nproc),
            process_id=int(pid))
    return True


def _worker_env(base, coord, n, rank):
    env = dict(base)
    env.update({"MXNET_COORDINATOR": coord,
                "MXNET_NUM_PROCESSES": str(n),
                "MXNET_PROCESS_ID": str(rank),
                # standard jax cluster-env spellings too
                "JAX_COORDINATOR_ADDRESS": coord,
                "JAX_NUM_PROCESSES": str(n),
                "JAX_PROCESS_ID": str(rank)})
    return env


def submit_local(args):
    coord = f"127.0.0.1:{args.port}"
    procs = []
    for rank in range(args.num_workers):
        env = _worker_env(os.environ, coord, args.num_workers, rank)
        for kv in args.env:
            k, _, v = kv.partition(":")
            env[k] = v
        procs.append(subprocess.Popen(args.command, env=env))
    rc = 0
    for p in procs:
        rc = p.wait() or rc
    return rc


def submit_ssh(args):
    with open(args.host_file) as f:
        hosts = [h.strip() for h in f if h.strip()
                 and not h.startswith("#")]
    if len(hosts) < args.num_workers:
        raise SystemExit(f"host file has {len(hosts)} hosts, need "
                         f"{args.num_workers}")
    coord = f"{hosts[0]}:{args.port}"
    cmd = " ".join(shlex.quote(c) for c in args.command)
    procs = []
    for rank in range(args.num_workers):
        envs = " ".join(
            f"{k}={shlex.quote(v)}"
            for k, v in _worker_env({}, coord, args.num_workers,
                                    rank).items())
        for kv in args.env:
            k, _, v = kv.partition(":")
            envs += f" {k}={shlex.quote(v)}"
        remote = f"cd {shlex.quote(args.sync_dir or '.')} && " \
            f"env {envs} {cmd}"
        procs.append(subprocess.Popen(
            ["ssh", "-o", "StrictHostKeyChecking=no", hosts[rank],
             remote]))
    rc = 0
    for p in procs:
        rc = p.wait() or rc
    return rc


def main(argv=None):
    parser = argparse.ArgumentParser(
        description="Launch a distributed mxnet_tpu job "
                    "(reference: tools/launch.py)")
    parser.add_argument("-n", "--num-workers", type=int, required=True,
                        help="number of processes to launch")
    parser.add_argument("-H", "--host-file", default=None,
                        help="hosts, one per line (ssh launcher)")
    parser.add_argument("--launcher", default="local",
                        choices=["local", "ssh"],
                        help="process launcher")
    parser.add_argument("--port", type=int, default=9357,
                        help="coordinator port")
    parser.add_argument("--sync-dir", default=None,
                        help="remote working dir (ssh)")
    parser.add_argument("--env", action="append", default=[],
                        help="VAR:value pairs for the workers")
    parser.add_argument("command", nargs=argparse.REMAINDER,
                        help="training command")
    args = parser.parse_args(argv)
    if not args.command:
        parser.error("no command given")
    if args.launcher == "ssh" or args.host_file:
        if not args.host_file:
            parser.error("ssh launcher requires --host-file")
        return submit_ssh(args)
    return submit_local(args)


if __name__ == "__main__":
    sys.exit(main())
