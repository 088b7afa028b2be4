"""Shared harness for multi-OS-process launcher tests: run N workers
through tools/launch.py (local mode, jax.distributed rendezvous) on a
FREE coordinator port, with the env scrubbed so each process owns one
CPU device. Worker bodies write per-rank result files the caller
asserts on."""
import os
import socket
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PREAMBLE = r"""
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, {repo!r})
from mxnet_tpu.tools import launch
assert launch.init(), "launcher env missing"
"""


def free_port():
    """An OS-assigned free TCP port (avoids rendezvous collisions with
    concurrently running launcher tests or orphans of timed-out ones)."""
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def run_launched_workers(tmp_path, body, n=2, timeout=360):
    """Write `_PREAMBLE + body` as the worker script (formatted with
    repo=REPO, outdir=str(tmp_path)) and run it under
    ``launch.py -n N --launcher local`` on a free port. Returns the
    CompletedProcess; asserts rc==0 with captured output on failure."""
    worker = tmp_path / "worker.py"
    worker.write_text((_PREAMBLE + body).format(repo=REPO,
                                                outdir=str(tmp_path)))
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)  # one CPU device per process
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO
    proc = subprocess.run(
        [sys.executable, "-m", "mxnet_tpu.tools.launch", "-n", str(n),
         "--launcher", "local", "--port", str(free_port()),
         sys.executable, str(worker)],
        env=env, capture_output=True, text=True, timeout=timeout,
        cwd=REPO)
    assert proc.returncode == 0, (proc.stdout[-2000:],
                                  proc.stderr[-2000:])
    return proc
