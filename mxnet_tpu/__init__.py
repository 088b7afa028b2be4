"""mxnet_tpu: a TPU-native framework with MXNet's capability surface.

A from-scratch rebuild of Apache MXNet (reference: xiezhq-hermann/
incubator-mxnet @1.5, mounted read-only at /root/reference) designed
TPU-first on JAX/XLA/Pallas:

- `mx.nd` — imperative NDArray on jax.Array (async via XLA dispatch)
- `mx.autograd` — tape of jax.vjp closures
- `mx.gluon` — Block/HybridBlock; hybridize == jax.jit
- `mx.sym` + Module — symbolic graphs lowered to one XLA computation
- `mx.kvstore` / parallel — ICI/DCN collectives via jax.sharding Mesh
- optimizers/metrics/io/model_zoo — API parity with the reference

Conventional import: ``import mxnet_tpu as mx``.
"""
from __future__ import annotations

import time as _time

_IMPORT_T0 = _time.monotonic()  # the "mxnet_tpu.import" span starts here

__version__ = "0.1.0"


def _maybe_init_distributed():
    """jax.distributed.initialize must run BEFORE anything touches the
    XLA backend, and the first op after import does — so when the
    launcher's rendezvous env is present (tools/launch.py
    MXNET_COORDINATOR), join the cluster here, first thing. The analog
    of the reference's implicit ps-lite bootstrap inside ``import
    mxnet`` when DMLC_PS_ROOT_URI is set."""
    import multiprocessing

    from . import env as _env

    coord = _env.get_str("MXNET_COORDINATOR")
    if not coord:
        return
    if multiprocessing.parent_process() is not None:
        # forkserver/spawn children (DataLoader workers, ...) inherit
        # the launcher env but must NOT re-join the cluster with the
        # parent's process_id — the coordinator would reject or hang
        return
    import jax

    if jax.distributed.is_initialized():
        return  # an explicit launch.init() beat us
    # rendezvous failures propagate: a silently un-joined worker would
    # leave its peers hanging at their first collective — and a launch
    # env with the coordinator but not the rank vars is itself such a
    # failure (defaulting to rank 0 of 1 would fork the cluster)
    nproc = _env.get_str("MXNET_NUM_PROCESSES")
    pid = _env.get_str("MXNET_PROCESS_ID")
    if nproc is None or pid is None:
        raise RuntimeError(
            "MXNET_COORDINATOR is set but MXNET_NUM_PROCESSES/"
            "MXNET_PROCESS_ID are not — refusing to join the cluster "
            "with guessed rank (every worker would claim rank 0)")
    jax.distributed.initialize(
        coordinator_address=coord,
        num_processes=int(nproc),
        process_id=int(pid))


def _maybe_enable_int64():
    """MXNET_INT64_TENSOR_SIZE=1 builds the reference with 64-bit tensor
    indexing and int64 arithmetic (reference: include/mxnet/libinfo.h:126,
    flag INT64_TENSOR_SIZE; nightly test_large_array.py). The TPU analog
    is JAX's x64 mode — it must be set before the first jax use."""
    from . import env as _env

    if (_env.get_str("MXNET_INT64_TENSOR_SIZE", "0") or "0").lower() in (
            "1", "true", "on"):
        import jax

        jax.config.update("jax_enable_x64", True)


_maybe_init_distributed()
_maybe_enable_int64()

from . import base
from .base import MXNetError
from . import context
from .context import Context, cpu, gpu, tpu, current_context, num_gpus, num_tpus
from . import ndarray
from . import ndarray as nd
from .ndarray import NDArray
from . import autograd
from . import random
from . import initializer
from . import initializer as init
from . import optimizer
from .optimizer import lr_scheduler
from . import metric
from . import io
from . import kvstore as kv
from . import kvstore
from . import gluon
from . import parallel
from . import pipeline  # noqa: F401
from . import resilience  # noqa: F401
from . import utils  # noqa: F401
from . import telemetry  # noqa: F401
from .utils import compile_cache as _compile_cache
from . import engine  # noqa: F401
from . import libinfo  # noqa: F401
from . import misc  # noqa: F401
from . import initialize as _initialize

_initialize.initialize()  # crash tracebacks + fork-safe engine (initialize.cc)
from . import symbol
from . import numpy as np
from . import numpy_extension as npx
from . import symbol as sym
from . import executor
from . import module
from . import module as mod
from . import model
from . import callback
from . import name  # noqa: F401
from . import attribute  # noqa: F401
from .attribute import AttrScope  # noqa: F401
from . import library  # noqa: F401
from . import recordio
from . import image  # noqa: F401
from . import rnn  # noqa: F401
from . import env  # noqa: F401
from . import tools  # noqa: F401
from . import contrib  # noqa: F401
from . import util  # noqa: F401
from . import log  # noqa: F401
from . import registry  # noqa: F401
from . import serving  # noqa: F401
from . import kvstore_server  # noqa: F401  (exits server-role processes)
from . import monitor as mon  # noqa: F401
from . import profiler  # noqa: F401
from . import monitor  # noqa: F401
from .monitor import Monitor  # noqa: F401
from . import visualization  # noqa: F401
from .visualization import print_summary  # noqa: F401
from . import runtime  # noqa: F401
from . import analysis  # noqa: F401
from . import test_utils  # noqa: F401
from . import operator  # noqa: F401
from . import rtc  # noqa: F401

operator._install_nd_custom()

# reference alias: mx.viz.plot_network / print_summary
viz = visualization

# keep reference-style aliases
Context = Context

# env-knob wiring (mxnet_tpu.env KNOBS table): global seed + profiler
# autostart, applied once at import like the reference's engine init
if env.get_str("MXNET_SEED"):
    random.seed(env.get_int("MXNET_SEED", 0))
if env.get_bool("MXNET_PROFILER_AUTOSTART"):
    profiler.set_config(aggregate_stats=True)
    profiler.start()
env.check()
# telemetry: JAX's compile durations become compile.* spans, and the
# import itself is the first span of the flight recorder (it began
# before the tracer's epoch, so its ts is negative)
_compile_cache.install_compile_listener()
if telemetry.tracing():
    telemetry.emit_span("mxnet_tpu.import", "setup", _IMPORT_T0,
                        _time.monotonic())
