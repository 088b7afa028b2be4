"""Device context.

TPU-native analog of mxnet.context.Context (reference:
python/mxnet/context.py, include/mxnet/base.h Context struct). Device types:
``cpu`` and ``tpu`` (``gpu`` is accepted as an alias of ``tpu`` so reference
scripts run unchanged). A Context maps to a concrete ``jax.Device``; NDArrays
are committed to that device with ``jax.device_put``.
"""
from __future__ import annotations

import threading

import jax

from .base import MXNetError

__all__ = ["Context", "cpu", "gpu", "tpu", "current_context", "num_gpus",
           "num_tpus", "gpu_memory_info"]


class Context:
    """Device context holding device type and id.

    Usable as a `with` scope to set the default context, like the reference
    (reference: python/mxnet/context.py:126-132).
    """

    _default_ctx = threading.local()

    devtype2str = {1: "cpu", 2: "tpu", 3: "cpu_pinned", 5: "cpu_shared"}
    devstr2type = {"cpu": 1, "tpu": 2, "gpu": 2, "cpu_pinned": 3, "cpu_shared": 5}

    def __init__(self, device_type, device_id=0):
        if isinstance(device_type, Context):
            self.device_typeid = device_type.device_typeid
            self.device_id = device_type.device_id
        else:
            self.device_typeid = Context.devstr2type[device_type]
            self.device_id = device_id
        self._old_ctx = None

    @property
    def device_type(self):
        return Context.devtype2str[self.device_typeid]

    def __hash__(self):
        return hash((self.device_typeid, self.device_id))

    def __eq__(self, other):
        return (
            isinstance(other, Context)
            and self.device_typeid == other.device_typeid
            and self.device_id == other.device_id
        )

    def __str__(self):
        return "%s(%d)" % (self.device_type, self.device_id)

    __repr__ = __str__

    def __enter__(self):
        if not hasattr(Context._default_ctx, "value"):
            Context._default_ctx.value = Context("cpu", 0)
        self._old_ctx = Context._default_ctx.value
        Context._default_ctx.value = self
        return self

    def __exit__(self, ptype, value, trace):
        Context._default_ctx.value = self._old_ctx

    # ---- jax mapping ----------------------------------------------------
    @property
    def jax_device(self):
        """The concrete jax.Device backing this context."""
        if self.device_type == "cpu":
            devs = [d for d in jax.devices() if d.platform == "cpu"]
            if not devs:
                devs = jax.devices()
        else:
            devs = [d for d in jax.devices() if d.platform != "cpu"]
            if not devs:
                # tpu(i) stands in for cpu devices only where the CPU
                # platform was asked for by name (JAX_PLATFORMS=cpu, as
                # the tests do) — never as a silent fallback on a
                # machine that was supposed to have a chip
                if jax.config.jax_platforms != "cpu":
                    raise MXNetError(
                        f"{self}: no accelerator device (jax lists only "
                        f"{jax.default_backend()!r}); set JAX_PLATFORMS=cpu"
                        " to run tpu contexts on host devices")
                devs = jax.devices()
        return devs[self.device_id % len(devs)]

    def empty_cache(self):
        """Free cached device memory (reference: Context.empty_cache,
        python/mxnet/context.py:161; GPUPooledStorageManager::ReleaseAll,
        src/storage/pooled_storage_manager.h). XLA/PJRT manages its own pool;
        this triggers a best-effort GC."""
        import gc

        gc.collect()


def cpu(device_id=0):
    return Context("cpu", device_id)


def tpu(device_id=0):
    return Context("tpu", device_id)


def gpu(device_id=0):
    """Alias of tpu() so reference scripts using mx.gpu() run on TPU."""
    return Context("tpu", device_id)


def num_tpus():
    return len([d for d in jax.devices() if d.platform != "cpu"])


def num_gpus():
    """Reference: mxnet.context.num_gpus — here the number of TPU chips."""
    return num_tpus()


def gpu_memory_info(device_id=0):
    """(free, total) accelerator memory in bytes (reference:
    context.gpu_memory_info over cudaMemGetInfo; here PJRT's per-device
    HBM accounting via the Storage interface). Raises on an invalid
    device id, matching the reference (and util.get_gpu_memory)."""
    from .util import get_gpu_memory

    return get_gpu_memory(device_id)


def current_context():
    if not hasattr(Context._default_ctx, "value"):
        Context._default_ctx.value = Context("cpu", 0)
    return Context._default_ctx.value
