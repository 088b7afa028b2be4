"""Cross-process DataLoader workers with shared-memory batch transfer.

Reference: python/mxnet/gluon/data/dataloader.py:28-156 — fork-based
worker pool whose NDArray pickling rides POSIX shm (ForkingPickler +
reduce_ndarray). TPU-native constraint: an initialized XLA runtime must
NOT be forked, so workers use the 'spawn' context with a one-time
initializer (CPU-only JAX in children), and batches come back as
(shm_name, shape, dtype) descriptors over multiprocessing.shared_memory
— the same zero-copy-on-transfer idea as the reference's shm NDArrays
without ever pickling tensor bytes through a pipe.
"""
from __future__ import annotations

import os
from multiprocessing import shared_memory

import numpy as onp

_WORKER_DATASET = None
_WORKER_BATCHIFY = None


def _init_worker(dataset, batchify_fn):
    """Spawn-context initializer: runs once per worker process BEFORE
    any jax use, pinning the child to CPU so worker processes never
    fight over the TPU."""
    global _WORKER_DATASET, _WORKER_BATCHIFY
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    _WORKER_DATASET = dataset
    _WORKER_BATCHIFY = batchify_fn


def _to_shm(arr):
    """numpy array -> (shm_name, shape, dtype); child leaks the handle
    on purpose — the parent owns unlink."""
    arr = onp.ascontiguousarray(arr)
    shm = shared_memory.SharedMemory(create=True, size=max(arr.nbytes, 1))
    view = onp.ndarray(arr.shape, arr.dtype, buffer=shm.buf)
    view[:] = arr
    name = shm.name
    shm.close()
    return (name, arr.shape, str(arr.dtype))


def _from_shm(desc):
    name, shape, dtype = desc
    shm = shared_memory.SharedMemory(name=name)
    arr = onp.ndarray(shape, onp.dtype(dtype), buffer=shm.buf).copy()
    shm.close()
    shm.unlink()
    return arr


def _encode(obj):
    """Replace numpy/NDArray leaves of a batch structure with shm
    descriptors."""
    if hasattr(obj, "asnumpy"):
        return ("__shm__", _to_shm(obj.asnumpy()))
    if isinstance(obj, onp.ndarray):
        return ("__shm__", _to_shm(obj))
    if isinstance(obj, (list, tuple)):
        return type(obj)(_encode(x) for x in obj)
    if isinstance(obj, dict):
        return {k: _encode(v) for k, v in obj.items()}
    return obj


def decode(obj):
    """Parent side: shm descriptors -> NDArray leaves."""
    from ... import ndarray as nd

    if isinstance(obj, tuple) and len(obj) == 2 and obj[0] == "__shm__":
        return nd.array(_from_shm(obj[1]))
    if isinstance(obj, (list, tuple)):
        return type(obj)(decode(x) for x in obj)
    if isinstance(obj, dict):
        return {k: decode(v) for k, v in obj.items()}
    return obj


def worker_make_batch(indices):
    """Runs in the worker: fetch samples, batchify, export via shm."""
    batch = _WORKER_BATCHIFY([_WORKER_DATASET[i] for i in indices])
    return _encode(batch)
