"""DeviceFeed: prefetching device-feed iterator.

Wraps any batch source — a DataIter (``io.py``), a gluon DataLoader, or
a plain iterable/generator — and keeps ``MXNET_DEVICE_PREFETCH`` batches
staged ON DEVICE ahead of the consuming step:

- a background worker thread pulls batches from the source (so host
  decode/augment/batchify runs off the step loop's critical path) and
  stages every array leaf with an async ``jax.device_put`` (so the H2D
  transfer of batch k+1 rides PJRT's copy stream while the compiled
  step consumes batch k — the reference's PrefetcherIter overlap,
  src/io/iter_prefetcher.h:142, extended through the transfer);
- the bounded queue holds at most ``depth`` staged batches (one more
  may be mid-staging in the worker), so prefetch never balloons HBM;
- staged buffers are freshly allocated by ``device_put`` and uniquely
  referenced by the queue item — safe to donate to a consuming
  executable once the caller owns the batch (donation-friendly);
- a source exception is captured and re-raised in the CONSUMER at the
  point of ``next()`` (never lost in the thread, never a deadlock), and
  ``close()``/``reset()`` drain the worker even when it is blocked on a
  full queue;
- ``depth=0`` (or ``MXNET_DEVICE_PREFETCH=0``) degrades to synchronous
  inline staging: no thread, no queue, bit-for-bit the behavior of the
  unpipelined loop.

Counters (``pipeline_counters()``): a ``prefetch_hit`` is a ``next()``
that found its batch already staged; a ``prefetch_stall`` had to wait on
the worker, and the wait time accumulates into ``prefetch_stall_s`` —
the time the step loop (and therefore the device) sat idle on data.
"""
from __future__ import annotations

import queue as _queue
import threading
import time

import numpy as onp

from ..resilience import faults as _faults
from ..telemetry import tracer as _telem
from . import (_count, _count_set, prefetch_depth)

__all__ = ["DeviceFeed"]


# end-of-stream marker: a dedicated object, NOT None — a buggy source
# yielding None must surface as a None batch in the consumer, never as
# a silently truncated epoch
_END = object()


class _Raised:
    """Wrapper distinguishing a propagated source exception from a
    batch that happens to BE an Exception instance."""

    __slots__ = ("exc",)

    def __init__(self, exc):
        self.exc = exc


class _Epoch:
    """One pass's worker state: queue + stop flag + thread, all local to
    the generation so a worker from before a reset can never deliver
    stale batches (or its end-of-stream sentinel) into the new pass."""

    __slots__ = ("q", "stop", "thread")

    def __init__(self, depth):
        self.q = _queue.Queue(maxsize=depth)
        self.stop = threading.Event()
        self.thread = None


class DeviceFeed:
    """Prefetching device-feed iterator (see module docstring).

    ``for batch in feed`` mirrors ``for batch in source`` with every
    array leaf resident on ``device``; numpy leaves come back as device
    NDArrays. A finished (or failed) feed re-arms a fresh pass on the
    next ``iter()`` — call ``source.reset()`` (or ``feed.reset()``,
    which forwards) first when the source is a rewindable DataIter.
    """

    def __init__(self, source, depth=None, device=None):
        self.source = source
        self.batch_size = getattr(source, "batch_size", None)
        self._depth = prefetch_depth() if depth is None \
            else max(0, int(depth))
        self._device = device
        self._epoch = None       # active _Epoch (async mode)
        self._sync_it = None     # active source iterator (passthrough)
        self._finished = False
        self._t_first = None     # first-next timestamp of this pass
        self._served = 0         # batches delivered this pass (cursor)
        self._skip_base = 0      # batches skip()'d before this pass
        self._staged_bytes = 0   # bytes the batch being staged has placed
        _count_set("prefetch_depth", self._depth)

    # -- staging ------------------------------------------------------------

    def _stage_leaf(self, x):
        import jax

        from ..ndarray import NDArray

        # registered fault point: a failed H2D transfer / staging OOM.
        # Fires in the worker thread; the exception propagates to the
        # consumer's next() exactly like a real device_put failure.
        _faults.maybe_fail("device_put")
        if isinstance(x, NDArray):
            x = x.data
        elif not isinstance(x, (onp.ndarray, jax.Array)):
            return x
        self._staged_bytes += x.nbytes
        return NDArray(jax.device_put(x, self._device))

    def _stage(self, item):
        """Map ``_stage_leaf`` over the batch structure (DataBatch /
        list / tuple / dict / bare array), preserving the container."""
        from ..io.io import DataBatch

        if isinstance(item, DataBatch):
            return DataBatch(
                data=[self._stage_leaf(d) for d in (item.data or [])],
                label=[self._stage_leaf(l) for l in (item.label or [])],
                pad=item.pad, index=item.index,
                bucket_key=item.bucket_key,
                provide_data=item.provide_data,
                provide_label=item.provide_label)
        if isinstance(item, (list, tuple)):
            return type(item)(self._stage(v) for v in item)
        if isinstance(item, dict):
            return {k: self._stage(v) for k, v in item.items()}
        return self._stage_leaf(item)

    # -- worker -------------------------------------------------------------

    @staticmethod
    def _put(ep, item):
        """Bounded put that ``close()`` can always unblock; False when
        stopped before the item landed."""
        while not ep.stop.is_set():
            try:
                ep.q.put(item, timeout=0.2)
                return True
            except _queue.Full:
                continue
        return False

    def _worker(self, ep):
        try:
            for batch in self.source:
                if ep.stop.is_set():
                    return
                # its own lane in the trace: staging runs on the
                # device-feed thread, parallel to the consumer's step
                # spans — the round-11 overlap, visible. It times the
                # enqueue of device_put, not the transfer's end.
                with _telem.span("pipeline.prefetch_stage",
                                 cat="pipeline") as sp:
                    self._staged_bytes = 0
                    staged = self._stage(batch)
                    sp.set(bytes=self._staged_bytes)
                if not self._put(ep, staged):
                    return
        except BaseException as e:  # noqa: BLE001 — re-raised in consumer
            self._put(ep, _Raised(e))
        finally:
            self._put(ep, _END)

    @property
    def position(self):
        """The epoch offset of the NEXT batch — ``skip()``'d prefix
        plus batches delivered this pass. This is the step cursor a
        CheckpointManager snapshot records (``cursor={"step": ...}``),
        so it must stay absolute across a skip-based resume: a second
        crash in the same epoch then resumes at the true offset
        instead of replaying the prefix twice."""
        return self._skip_base + self._served

    def skip(self, n):
        """Advance the SOURCE past ``n`` batches without staging them
        (resume repositioning before iteration starts); ``position``
        counts them. Only valid on a one-shot source (generator /
        fresh iterator): a re-iterable source would rewind when the
        worker later calls ``iter`` on it, silently undoing the skip —
        that raises instead."""
        if n <= 0:
            return self
        if self._epoch is not None or self._sync_it is not None:
            raise RuntimeError("DeviceFeed.skip() must run before "
                               "iteration starts")
        it = iter(self.source)
        if it is not iter(self.source):
            raise RuntimeError(
                "DeviceFeed.skip() needs a one-shot source (iter(src) "
                "is src); re-iterable sources would rewind when the "
                "feed starts — slice the source instead")
        for _ in range(n):
            next(it)
        self._skip_base += n
        return self

    def _start(self):
        ep = _Epoch(self._depth)
        ep.thread = threading.Thread(
            target=self._worker, args=(ep,), daemon=True,
            name="device-feed")
        self._epoch = ep
        self._finished = False
        self._t_first = None
        self._served = 0
        ep.thread.start()

    # -- iteration ----------------------------------------------------------

    def __iter__(self):
        if self._finished:
            # previous pass ended (exhausted or failed): re-arm a fresh
            # one over the source's current position
            self.close()
        return self

    def __next__(self):
        if self._finished:
            raise StopIteration
        if self._depth <= 0:
            return self._next_sync()
        if self._epoch is None:
            self._start()
        ep = self._epoch
        t0 = time.perf_counter()
        if self._t_first is None:
            self._t_first = t0
        stalled = ep.q.empty()
        tm0 = time.monotonic() if _telem.tracing() else 0.0
        item = ep.q.get()
        if tm0:
            _telem.emit_span("pipeline.feed_wait", "pipeline", tm0,
                             time.monotonic(), stalled=stalled)
        wait = time.perf_counter() - t0
        if item is _END:
            self._end_pass()
            raise StopIteration
        if isinstance(item, _Raised):
            _count("feed_errors")
            self._end_pass()
            raise item.exc
        if stalled:
            _count("prefetch_stalls")
            _count("prefetch_stall_s", wait)
        else:
            _count("prefetch_hits")
        _count("prefetch_batches")
        self._served += 1
        return item

    next = __next__

    def _next_sync(self):
        """depth=0 passthrough: inline pull + stage, no thread."""
        if self._sync_it is None:
            self._sync_it = iter(self.source)
            self._t_first = time.perf_counter()
            self._served = 0
        try:
            item = self._stage(next(self._sync_it))
        except StopIteration:
            self._end_pass()
            raise
        self._served += 1
        return item

    def _end_pass(self):
        if self._t_first is not None:
            _count("feed_active_s", time.perf_counter() - self._t_first)
            self._t_first = None
        self._finished = True
        self._epoch = None
        self._sync_it = None

    # -- lifecycle ----------------------------------------------------------

    def close(self):
        """Stop and join the worker, discarding staged batches.
        Idempotent; safe mid-pass (a worker blocked on the full queue is
        drained out, never deadlocked) and from ``__del__``."""
        ep = self._epoch
        self._epoch = None
        self._sync_it = None
        self._skip_base = 0
        if self._t_first is not None:
            _count("feed_active_s", time.perf_counter() - self._t_first)
            self._t_first = None
        self._finished = False
        if ep is None:
            return
        ep.stop.set()
        # every get() frees a slot; _put re-checks stop each 0.2s
        while ep.thread.is_alive():
            try:
                ep.q.get(timeout=0.1)
            except _queue.Empty:
                pass
        ep.thread.join()

    def reset(self):
        """DataIter-style rewind: drain the worker, reset the source,
        re-arm lazily on the next ``next()``."""
        self.close()
        reset = getattr(self.source, "reset", None)
        if reset is not None:
            reset()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def __del__(self):
        try:
            from ..utils import locks as _locks

            # finalizers interleave arbitrarily; the witness must not
            # attribute the engine waits in close() to whatever locks
            # the interrupted thread happened to hold
            with _locks.exempt("gc finalizer on unreachable feed"):
                self.close()
        except Exception:  # graft-lint: allow(L501)
            pass

    def __len__(self):
        return len(self.source)
