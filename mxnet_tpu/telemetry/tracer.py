"""Span tracer: the causal-timeline half of the telemetry subsystem.

Reference analog: MXNet's engine profiler (``MXSetProfilerConfig`` /
``MXDumpProfile``), which stamps every engine op into a chrome://tracing
timeline. Here the spans are host-side seams (dispatch, fused step,
pipeline stages, serving request lifecycle) — device compute is XLA's
and lives in the XPlane trace the profiler already drives — but the
contract is the same: nested/parallel spans with parent/child causality,
exportable to Perfetto.

Design constraints, in order:

1. **Zero-cost disabled.** ``MXNET_TELEMETRY=0`` must add nothing
   measurable to the eager-dispatch and training-step hot loops: one
   env-dict lookup and an integer compare, no allocation, no lock.
   ``span(...)`` returns a shared no-op context manager.
2. **Never block the hot path.** The buffer is a bounded
   ``deque(maxlen=...)`` ring: appends are O(1), GIL-atomic, and when
   full the OLDEST span drops (a long-running server keeps its most
   recent window, like any flight recorder). Drops are counted
   (``dropped_spans``), never waited on.
3. **Causality.** Each thread keeps a span stack: a span opened inside
   another records it as parent, so the exported trace nests. Across
   threads — where a request's spans hop from the HTTP handler to the
   batcher worker — causality rides the **trace id** (request-scoped,
   propagated via :func:`trace_context` or an explicit ``trace_id=``
   argument), which every span stamps into its args.

Levels (``MXNET_TELEMETRY``): ``0`` off; ``1`` (the default, also when
the variable is unset) structural spans (build, step, compile, batch,
request lifecycle, checkpoint, disk IO — a handful per step / request):
the flight recorder is on unless switched off; ``2`` adds
high-frequency detail (per-op eager dispatch, per-rewrite-pass spans).
Levels gate at span creation, so a level-2 call site costs only the env
read when the level is 1.

Clock: ``time.monotonic()`` everywhere (one clock across every thread;
serving deadline math already lives on it — graft_lint L602).
Timestamps are exported in microseconds relative to the tracer epoch.
The epoch is anchored to the wall clock once, at import
(:func:`epoch_unix_ns`): an event lies at ``epoch_unix_ns() + ts * 1000``
unix nanoseconds, the clock a device trace's ``profile_start_time`` is
on, so a dump can be laid over an XProf trace.
"""
from __future__ import annotations

import itertools
import os
import threading
import time
from collections import deque

__all__ = ["TELEMETRY_KNOB", "level", "tracing", "span", "instant",
           "emit_span", "trace_context", "current_trace_id",
           "new_trace_id", "events", "reset", "dropped_spans",
           "buffer_capacity", "thread_names", "epoch_unix_ns",
           "current_span_id"]

TELEMETRY_KNOB = "MXNET_TELEMETRY"
_BUFFER_KNOB = "MXNET_TELEMETRY_BUFFER"
# A full ring is what a server left running for weeks keeps resident:
# 8192 events of 0.5-0.6 KB each (tracemalloc; docs/TELEMETRY.md), under
# 5 MB. A training run's set-up plus a minute of steps is a few thousand
# events.
_DEFAULT_CAPACITY = 8192


def level():
    """``MXNET_TELEMETRY`` as an int (0 off / 1 structural / 2 verbose;
    unset reads 1). Read per call — the hot-path cost of the disabled
    tracer IS this read, one dict lookup — so tests and benchmarks
    toggle it without reimport. Not routed through ``env.get_int`` on
    purpose: that helper logs on garbage, and this runs on every
    dispatch."""
    v = os.environ.get(TELEMETRY_KNOB)  # graft-lint: allow(L101)
    if not v:
        return 1
    try:
        return int(v)
    except ValueError:
        return 1  # a set-but-garbled knob means "on"


def tracing(need=1):
    """True when spans at detail level ``need`` are being recorded."""
    return level() >= need


class _Ring:
    """Bounded drop-oldest event ring. ``deque.append`` is GIL-atomic,
    so the hot path takes no lock; the emitted counter is a plain int
    (exact single-threaded, may undercount under heavy cross-thread
    races — it guards a diagnostic, not an invariant)."""

    __slots__ = ("buf", "emitted")

    def __init__(self, capacity):
        self.buf = deque(maxlen=int(capacity))
        self.emitted = 0

    @property
    def dropped(self):
        return max(0, self.emitted - len(self.buf))


def _capacity():
    try:
        cap = int(os.environ.get(  # graft-lint: allow(L101)
            _BUFFER_KNOB, _DEFAULT_CAPACITY))
    except ValueError:
        cap = _DEFAULT_CAPACITY
    return max(16, cap)


def _anchor(tries=5):
    """(monotonic seconds, unix ns) of one instant: the wall clock read
    between two monotonic reads, the tightest of a few tries kept."""
    best = None
    for _ in range(tries):
        a = time.monotonic()
        wall = time.time_ns()
        b = time.monotonic()
        if best is None or b - a < best[0]:
            best = (b - a, (a + b) / 2.0, wall)
    return best[1], best[2]


#: tracer epoch: every exported ts is monotonic-µs since this instant,
#: which the wall clock read as _EPOCH_UNIX_NS
_EPOCH, _EPOCH_UNIX_NS = _anchor()
_RING = _Ring(_capacity())
_SPAN_IDS = itertools.count(1)  # next() is GIL-atomic
_THREADS = {}  # tid -> thread name, for exporter "M" metadata events
_PID = os.getpid()
_monotonic = time.monotonic  # hot-path local binding


class _TLState(threading.local):
    """Per-thread tracer state. The subclass ``__init__`` runs once per
    thread on first touch, so the hot path reads plain attributes — a
    bare ``threading.local`` pays an AttributeError-guarded ``getattr``
    on every span from a thread that never opened a trace context."""

    def __init__(self):
        self.stack = []  # open span ids (lexical nesting)
        self.trace = []  # trace-id stack (trace_context scopes)
        self.tid = ident = threading.get_ident() % 100000
        # assignment, not setdefault: the OS reuses idents of exited
        # threads, and the stale owner's name must not shadow the
        # thread currently holding the ident
        _THREADS[ident] = threading.current_thread().name


_TLS = _TLState()


def _tid():
    return _TLS.tid


def thread_names():
    """{tid: thread name} of every thread that touched the tracer."""
    return dict(_THREADS)


def _stack():
    return _TLS.stack


def current_span_id():
    """Id of the calling thread's innermost open span, or None."""
    stack = _TLS.stack
    return stack[-1] if stack else None


def epoch_unix_ns():
    """Unix nanoseconds of the tracer epoch: an event's wall-clock start
    is ``epoch_unix_ns() + ts * 1000`` (``ts`` in µs), its duration stays
    a monotonic difference."""
    return _EPOCH_UNIX_NS


# -- trace-id propagation ---------------------------------------------------

def new_trace_id():
    """A fresh request-scoped trace id (hex, cheap, unique enough for
    correlating one process's spans with its HTTP responses)."""
    return f"{_PID & 0xffff:04x}{next(_SPAN_IDS) & 0xffffff:06x}" \
           f"{int((time.monotonic() - _EPOCH) * 1e6) & 0xffffff:06x}"


class _TraceCtx:
    __slots__ = ("trace_id",)

    def __init__(self, trace_id):
        self.trace_id = trace_id

    def __enter__(self):
        _TLS.trace.append(self.trace_id)
        return self.trace_id

    def __exit__(self, *exc):
        st = _TLS.trace
        if st:
            st.pop()


def trace_context(trace_id=None):
    """Scope the calling thread to ``trace_id`` (generated when None):
    every span/instant emitted inside — and only inside — stamps it.
    The id itself is returned by ``__enter__`` so the HTTP layer can
    echo it back to the client."""
    return _TraceCtx(trace_id or new_trace_id())


def current_trace_id():
    """The calling thread's active trace id, or None."""
    st = _TLS.trace
    return st[-1] if st else None


# -- span emission ----------------------------------------------------------

def _emit(ev):
    ring = _RING
    ring.buf.append(ev)
    ring.emitted += 1


def emit_span(name, cat, t0, t1, trace_id=None, parent=None, **attrs):
    """Record a completed span from explicit ``time.monotonic()``
    endpoints — for durations measured before the tracer gets involved
    (a request's queue wait runs from ``t_submit``, stamped in
    ``submit()``, to batch formation in a worker thread). Honors the
    ambient trace context when ``trace_id`` is not given; ``parent`` is
    the caller's to give (:func:`current_span_id` where the duration
    lies inside the thread's open span). No level check: the caller
    gates (it usually already knows)."""
    args = attrs
    tid = trace_id if trace_id is not None else current_trace_id()
    if tid is not None:
        args["trace_id"] = tid
    if parent is not None:
        args["parent"] = parent
    _emit({"name": name, "cat": cat, "ph": "X",
           "ts": (t0 - _EPOCH) * 1e6,
           "dur": max(0.0, (t1 - t0) * 1e6),
           "pid": _PID, "tid": _tid(), "args": args})


def instant(name, cat="event", need=1, trace_id=None, **attrs):
    """An instant event ('i', thread-scoped) at detail level ``need``.
    No-op (one env read) below that level."""
    if level() < need:
        return
    args = attrs
    tid = trace_id if trace_id is not None else current_trace_id()
    if tid is not None:
        args["trace_id"] = tid
    stack = _stack()
    if stack:
        args["parent"] = stack[-1]
    _emit({"name": name, "cat": cat, "ph": "i", "s": "t",
           "ts": (time.monotonic() - _EPOCH) * 1e6,
           "pid": _PID, "tid": _tid(), "args": args})


class _NullSpan:
    """The disabled path: one shared instance, no state, no clocks."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs):
        """No-op attr sink (mirrors _Span.set)."""


_NULL = _NullSpan()


class _Span:
    __slots__ = ("name", "cat", "trace_id", "args", "_t0", "_id",
                 "_parent")

    def __init__(self, name, cat, trace_id, args):
        self.name = name
        self.cat = cat
        self.trace_id = trace_id
        self.args = args

    def set(self, **attrs):
        """Attach attributes discovered mid-span (cache hit/miss,
        batch rows) to the span being recorded."""
        self.args.update(attrs)

    def __enter__(self):
        stack = _TLS.stack
        self._parent = stack[-1] if stack else None
        self._id = sid = next(_SPAN_IDS)
        stack.append(sid)
        self._t0 = _monotonic()
        return self

    def __exit__(self, exc_type, exc, tb):
        t1 = _monotonic()
        tls = _TLS
        stack = tls.stack
        sid = self._id
        if stack and stack[-1] == sid:
            stack.pop()
        args = self.args
        args["span_id"] = sid
        if self._parent is not None:
            args["parent"] = self._parent
        tid = self.trace_id
        if tid is None:
            tr = tls.trace
            tid = tr[-1] if tr else None
        if tid is not None:
            args["trace_id"] = tid
        if exc_type is not None:
            args["error"] = exc_type.__name__
        ring = _RING
        ring.buf.append({"name": self.name, "cat": self.cat, "ph": "X",
                         "ts": (self._t0 - _EPOCH) * 1e6,
                         "dur": (t1 - self._t0) * 1e6,
                         "pid": _PID, "tid": tls.tid, "args": args})
        ring.emitted += 1
        return False


def span(name, cat="host", need=1, trace_id=None, **attrs):
    """The span context manager::

        with telemetry.span("serving.execute", cat="serving", rows=n):
            ...

    Below detail level ``need`` this returns a shared no-op — the
    disabled cost is the env read inside :func:`level`. Attributes are
    exported as the Chrome-trace event's ``args``; the ambient trace
    id (or an explicit ``trace_id=``) and the parent span id ride
    along, which is what makes one request's spans reconstructible
    across threads."""
    if level() < need:
        return _NULL
    return _Span(name, cat, trace_id, attrs)


# -- reading / lifecycle ----------------------------------------------------

def events():
    """Snapshot of the ring's events, oldest first (list copy; the
    ring keeps filling)."""
    return list(_RING.buf)


def dropped_spans():
    """Events evicted by ring wraparound since the last reset."""
    return _RING.dropped


def buffer_capacity():
    return _RING.buf.maxlen


def reset(capacity=None):
    """Drop all recorded events (tests, benchmarks); optionally resize
    the ring. Thread name registry survives — tids stay meaningful."""
    global _RING
    _RING = _Ring(capacity if capacity is not None else _capacity())
