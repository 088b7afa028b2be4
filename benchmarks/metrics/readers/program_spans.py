"""The program's own spans (``mxnet_tpu.telemetry``), read after the run.

The tracer's ring is module state of the program and outlives the loop's
``release()``. Its events are put on unix nanoseconds by the tracer's
anchor (``epoch_unix_ns() + ts_us * 1000``), the clock of the benchmark's
own ``bench.*`` spans, and the time of the spans named in ``names`` is
summed, an interval that lies inside another of the same thread counted
once (a trace of a jitted function holds the traces of those it calls).

``phase: "window"`` keeps the spans inside the traced slice (first start
to last end of the benchmark's spans) and gives milliseconds per step of
that slice, a step being one ``spmd.step`` span in it. ``phase: "setup"``
keeps the spans that ended before the slice began (the window's steps
before the slice compile nothing, and the reference's compiles start
after it) and gives seconds, or their number with ``count``.

Nothing is read (``None``) when the program has no such tracer, when its
ring dropped events, when it holds none of the names, or when the run
traced no slice."""
import sys

import trace_reduce

STEP = "spmd.step"


def program_events():
    """[(name, unix_ns, dur_ns, thread, args), ...] of the complete spans
    in the program's ring, or None where there is nothing sound to read."""
    try:
        from mxnet_tpu.telemetry import tracer
    except ImportError:
        return None
    anchor = getattr(tracer, "epoch_unix_ns", None)
    if anchor is None or tracer.dropped_spans():
        return None
    base = anchor()
    return [(e["name"], base + int(e["ts"] * 1000), int(e["dur"] * 1000),
             e["tid"], e["args"])
            for e in tracer.events() if e.get("ph") == "X"]


def slice_of(bench_spans):
    """(first start, last end) of the benchmark's own spans, which it
    keeps only while the profiler runs: the traced slice in unix ns."""
    if not bench_spans:
        return None
    return (min(s for _, s, _ in bench_spans),
            max(s + d for _, s, d in bench_spans))


def select(events, names, phase, window):
    """The events named in ``names`` that lie inside ``window``
    (``phase`` "window") or ended before it began ("setup")."""
    lo, hi = window
    if phase == "window":
        return [e for e in events
                if e[0] in names and e[1] >= lo and e[1] + e[2] <= hi]
    if phase == "setup":
        return [e for e in events if e[0] in names and e[1] + e[2] <= lo]
    raise ValueError(f"phase {phase!r} is neither 'window' nor 'setup'")


def covered_ns(events):
    """Nanoseconds the events cover, thread by thread."""
    by_thread = {}
    for _, s, d, tid, _ in events:
        by_thread.setdefault(tid, []).append((s, s + d))
    return sum(b - a for ivs in by_thread.values()
               for a, b in trace_reduce.union(ivs))


def clock_offset(bench_spans, trace_host):
    """Unix ns minus the trace's ns, from a span that both lists hold
    (the reduction moved the benchmark's spans onto the trace's clock);
    None where the trace has no such span."""
    starts = {(n, d): s for n, s, d in bench_spans}
    for n, s, d in trace_host:
        if (n, d) in starts:
            return starts[(n, d)] - s
    return None


def innermost(events):
    """The events that are no other event's parent."""
    parents = {e[4].get("parent") for e in events}
    return [e for e in events if e[4].get("span_id") not in parents]


def log_idle_gaps(ctx, events, window):
    """Each idle gap of device 0 by the program's own span, once a run."""
    if ctx.trace is None or ctx.measured.get("program_idle_gaps") is not None:
        return
    offset = clock_offset(ctx.tracer.spans, ctx.trace["host"])
    if offset is None:
        return
    lo, hi = window
    host = sorted((n, s - offset, d) for n, s, d, _, _ in innermost(
        [e for e in events if e[1] + e[2] >= lo and e[1] <= hi]))
    devs = ctx.trace["devices"]
    gaps = trace_reduce.idle_gaps(
        devs[min(devs)], ctx.measured["trace_summary"]["window"], host=host)
    ctx.measured["program_idle_gaps"] = gaps
    print(f"[bench] idle gaps of device 0 by the program's spans: {gaps}",
          file=sys.stderr)


def read(ctx, names, phase, count=False):
    events = program_events()
    window = slice_of(ctx.tracer.spans)
    if not events or window is None:
        return None
    picked = select(events, set(names), phase, window)
    if not picked:
        return None
    if phase == "setup":
        return len(picked) if count else covered_ns(picked) / 1e9
    log_idle_gaps(ctx, events, window)
    steps = len(select(events, {STEP}, phase, window))
    if not steps:
        return None
    return covered_ns(picked) / 1e6 / steps
