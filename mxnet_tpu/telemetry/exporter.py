"""Chrome-trace / Perfetto JSON exporter.

One assembly point for everything the process knows about time:

- the tracer ring's span/instant events (``tracer.events()``),
- thread-name metadata (``"ph": "M"`` events, so Perfetto labels the
  batcher worker, DeviceFeed prefetcher, checkpoint writer threads by
  name instead of tid),
- one counter sample per registry family (``"ph": "C"``), named
  ``<family>/<counter>`` — the same legacy sample names
  ``profiler.dump()`` has always emitted (``eager_jit_cache/hits``,
  ``compile_cache/disk_hits``...), so existing trace consumers keep
  parsing,
- optionally, caller-supplied extra events — ``profiler.dump()`` passes
  its legacy ``_events`` list (Domain/Task/Frame scopes, ``record_op``
  dispatch events) so the two timelines land in ONE file.

The output is the Trace Event Format JSON array-of-dicts that
chrome://tracing and https://ui.perfetto.dev load directly:
``{"traceEvents": [{"name", "cat", "ph", "ts", "dur", "pid", "tid",
"args"}, ...], "displayTimeUnit": "ms", "otherData": {"epoch_unix_ns"}}``:
``ts`` is µs since the tracer epoch, and ``otherData.epoch_unix_ns`` is
that epoch on the wall clock.
"""
from __future__ import annotations

import json

from . import tracer
from . import metrics as _metrics

__all__ = ["counter_samples", "thread_metadata", "build_trace",
           "dump_trace"]


def counter_samples(ts=None):
    """One ``"ph": "C"`` sample per numeric counter in every registry
    family, stamped at ``ts`` (µs; default: now on the tracer clock).
    Sample names are ``<family>/<counter>`` — the legacy
    ``profiler.dump()`` naming, kept verbatim."""
    _metrics._bootstrap_probes()
    if ts is None:
        import time

        ts = (time.monotonic() - tracer._EPOCH) * 1e6
    out = []
    for family, snap in _metrics.snapshot().items():
        for cname in sorted(snap):
            cval = snap[cname]
            if isinstance(cval, bool):
                cval = int(cval)
            if not isinstance(cval, (int, float)):
                continue
            out.append({"name": f"{family}/{cname}", "cat": "counter",
                        "ph": "C", "ts": ts, "pid": tracer._PID,
                        "args": {cname: cval}})
    return out


def thread_metadata():
    """``"ph": "M"`` thread_name events for every thread that emitted
    a span — Perfetto shows 'batcher-worker'/'prefetch-0' lanes."""
    return [{"name": "thread_name", "ph": "M", "pid": tracer._PID,
             "tid": tid, "args": {"name": name}}
            for tid, name in sorted(tracer.thread_names().items())]


def build_trace(extra_events=None, counters=True):
    """Assemble the full Chrome-trace payload dict (no IO).

    ``extra_events`` are appended verbatim (the profiler's legacy event
    list rides along here); ``counters=False`` skips the registry
    sample pass (the overhead bench times pure span export)."""
    events = thread_metadata()
    events.extend(tracer.events())
    if extra_events:
        events.extend(extra_events)
    if counters:
        events.extend(counter_samples())
    # the clock anchor: ts 0 is this unix time, so the dump can be laid
    # over a device trace (XProf's profile_start_time is on that clock)
    other = {"epoch_unix_ns": tracer.epoch_unix_ns()}
    dropped = tracer.dropped_spans()
    if dropped:
        other["dropped_spans"] = dropped
    return {"traceEvents": events, "displayTimeUnit": "ms",
            "otherData": other}


def dump_trace(path, extra_events=None, counters=True):
    """Write the assembled trace to ``path`` and return the payload —
    ``json.load(open(path))`` round-trips, and the file opens directly
    in Perfetto / chrome://tracing."""
    payload = build_trace(extra_events=extra_events, counters=counters)
    with open(path, "w") as f:
        json.dump(payload, f)
    return payload
