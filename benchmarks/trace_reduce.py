"""From a profiler trace (XPlane) to device metrics.

``load(dir)`` reads the ``*.xplane.pb`` that ``jax.profiler`` wrote with
nothing but JAX, and keeps the device planes (``/device:TPU:<n>``) with
their ``XLA Ops`` line, and the host's annotation events. Everything else
is arithmetic on ``(name, start_ns, duration_ns)`` tuples, which the
tests beside this file drive directly.

There is NO fallback to host events: a trace without a device lane is an
error (``NoDeviceLane``), because summing host events as device time is
how a host-only trace gets read as a busy chip.
"""
from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
# HLO collectives, as their ops are named on the device's op line
COLLECTIVE = re.compile(
    r"^%?(all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute|collective-broadcast)", re.I)


class NoDeviceLane(RuntimeError):
    """The trace holds no TPU plane with an op line."""


def find_xplane(path):
    if os.path.isfile(path):
        return path
    hits = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                            recursive=True))
    if not hits:
        raise FileNotFoundError(f"no *.xplane.pb under {path}")
    return hits[-1]


def load(path, host_prefix="bench.", host_spans=()):
    """{"devices": {ordinal: [(name, start_ns, dur_ns), ...]},
        "host": [(name, start_ns, dur_ns), ...]}.

    Device events are those of each TPU plane's ``XLA Ops`` line, in
    nanoseconds from the profile's start. Host events are the profiler's
    own annotations whose name starts with ``host_prefix`` (where its host
    tracer was on) and ``host_spans``: spans ``(name, unix_ns, dur_ns)``
    the caller kept on the wall clock, moved onto the trace's clock by the
    profile's start time (``Task Environment``). Without that time they
    are dropped: a gap is then ``(unannotated)``, never guessed."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(find_xplane(path))
    devices, host, started = {}, [], None
    for plane in data.planes:
        if plane.name == "Task Environment":
            started = dict(plane.stats).get("profile_start_time")
    if started is not None:
        host += [(n, int(s) - int(started), int(d)) for n, s, d in host_spans]
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[int(m.group(1))] = [
                        (e.name, int(e.start_ns), int(e.duration_ns))
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(host_prefix):
                        host.append((e.name, int(e.start_ns),
                                     int(e.duration_ns)))
    if not devices or not any(devices.values()):
        raise NoDeviceLane(
            f"no '/device:TPU:<n>' plane with a non-empty {OPS_LINE!r} line "
            f"in {path}: planes {[p.name for p in data.planes]}")
    return {"devices": devices, "host": sorted(host, key=lambda e: e[1])}


# ---------------------------------------------------------------------------
# arithmetic on events

def union(intervals):
    """Merged, sorted [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _clip(events, window):
    lo, hi = window
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            yield name, a, b


def busy_ns(events, window):
    """Nanoseconds of ``window`` in which at least one op ran."""
    return sum(e - s for s, e in
               union((a, b) for _, a, b in _clip(events, window)))


def window_of(events):
    """First op start to last op end."""
    if not events:
        raise NoDeviceLane("no device events")
    return (min(s for _, s, _ in events), max(s + d for _, s, d in events))


def per_op(events, window, top=10, key=None):
    """[[name, seconds], ...] of the ops with most device time."""
    tot = {}
    for name, a, b in _clip(events, window):
        k = key(name) if key else name
        tot[k] = tot.get(k, 0) + (b - a)
    rows = sorted(tot.items(), key=lambda kv: -kv[1])[:top]
    return [[k, v / 1e9] for k, v in rows]


def op_family(name):
    """'%fusion.123' -> 'fusion', '%convolution.42 = ...' -> 'convolution'."""
    base = name.lstrip("%").split(" ")[0].split("(")[0]
    return re.sub(r"[.\d]+$", "", base) or base


def time_of(events, window, pattern):
    """(seconds, count) of the ops whose name matches ``pattern``."""
    rx = re.compile(pattern)
    ns = n = 0
    for name, a, b in _clip(events, window):
        if rx.search(name):
            ns += b - a
            n += 1
    return ns / 1e9, n


def exposed_collective_ns(events, window):
    """Nanoseconds in which a collective ran on this device and no other
    op did: the part of the exchange that compute does not hide."""
    coll, comp = [], []
    for name, a, b in _clip(events, window):
        (coll if COLLECTIVE.match(name) else comp).append((a, b))
    coll, comp = union(coll), union(comp)
    hidden = 0
    j = 0
    for s, e in coll:
        while j < len(comp) and comp[j][1] <= s:
            j += 1
        k = j
        while k < len(comp) and comp[k][0] < e:
            hidden += min(e, comp[k][1]) - max(s, comp[k][0])
            k += 1
    return sum(e - s for s, e in coll) - hidden


def idle_gaps(events, window, host=(), top=10):
    """[[what the host was doing, seconds], ...]: the device's idle time
    inside ``window``, each gap attributed to the host annotation that
    overlaps it most (``(unannotated)`` where none does), summed by name,
    longest first."""
    busy = union((a, b) for _, a, b in _clip(events, window))
    gaps, cur = [], window[0]
    for s, e in busy:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if window[1] > cur:
        gaps.append((cur, window[1]))
    tot = {}
    for gs, ge in gaps:
        best, best_ov = "(unannotated)", 0
        for name, s, d in host:
            if s >= ge:
                break
            ov = min(ge, s + d) - max(gs, s)
            if ov > best_ov:
                best, best_ov = name, ov
        tot[best] = tot.get(best, 0) + (ge - gs)
    rows = sorted(tot.items(), key=lambda kv: -kv[1])[:top]
    return [[k, v / 1e9] for k, v in rows]


def summary(trace, window=None):
    """What every traced run reports: per device busy seconds, averaged
    over the devices, the window, and the breakdown of device 0."""
    devs = trace["devices"]
    if not devs or not any(devs.values()):
        raise NoDeviceLane("no device events")
    first = devs[min(devs)]
    if window is None:
        lo = min(window_of(ev)[0] for ev in devs.values() if ev)
        hi = max(window_of(ev)[1] for ev in devs.values() if ev)
        window = (lo, hi)
    busy = [busy_ns(ev, window) for ev in devs.values()]
    return {
        "window": window,
        "window_s": (window[1] - window[0]) / 1e9,
        "busy_s": sum(busy) / len(busy) / 1e9,
        "device_ops": per_op(first, window, key=op_family),
        "idle_gaps": idle_gaps(first, window, trace["host"]),
    }
