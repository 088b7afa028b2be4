"""Device milliseconds per step of the ops of device 0 that are found by
their HLO instruction's name (a Pallas kernel's ``name=``), inside the
traced window. The window's steps are the traced run's rate over the
items of a step, times the window's seconds.

``while_carrying`` also takes a ``while`` whose result tuple holds an
array of the kernel's shape: the name of the function in
``flops/<config>.py`` that gives (batch*heads, seq, head_dim). The flash
backward is such a loop, a scan that carries dk and dv as float32
(B, H, S, D); an array counts when its last two dimensions are (S, D) and
the ones before multiply to B*H, so that a scan over layers, whose carry
is (B, S, E), is not read as attention. Returns nothing when no such op
ran."""
import math
import re
import sys

import trace_reduce

_WHILE = re.compile(r"^%\S+ = (\(.*\)) while\(")
_ARRAY = re.compile(r"\b[a-z]+\d+\w*\[([\d,]+)\]")


def carries(instruction, shape):
    """True when ``instruction`` is a ``while`` whose result tuple holds
    an array of (..., S, D) with the leading dimensions multiplying to
    ``shape``'s first."""
    m = _WHILE.match(instruction)
    if not m:
        return False
    lead, tail = shape[0], tuple(shape[1:])
    for dims in _ARRAY.findall(m.group(1)):
        dims = tuple(int(d) for d in dims.split(","))
        if len(dims) > len(tail) and dims[-len(tail):] == tail and \
                math.prod(dims[:-len(tail)]) == lead:
            return True
    return False


def device_seconds(events, window, match, shape=None):
    """(seconds, calls) of the events in ``window`` whose instruction
    matches ``match`` or is a ``while`` carrying ``shape``."""
    rx = re.compile(match)
    picked = [e for e in events
              if rx.search(e[0]) or (shape and carries(e[0], shape))]
    return trace_reduce.time_of(picked, window, "")


def read(ctx, match, while_carrying=None):
    m = ctx.measured
    summ = m.get("trace_summary")
    if ctx.trace is None or not summ or not m.get("traced_rate") \
            or not m.get("items_per_step"):
        return None
    shape = (getattr(ctx.flops, while_carrying)(ctx.cfg, ctx.traffic)
             if while_carrying else None)
    devs = ctx.trace["devices"]
    seconds, calls = device_seconds(devs[min(devs)], summ["window"], match,
                                    shape)
    if not calls:
        return None
    steps = m["traced_rate"] / m["items_per_step"] * summ["window_s"]
    print(f"[bench] ops matching {match!r}"
          + (f" or a while carrying {shape}" if shape else "")
          + f": {calls} calls, {seconds:.6f} s on the device in "
          f"{steps:.2f} steps", file=sys.stderr)
    return seconds * 1e3 / steps
