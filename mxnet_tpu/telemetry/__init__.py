"""Unified telemetry: span tracing + one metrics registry (round 18).

The observability layer the reference framework ships as its engine
profiler (``MXSetProfilerConfig`` / ``MXDumpProfile`` →
chrome://tracing), grown for the asynchronous stack rounds 11–17
built: nested/parallel spans make pipeline overlap and continuous
batching *visible*, a request-scoped trace id follows one HTTP request
through batcher/session/state-store threads, and every counter family
in the process — training and serving — reads and scrapes from one
registry.

Four pieces, importable à la carte:

- :mod:`.tracer` — ``span()`` / ``instant()`` / ``trace_context()``,
  ``MXNET_TELEMETRY={0,1,2}``-gated (default 1), bounded drop-oldest
  ring, anchored to the wall clock (``epoch_unix_ns``).
- :mod:`.metrics` — :class:`MetricsRegistry` (:data:`REGISTRY`):
  owned :class:`CounterFamily` dicts + probed families + ONE
  Prometheus exposition for training and serving.
- :mod:`.exporter` — ``dump_trace(path)``: Chrome-trace/Perfetto JSON
  of spans + thread names + registry counter samples.
- :mod:`.scopes` — the table from a compiled program's instructions to
  the scopes they were traced under, which splits a device trace by
  block and by phase.

``profiler`` keeps its MXNet-parity surface (``set_config`` /
``dump`` / ``dumps`` / ``*_counters()``) as thin views over this
package. This package imports nothing from the rest of ``mxnet_tpu``
at module level — it must be loadable before (and without) jax.

See ``docs/TELEMETRY.md``.
"""
from __future__ import annotations

from .tracer import (TELEMETRY_KNOB, buffer_capacity, current_trace_id,
                     dropped_spans, emit_span, epoch_unix_ns, events,
                     instant, level,
                     new_trace_id, reset as reset_trace, span,
                     thread_names, trace_context, tracing)
from .metrics import (REGISTRY, CounterFamily, MetricsRegistry,
                      counter_family, family_snapshot, prometheus_text,
                      register_exposition, register_family, snapshot)
from .exporter import build_trace, counter_samples, dump_trace
from . import scopes

__all__ = [
    # tracer
    "TELEMETRY_KNOB", "level", "tracing", "span", "instant",
    "emit_span", "trace_context", "current_trace_id", "new_trace_id",
    "events", "reset_trace", "dropped_spans", "buffer_capacity",
    "thread_names", "epoch_unix_ns",
    # metrics
    "REGISTRY", "MetricsRegistry", "CounterFamily", "counter_family",
    "register_family", "register_exposition", "family_snapshot",
    "snapshot", "prometheus_text",
    # exporter
    "build_trace", "counter_samples", "dump_trace",
    # instruction -> scope tables of compiled programs
    "scopes",
]
