"""The benchmark's one harness.

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One run is one process. It finds the cell in ``BENCHMARK.json``, and by
the names written there the files that belong to it: the configuration
(``configs/<config>.json``), its builder (``models/<config>.py``), its
plain reference (``reference/<config>.py``), its operation counts
(``flops/<config>.py``), the traffic mix (``traffic/<traffic>.json``)
with the loop that drives it (``loops/<kind>.py``), the limits of
``correct`` (``limits/<cell>.json``) and each per-layer metric's reader
(``metrics/<metric>.json`` -> ``metrics/readers/<reader>.py``). This file
knows no model, cell or metric by name.

It refuses to start unless JAX's first device is a TPU whose kind is in
``peaks.json`` and the cell's chips are present. ``--rehearse`` lifts
that check, shrinks the sizes to the ``rehearse`` entries of the data
files and interprets kernels, to walk the control flow on the CPU; a
rehearsal never prints the result line.
"""
from __future__ import annotations

import time

_T0 = time.perf_counter()          # process start, for setup_s

import argparse                     # noqa: E402
import contextlib                   # noqa: E402
import gc                           # noqa: E402
import importlib.util               # noqa: E402
import json                         # noqa: E402
import os                           # noqa: E402
import shutil                       # noqa: E402
import sys                          # noqa: E402
import tempfile                     # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (HERE, ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)


# ---------------------------------------------------------------------------
# files found by name

def load_json(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_module(*parts):
    """A Python file under benchmarks/ by path: names such as
    ``opt-1.3b.py`` are no module names."""
    path = os.path.join(HERE, *parts)
    name = "bench_" + "_".join(parts).replace(".py", "").replace(
        "-", "_").replace(".", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def log(msg):
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


class Refused(SystemExit):
    """Exit without a result line."""

    def __init__(self, msg):
        log("refused: " + msg)
        super().__init__(2)


# ---------------------------------------------------------------------------
# the run's context: what the loops, builders and readers share

class _Span:
    """One host span on the wall clock, kept in memory."""

    __slots__ = ("spans", "name", "t0")

    def __init__(self, spans, name):
        self.spans, self.name = spans, name

    def __enter__(self):
        self.t0 = time.time_ns()

    def __exit__(self, *exc):
        self.spans.append((self.name, self.t0, time.time_ns() - self.t0))


class Tracer:
    """Profiles a slice of the measured window (``--trace 1``). The loop
    calls ``tick(elapsed)`` once per iteration. Only the device is traced:
    with the profiler's host tracer on, twelve ResNet-50 steps wrote 330 MB,
    took half a minute to stop and stalled steps by up to 1.5 s (my chip
    run, PR 27); with it off they wrote 8 MB and ran at their untraced
    time. So the benchmark keeps its own spans on the wall clock, and the
    reduction puts them on the trace's clock by the profile's start time."""

    def __init__(self, on, start_s, length_s):
        self.on, self.start_s, self.length_s = on, start_s, length_s
        self.dir = None
        self.active = False
        self.done = False
        self.stall_s = 0.0          # time spent starting/stopping
        self.spans = []

    def tick(self, elapsed, sync=None):
        if not self.on or self.done:
            return
        if not self.active and elapsed >= self.start_s:
            import jax

            t = time.perf_counter()
            self.dir = tempfile.mkdtemp(prefix="bench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 0
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self.active = True
            self.began = elapsed
            self.stall_s += time.perf_counter() - t
        elif self.active and elapsed >= self.began + self.length_s:
            self.stop(sync)

    def stop(self, sync=None):
        if not self.active:
            return
        import jax

        t = time.perf_counter()
        if sync is not None:
            sync()
        jax.profiler.stop_trace()
        self.active = False
        self.done = True
        self.stall_s += time.perf_counter() - t

    def span(self, name):
        if self.active:
            return _Span(self.spans, name)
        return contextlib.nullcontext()

    def read(self):
        """The reduced trace, or None when nothing was traced."""
        if not self.done:
            return None
        import trace_reduce

        try:
            return trace_reduce.load(self.dir, host_spans=self.spans)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


class Ctx:
    """One run's shared state."""

    def __init__(self, bench, cell, args, fault=None):
        self.bench, self.cell, self.args = bench, cell, args
        self.name = cell["name"]
        self.seed = int(args.seed)
        self.chips = int(cell["chips"])
        self.rehearse = bool(args.rehearse)
        self.fault = fault
        self.cfg_name = cell["config"]
        self.cfg = load_json("configs", self.cfg_name + ".json")
        self.traffic = load_json("traffic", cell["traffic"] + ".json")
        if self.rehearse:
            self.cfg.update(self.cfg.get("rehearse", {}))
            self.traffic.update(self.traffic.get("rehearse", {}))
        self.model = load_module("models", self.cfg_name + ".py")
        self.ref = load_module("reference", self.cfg_name + ".py")
        self.flops = load_module("flops", self.cfg_name + ".py")
        try:
            self.limits = load_json("limits", self.name + ".json")["limits"]
        except FileNotFoundError:
            self.limits = {}
        self.measured = {}          # what the loop measured, for readers
        self.trace = None           # reduced trace, for readers
        self.peak = None            # this device's row of peaks.json
        self.devices = []
        self.tracer = Tracer(
            bool(args.trace), float(args.seconds) / 3.0,
            min(float(self.traffic.get("trace_seconds", 3.0)),
                float(args.seconds) / 3.0))

    def span(self, name):
        return self.tracer.span(name)

    def rng(self, stream=0):
        import numpy as onp

        return onp.random.default_rng(
            [self.seed & 0xFFFFFFFF, self.seed >> 32, stream])


# ---------------------------------------------------------------------------

def find_cell(bench, name):
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise Refused(f"no workload {name!r} in BENCHMARK.json")


def metrics_of(bench, group, cell_name, reported):
    """Names of the ``group`` metrics this cell reports."""
    out = []
    for m in bench[group]:
        cells = m.get("workloads")
        if cells is not None:
            if cell_name in cells:
                out.append(m)
        elif group == "end_to_end":
            if m["name"] in reported:
                out.append(m)
        else:
            if m["moves"] in reported:
                out.append(m)
    return out


def place_caches():
    """JAX's persistent cache goes where JAX_COMPILATION_CACHE_DIR says,
    else to the fixed <checkout>/.jax_cache: the rule the program's
    utils/compile_cache.py follows, so both write one directory."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))


def look_for_chip(ctx):
    import jax

    devs = jax.devices()
    first = devs[0]
    if not ctx.rehearse:
        if first.platform != "tpu":
            raise Refused(f"JAX's first device is {first.platform!r}, not a "
                          "TPU; the benchmark measures nothing elsewhere")
        if len(devs) < ctx.chips:
            raise Refused(f"cell {ctx.name} needs {ctx.chips} chip(s), JAX "
                          f"has {len(devs)}")
    elif len(devs) < ctx.chips:
        raise Refused(f"rehearsal of {ctx.name} needs {ctx.chips} devices: "
                      "XLA_FLAGS=--xla_force_host_platform_device_count=4")
    peaks = load_json("peaks.json")["device_kinds"]
    if first.device_kind in peaks:
        ctx.peak = peaks[first.device_kind]
    elif not ctx.rehearse:
        raise Refused(f"device_kind {first.device_kind!r} is not in "
                      "benchmarks/peaks.json; add it with its source")
    ctx.devices = devs[:ctx.chips]


def device_report(ctx, loop):
    """The device as JAX reports it. ``memory_peak_bytes`` is the peak on
    the fullest chip: the runtime's ``peak_bytes_in_use``, or what is
    resident now plus the temporaries of the window's executable where
    that is more (the runtime's figure leaves the temporaries out)."""
    first = ctx.devices[0]
    peak = resident = 0
    for d in ctx.devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
        resident = max(resident, int(stats.get("bytes_in_use", 0)))
    try:
        temp = loop.temp_bytes()
    except Exception as e:           # reported, never fatal
        log(f"no memory analysis of the window's executable: {e!r}")
        temp = 0
    return {"platform": first.platform, "kind": first.device_kind,
            "count": len(ctx.devices),
            "memory_peak_bytes": max(peak, resident + temp),
            "runtime_peak_bytes": peak, "resident_bytes": resident,
            "program_temp_bytes": temp}


def execute(args, fault=None, tweak=None, bench=None):
    """Everything after the command line; returns the result object.
    ``fault`` breaks the timed path underneath, ``tweak(ctx)`` edits the
    context before anything is built and ``bench`` stands in for
    ``BENCHMARK.json``: all three exist for the tests beside the harness,
    and no run of the benchmark sets them."""
    if bench is None:
        bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cell = find_cell(bench, args.workload)
    ctx = Ctx(bench, cell, args, fault)
    if tweak is not None:
        tweak(ctx)
    place_caches()
    look_for_chip(ctx)
    loop = load_module("loops", ctx.traffic["kind"] + ".py").Loop(ctx)

    loop.setup()                       # build, warm the cell's shapes
    # The collector is left as a user of the program has it; what it
    # costs inside the window is logged (PERF.md, Open questions).
    pauses = []
    gc.callbacks.append(
        lambda phase, info: pauses.append((phase, time.perf_counter())))
    setup_s = time.perf_counter() - _T0
    log(f"{ctx.name}: set-up {setup_s:.2f}s "
        f"{ctx.measured.get('setup_parts_s', '')}; measuring {args.seconds}s")
    loop.window(float(args.seconds))   # the measured window
    ctx.tracer.stop()
    gc.callbacks.pop()
    took = [b[1] - a[1] for a, b in zip(pauses, pauses[1:])
            if a[0] == "start" and b[0] == "stop"]
    log(f"window: {ctx.measured.get('steps')} steps, p90 of single steps "
        f"{ctx.measured.get('single_step_ms_p90', 0):.2f} ms, longest gap "
        f"between completions {ctx.measured.get('longest_ms', 0):.1f} ms; "
        f"collector "
        f"ran {len(took)} times, {sum(took) * 1e3:.1f} ms in all, longest "
        f"{max(took, default=0) * 1e3:.1f} ms")
    ctx.measured["setup_s"] = setup_s
    device = device_report(ctx, loop)  # the peak, before any reference
    loop.release()                     # the program's state is freed
    gc.collect()

    if args.trace:
        import trace_reduce

        try:
            ctx.trace = ctx.tracer.read()
        except trace_reduce.NoDeviceLane as e:
            if not ctx.rehearse:
                raise
            log(f"rehearsal: {e}; trace metrics are left out")
        if ctx.trace is not None:
            summ = trace_reduce.summary(ctx.trace)
            ctx.measured["trace_summary"] = summ
            device["busy_s"] = summ["busy_s"]
            device["window_s"] = summ["window_s"]

    numbers = loop.verify()            # against the plain reference
    correct = bool(numbers) and all(
        v["value"] is not None and v["value"] <= v["limit"]
        for v in numbers.values())

    reported = set(ctx.measured)
    metrics = {}
    if args.trace:
        for m in metrics_of(bench, "per_layer", ctx.name, reported):
            spec = load_json("metrics", m["name"] + ".json")
            reader = load_module("metrics", "readers", spec["reader"] + ".py")
            value = reader.read(ctx, **spec.get("args", {}))
            if value is not None:       # nothing to read: left out
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in metrics_of(bench, "end_to_end", ctx.name, reported):
            metrics[m["name"]] = {"value": ctx.measured[m["name"]],
                                  "unit": m["unit"]}
    result = {"correct": correct,
              "attempted": ctx.measured.get("attempted", 0),
              "failed": ctx.measured.get("failed", 0),
              "metrics": metrics, "device": device}
    if "trace_summary" in ctx.measured:
        summ = ctx.measured["trace_summary"]
        result["breakdown"] = {"device_ops": summ["device_ops"],
                               "idle_gaps": summ["idle_gaps"]}
    result["compared"] = numbers       # comes last: each number, its limit
    if ctx.measured.get("compared_at"):
        log(f"worst leaves: {ctx.measured['compared_at']}")
    for k, v in numbers.items():
        log(f"compared {k}: {v['value']} limit {v['limit']}"
            + ("" if v["value"] is not None and v["value"] <= v["limit"]
               else "  <-- NOT WITHIN"))
    return result


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on the CPU; prints no result line")
    return ap.parse_args(argv)


def main(argv=None):
    args = parse(argv)
    if not os.path.isdir(os.path.join(ROOT, "mxnet_tpu")):
        raise Refused("no system under test beside benchmarks/ "
                      f"({ROOT}/mxnet_tpu is missing)")
    if args.rehearse:
        # kernels interpreted, the CPU asked for by name
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    result = execute(args)
    if args.rehearse:
        log("rehearsal finished (no result line): "
            + json.dumps(result)[:2000])
        return 0
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
