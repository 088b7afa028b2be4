"""Device time of the compiled step by the scope its ops were traced
under: by phase (forward, backward, update), by block, by whatever a
regular expression on the op's ``op_name`` says.

A trace names an op by its HLO instruction and carries no scope. The
program publishes, once a build, the table from the step's instructions
to their ``op_name`` (``mxnet_tpu.telemetry.scopes.table("spmd_step")``:
``{instruction: {"op_name", "members"}}``, a fusion under its root's
``op_name`` with the ``op_name``\\ s fused into it as ``members``), which
outlives the loop's ``release()`` as the tracer's ring does. Device 0's
events of the traced window are keyed by the token before ``" = "``.

The ``XLA Ops`` line nests: a ``while`` lies over the ops of its body.
Each event is given its own time less that of the events inside it, so
that every busy nanosecond belongs to one instruction and the sums add up
to the window's busy time. A fusion counts where the compiler's metadata
on the fusion puts it (the op it was built around: a weight gradient's
product, not the optimizer fused behind it; the table gives one without
metadata its last member's); an event whose instruction the table lacks,
or that has no ``op_name``, takes that of the event it lies in.

``scope`` is searched in the ``op_name``; ``not_instruction``, on the
instruction's name as the trace has it (``%flash_bwd.3``), leaves out ops
that have a metric of their own (the Pallas kernels); the result is
milliseconds a step, the window's steps taken as ``op_time`` takes them
(the traced run's rate over the items of a step, times the window's
seconds), or with ``share`` percent of the window's busy time. Once a run
the whole split goes to the log: every phase, every scope down to the
functions' own, the fusions of more than one block or phase, and what no
scope claims.

Nothing is read (``None``) where the program has no such module or
published no table, where nothing was traced, or where no op matches."""
import re
import sys

import trace_reduce

PROGRAM = "spmd_step"
PHASES = (("fwd", f"jit({PROGRAM})/jvp(fwd)/"),
          ("bwd", f"jit({PROGRAM})/transpose(jvp(fwd))/"),
          ("update", f"jit({PROGRAM})/update/"))
# what JAX's transforms put around a scope's name, and the names that
# are its own control flow, not the program's scopes
_WRAPPED = re.compile(r"^(?:jvp|transpose|vmap)\((.*)\)$")
_CALLED = re.compile(r"^\w+\(.*\)$")
_STRUCTURE = {"", "while", "body", "cond", "closed_call", "checkpoint",
              "rematted_computation", "pallas_call"}
_DEPTH = 5


def program_table():
    """The step's table as the program published it, or None."""
    try:
        from mxnet_tpu.telemetry import scopes
    except ImportError:
        return None
    return scopes.table(PROGRAM) or None


def instruction(event_name):
    """``'%fusion.12 = bf16[...] fusion(...)'`` -> ``'fusion.12'``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def attribute(events, window, table):
    """``[(instruction, op_name, members, ns), ...]``: each event of
    ``events`` inside ``window`` with its own nanoseconds (its time less
    that of the events nested in it) and its ``op_name`` from ``table``,
    or the enclosing event's where it has none."""
    clipped = sorted(((a, -b, name) for name, a, b in
                      trace_reduce._clip(events, window)))
    rows, stack = [], []            # stack: [end, row index]
    for a, nb, name in clipped:
        b = -nb
        while stack and stack[-1][0] <= a:
            stack.pop()
        key = instruction(name)
        entry = table.get(key)
        op = entry["op_name"] if entry else ""
        if stack:
            end, parent = stack[-1]
            b = min(b, end)
            rows[parent][3] -= b - a
            op = op or rows[parent][1]
        rows.append([key, op, entry["members"] if entry else [], b - a])
        stack.append((b, len(rows) - 1))
    return [tuple(r) for r in rows]


def phase_of(op_name):
    for phase, prefix in PHASES:
        if op_name.startswith(prefix):
            return phase
    return None


def scope_path(op_name):
    """The scopes of an ``op_name`` as the program opened them, at most
    ``_DEPTH``: the part after the phase (after its last mention: a
    backward inside a ``custom_vjp`` repeats the whole path), without the
    primitive at the end, JAX's transforms unwrapped (``jvp(runs)`` is
    ``runs``), and without called functions (``jit(_where)``), control
    flow and einsum specs."""
    parts = op_name.split(";", 1)[0].split("/")[1:-1]
    names = []
    for p in parts:
        m = _WRAPPED.match(p)
        while m:
            p = m.group(1)
            m = _WRAPPED.match(p)
        names.append(p)
    for marker in ("fwd", "update"):
        if marker in names:
            names = names[len(names) - names[::-1].index(marker):]
            break
    return [p for p in names if p not in _STRUCTURE
            and not _CALLED.match(p) and "," not in p][:_DEPTH]


def block_of(op_name):
    """The block-level scope: ``blocks/2/attn`` of a numbered child of a
    container, else the first scope (``embed``, ``head``, ``loss``)."""
    path = scope_path(op_name)
    if len(path) >= 3 and path[1].isdigit():
        return "/".join(path[:3])
    return path[0] if path else ""


def split(rows, busy_ns):
    """The log's numbers from ``attribute``'s rows: ``{"phases",
    "scopes", "mixed_blocks_ns", "mixed_phases_ns", "unclaimed"}``;
    phases and scopes as ``{key: [ns, calls]}``, a scope's key ``(label,
    path)``. The label is the phase; ``bwd.remat`` for a backward op under
    ``rematted_computation``; and for a fusion whose members are of
    further phases those behind a ``+`` (``bwd+update``: a weight
    gradient with its optimizer fused behind it), under its own path or,
    where it has none, the first block among those members'."""
    phases, scopes, unclaimed = {}, {}, {}
    mixed_blocks = mixed_phases = 0

    def add(into, key, ns):
        got = into.setdefault(key, [0, 0])
        got[0] += ns
        got[1] += 1

    for key, op, members, ns in rows:
        phase = phase_of(op)
        if phase is None:
            add(unclaimed, (trace_reduce.op_family(key), op), ns)
            continue
        add(phases, phase, ns)
        label, path = phase, "/".join(scope_path(op))
        if phase == "bwd" and "rematted_computation" in op:
            label = "bwd.remat"
        others = sorted({phase_of(m) for m in members} - {None, phase})
        if others:
            mixed_phases += ns
            label += "+" + "+".join(others)
            path = path or next(filter(None, (
                block_of(m) for m in members if phase_of(m) in others)), "")
        if len({block_of(m) for m in members} - {""}) > 1:
            mixed_blocks += ns
        add(scopes, (label, path), ns)
    return {"phases": phases, "scopes": scopes, "unclaimed": unclaimed,
            "mixed_blocks_ns": mixed_blocks, "mixed_phases_ns": mixed_phases,
            "busy_ns": busy_ns}


def log_split(got, steps, table_size, out=None):
    """The whole split, once a run, to stderr."""
    out = out or sys.stderr
    busy = got["busy_ns"] or 1

    def line(what, ns, calls=None):
        return (f"[bench]   {what}: {ns / 1e9:.6f} s"
                + (f", {calls} calls" if calls is not None else "")
                + f", {ns / 1e6 / steps:.3f} ms a step, "
                f"{100.0 * ns / busy:.2f}%")

    claimed = sum(ns for ns, _ in got["phases"].values())
    print(f"[bench] scopes of {PROGRAM}: a table of {table_size} "
          f"instructions; device 0 busy {busy / 1e9:.6f} s in {steps:.2f} "
          f"steps, {100.0 * claimed / busy:.2f}% of it under a phase",
          file=out)
    for phase, (ns, calls) in sorted(got["phases"].items()):
        print(line(f"phase {phase}", ns, calls), file=out)
    over_blocks = {}
    for (label, path), (ns, calls) in got["scopes"].items():
        held = over_blocks.setdefault(
            (label, re.sub(r"/\d+(?=/|$)", "/*", path)), [0, 0])
        held[0] += ns
        held[1] += calls
    for title, scopes in (("by scope, the numbered blocks together",
                           over_blocks), ("by scope", got["scopes"])):
        print(f"[bench] {title}:", file=out)
        for (label, path), (ns, calls) in sorted(
                scopes.items(), key=lambda kv: -kv[1][0]):
            print(line(f"{label} {path or '(no scope)'}", ns, calls),
                  file=out)
    print(line("in fusions whose members are of more than one block-level "
               "scope", got["mixed_blocks_ns"]), file=out)
    print(line("in fusions whose members are of more than one phase",
               got["mixed_phases_ns"]), file=out)
    rest = sorted(got["unclaimed"].items(), key=lambda kv: -kv[1][0])
    print(line("under no phase", busy - claimed), file=out)
    for (family, op), (ns, calls) in rest[:20]:
        print(line(f"  {family} {op or '(no op_name)'}", ns, calls),
              file=out)


def steps_of(measured):
    """The traced window's steps, as ``op_time`` takes them."""
    return (measured["traced_rate"] / measured["items_per_step"]
            * measured["trace_summary"]["window_s"])


def rows_of(ctx, table):
    """``attribute``'s rows of this run's window and its busy time, made
    once; the split is logged when they are."""
    m = ctx.measured
    if "scope_rows" not in m:
        summ = m["trace_summary"]
        devs = ctx.trace["devices"]
        first = devs[min(devs)]
        m["scope_rows"] = attribute(first, summ["window"], table)
        m["scope_busy_ns"] = trace_reduce.busy_ns(first, summ["window"])
        log_split(split(m["scope_rows"], m["scope_busy_ns"]), steps_of(m),
                  len(table))
    return m["scope_rows"], m["scope_busy_ns"]


def read(ctx, scope, not_instruction=None, share=False):
    m = ctx.measured
    summ = m.get("trace_summary")
    table = program_table()
    if table is None or ctx.trace is None or not summ \
            or not m.get("traced_rate") or not m.get("items_per_step"):
        return None
    rows, busy = rows_of(ctx, table)
    rx = re.compile(scope)
    skip = re.compile(not_instruction) if not_instruction else None
    picked = [ns for key, op, _, ns in rows
              if rx.search(op) and not (skip and skip.search("%" + key))]
    if not picked or busy <= 0:
        return None
    if share:
        return 100.0 * sum(picked) / busy
    return sum(picked) / 1e6 / steps_of(m)
