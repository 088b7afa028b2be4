"""Which scope each instruction of a compiled program belongs to.

A device trace names an op by its HLO instruction (``%fusion.123 = ...``
on the profiler's ``XLA Ops`` line) and says nothing of where in the
model it came from. The compiled program's own HLO text does: every
instruction carries ``metadata={op_name="jit(spmd_step)/jvp(fwd)/blocks/
2/attn/qkv/dot_general"}``, the path of ``jax.named_scope``\\ s it was
traced under (a block's scope is the name its parent gave it,
``gluon/block.py``). :func:`parse` turns that text into a table from
instruction to scope; the program that compiled the step
:func:`publish`\\ es it under the program's name, and a reader joins it
with a trace by the instruction's name (:func:`table`) after the trainer
is gone, as it reads the tracer's ring.

No second tracing system: no clock, no events, no knob of its own. The
publisher asks ``tracer.tracing()`` first, so under ``MXNET_TELEMETRY=0``
nothing is parsed or kept. See ``docs/TELEMETRY.md``, "Scopes".
"""
from __future__ import annotations

import re

__all__ = ["parse", "publish", "table", "reset"]

# program name -> {instruction: {"op_name": str, "members": [str, ...]}}
_TABLES = {}

# "  ROOT %fusion.12 = bf16[8,128]{1,0} fusion(...), kind=kLoop, ..."
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = ")
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
# the computations whose instructions run as ops of their own on the
# device, beside the entry's: a loop's, a conditional's, a call's
_BODIES = re.compile(
    r"\b(?:body|condition|true_computation|false_computation)=%?([\w.\-]+)")
_BRANCHES = re.compile(r"\bbranch_computations=\{([^}]*)\}")
_CALL_TARGET = re.compile(r"\bto_apply=%?([\w.\-]+)")
_OPCODE = re.compile(r"\s(while|conditional|call|fusion)\(")


def _op_name(line):
    """The ``op_name`` of an instruction's metadata, ``""`` without one."""
    i = line.find('op_name="')
    return "" if i < 0 else line[i + 9:line.find('"', i + 9)]


def _computations(hlo_text):
    """``({name: [instruction line, ...]}, entry name)`` of an HLO
    module's text."""
    comps, entry, lines = {}, None, None
    for line in hlo_text.splitlines():
        if lines is None:
            if line.endswith("{") and line[:1] not in ("", " ", "\t"):
                head = line.split(" ", 2)
                is_entry = head[0] == "ENTRY"
                name = (head[1] if is_entry else head[0]).lstrip("%")
                if is_entry:
                    entry = name
                lines = comps[name] = []
        elif line.startswith("}"):
            lines = None
        else:
            lines.append(line)
    return comps, entry


def parse(hlo_text):
    """``{instruction: {"op_name": str, "members": [op_name, ...]}}`` of
    an optimised HLO module's text: one entry for each instruction of the
    entry computation and of every ``while`` / ``conditional`` / ``call``
    computation reached from it, the ones a device trace shows as ops.
    The key is the instruction's name without its ``%``; ``op_name`` is
    ``""`` where the instruction has no metadata. A ``fusion`` has in
    ``members`` the distinct ``op_name``\\ s of the instructions of the
    computation it calls, in their order (``[]`` for every other
    instruction): what was fused into it. Its own ``op_name`` is the one
    the compiler left on the fusion (the op it was built around) or,
    where it left none, the last member's."""
    comps, entry = _computations(hlo_text)
    out, seen, todo = {}, set(), [entry] if entry else []
    while todo:
        comp = todo.pop()
        if comp in seen or comp not in comps:
            continue
        seen.add(comp)
        for line in comps[comp]:
            m = _INSTRUCTION.match(line)
            if not m:
                continue
            # the attributes, not a quoted op_name, say what is called
            attrs = line.split(", metadata={", 1)[0]
            members = []
            kind = _OPCODE.search(attrs)
            kind = kind.group(1) if kind else None
            if kind == "fusion":
                called = _CALLS.search(attrs)
                for inner in comps.get(called.group(1), ()) if called else ():
                    got = _op_name(inner)
                    if got and got not in members:
                        members.append(got)
            elif kind in ("while", "conditional"):
                todo += _BODIES.findall(attrs)
                for group in _BRANCHES.findall(attrs):
                    todo += [b.strip().lstrip("%") for b in group.split(",")]
            elif kind == "call":
                todo += _CALL_TARGET.findall(attrs)
            out[m.group(1)] = {
                "op_name": _op_name(line) or (members[-1] if members else ""),
                "members": members}
    return out


def publish(program, table):
    """Keep ``table`` (from :func:`parse`) as ``program``'s, in place of
    an earlier one."""
    _TABLES[program] = table


def table(program):
    """The table last published as ``program``'s, or None."""
    return _TABLES.get(program)


def reset():
    """Forget every table (tests)."""
    _TABLES.clear()
