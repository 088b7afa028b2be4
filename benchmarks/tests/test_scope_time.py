"""The reader of device time by scope, ``scope_time``, on hand-made events
with a hand-made table, and on ``testdata/tiny_moe.xplane.pb`` (one v5e,
my chip run, PR 30: two steps of a tiny mixture-of-experts decoder) with
a table written for the names of its instructions."""
import io
import os
import types

import pytest

import run
import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "testdata")
scope_time = run.load_module("metrics", "readers", "scope_time.py")

FWD = "jit(spmd_step)/jvp(fwd)/"
BWD = "jit(spmd_step)/transpose(jvp(fwd))/"
UPD = "jit(spmd_step)/update/"


def _entry(op_name="", members=()):
    return {"op_name": op_name, "members": list(members)}


# one step of 100 ns: a forward product, a flash kernel, a loop under the
# scope runs with two ops in its body (one the table lacks), the backward
# kernel with XLA's delta beside it, recomputation, a weight gradient
# fused with its update, and an op no scope claims
TABLE = {
    "fusion.1": _entry(FWD + "blocks/0/attn/qkv/dot_general",
                       [FWD + "blocks/0/ln1/mul",
                        FWD + "blocks/0/attn/qkv/dot_general"]),
    "flash_fwd.2": _entry(FWD + "blocks/0/attn/attn/flash_fwd"),
    "while.3": _entry(FWD + "blocks/0/moe/moe/jvp(runs)/jit(searchsorted)"
                      "/vmap()/while"),
    "fusion.4": _entry(FWD + "blocks/0/moe/moe/jvp(runs)/jit(searchsorted)"
                       "/vmap()/while/body/closed_call/gather"),
    "flash_bwd.5": _entry(BWD + "blocks/0/attn/attn/flash_bwd/flash_bwd"),
    "fusion.6": _entry(BWD + "blocks/0/attn/attn/flash_bwd/reduce_sum"),
    "fusion.7": _entry(BWD + "blocks/0/attn/jvp(fwd)/blocks/0/attn/"
                       "checkpoint/rematted_computation/conv/mul"),
    "add_subtract_fusion.8": _entry(
        UPD + "sub", [BWD + "convert_element_type", BWD + "head/dot_general",
                      UPD + "mul", UPD + "sub"]),
    "copy.9": _entry(""),
    "fusion.10": _entry(BWD + "loss/jit(log_softmax)/sub"),
}
EVENTS = [
    ("%fusion.1 = bf16[8] fusion(x)", 0, 10),
    ("%flash_fwd.2 = bf16[8] custom-call(x)", 10, 20),
    ("%while.3 = (s32[], s32[8]) while(x)", 30, 20),
    ("%fusion.4 = s32[8] fusion(x)", 32, 5),
    ("%fusion.99 = s32[8] fusion(x)", 40, 5),       # not in the table
    ("%flash_bwd.5 = bf16[8] custom-call(x)", 50, 20),
    ("%fusion.6 = f32[8] fusion(x)", 70, 4),
    ("%fusion.7 = f32[8] fusion(x)", 74, 6),
    ("%add_subtract_fusion.8 = f32[8] fusion(x)", 80, 10),
    ("%copy.9 = f32[8] copy(x)", 90, 2),
    ("%fusion.10 = f32[8] fusion(x)", 92, 4),
]


def _ctx(events=EVENTS, table=TABLE, monkeypatch=None, steps=1.0):
    monkeypatch.setattr(scope_time, "program_table", lambda: table)
    trace = {"devices": {0: list(events)}, "host": []}
    summ = tr.summary(trace)
    # steps = traced_rate / items_per_step * window_s
    return types.SimpleNamespace(trace=trace, measured={
        "trace_summary": summ, "items_per_step": 1.0,
        "traced_rate": steps / summ["window_s"]})


def _ms(ns):
    return ns / 1e6


def test_an_events_own_time_leaves_out_the_events_inside_it():
    rows = scope_time.attribute(EVENTS, (0, 100), TABLE)
    own = {key: ns for key, _, _, ns in rows}
    assert own["while.3"] == 20 - 5 - 5
    assert own["fusion.4"] == 5 and own["fusion.99"] == 5
    assert sum(own.values()) == tr.busy_ns(EVENTS, (0, 100)) == 96
    # the op the table lacks is its loop's
    ops = {key: op for key, op, _, _ in rows}
    assert ops["fusion.99"] == TABLE["while.3"]["op_name"]
    assert ops["copy.9"] == ""
    # clipped to the window as every other reader clips
    rows = scope_time.attribute(EVENTS, (35, 60), TABLE)
    assert {k: ns for k, _, _, ns in rows} == {
        "while.3": 15 - 2 - 5, "fusion.4": 2, "fusion.99": 5,
        "flash_bwd.5": 10}


def test_the_sum_by_scope_is_ms_a_step(monkeypatch, capsys):
    ctx = _ctx(monkeypatch=monkeypatch, steps=2.0)
    fwd = scope_time.read(ctx, r"^jit\(spmd_step\)/jvp\(fwd\)/")
    bwd = scope_time.read(ctx, r"^jit\(spmd_step\)/transpose\(jvp\(fwd\)\)/")
    upd = scope_time.read(ctx, r"^jit\(spmd_step\)/update/")
    assert fwd == pytest.approx(_ms(10 + 20 + 20) / 2)
    assert bwd == pytest.approx(_ms(20 + 4 + 6 + 4) / 2)
    assert upd == pytest.approx(_ms(10) / 2)
    # the three phases and the op no scope claims are all the busy time
    assert (fwd + bwd + upd) * 2 == pytest.approx(_ms(96 - 2))
    assert scope_time.read(ctx, r"/(ln_f|head|loss)/") == \
        pytest.approx(_ms(4) / 2)
    assert scope_time.read(ctx, r"/no_such_scope/") is None
    # the split is logged once a run, whatever is read
    assert capsys.readouterr().err.count("scopes of spmd_step") == 1


def test_not_instruction_leaves_the_named_kernels_out(monkeypatch):
    ctx = _ctx(monkeypatch=monkeypatch)
    read = scope_time.read
    assert read(ctx, r"/blocks/\d+/attn/") == \
        pytest.approx(_ms(10 + 20 + 20 + 4 + 6))
    assert read(ctx, r"/blocks/\d+/attn/", r"^%(flash_|gdn_)") == \
        pytest.approx(_ms(10 + 4 + 6))
    assert read(ctx, r"/flash_bwd/", r"^%flash_bwd") == pytest.approx(_ms(4))
    assert read(ctx, r"/blocks/\d+/moe/", r"^%moe_gmm_") == \
        pytest.approx(_ms(20))


def test_share_is_percent_of_the_windows_busy_time(monkeypatch):
    ctx = _ctx(monkeypatch=monkeypatch, steps=3.0)
    known = r"^jit\(spmd_step\)/(jvp\(fwd\)|transpose\(jvp\(fwd\)\)|update)/"
    assert scope_time.read(ctx, known, share=True) == \
        pytest.approx(100.0 * 94 / 96)
    # an event the table lacks outside every loop is no scope's
    ctx = _ctx(events=EVENTS + [("%fusion.77 = f32[8] fusion(x)", 96, 4)],
               monkeypatch=monkeypatch)
    assert scope_time.read(ctx, known, share=True) == \
        pytest.approx(100.0 * 94 / 100)


def test_no_table_no_trace_and_no_rate_read_nothing(monkeypatch):
    ctx = _ctx(table=None, monkeypatch=monkeypatch)
    assert scope_time.read(ctx, "fwd") is None
    ctx = _ctx(monkeypatch=monkeypatch)
    ctx.trace = None
    assert scope_time.read(ctx, "fwd") is None
    ctx = _ctx(monkeypatch=monkeypatch)
    ctx.measured["traced_rate"] = None
    assert scope_time.read(ctx, "fwd") is None
    ctx = _ctx(monkeypatch=monkeypatch)
    del ctx.measured["trace_summary"]
    assert scope_time.read(ctx, "fwd") is None


def test_a_program_without_the_module_or_a_table_gives_none(monkeypatch):
    import mxnet_tpu.telemetry as telemetry

    telemetry.scopes.reset()
    assert scope_time.program_table() is None
    telemetry.scopes.publish("spmd_step", {"fusion.1": _entry(FWD + "x")})
    assert scope_time.program_table() == {"fusion.1": _entry(FWD + "x")}
    telemetry.scopes.reset()
    # the parent commit: no such module
    monkeypatch.delattr(telemetry, "scopes")
    monkeypatch.setitem(__import__("sys").modules,
                        "mxnet_tpu.telemetry.scopes", None)
    assert scope_time.program_table() is None


def test_scope_paths_unwrap_transforms_and_drop_control_flow():
    path = scope_time.scope_path
    assert path(FWD + "blocks/2/attn/qkv/dot_general") == \
        ["blocks", "2", "attn", "qkv"]
    assert path(TABLE["fusion.4"]["op_name"]) == \
        ["blocks", "0", "moe", "moe", "runs"]
    # a backward inside a custom_vjp repeats the path: the last counts
    assert path(BWD + "blocks/0/moe/moe/transpose(jvp(fwd))/blocks/0/moe/"
                "moe/jvp(combine)/jit(_take)/gather") == \
        ["blocks", "0", "moe", "moe", "combine"]
    assert path(TABLE["fusion.7"]["op_name"]) == \
        ["blocks", "0", "attn", "conv"]
    assert path(FWD + "blocks/0/attn/attn/bhqd,bhkd->bhqk/dot_general") == \
        ["blocks", "0", "attn", "attn"]
    assert path(UPD + "mul") == [] and path("xd") == []
    assert path("a/b;" + FWD + "head/dot_general") == []
    block = scope_time.block_of
    assert block(FWD + "blocks/2/attn/qkv/dot_general") == "blocks/2/attn"
    assert block(BWD + "head/dot_general") == "head"
    assert block(UPD + "mul") == ""


def test_the_log_holds_every_scope_the_mixed_fusions_and_the_rest(
        monkeypatch):
    rows = scope_time.attribute(EVENTS, (0, 100), TABLE)
    got = scope_time.split(rows, 96)
    assert {k: v[0] for k, v in got["phases"].items()} == \
        {"fwd": 50, "bwd": 34, "update": 10}
    assert got["scopes"][("fwd", "blocks/0/moe/moe/runs")] == [20, 3]
    assert got["scopes"][("bwd.remat", "blocks/0/attn/conv")] == [6, 1]
    assert got["scopes"][("bwd", "blocks/0/attn/attn/flash_bwd")] == [24, 2]
    # the weight gradient fused with its update, by its gradient's block
    assert got["scopes"][("update+bwd", "head")] == [10, 1]
    assert got["mixed_phases_ns"] == 10
    # ln1 fused into the q|k|v product: two block-level scopes
    assert got["mixed_blocks_ns"] == 10
    assert got["unclaimed"] == {("copy", ""): [2, 1]}
    out = io.StringIO()
    scope_time.log_split(got, 2.0, len(TABLE), out=out)
    text = out.getvalue()
    assert "97.92% of it under a phase" in text
    assert "fwd blocks/0/moe/moe/runs: 0.000000 s, 3 calls" in text
    assert "fwd blocks/*/moe/moe/runs: 0.000000 s, 3 calls" in text
    assert "of more than one block-level scope: 0.000000 s, 0.000 ms" in text
    assert "copy (no op_name)" in text


def test_recorded_moe_trace_with_a_table_for_its_instructions(monkeypatch):
    """Every instruction of the recorded step gets a scope by its family
    (what a table of the program would say of them is not recorded): the
    grouped products and the loops under a block's moe scope, the flash
    kernels under attn, the updates under update."""
    trace = tr.load(os.path.join(DATA, "tiny_moe.xplane.pb"))
    events = trace["devices"][0]
    where = {"moe_gmm_fwd": FWD + "blocks/0/moe/moe/jvp(moe_gmm)/",
             "moe_gmm_dlhs": BWD + "blocks/0/moe/moe/moe_gmm_bwd/",
             "moe_gmm_drhs": BWD + "blocks/0/moe/moe/moe_gmm_bwd/",
             "while": FWD + "blocks/0/moe/moe/jvp(runs)/",
             "sort": FWD + "blocks/0/moe/layout/",
             "flash_fwd": FWD + "blocks/0/attn/attn/",
             "flash_bwd": BWD + "blocks/0/attn/attn/flash_bwd/",
             "add_subtract_fusion": UPD}
    table = {}
    for name, _, _ in events:
        key = scope_time.instruction(name)
        family = tr.op_family(name)
        if family in where:
            table[key] = _entry(where[family] + family)
    ctx = _ctx(events=events, table=table, monkeypatch=monkeypatch, steps=2.0)
    window = ctx.measured["trace_summary"]["window"]
    read = scope_time.read
    # a leaf kernel's time is what op_time reads under its name
    gmm = tr.time_of(events, window, r"^%moe_gmm_")[0]
    moe = read(ctx, r"/blocks/\d+/moe/")
    glue = read(ctx, r"/blocks/\d+/moe/", r"^%moe_gmm_")
    assert moe - glue == pytest.approx(gmm * 1e3 / 2)
    # the loops' bodies are the loops': nothing is counted twice
    loops = tr.time_of(events, window, r"^%while[.\d]* = ")[0]
    sorts = tr.time_of(events, window, r"^%sort[.\d]* = ")[0]
    assert glue == pytest.approx((loops + sorts) * 1e3 / 2, rel=1e-6)
    upd = read(ctx, r"^jit\(spmd_step\)/update/")
    assert upd == pytest.approx(
        tr.time_of(events, window, r"^%add_subtract_fusion")[0] * 1e3 / 2)
    share = read(ctx, r"^jit\(spmd_step\)/", share=True)
    rows = ctx.measured["scope_rows"]
    assert sum(ns for *_, ns in rows) == ctx.measured["scope_busy_ns"]
    assert 0 < share < 100
