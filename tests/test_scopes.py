"""Scopes of the compiled step: a block's forward runs under the name its
parent gave it (``gluon/block.py``), the functions of the expert layer
and of the token mixers under their own, and ``SPMDTrainer`` publishes
the table from the step's instructions to those scopes
(``telemetry.scopes``), once a build, under ``spmd.build.scopes``.
``parse`` on written-out HLO text; the table of a tiny ``MoEDecoderLM``
(an attention layer and a Gated DeltaNet layer, a shared expert) and of a
tiny ``TransformerLM``; the step itself unchanged by any of it."""
import contextlib
import re

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon, models, parallel, telemetry
from mxnet_tpu.gluon import nn
from mxnet_tpu.telemetry import scopes

nd = mx.nd

FWD = "jit(spmd_step)/jvp(fwd)/"

HLO = '''HloModule jit_spmd_step, is_scheduled=true, entry_computation_layout={(f32[8]{0})->f32[8]{0}}

FileNames
1 "/root/repo/mxnet_tpu/parallel/spmd.py"

%fused_computation.1 (param_0: f32[8], param_1: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  %param_1 = f32[8]{0} parameter(1)
  %multiply.1 = f32[8]{0} multiply(%param_0, %param_1), metadata={op_name="jit(spmd_step)/jvp(fwd)/blocks/0/ln1/mul" stack_frame_id=3}
  %multiply.2 = f32[8]{0} multiply(%multiply.1, %param_1), metadata={op_name="jit(spmd_step)/jvp(fwd)/blocks/0/ln1/mul" stack_frame_id=3}
  ROOT %add.2 = f32[8]{0} add(%multiply.2, %param_1), metadata={op_name="jit(spmd_step)/jvp(fwd)/blocks/0/attn/qkv/add"}
}

%region_0.5 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add.9 = f32[] add(%a, %b), metadata={op_name="jit(spmd_step)/jvp(fwd)/loss/reduce_sum"}
}

%body.7 (carry: (s32[], f32[8])) -> (s32[], f32[8]) {
  %carry = (s32[], f32[8]{0}) parameter(0)
  %gte.1 = s32[] get-tuple-element(%carry), index=0
  %gte.2 = f32[8]{0} get-tuple-element(%carry), index=1
  %negate.3 = f32[8]{0} negate(%gte.2), metadata={op_name="jit(spmd_step)/jvp(fwd)/blocks/0/moe/moe/jvp(runs)/jit(searchsorted)/while/body/neg"}
  ROOT %tuple.4 = (s32[], f32[8]{0}) tuple(%gte.1, %negate.3)
}

%cond.8 (carry.1: (s32[], f32[8])) -> pred[] {
  %carry.1 = (s32[], f32[8]{0}) parameter(0)
  %gte.5 = s32[] get-tuple-element(%carry.1), index=0
  %constant.6 = s32[] constant(14)
  ROOT %lt.1 = pred[] compare(%gte.5, %constant.6), direction=LT, metadata={op_name="jit(spmd_step)/jvp(fwd)/blocks/0/moe/moe/jvp(runs)/jit(searchsorted)/while/cond/lt"}
}

%branch_a.10 (x.1: f32[8]) -> f32[8] {
  %x.1 = f32[8]{0} parameter(0)
  ROOT %exp.1 = f32[8]{0} exponential(%x.1), metadata={op_name="jit(spmd_step)/update/cond/branch_0_fun/exp"}
}

%branch_b.11 (x.2: f32[8]) -> f32[8] {
  %x.2 = f32[8]{0} parameter(0)
  ROOT %log.1 = f32[8]{0} log(%x.2), metadata={op_name="jit(spmd_step)/update/cond/branch_1_fun/log"}
}

%called.12 (x.3: f32[8]) -> f32[8] {
  %x.3 = f32[8]{0} parameter(0)
  ROOT %sqrt.1 = f32[8]{0} sqrt(%x.3), metadata={op_name="jit(spmd_step)/update/sqrt"}
}

ENTRY %main.20 (p0: f32[8], p1: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0), metadata={op_name="param_vals[0]"}
  %p1 = f32[8]{0} parameter(1), metadata={op_name="xd"}
  %copy.1 = f32[8]{0:T(128)} copy(%p1)
  %fusion.3 = f32[8]{0} fusion(%p0, %copy.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(spmd_step)/jvp(fwd)/blocks/0/attn/qkv/add" stack_frame_id=9}
  %constant.2 = s32[] constant(0)
  %tuple.0 = (s32[], f32[8]{0}) tuple(%constant.2, %fusion.3)
  %while.5 = (s32[], f32[8]{0}) while(%tuple.0), condition=%cond.8, body=%body.7, metadata={op_name="jit(spmd_step)/jvp(fwd)/blocks/0/moe/moe/jvp(runs)/jit(searchsorted)/while"}, backend_config={"known_trip_count":{"n":"14"}}
  %gte.9 = f32[8]{0} get-tuple-element(%while.5), index=1
  %zero = f32[] constant(0)
  %reduce.6 = f32[] reduce(%gte.9, %zero), dimensions={0}, to_apply=%region_0.5, metadata={op_name="jit(spmd_step)/jvp(fwd)/loss/reduce_sum"}
  %pred.1 = pred[] constant(true)
  %conditional.7 = f32[8]{0} conditional(%pred.1, %gte.9, %gte.9), true_computation=%branch_a.10, false_computation=%branch_b.11, metadata={op_name="jit(spmd_step)/update/cond"}
  %call.8 = f32[8]{0} call(%conditional.7), to_apply=%called.12
  ROOT %add_subtract_fusion = f32[8]{0} fusion(%call.8, %p0), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(spmd_step)/update/sub"}
}
'''


@pytest.fixture(autouse=True)
def _clean():
    telemetry.reset_trace()
    scopes.reset()
    yield
    telemetry.reset_trace()
    scopes.reset()


def _named(name):
    return [e for e in telemetry.events() if e["name"] == name]


# ---------------------------------------------------------------------------
# parse, on text

@pytest.mark.parametrize("text", [HLO, HLO.replace("%", "")],
                         ids=["with_percent", "without_percent"])
def test_parse_keys_entry_instructions_by_name(text):
    table = scopes.parse(text)
    assert table["fusion.3"]["op_name"] == FWD + "blocks/0/attn/qkv/add"
    assert table["p1"] == {"op_name": "xd", "members": []}
    # ROOT is an instruction like any other
    assert table["add_subtract_fusion"]["op_name"] == \
        "jit(spmd_step)/update/sub"
    # what a fusion calls is in its members, not in the table
    assert "multiply.1" not in table and "add.2" not in table
    # nor is what a reduce applies
    assert "add.9" not in table


def test_parse_follows_loops_conditionals_and_calls():
    table = scopes.parse(HLO)
    assert table["while.5"]["op_name"].endswith("/jit(searchsorted)/while")
    assert table["negate.3"]["op_name"].endswith("/while/body/neg")
    assert table["lt.1"]["op_name"].endswith("/while/cond/lt")
    assert table["exp.1"]["op_name"] == \
        "jit(spmd_step)/update/cond/branch_0_fun/exp"
    assert table["log.1"]["op_name"].endswith("branch_1_fun/log")
    assert table["sqrt.1"]["op_name"] == "jit(spmd_step)/update/sqrt"
    branches = HLO.replace(
        "true_computation=%branch_a.10, false_computation=%branch_b.11",
        "branch_computations={%branch_a.10, %branch_b.11}")
    assert scopes.parse(branches) == table


def test_parse_gives_a_fusion_the_distinct_op_names_fused_into_it():
    table = scopes.parse(HLO)
    assert table["fusion.3"]["members"] == [
        FWD + "blocks/0/ln1/mul", FWD + "blocks/0/attn/qkv/add"]
    assert table["add_subtract_fusion"]["members"] == \
        table["fusion.3"]["members"]
    assert table["while.5"]["members"] == []
    # a fusion the compiler left no metadata on is its last member's
    bare = HLO.replace(', metadata={op_name="jit(spmd_step)/jvp(fwd)/blocks/'
                       '0/attn/qkv/add" stack_frame_id=9}', "")
    assert bare != HLO
    assert scopes.parse(bare)["fusion.3"] == table["fusion.3"]


def test_parse_keeps_an_instruction_without_metadata():
    table = scopes.parse(HLO)
    assert table["copy.1"] == {"op_name": "", "members": []}
    assert table["gte.1"] == {"op_name": "", "members": []}
    assert table["call.8"]["op_name"] == ""
    assert scopes.parse("") == {} and scopes.parse("HloModule x\n") == {}


def test_publish_keeps_the_last_table_of_a_program():
    assert scopes.table("spmd_step") is None
    scopes.publish("spmd_step", {"a": {"op_name": "x", "members": []}})
    scopes.publish("other", {})
    scopes.publish("spmd_step", {"b": {"op_name": "y", "members": []}})
    assert list(scopes.table("spmd_step")) == ["b"]
    assert scopes.table("other") == {}
    assert telemetry.scopes is scopes and "scopes" in telemetry.__all__


# ---------------------------------------------------------------------------
# the tables of two tiny models through SPMDTrainer

def _moe_net():
    return models.MoEDecoderLM(
        vocab_size=64, embed_dim=32, num_layers=2, num_heads=2,
        num_kv_heads=1, head_dim=16, num_experts=4, expert_dim=16, top_k=2,
        shared_expert=16, output_gate=True, rotary_dim=8,
        attention=["causal", {"gated_delta": dict(
            num_k_heads=1, num_v_heads=2, head_k_dim=16, head_v_dim=16)}])


def _run(net, steps=1, seed=5):
    """``net`` through ``steps`` steps of a fresh trainer: (trainer, x, y,
    the losses' raw bits)."""
    import jax

    mx.random.seed(seed)
    net.initialize()
    trainer = parallel.SPMDTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), optimizer="adamw",
        optimizer_params={"learning_rate": 0.01},
        mesh=parallel.make_mesh({"dp": 1}, devices=jax.devices()[:1]))
    rng = onp.random.default_rng(seed)
    x = nd.array(rng.integers(0, 64, (2, 64)).astype("int32"))
    y = nd.array(rng.integers(0, 64, (2, 64)).astype("int32"))
    losses = [trainer.step(x, y).asnumpy().tobytes() for _ in range(steps)]
    return trainer, x, y, losses


def _op_names(table):
    return {e["op_name"] for e in table.values()} | {
        m for e in table.values() for m in e["members"]}


@pytest.fixture(scope="module")
def moe_names():
    telemetry.reset_trace()
    scopes.reset()
    _run(_moe_net())
    return _op_names(scopes.table("spmd_step"))


@pytest.fixture(scope="module")
def lm_names():
    telemetry.reset_trace()
    scopes.reset()
    _run(models.TransformerLM(64, embed_dim=32, num_layers=2, num_heads=2,
                              tie_weights=True))
    return _op_names(scopes.table("spmd_step"))


@pytest.mark.parametrize("scope", [
    r"^jit\(spmd_step\)/jvp\(fwd\)/blocks/0/attn/",
    r"^jit\(spmd_step\)/transpose\(jvp\(fwd\)\)/blocks/1/moe/",
    r"^jit\(spmd_step\)/update/",
    r"/jvp\(fwd\)/embed/", r"/blocks/1/ln2/", r"/ln_f/", r"/head/", r"/loss/",
    r"/blocks/0/attn/qkv/", r"/blocks/1/attn/qkvz/", r"/blocks/1/attn/out/",
    # the functions of the expert layer
    r"/moe/router/", r"/moe/layout/", r"/moe/shared/",
    r"/moe/moe/(jvp\()?runs\)?/", r"/moe/moe/(jvp\()?dispatch\)?/",
    r"/moe/moe/(jvp\()?combine\)?/", r"/moe/moe/(jvp\()?moe_gmm\)?/",
    r"/moe_gmm_bwd/",
    # and of the two mixers
    r"/blocks/0/attn/rope/", r"/blocks/0/attn/qk_norm/",
    r"/blocks/0/attn/gate/", r"/blocks/0/attn/attn/", r"/attn/flash_bwd/",
    r"/blocks/1/attn/conv/", r"/blocks/1/attn/l2norm/",
    r"/blocks/1/attn/gate_norm/", r"/blocks/1/attn/gdn/",
    r"/checkpoint/rematted_computation/conv/",
])
def test_the_moe_decoders_table_has_instructions_under(moe_names, scope):
    assert any(re.search(scope, n) for n in moe_names), scope


@pytest.mark.parametrize("scope", [
    r"^jit\(spmd_step\)/jvp\(fwd\)/blocks/0/attn/qkv/",
    r"^jit\(spmd_step\)/transpose\(jvp\(fwd\)\)/blocks/1/ffn1/",
    r"^jit\(spmd_step\)/update/",
    r"/embed/", r"/pos_embed/", r"/blocks/1/ln1/", r"/blocks/0/ffn2/",
    r"/ln_f/", r"/loss/",
    r"/jvp\(fwd\)/head/",       # the tied head: no child block opens it
])
def test_the_transformers_table_has_instructions_under(lm_names, scope):
    assert any(re.search(scope, n) for n in lm_names), scope


def test_no_rope_scope_in_a_layer_without_positions(moe_names):
    # the Gated DeltaNet layer has none of the attention layer's scopes
    assert not any(re.search(r"/blocks/1/attn/(rope|qk_norm|gate)/", n)
                   for n in moe_names)


# ---------------------------------------------------------------------------
# the span, the knob, and the step left as it was

def test_one_scopes_span_a_build_with_what_it_read(monkeypatch):
    monkeypatch.setenv("MXNET_TELEMETRY", "1")
    trainer, x, y, _ = _run(_moe_net(), steps=3)
    (span,) = _named("spmd.build.scopes")
    table = scopes.table("spmd_step")
    assert span["args"]["instructions"] == len(table) > 100
    assert span["args"]["bytes"] > 10_000
    # after the first step's own span, before the second's
    first, second = _named("spmd.step")[:2]
    assert first["ts"] + first["dur"] <= span["ts"] <= second["ts"]
    # step_hlo reads the same text
    assert scopes.parse(trainer.step_hlo(x, y)) == table


def test_building_the_table_compiles_nothing_again(monkeypatch):
    monkeypatch.setenv("MXNET_TELEMETRY", "1")
    trainer, x, y, _ = _run(_moe_net(), steps=3)
    for phase in ("compile.trace", "compile.lower", "compile.backend"):
        mine = [e for e in _named(phase)
                if "spmd_step" in (e["args"]["fun_name"] or "")]
        assert len(mine) == 1, (phase, mine)
    assert len([e for e in _named("retrace")
                if e["args"]["label"] == "spmd_step"]) == 1
    telemetry.reset_trace()
    trainer.step(x, y)
    assert not [e for e in telemetry.events()
                if e["name"].startswith("compile.")
                or e["name"] in ("retrace", "spmd.build.scopes")]


def test_telemetry_off_parses_and_keeps_nothing(monkeypatch):
    monkeypatch.setenv("MXNET_TELEMETRY", "0")
    calls = []
    monkeypatch.setattr(scopes, "parse",
                        lambda text: calls.append(len(text)) or {})
    _run(_moe_net(), steps=2)
    assert scopes.table("spmd_step") is None and not calls
    assert not telemetry.events()


def test_block_scopes_change_neither_the_losses_nor_the_programs(
        monkeypatch):
    """The same step with every block's scope taken away: the losses to
    the bit, and as many compile spans (scopes are metadata: no program
    more, eager or compiled)."""
    monkeypatch.setenv("MXNET_TELEMETRY", "1")
    _run(_moe_net(), steps=1)            # the eager ops' programs, once

    def count():
        return {p: len(_named("compile." + p))
                for p in ("lower", "backend")}

    telemetry.reset_trace()
    with_scopes = _run(_moe_net(), steps=3)[3]
    spans_with = count()
    monkeypatch.setattr(gluon.Block, "_scope",
                        lambda self: contextlib.nullcontext())
    telemetry.reset_trace()
    without = _run(_moe_net(), steps=3)[3]
    assert with_scopes == without and len(set(without)) == 3
    assert spans_with == count() and spans_with["backend"] >= 1


def test_losses_are_the_same_bits_with_telemetry_on_and_off(monkeypatch):
    monkeypatch.setenv("MXNET_TELEMETRY", "1")
    on = _run(_moe_net(), steps=3)[3]
    monkeypatch.setenv("MXNET_TELEMETRY", "0")
    off = _run(_moe_net(), steps=3)[3]
    assert on == off and len(set(on)) == 3


# ---------------------------------------------------------------------------
# a block's scope is the name its first parent gave it

def test_a_childs_scope_is_the_name_it_was_registered_under():
    net = _moe_net()
    assert net._scope_name is None
    assert net.embed._scope_name == "embed"
    assert net.blocks._scope_name == "blocks"
    first = net.blocks[0]
    assert first._scope_name == "0" and net.blocks[1]._scope_name == "1"
    assert first.attn._scope_name == "attn"
    assert first.attn.qkv._scope_name == "qkv"
    assert first.moe._scope_name == "moe"
    seq = nn.HybridSequential()
    seq.add(nn.Dense(4), nn.Dense(4))
    seq.register_child(nn.Dense(4), "last")
    assert [c._scope_name for c in seq._children.values()] == \
        ["0", "1", "last"]


def test_a_block_under_two_parents_and_one_under_none_do_not_fail():
    shared = nn.Dense(4, in_units=4)

    class Twice(gluon.HybridBlock):
        def __init__(self):
            super().__init__()
            self.first = shared

        def hybrid_forward(self, F, x):
            return self.first(x)

    a, b = Twice(), Twice()
    b.second = shared                     # a second name, a second parent
    assert shared._scope_name == "first"  # the first registration's
    for net in (a, b, shared):
        net.initialize()
    x = nd.ones((2, 4))
    want = shared(x).asnumpy()            # no parent's call: its own name
    onp.testing.assert_array_equal(a(x).asnumpy(), want)
    onp.testing.assert_array_equal(b(x).asnumpy(), want)
    alone = nn.Dense(3, in_units=4)       # no parent at all: no scope
    alone.initialize()
    assert alone._scope_name is None and alone(x).shape == (2, 3)


def test_an_eager_call_returns_what_it_returned(monkeypatch):
    mx.random.seed(11)
    net = _moe_net()
    net.initialize()
    x = nd.array(onp.arange(128).reshape(2, 64) % 64, dtype="int32")
    with mx.autograd.pause(train_mode=False):
        scoped = net(x).asnumpy()
        monkeypatch.setattr(gluon.Block, "_scope",
                            lambda self: contextlib.nullcontext())
        plain = net(x).asnumpy()
    assert scoped.shape == (2, 64, 64)
    onp.testing.assert_array_equal(scoped, plain)
    # and through hybridize, whose one program is traced under the scopes
    monkeypatch.undo()
    net.hybridize()
    with mx.autograd.pause(train_mode=False):
        onp.testing.assert_allclose(net(x).asnumpy(), scoped, rtol=2e-5,
                                    atol=2e-6)
