"""The Pallas TPU kernels, put to the chip's own compiler at real widths.

The TPU compiler is installed in the CPU sandbox and compiles for a chip
that is *described*, not attached (``jax.experimental.topologies``), so
what it would refuse at bind time on the chip — a block over VMEM, a
primitive the Pallas TPU lowering lacks — is caught here at no chip
time. Nothing runs: these tests say nothing about results or speed
(``chip_smoke.py kernels`` runs each kernel against its lax twin on the
chip).

Only one process may hold the TPU library, so the topology is described
inside a module-scoped fixture of THIS file only (never at import time,
never in conftest, never autouse), and every compile happens in the
test's own process with the persistent compilation cache off (a
described-chip executable can be written to it but not read back).
"""
import json
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from mxnet_tpu import kernels
from mxnet_tpu.kernels import cost_model
from mxnet_tpu.kernels.attention import _attention_decode
from mxnet_tpu.kernels import flash_attention as fa
from mxnet_tpu.kernels.flash_attention import (
    _decode_flash, _flash, _pallas_forward)
from mxnet_tpu.kernels.norm_act import _pallas_norm_act


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, one_chip, *shapes_dtypes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in shapes_dtypes]
    return jax.jit(fn).lower(*args).compile()  # graft-lint: allow(jit-nocache)


def _assert_kernel(compiled, name):
    """The kernel is there, under the instruction name a device trace
    shows it by (``benchmarks/metrics/readers/op_time.py`` matches it)."""
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert re.search(r"%" + name + r"(\.\d+)? = [^\n]*custom-call\(", text), \
        [ln for ln in text.splitlines() if "custom-call(" in ln]


# ---------------------------------------------------------------------------
# shapes the chip's compiler takes

FLASH_SHAPES = [
    ((8, 12, 512, 64), jnp.bfloat16),    # BERT-base heads, seq 512
    ((8, 12, 512, 64), jnp.float32),
    ((2, 16, 2048, 128), jnp.bfloat16),  # long-context LM heads
    ((4, 4, 100, 64), jnp.float32),      # ragged seq: padded to the tile
    ((2, 32, 2048, 64), jnp.bfloat16),   # the cell opt1.3b-train-s2048
]


@pytest.mark.parametrize("shape,dtype", FLASH_SHAPES)
def test_flash_forward_compiles(one_chip, shape, dtype):
    c = _compile(lambda q, k, v: _pallas_forward(q, k, v, 0.125, True,
                                                 False),
                 one_chip, (shape, dtype), (shape, dtype), (shape, dtype))
    _assert_kernel(c, "flash_fwd")
    assert cost_model.pallas_fits_vmem("attention", shape[-2:],
                                       jnp.dtype(dtype).itemsize)


@pytest.mark.parametrize("shape,dtype", FLASH_SHAPES)
def test_flash_backward_compiles(one_chip, shape, dtype):
    """``jax.grad`` through the kernel path, at the chooser's tiles: the
    forward that also writes the row log-sum-exp, and the fused backward
    under the one name ``attn_bwd_ms.tokens`` finds it by. No ``while``
    is left: the scan is the other path."""
    def loss(q, k, v):
        # flash_attention()'s own scope; it picks "pallas" by the backend,
        # which is the CPU here. (The jvp wraps the outermost scope's
        # name: without one the forward is named jvp_flash_fwd_.)
        with jax.named_scope("attn"):
            o = _flash(q, k, v, 0.125, True, "pallas")
        return o.astype(jnp.float32).sum()

    before = kernels.counters()
    c = _compile(jax.grad(loss, (0, 1, 2)), one_chip,
                 (shape, dtype), (shape, dtype), (shape, dtype))
    _assert_kernel(c, "flash_fwd")
    _assert_kernel(c, "flash_bwd")
    assert not re.search(r"\bwhile\(", c.as_text())
    after = kernels.counters()
    assert after["flash_bwd_pallas"] == before.get("flash_bwd_pallas", 0) + 1
    assert after.get("flash_bwd_scan", 0) == before.get("flash_bwd_scan", 0)


@pytest.mark.parametrize("b,hq,hkv,seq,d,subtiles,visits", [
    # the cell sdar30b-train-bd-s4096: forward at (1024, 1024) in strips
    # of 512 the band's 4 x 2 and the diagonal's 8 x 3 sub-tiles of
    # 12 x 4, backward at (256, 512) in strips of 256 16 + 16 x (1 + 2)
    # of 48 x 2; 24 live tiles of 64 forward, 160 of 512 backward
    (2, 32, 4, 4096, 128, (32 + 64, 48 + 96), 24 + 160),
    # no grouping, D=64: (1024, 1024) 2 + 2 x 3 of 3 x 4; at (512, 512) a
    # tile is one strip, its 6 computed whole; 3 live tiles of 4, 8 of 16
    (1, 8, 8, 1024, 64, (8 + 6, 12 + 6), 3 + 8),
])
def test_masked_grouped_flash_compiles(one_chip, b, hq, hkv, seq, d,
                                       subtiles, visits):
    """The block-diffusion mask over 2 x seq positions with grouped
    heads, under ``jax.grad`` at the chooser's tiles: both kernels keep
    their names (the visits of the live tiles ride in as prefetched
    scalar arrays, the grid's last dimension), the strips of the partly
    masked tiles lower with them, and the cell's counters count the
    lowering, its sub-tiles and its grid steps, none of them dead."""
    mask = fa.BlockDiffusionMask(seq, 4)
    dtype = jnp.bfloat16

    def loss(q, k, v):
        with jax.named_scope("attn"):
            o = _flash(q, k, v, d ** -0.5, False, "pallas", mask)
        return o.astype(jnp.float32).sum()

    before = kernels.counters()
    c = _compile(jax.grad(loss, (0, 1, 2)), one_chip,
                 ((b, hq, 2 * seq, d), dtype), ((b, hkv, 2 * seq, d), dtype),
                 ((b, hkv, 2 * seq, d), dtype))
    _assert_kernel(c, "flash_fwd")
    _assert_kernel(c, "flash_bwd")
    assert not re.search(r"\bwhile\(", c.as_text())
    after = kernels.counters()
    assert after["flash_mask_pallas"] > before.get("flash_mask_pallas", 0)
    assert after.get("flash_bwd_scan", 0) == before.get("flash_bwd_scan", 0)
    for name, n in zip(("live", "tile"), subtiles):
        name = "flash_mask_subtiles_" + name
        assert after[name] - before.get(name, 0) == n, name
    for name in ("flash_grid_steps", "flash_grid_steps_live"):
        assert after[name] - before.get(name, 0) == visits, name


@pytest.mark.parametrize("window", [4096, None], ids=["window", "causal"])
def test_long_head_backward_compiles_in_segments(one_chip, window):
    """The cell smallthinker21b-train-s16384: (1, 28 over 4, 16384, 128)
    bf16, a window layer and the global one. A head's float32 dq is 8 MiB,
    past any block the budget admits, so the backward walks the head in
    four segments of 4,096 rows at (512, 512) tiles: one ``flash_bwd``, no
    ``while``, and no (S, S) array anywhere in the program. A window
    layer's grid is its visits: 70 live tiles forward, 252 backward and
    the 8 k tiles of the first segment's band that only get their zeros
    written; the global layer keeps its rectangle, dead steps and all."""
    seq, d, dtype = 16384, 128, jnp.bfloat16
    mask = fa.SlidingWindowMask(seq, window) if window else None

    def loss(q, k, v):
        with jax.named_scope("attn"):
            o = _flash(q, k, v, d ** -0.5, mask is None, "pallas", mask)
        return o.astype(jnp.float32).sum()

    before = kernels.counters()
    c = _compile(jax.grad(loss, (0, 1, 2)), one_chip,
                 ((1, 28, seq, d), dtype), ((1, 4, seq, d), dtype),
                 ((1, 4, seq, d), dtype))
    _assert_kernel(c, "flash_fwd")
    _assert_kernel(c, "flash_bwd")
    text = c.as_text()
    assert not re.search(r"\bwhile\(", text)
    assert f"{seq},{seq}]" not in text
    after = kernels.counters()
    assert after["flash_bwd_pallas"] == before.get("flash_bwd_pallas", 0) + 1
    assert after.get("flash_bwd_scan", 0) == before.get("flash_bwd_scan", 0)
    assert after["flash_bwd_q_segments"] == before.get(
        "flash_bwd_q_segments", 0) + 4
    if window:
        assert after["flash_mask_pallas"] > before.get("flash_mask_pallas", 0)
    steps, live = (after[name] - before.get(name, 0) for name in
                   ("flash_grid_steps", "flash_grid_steps_live"))
    assert (steps, live) == (70 + 252 + 8, 70 + 252) if window else \
        (16 * 16 + 4 * 32 * 8, 136 + 528)
    assert cost_model.pallas_fits_vmem("attention", (seq, d), 2)


def test_head_size_256_in_a_group_of_8_compiles(one_chip):
    """The full layer of the cell qwen3next80b-train-s8192: (1, 16 over
    2, 8192, 256) bf16 causal. Forward at (512, 1024), backward at
    (512, 512) in four segments of 2,048 query rows, as the chooser
    prices them: one ``flash_fwd``, one ``flash_bwd``, no ``while``."""
    seq, d, dtype = 8192, 256, jnp.bfloat16
    assert fa.choose_tiles(seq, seq, d, 2) == (512, 1024)
    assert fa.choose_backward(seq, seq, d, 2) == (512, 512, 2048)

    def loss(q, k, v):
        with jax.named_scope("attn"):
            o = _flash(q, k, v, d ** -0.5, True, "pallas")
        return o.astype(jnp.float32).sum()

    before = kernels.counters()
    c = _compile(jax.grad(loss, (0, 1, 2)), one_chip,
                 ((1, 16, seq, d), dtype), ((1, 2, seq, d), dtype),
                 ((1, 2, seq, d), dtype))
    _assert_kernel(c, "flash_fwd")
    _assert_kernel(c, "flash_bwd")
    text = c.as_text()
    assert not re.search(r"\bwhile\(", text)
    assert f"{seq},{seq}]" not in text
    after = kernels.counters()
    assert after["flash_bwd_pallas"] == before.get("flash_bwd_pallas", 0) + 1
    assert after.get("flash_bwd_scan", 0) == before.get("flash_bwd_scan", 0)
    assert after["flash_bwd_q_segments"] == before.get(
        "flash_bwd_q_segments", 0) + 4
    assert cost_model.pallas_fits_vmem("attention", (seq, d), 2)


@pytest.mark.parametrize("b,hk,hv,seq,dtype", [
    (1, 16, 32, 8192, jnp.bfloat16),    # a linear layer of the same cell
    (1, 2, 4, 1000, jnp.float32),       # float32 at ``highest``, ragged
])
def test_gated_delta_rule_compiles(one_chip, b, hk, hv, seq, dtype):
    """The gated delta rule under ``jax.grad``: the walk over chunks and
    its reverse under the instruction names ``gdn_ms.tokens`` and the two
    rooflines find them by, the preparation around them XLA's."""
    from mxnet_tpu.kernels import gated_delta as gd

    d = 128
    assert gd.eligible(d, d, 64, jnp.dtype(dtype).itemsize)

    def loss(q, k, v, g, beta):
        with jax.named_scope("gdn"):
            o = gd._rule(q, k, v, g, beta, 64, False)
        return o.astype(jnp.float32).sum()

    seq_p = -(-seq // gd.step_rows(seq, 64)) * gd.step_rows(seq, 64)
    before = kernels.counters()
    c = _compile(jax.value_and_grad(loss, (0, 1, 2, 3, 4)), one_chip,
                 ((b, hk, seq_p, d), dtype), ((b, hk, seq_p, d), dtype),
                 ((b, hv, seq_p, d), dtype), ((b, hv, seq_p), jnp.float32),
                 ((b, hv, seq_p), jnp.float32))
    _assert_kernel(c, "gdn_fwd")
    _assert_kernel(c, "gdn_bwd")
    assert not re.search(r"\bwhile\(", c.as_text())
    after = kernels.counters()
    assert after["gdn_chunks"] - before.get("gdn_chunks", 0) \
        == 2 * seq_p // 64


@pytest.mark.parametrize("m,k,n,g,dtype", [
    (16384, 2048, 1536, 16, jnp.bfloat16),  # the cell's gate + up product
    (16384, 768, 2048, 16, jnp.bfloat16),   # and its down product
    (512, 128, 128, 3, jnp.bfloat16),
    # float32 blocks are twice as large: at 512 columns dlhs's block of
    # rhs and its transpose overran VMEM on the chip (my chip run, PR 30)
    (4096, 768, 2048, 16, jnp.float32),
    (4096, 2048, 1536, 16, jnp.float32),
])
def test_grouped_matmul_compiles(one_chip, m, k, n, g, dtype):
    """The three grouped products under ``jax.grad`` at the tiles the
    budget allows, each under the instruction name ``moe_gmm_ms.tokens``
    finds it by."""
    from mxnet_tpu.kernels import grouped_matmul as gm

    assert gm.eligible(m, k, n, jnp.dtype(dtype).itemsize)

    def loss(lhs, rhs, sizes):
        with jax.named_scope("moe_gmm"):
            out = gm._gmm(lhs, rhs, sizes, "pallas")
        return out.astype(jnp.float32).sum()

    # the value too: a sum's gradient alone needs no forward product;
    # float32 as chip_smoke.py runs it, every product at ``highest``,
    # whose three bfloat16 parts of each operand are VMEM too
    with jax.default_matmul_precision(
            "highest" if dtype == jnp.float32 else "default"):
        c = _compile(jax.value_and_grad(loss, (0, 1)), one_chip,
                     ((m, k), dtype), ((g, k, n), dtype), ((g,), jnp.int32))
    for name in ("moe_gmm_fwd", "moe_gmm_dlhs", "moe_gmm_drhs"):
        _assert_kernel(c, name)


def _compile_trainer_step(trainer, x, y, one_chip, monkeypatch):
    """``SPMDTrainer``'s own step function for the batch (x, y), compiled
    for the described chip with its parameters, optimizer state and
    counters donated as the trainer donates them. The trainer is built
    on the CPU as ever; only the traced step goes to the chip, the
    kernels chosen as on a TPU."""
    trainer._ensure_built(x, y)
    shapes = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        (trainer._param_vals, trainer._states, trainer._aux,
         jnp.asarray(x), jnp.asarray(y)))
    with monkeypatch.context() as m:
        m.setattr(jax, "default_backend", lambda: "tpu")
        return jax.jit(  # graft-lint: allow(jit-nocache)
            trainer._compiled.__wrapped__,
            donate_argnums=(0, 1, 2)).lower(*shapes).compile()


def test_kernels_keep_their_names_inside_the_trainers_step(one_chip,
                                                           monkeypatch):
    """``SPMDTrainer``'s own step function of a small ``MoEDecoderLM``
    under block diffusion, compiled for the chip: the two flash kernels
    and the three grouped products are there under the instruction names
    the benchmark's readers go by, with ``jvp(fwd)`` and its transpose
    around them, and the counters count kernels and no plain twin. The
    trainer is built on the CPU as ever; only the traced step goes to
    the described chip, the kernels chosen as on a TPU."""
    import numpy as onp

    from mxnet_tpu import models, parallel
    from mxnet_tpu.gluon.loss import L2Loss

    net = models.MoEDecoderLM(
        vocab_size=256, embed_dim=128, num_layers=2, num_heads=2,
        num_kv_heads=1, head_dim=128, num_experts=8, expert_dim=128,
        top_k=2, experts_held=(0, 4), attention={"block_length": 4})
    net.initialize()
    trainer = parallel.SPMDTrainer(
        net, L2Loss(), optimizer="adamw",
        optimizer_params={"learning_rate": 1e-3},
        mesh=parallel.make_mesh({"dp": 1}, devices=jax.devices()[:1]),
        compute_dtype="bfloat16")
    x = onp.zeros((2, 128), "int32")
    y = onp.zeros((2, 64, 256), "float32")
    trainer._ensure_built(x, y)     # its eager forward takes the CPU's twins
    before = kernels.counters()
    c = _compile_trainer_step(trainer, x, y, one_chip, monkeypatch)
    for name in ("flash_fwd", "flash_bwd", "moe_gmm_fwd", "moe_gmm_dlhs",
                 "moe_gmm_drhs"):
        _assert_kernel(c, name)
    after = kernels.counters()
    for name in ("flash_mask_pallas", "flash_bwd_pallas", "moe_gmm_pallas"):
        assert after.get(name, 0) > before.get(name, 0), name
    for name in ("flash_bwd_scan", "moe_gmm_plain"):
        assert after.get(name, 0) == before.get(name, 0), name


def test_gated_delta_layers_keep_their_names_inside_the_trainers_step(
        one_chip, monkeypatch):
    """``SPMDTrainer``'s step of a small ``MoEDecoderLM`` under [gated
    delta, gated full attention with partial RoPE] and a shared expert,
    compiled for the chip: the rule's four kernels and the flash kernels
    under the instruction names the benchmark's readers go by, counted
    as kernels and no twin."""
    import numpy as onp

    from mxnet_tpu import models, parallel
    from mxnet_tpu.gluon.loss import L2Loss

    linear = {"gated_delta": dict(num_k_heads=1, num_v_heads=2,
                                  head_k_dim=128, head_v_dim=128)}
    net = models.MoEDecoderLM(
        vocab_size=256, embed_dim=128, num_layers=2, num_heads=2,
        num_kv_heads=1, head_dim=128, num_experts=8, expert_dim=128,
        top_k=2, experts_held=(0, 4), attention=[linear, "causal"],
        rotary_dim=32, output_gate=True, shared_expert=128)
    net.initialize()
    trainer = parallel.SPMDTrainer(
        net, L2Loss(), optimizer="adamw",
        optimizer_params={"learning_rate": 1e-3},
        mesh=parallel.make_mesh({"dp": 1}, devices=jax.devices()[:1]),
        compute_dtype="bfloat16")
    x = onp.zeros((2, 256), "int32")
    y = onp.zeros((2, 256, 256), "float32")
    trainer._ensure_built(x, y)     # its eager forward takes the CPU's twins
    before = kernels.counters()
    c = _compile_trainer_step(trainer, x, y, one_chip, monkeypatch)
    for name in ("gdn_prep_fwd", "gdn_fwd", "gdn_prep_refwd", "gdn_bwd",
                 "gdn_prep_bwd", "flash_fwd", "flash_bwd", "moe_gmm_fwd",
                 "delta_prologue_fwd", "delta_prologue_bwd"):
        _assert_kernel(c, name)
    after = kernels.counters()
    for name in ("gdn_pallas", "flash_bwd_pallas", "moe_gmm_pallas"):
        assert after.get(name, 0) > before.get(name, 0), name
    for name in ("gdn_plain", "flash_bwd_scan", "moe_gmm_plain",
                 "delta_prologue_plain"):
        assert after.get(name, 0) == before.get(name, 0), name
    assert after["gdn_chunks"] - before.get("gdn_chunks", 0) == 2 * 4
    assert after["delta_prologue_pallas"] - before.get(
        "delta_prologue_pallas", 0) == 1
    # the convolution and the norms are the kernels' alone: no op of the
    # step was traced under the plain twin's scopes, float32 or other
    text = c.as_text()
    assert not re.search(r'op_name="[^"]*/(conv|l2norm)/', text)


def test_delta_prologue_kernels_compile(one_chip, monkeypatch):
    """The Gated DeltaNet prologue of the cell qwen3next80b-train-s8192
    at its shape, ``qkvz`` (1, 8192, 12288) bf16 of 16 key and 32 value
    heads of 128, value and gradient in both inputs: ``delta_prologue_fwd``
    and ``delta_prologue_bwd`` under the names ``delta_prologue_ms.tokens``
    reads, at the tiles the budget gives (1,024 positions of 2 heads),
    counted once as kernels, and no (8192, 8192) float32 array in the
    program, where the XLA passes held several."""
    from mxnet_tpu.kernels import delta_prologue as dp

    b, s, hk, hv, d = 1, 8192, 16, 32, 128
    assert dp.tiles(s, dp.Layout(hk, hv, d, d, 4), 2) == (1024, 2)

    def loss(qkvz, conv_w):
        q, k, v = dp.delta_prologue(qkvz, conv_w, hk, hv, d, d)
        return sum(a.astype(jnp.float32).sum() for a in (q, k, v))

    before = kernels.counters()
    with monkeypatch.context() as m:
        m.setattr(jax, "default_backend", lambda: "tpu")
        c = _compile(jax.value_and_grad(loss, (0, 1)), one_chip,
                     ((b, s, 2 * (hk + hv) * d), jnp.bfloat16),
                     ((4, (2 * hk + hv) * d), jnp.bfloat16))
    _assert_kernel(c, "delta_prologue_fwd")
    _assert_kernel(c, "delta_prologue_bwd")
    assert "f32[1,8192,8192]" not in c.as_text()
    after = kernels.counters()
    assert after["delta_prologue_pallas"] == before.get(
        "delta_prologue_pallas", 0) + 1
    assert after.get("delta_prologue_plain", 0) == before.get(
        "delta_prologue_plain", 0)


def test_the_sdar_cells_step_takes_the_prologue_kernels(one_chip,
                                                        monkeypatch):
    """``SPMDTrainer``'s step of the cell sdar30b-train-bd-s4096 at its
    own depth (4 layers), widths, vocabulary (18,992 rows) and batch (2
    sequences of [xt ; x0], 8,192 positions), under its weighted loss,
    compiled for the chip: each layer's q/k norms, RoPE and layout are
    the kernel pair ``qk_prologue_fwd`` / ``qk_prologue_bwd`` (counted,
    no twin), and the step's temporaries stay under those of the same
    step with the plain twin (6,556,542,976 B; 5,987,084,800 with the
    kernels). Plain SGD: Adam's two moments would double the host memory
    of this test and change no temporary of the prologue. The trainer is
    built on a short batch: its parameters do not depend on the length,
    and its eager forward on the CPU would hold a (1, 32, 8192, 8192)
    float32 score."""
    import numpy as onp

    from mxnet_tpu import models, parallel
    from mxnet_tpu.gluon.loss import Loss

    class WeightedCrossEntropy(Loss):
        def __init__(self):
            super().__init__(None, 0)

        def hybrid_forward(self, F, pred, label):
            logp = F.log_softmax(pred, axis=-1)
            return -F.mean(F.pick(logp, label[:, 0], axis=-1)
                           * label[:, 1], axis=1)

    net = models.MoEDecoderLM(
        vocab_size=18992, embed_dim=2048, num_layers=4, num_heads=32,
        num_kv_heads=4, head_dim=128, num_experts=128, expert_dim=768,
        top_k=8, experts_held=(0, 16), attention={"block_length": 4})
    net.initialize()
    trainer = parallel.SPMDTrainer(
        net, WeightedCrossEntropy(), optimizer="sgd",
        optimizer_params={"learning_rate": 1e-7},
        mesh=parallel.make_mesh({"dp": 1}, devices=jax.devices()[:1]),
        compute_dtype="bfloat16")
    trainer._ensure_built(onp.zeros((1, 256), "int32"),
                          onp.zeros((1, 2, 128), "float32"))
    before = kernels.counters()
    c = _compile_trainer_step(trainer, onp.zeros((2, 8192), "int32"),
                              onp.zeros((2, 2, 4096), "float32"), one_chip,
                              monkeypatch)
    for name in ("qk_prologue_fwd", "qk_prologue_bwd", "flash_fwd",
                 "flash_bwd"):
        _assert_kernel(c, name)
    after = kernels.counters()
    assert after["qk_prologue_pallas"] - before.get(
        "qk_prologue_pallas", 0) == 4
    assert after.get("qk_prologue_plain", 0) == before.get(
        "qk_prologue_plain", 0)
    assert "delta_prologue" not in c.as_text()      # no Gated DeltaNet
    assert c.memory_analysis().temp_size_in_bytes <= 6_556_542_976


def test_the_phi4flash_cells_step_fits_and_takes_the_scan_kernels(
        one_chip, monkeypatch):
    """``SPMDTrainer``'s step of the cell phi4flash3.8b-train-s8192 at its
    own depth (6 layers: Mamba, window 512, Mamba, full, GMU, cross),
    widths, vocabulary slice (25,008 rows) and length (1 x 8,192),
    built by the benchmark's own ``build_net`` and loss block, compiled for
    the chip: both Mamba layers' scans are the kernel pair (counted, no
    twin; 32 chunks a pass) under the names ``ssm_scan_ms.tokens`` reads,
    the flash kernels beside them, and the step's temporaries stay under
    4.1 GB (3,868,037,120 B as built; 7,418,326,528 before the MLP and the
    attention maps ran again in the backward pass), so that with the
    11.43 GB resident the cell holds at most 92% of the chip's 16.9.
    Plain SGD: Adam's two moments would double the host memory of this
    test and change no temporary. The trainer is built on a short batch:
    its parameters do not depend on the length."""
    import importlib.util

    import numpy as onp

    from mxnet_tpu import parallel

    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks")
    spec = importlib.util.spec_from_file_location(
        "chip_compile_phi4flash",
        os.path.join(bench, "models", "phi4-mini-flash-3.8b.py"))
    model = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(model)
    with open(os.path.join(bench, "configs",
                           "phi4-mini-flash-3.8b.json")) as f:
        cfg = json.load(f)
    net = model.build_net(cfg)
    net.initialize()
    trainer = parallel.SPMDTrainer(
        net, model.loss_block(cfg), optimizer="sgd",
        optimizer_params={"learning_rate": 1e-7},
        mesh=parallel.make_mesh({"dp": 1}, devices=jax.devices()[:1]),
        compute_dtype="bfloat16")
    short = onp.zeros((1, 256), "int32")
    trainer._ensure_built(short, short)
    before = kernels.counters()
    tokens = onp.zeros((1, 8192), "int32")
    c = _compile_trainer_step(trainer, tokens, tokens, one_chip,
                              monkeypatch)
    for name in ("ssm_scan_fwd", "ssm_scan_bwd", "flash_fwd", "flash_bwd"):
        _assert_kernel(c, name)
    after = kernels.counters()
    assert after["ssm_scan_pallas"] - before.get("ssm_scan_pallas", 0) == 2
    assert after.get("ssm_scan_plain", 0) == before.get("ssm_scan_plain", 0)
    assert after["ssm_scan_chunks"] - before.get("ssm_scan_chunks", 0) \
        == 2 * 2 * 8192 // 256
    assert after.get("flash_bwd_scan", 0) == before.get("flash_bwd_scan", 0)
    assert "delta_prologue" not in c.as_text()      # no Gated DeltaNet
    assert c.memory_analysis().temp_size_in_bytes < 4.1e9


def test_short_conv_kernels_compile(one_chip, monkeypatch):
    """LFM2's gated short convolution at the shape of the cell
    lfm2moe24b-train-s8192, ``bcx`` (1, 8192, 6144) bf16 of 2,048
    channels, value and gradient in both inputs: ``short_conv_fwd`` and
    ``short_conv_bwd`` under the names ``short_conv_ms.tokens`` reads,
    at the tiles the budget gives (1,024 positions of 512 channels
    forward, of 256 backward), counted once as kernels, and no padded
    (1, 8194, 2048) float32 ``B * x``, which the twin's passes hold."""
    from mxnet_tpu.kernels import short_conv as sc

    assert sc.tiles(8192, 2048, 2, False) == (1024, 512)
    assert sc.tiles(8192, 2048, 2, True) == (1024, 256)

    def loss(bcx, conv_w):
        return sc.short_conv(bcx, conv_w).astype(jnp.float32).sum()

    before = kernels.counters()
    with monkeypatch.context() as m:
        m.setattr(jax, "default_backend", lambda: "tpu")
        c = _compile(jax.value_and_grad(loss, (0, 1)), one_chip,
                     ((1, 8192, 6144), jnp.bfloat16),
                     ((3, 2048), jnp.bfloat16))
    _assert_kernel(c, "short_conv_fwd")
    _assert_kernel(c, "short_conv_bwd")
    assert "f32[1,8194,2048]" not in c.as_text()
    after = kernels.counters()
    assert after["short_conv_pallas"] == before.get(
        "short_conv_pallas", 0) + 1
    assert after.get("short_conv_plain", 0) == before.get(
        "short_conv_plain", 0)


@pytest.mark.parametrize("chunk", [256, 128])
def test_selective_scan_kernels_compile(one_chip, monkeypatch, chunk):
    """A Mamba layer's selective scan at the shape of the cell
    phi4flash3.8b-train-s8192, u, dt, z, B, C bfloat16 (1, 8192) of 5,120
    channels and 16 states, value and gradient in all eight inputs:
    ``ssm_scan_fwd`` and ``ssm_scan_bwd`` under the names
    ``ssm_scan_ms.tokens`` reads, counted once as kernels over 8192 /
    ``chunk`` chunks a pass, at the default chunk and at 128 rows; the
    backward's stored gradient of every position's state fits beside its
    other scratch."""
    from mxnet_tpu.kernels import selective_scan as ss

    def loss(*args):
        return ss.selective_scan(*args, chunk=chunk).astype(
            jnp.float32).sum()

    before = kernels.counters()
    with monkeypatch.context() as m:
        m.setattr(jax, "default_backend", lambda: "tpu")
        c = _compile(jax.value_and_grad(loss, range(8)), one_chip,
                     ((1, 8192, 5120), jnp.bfloat16),
                     ((1, 8192, 5120), jnp.bfloat16),
                     ((5120, 16), jnp.float32),
                     ((1, 8192, 16), jnp.bfloat16),
                     ((1, 8192, 16), jnp.bfloat16),
                     ((5120,), jnp.float32),
                     ((1, 8192, 5120), jnp.bfloat16),
                     ((5120,), jnp.float32))
    _assert_kernel(c, "ssm_scan_fwd")
    _assert_kernel(c, "ssm_scan_bwd")
    after = kernels.counters()
    assert after["ssm_scan_pallas"] == before.get("ssm_scan_pallas", 0) + 1
    assert after.get("ssm_scan_plain", 0) == before.get("ssm_scan_plain", 0)
    assert after["ssm_scan_chunks"] - before.get("ssm_scan_chunks", 0) \
        == 2 * 8192 // chunk


def test_the_lfm2_cells_step_fits_and_takes_the_short_conv_kernels(
        one_chip, monkeypatch):
    """``SPMDTrainer``'s step of the cell lfm2moe24b-train-s8192 at its
    own depth (6 layers: two dense conv layers, attention, three expert
    conv layers), widths, vocabulary slice (8,192 rows, tied) and length
    (1 x 8,192), built by the benchmark's own ``build_net`` and loss
    block, compiled for the chip: the five conv layers' mixers are the
    kernel pair (counted, no twin) under the names
    ``short_conv_ms.tokens`` reads, the flash kernels and the grouped
    products beside them, and the step's temporaries stay under 4.8 GB
    (4,546,375,680 B as built), so that with the 9.16 GB resident the
    cell holds about 81% of the chip's 16.9. Plain SGD: Adam's two
    moments would double the host memory of this test and change no
    temporary. The trainer is built on a short batch: its parameters do
    not depend on the length."""
    import importlib.util

    import numpy as onp

    from mxnet_tpu import parallel

    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks")
    spec = importlib.util.spec_from_file_location(
        "chip_compile_lfm2", os.path.join(bench, "models",
                                          "lfm2-24b-a2b.py"))
    model = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(model)
    with open(os.path.join(bench, "configs", "lfm2-24b-a2b.json")) as f:
        cfg = json.load(f)
    net = model.build_net(cfg)
    net.initialize()
    trainer = parallel.SPMDTrainer(
        net, model.loss_block(cfg), optimizer="sgd",
        optimizer_params={"learning_rate": 1e-7},
        mesh=parallel.make_mesh({"dp": 1}, devices=jax.devices()[:1]),
        compute_dtype="bfloat16")
    short = onp.zeros((1, 256), "int32")
    trainer._ensure_built(short, short)
    before = kernels.counters()
    tokens = onp.zeros((1, 8192), "int32")
    c = _compile_trainer_step(trainer, tokens, tokens, one_chip,
                              monkeypatch)
    for name in ("short_conv_fwd", "short_conv_bwd", "flash_fwd",
                 "flash_bwd", "moe_gmm_fwd"):
        _assert_kernel(c, name)
    after = kernels.counters()
    assert after["short_conv_pallas"] - before.get(
        "short_conv_pallas", 0) == 5
    assert after.get("short_conv_plain", 0) == before.get(
        "short_conv_plain", 0)
    assert c.memory_analysis().temp_size_in_bytes < 4.8e9


@pytest.mark.parametrize("b,s,v", [
    (1, 8192, 18992),     # qwen3next80b-train-s8192: one sequence a step
    (2, 2048, 50272),     # opt1.3b-train-s2048: two
])
def test_next_token_loss_compiles_dense(one_chip, b, s, v):
    """The cells' loss block at their widths: the head's product
    (E = 2048, bfloat16), float32 logits, ``log_softmax`` of all
    positions but the last, ``pick`` of the next token, the mean, and
    ``jax.grad`` to the hidden state and the head's weight. With the
    element gathered, XLA:TPU at ONE sequence a batch kept the gather's
    scatter-add: a zero-filled flat ``f32[(s - 1) * v]``, a serial
    scatter into it and a ``while`` of 148 trips that re-tiled it for
    the softmax's gradient, three logits-sized temporaries in all (1.867
    GB at the first shape; PERF.md section 6, PR 37). Taken by a mask
    the program is dense at either batch: one float32 logits array
    (0.623 and 0.824 GB)."""
    from mxnet_tpu.ndarray.ops_index import pick

    def loss(h, w, label):
        pred = jnp.einsum("bse,ve->bsv", h, w).astype(jnp.float32)
        logp = jax.nn.log_softmax(pred[:, :-1], axis=-1)
        return jnp.mean(-pick(logp, label[:, 1:], axis=-1, keepdims=True))

    c = _compile(jax.grad(loss, argnums=(0, 1)), one_chip,
                 ((b, s, 2048), jnp.bfloat16), ((v, 2048), jnp.bfloat16),
                 ((b, s), jnp.int32))
    text = c.as_text()
    assert " while(" not in text
    assert " scatter(" not in text
    logits = b * (s - 1) * v * 4
    assert abs(c.memory_analysis().temp_size_in_bytes / logits - 1) < 0.05


@pytest.fixture(scope="module")
def opt_layer():
    """One ``TransformerLM`` layer at the widths of the cell
    ``opt1.3b-train-s2048`` (hidden 2048, ffn 8192, 32 heads, 2 x 2048
    tokens); the vocabulary cut to 4096 to keep the compile quick."""
    from mxnet_tpu import models, nd

    net = models.TransformerLM(
        vocab_size=4096, embed_dim=2048, num_layers=1, num_heads=32,
        ffn_dim=8192, max_len=2048, tie_weights=True)
    net.initialize()
    net(nd.zeros((1, 8), dtype="int32"))        # deferred shapes
    return net


def _computations(text):
    """``{name: body}`` of an optimised HLO module's computations."""
    return {m.group(1): m.group(2) for m in re.finditer(
        r"^(?:ENTRY )?%([\w.\-]+) \([^\n]*\{\n(.*?)^\}", text,
        re.M | re.S)}


def test_no_norm_is_recomputed_inside_a_weight_gradient(one_chip, opt_layer,
                                                        monkeypatch):
    """``SPMDTrainer``'s step of the OPT cell's layer (bf16 on float32
    masters, adamw), compiled for the chip. XLA fuses each dense
    weight's gradient product with its Adam update (``convolution`` and
    the update's ``sqrt`` / ``divide`` in one computation); a norm's
    output reaches that product as a stored array
    (``ops_nn.stored_residual``), so no such computation holds a
    ``rsqrt`` or, as a nested fusion, the cloned normalise-scale-shift.
    (The activation's ``maximum`` may be cloned in: it costs the product
    nothing.) Without the helper the q|k|v and ffn1 gradients hold the
    clone, which this test's second half shows."""
    import numpy as onp

    from mxnet_tpu import parallel
    from mxnet_tpu.gluon.loss import SoftmaxCrossEntropyLoss
    from mxnet_tpu.ndarray import ops_nn

    def compiled_step():
        trainer = parallel.SPMDTrainer(
            opt_layer, SoftmaxCrossEntropyLoss(), optimizer="adamw",
            optimizer_params={"learning_rate": 1e-4},
            mesh=parallel.make_mesh({"dp": 1}, devices=jax.devices()[:1]),
            compute_dtype="bfloat16")
        x = onp.zeros((2, 2048), "int32")
        return _compile_trainer_step(trainer, x, x, one_chip,
                                     monkeypatch).as_text()

    def cloned_norms(text):
        comps = _computations(text)
        found = []
        for name, body in comps.items():
            if not ("convolution(" in body and " sqrt(" in body
                    and " divide(" in body):
                continue
            nested = re.findall(r" fusion\([^\n]*calls=%([\w.\-]+)", body)
            if " rsqrt(" in body or any(
                    " multiply(" in comps[n] or " rsqrt(" in comps[n]
                    for n in nested):
                found.append(name)
        return found

    before = kernels.counters().get("norm_out_stored", 0)
    text = compiled_step()
    assert kernels.counters()["norm_out_stored"] == before + 3  # ln1 ln2 ln_f
    assert "convolution(" in text and cloned_norms(text) == []
    monkeypatch.setattr(ops_nn, "stored_residual", lambda y: y)
    assert len(cloned_norms(compiled_step())) == 2


def test_forward_only_trace_compiles_as_without_the_stored_residual(
        one_chip, opt_layer, monkeypatch):
    """A forward-only ``jax.jit`` of the same layer (what ``hybridize``
    and ``serving.InferenceSession`` trace): the helper's primal is the
    identity, so the optimised HLO is the one the layer compiles to
    without it, instruction for instruction (source lines in the
    metadata aside), and nothing is counted."""
    from mxnet_tpu import autograd, nd
    from mxnet_tpu.ndarray import ops_nn

    net = opt_layer
    params = [p._ndarray for _, p in sorted(net.collect_params().items())]

    def forward(values, tokens):
        saved = [p._data for p in params]
        try:
            for p, v in zip(params, values):
                p._data = v.astype(jnp.bfloat16)
            with autograd.pause(train_mode=False):
                return net.forward(nd.NDArray(tokens)).data
        finally:
            for p, v in zip(params, saved):
                p._data = v

    shapes = ([jax.ShapeDtypeStruct(p.shape, jnp.float32, sharding=one_chip)
               for p in params],
              jax.ShapeDtypeStruct((2, 2048), jnp.int32, sharding=one_chip))

    def hlo():
        with monkeypatch.context() as m:
            m.setattr(jax, "default_backend", lambda: "tpu")
            text = jax.jit(forward).lower(  # graft-lint: allow(jit-nocache)
                *shapes).compile().as_text()
        return re.sub(r", metadata=\{[^}]*\}", "", text)

    before = kernels.counters().get("norm_out_stored", 0)
    with_helper = hlo()
    assert kernels.counters().get("norm_out_stored", 0) == before
    monkeypatch.setattr(ops_nn, "stored_residual", lambda y: y)
    assert with_helper == hlo()
    assert "flash_fwd" in with_helper


@pytest.mark.parametrize("b,h,s,d,dtype", [
    (8, 12, 1024, 64, jnp.float32),
    (32, 16, 4096, 128, jnp.float32),
    (8, 16, 8192, 128, jnp.bfloat16),
])
def test_decode_flash_compiles(one_chip, b, h, s, d, dtype):
    c = _compile(lambda q, k, v, n: _decode_flash(q, k, v, n, 0.125, False),
                 one_chip, ((b, h, d), dtype), ((b, h, s, d), dtype),
                 ((b, h, s, d), dtype), ((b,), jnp.int32))
    _assert_kernel(c, "flash_decode")
    assert cost_model.pallas_fits_vmem("attention_decode", (s, d),
                                       jnp.dtype(dtype).itemsize)


@pytest.mark.parametrize("rows,c,dtype,act", [
    (4096, 768, jnp.float32, ("relu", ())),   # BERT-base, batch x seq rows
    (1000, 768, jnp.float32, ("activation", (("act_type", "tanh"),))),
    (8192, 4096, jnp.bfloat16, ("leaky_relu", (("act_type", "leaky"),))),
])
def test_norm_act_compiles(one_chip, rows, c, dtype, act):
    comp = _compile(
        lambda x, g, b: _pallas_norm_act(x, g, b, 1e-5, act[0], act[1],
                                         False),
        one_chip, ((rows, c), dtype), ((c,), dtype), ((c,), dtype))
    _assert_kernel(comp, "norm_act")
    assert cost_model.pallas_fits_vmem("norm_act", (rows, c),
                                       jnp.dtype(dtype).itemsize)


# ---------------------------------------------------------------------------
# shapes it refuses: the eligibility gate must say so first

def test_decode_oversize_row_refused_by_gate(one_chip):
    """fp32 S8192 D128: a whole (S, D) K row and V row per grid step,
    double-buffered, is 16 MiB — the compiler runs out of VMEM. The gate
    refuses from shape and dtype, and the registered op then takes its
    lax twin and counts the refusal instead of failing to compile."""
    b, h, s, d = 8, 16, 8192, 128
    assert cost_model._pallas_refusal("attention_decode", (s, d), 4) \
        == "vmem_bound"
    assert cost_model._pallas_refusal("attention_decode", (s, d), 2) is None
    with pytest.raises(Exception, match="vmem"):
        _compile(lambda q, k, v, n: _decode_flash(q, k, v, n, 0.125, False),
                 one_chip, ((b, h, d), jnp.float32),
                 ((b, h, s, d), jnp.float32), ((b, h, s, d), jnp.float32),
                 ((b,), jnp.int32))
    before = kernels.counters().get("fallback_vmem_bound", 0)
    c = _compile(
        lambda q, kc, vc, pos: _attention_decode(
            q, kc, vc, pos, num_heads=h, sm_scale=0.125, impl="pallas"),
        one_chip, ((b, h * d), jnp.float32), ((b, s, h * d), jnp.float32),
        ((b, s, h * d), jnp.float32), ((b, 1), jnp.int32))
    assert "tpu_custom_call" not in c.as_text()
    assert kernels.counters()["fallback_vmem_bound"] == before + 1


def test_flash_oversize_tile_refused_by_estimate(one_chip):
    """bf16 S4096 D64 at forced 2048 x 2048 tiles: the compiler runs out
    of VMEM on the float32 score tile. The footprint the chooser goes by
    says so first (and is the careful side: the compiler still takes
    2048 x 1024, the estimate stops at the 1024 x 1024 it chooses)."""
    shape, dtype = (1, 2, 4096, 64), jnp.bfloat16

    def fwd(bq, bk):
        return _compile(lambda q, k, v: _pallas_forward(
            q, k, v, 0.125, True, False, bq=bq, bk=bk), one_chip,
            (shape, dtype), (shape, dtype), (shape, dtype))

    assert fa.tile_vmem_bytes(2048, 2048, 4096, 64, 2) \
        > cost_model._VMEM_BUDGET_BYTES
    with pytest.raises(Exception, match="vmem"):
        fwd(2048, 2048)
    assert fa.tile_vmem_bytes(2048, 1024, 4096, 64, 2) \
        > cost_model._VMEM_BUDGET_BYTES
    _assert_kernel(fwd(2048, 1024), "flash_fwd")
    assert fa.choose_tiles(4096, 4096, 64, 2) == (1024, 1024)


def test_norm_act_oversize_row_refused_by_gate(one_chip):
    """fp32 8192x8192: the (128, C) row block, in and out and
    double-buffered, is 16 MiB (no column tiling). The gate refuses, the
    cluster still fuses, priced as lax, under the reason the pass
    counts."""
    shape = (8192, 8192)
    assert cost_model._pallas_refusal("norm_act", shape, 4) == "vmem_bound"
    with pytest.raises(Exception, match="vmem"):
        _compile(lambda x, g, b: _pallas_norm_act(x, g, b, 1e-5, "relu", (),
                                                  False),
                 one_chip, (shape, jnp.float32), ((8192,), jnp.float32),
                 ((8192,), jnp.float32))
    d = cost_model.decide("norm_act", 2, out_shape=shape, backend="tpu")
    assert (d.fuse, d.impl, d.reason) == (True, "lax", "vmem_bound")
    d = cost_model.decide("norm_act", 2, out_shape=(8192, 4096),
                          backend="tpu")
    assert (d.fuse, d.impl, d.reason) == (True, "pallas", "ok")


@pytest.mark.parametrize("act_type", ["gelu", "elu", "selu"])
def test_norm_act_unlowerable_activation_refused_by_gate(one_chip,
                                                         act_type):
    """erfc (exact GELU) and expm1 (ELU/SELU) have no Pallas TPU
    lowering: LayerNorm->GELU must price as lax on the chip."""
    with pytest.raises(Exception, match="Unimplemented primitive"):
        _compile(
            lambda x, g, b: _pallas_norm_act(
                x, g, b, 1e-5, "leaky_relu", (("act_type", act_type),),
                False),
            one_chip, ((256, 512), jnp.float32), ((512,), jnp.float32),
            ((512,), jnp.float32))
    d = cost_model.decide("norm_act", 2, out_shape=(256, 512),
                          backend="tpu", act_type=act_type)
    assert (d.fuse, d.impl, d.reason) == (True, "lax", "act_unlowerable")
