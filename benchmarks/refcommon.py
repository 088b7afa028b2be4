"""What every plain reference shares: the precision a reference is
computed in, and the two optimizers as their configurations state them.

Nothing here imports the program. A reference is float32 ``jax.numpy``
at ``highest`` matmul precision; the lower precisions exist only for the
control of "How correct is decided" (the reference, put in the program's
place, one precision below what the configuration states).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

PRECISIONS = ("float32", "bfloat16", "fp8", "fp8_forward")


def _fp8(x):
    """Per-tensor scaled e4m3 quantisation, dequantised to bfloat16: what
    a product's operand holds when it is fed in fp8. The scale is not
    differentiated (straight-through), as fp8 training recipes do."""
    xf = x.astype(jnp.float32)
    scale = lax.stop_gradient(jnp.max(jnp.abs(xf))) / 448.0 + 1e-30
    q = (xf / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    # straight-through: value of q*scale, gradient of identity
    return (xf + lax.stop_gradient(q * scale - xf)).astype(jnp.bfloat16)


def _e5m2(g):
    """A gradient as a backward product takes it in fp8: per-tensor
    scaled e5m2, the type fp8 training recipes give gradients."""
    gf = g.astype(jnp.float32)
    scale = jnp.max(jnp.abs(gf)) / 57344.0 + 1e-30
    return ((gf / scale).astype(jnp.float8_e5m2).astype(jnp.float32)
            * scale).astype(g.dtype)


@jax.custom_vjp
def _fp8_backward(x):
    return x


_fp8_backward.defvjp(lambda x: (x, None), lambda _, g: (_e5m2(g),))


class Prec:
    """How the operands of matmuls and convolutions are rounded, and in
    what type results travel between layers. ``bfloat16`` rounds both to
    bfloat16. ``fp8`` is the step below it that a later PR would take:
    every product, forward and backward, takes its operands in per-tensor
    scaled fp8 (weights and activations e4m3, gradients e5m2), and
    everything else is as the bfloat16 path keeps it. ``fp8_forward``
    leaves the gradients in bfloat16: the mildest use of fp8, read beside
    the control (PERF.md). The statistics of a norm and the accumulation
    of a product are float32 throughout."""

    def __init__(self, name):
        if name not in PRECISIONS:
            raise ValueError(f"unknown precision {name!r}")
        self.name = name
        self.act = jnp.float32 if name == "float32" else jnp.bfloat16
        self.lax = lax.Precision.HIGHEST if name == "float32" \
            else lax.Precision.DEFAULT

    def operand(self, x):
        if self.name == "float32":
            return x.astype(jnp.float32)
        if self.name == "bfloat16":
            return x.astype(jnp.bfloat16)
        return _fp8(x)              # fp8, fp8_forward

    def product(self, x):
        """The result of a product whose operands went through
        ``operand``: its backward products take the gradient through the
        same precision."""
        return _fp8_backward(x) if self.name == "fp8" else x

    def store(self, x):
        """A layer's result as this precision keeps it."""
        return x.astype(self.act)

    def matmul(self, a, b):
        """a @ b with float32 accumulation, result as this precision
        keeps it."""
        out = jnp.matmul(self.operand(a), self.operand(b),
                         precision=self.lax,
                         preferred_element_type=jnp.float32)
        return self.store(self.product(out))

    def conv(self, x, w, stride, pad):
        """NHWC x HWIO convolution; the result is in the activation type
        (the chip accumulates in float32 either way)."""
        return self.store(self.product(lax.conv_general_dilated(
            self.operand(x), self.operand(w), (stride, stride),
            [(pad, pad), (pad, pad)],
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=self.lax)))


def softmax_xent(logits, labels):
    """Mean cross-entropy of integer labels, in float32."""
    lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    picked = jnp.take_along_axis(lp, labels[..., None].astype(jnp.int32),
                                 axis=-1)
    return -jnp.mean(picked)


# ---------------------------------------------------------------------------
# optimizers, as the configurations state them (MXNet semantics)

def opt_init(spec, params):
    kind = spec["kind"]
    if kind == "sgd":
        return {k: jnp.zeros_like(v) for k, v in params.items()}
    if kind == "adam":
        return {k: (jnp.zeros_like(v), jnp.zeros_like(v))
                for k, v in params.items()}
    raise ValueError(kind)


def opt_update(spec, params, grads, state, t):
    """One update of every leaf; ``t`` is the 1-based step.

    sgd:  mom = momentum*mom - lr*g ; w += mom       (MXNet sgd_mom_update)
    adam: m, v moments; lr_t = lr*sqrt(1-b2^t)/(1-b1^t);
          w -= lr_t * m / (sqrt(v) + eps)             (MXNet Adam/AdamW, wd 0)
    """
    lr = spec["learning_rate"]
    new_p, new_s = {}, {}
    if spec["kind"] == "sgd":
        mu = spec["momentum"]
        for k, w in params.items():
            mom = mu * state[k] - lr * grads[k]
            new_p[k], new_s[k] = w + mom, mom
        return new_p, new_s
    b1, b2, eps = spec["beta1"], spec["beta2"], spec["epsilon"]
    tf = jnp.float32(t)
    lr_t = lr * jnp.sqrt(1.0 - b2 ** tf) / (1.0 - b1 ** tf)
    for k, w in params.items():
        m, v = state[k]
        g = grads[k]
        m = b1 * m + (1.0 - b1) * g
        v = b2 * v + (1.0 - b2) * jnp.square(g)
        new_p[k] = w - lr_t * m / (jnp.sqrt(v) + eps)
        new_s[k] = (m, v)
    return new_p, new_s


def grad_scale_from_state(spec):
    """The optimizer's first state is the first gradient times this:
    sgd's momentum buffer after one step is -lr*g, Adam's first moment
    (1-beta1)*g. Dividing the state by it gives the gradient."""
    if spec["kind"] == "sgd":
        return -spec["learning_rate"]
    return 1.0 - spec["beta1"]


def first_moment(spec, leaf_state):
    """The part of a leaf's optimizer state that is linear in the first
    gradient (sgd: the momentum buffer; adam: m)."""
    return leaf_state if spec["kind"] == "sgd" else leaf_state[0]


SAMPLE = 1 << 16


def sample(a):
    """Up to SAMPLE elements of ``a`` on a lattice over its flattened
    form, the same on both sides of a comparison: an odd stride, so that
    it walks across rows and columns. Nothing of the seed enters, so that
    the jitted step that samples is one program for every seed."""
    flat = a.reshape(-1)
    stride = max(1, flat.shape[0] // SAMPLE)
    if stride > 1 and stride % 2 == 0:
        stride += 1
    return flat[stride // 2::stride][:SAMPLE].astype(jnp.float32)


def key_from_seed(seed, stream=0):
    """A PRNG key from any whole number a little over 2**31: the low 32
    bits seed it, the rest and the stream are folded in."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    key = jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)
    return jax.random.fold_in(key, stream)
