"""NN core ops: convolution, pooling, dense, norms, softmax, dropout, RNN.

TPU-native equivalents of ``src/operator/nn/`` (reference: convolution-inl.h,
pooling-inl.h, fully_connected-inl.h, batch_norm.cc, layer_norm.cc,
softmax.cc, dropout-inl.h, rnn-inl.h). Where the reference dispatches to
cuDNN/MKLDNN kernels, these bodies lower to XLA HLO (conv_general_dilated,
reduce_window, dot_general) which the TPU compiler tiles onto the MXU;
the fused RNN op is a ``lax.scan`` (compiler-friendly control flow) instead
of the reference's cuDNN RNN descriptor path (rnn-inl.h:447-482).
"""
from __future__ import annotations

import numpy as onp

import jax
import jax.numpy as jnp
from jax import lax

from .ops_index import pick
from .registry import register


def _tup(v, n):
    if v is None:
        return (0,) * n if n else v
    if isinstance(v, int):
        return (v,) * n
    t = tuple(v)
    return t if len(t) == n else t + t[-1:] * (n - len(t))


# --------------------------------------------------------------- dense ----

@register()
def fully_connected(data, weight, bias=None, num_hidden=0, no_bias=False,
                    flatten=True):
    """Reference: src/operator/nn/fully_connected-inl.h. weight is
    (num_hidden, input_dim) as in MXNet; lowers to one MXU dot_general."""
    if flatten and data.ndim > 2:
        data = data.reshape(data.shape[0], -1)
    out = jnp.matmul(data, weight.T)
    if bias is not None and not no_bias:
        out = out + bias
    return out


# ---------------------------------------------------------------- conv ----

_CONV_DIMS = {1: ("NCW", "OIW", "NCW"), 2: ("NCHW", "OIHW", "NCHW"),
              3: ("NCDHW", "OIDHW", "NCDHW")}
# channel-last layouts (reference: NHWC/NDHWC 'only supported on GPU' —
# here they exist because NHWC is the layout XLA:TPU's conv emitters
# prefer; weight rides as (O, *spatial, I) like cuDNN's NHWC filters)
_CHANNEL_LAST = {"NWC": 1, "NHWC": 2, "NDHWC": 3}


def _conv_dims(nd, layout):
    if layout in _CHANNEL_LAST:
        rhs = "O" + layout[1:-1] + "I"
        return (layout, rhs, layout)
    return _CONV_DIMS[nd]


@register()
def convolution(data, weight, bias=None, kernel=None, stride=None, dilate=None,
                pad=None, num_filter=0, num_group=1, no_bias=False,
                layout=None):
    """Reference: src/operator/nn/convolution-inl.h (cuDNN path
    nn/cudnn/cudnn_convolution-inl.h). XLA conv_general_dilated. Default
    NCHW for API parity; layout='NHWC' (weight (O, kh, kw, I)) keeps the
    channel dimension in XLA's preferred minor position on TPU."""
    nd = len(kernel) if kernel is not None else data.ndim - 2
    stride = _tup(stride or 1, nd)
    dilate = _tup(dilate or 1, nd)
    pad = _tup(pad or 0, nd)
    dn = lax.conv_dimension_numbers(data.shape, weight.shape,
                                    _conv_dims(nd, layout))
    out = lax.conv_general_dilated(
        data, weight, window_strides=stride,
        padding=[(p, p) for p in pad], rhs_dilation=dilate,
        dimension_numbers=dn, feature_group_count=num_group,
        preferred_element_type=jnp.float32 if data.dtype == jnp.float32 else None)
    out = out.astype(data.dtype)
    if bias is not None and not no_bias:
        bshape = ((1,) * (nd + 1) + (-1,)) if layout in _CHANNEL_LAST \
            else ((1, -1) + (1,) * nd)
        out = out + bias.reshape(bshape)
    return out


@register()
def deconvolution(data, weight, bias=None, kernel=None, stride=None,
                  dilate=None, pad=None, adj=None, num_filter=0, num_group=1,
                  no_bias=True, target_shape=None, layout=None):
    """Transposed convolution (reference: src/operator/nn/deconvolution-inl.h).
    Channel-first layouts only."""
    if layout in _CHANNEL_LAST:
        raise ValueError(
            "deconvolution supports channel-first layouts only "
            "(NCW/NCHW/NCDHW)")
    nd = len(kernel)
    stride = _tup(stride or 1, nd)
    pad = _tup(pad or 0, nd)
    adj = _tup(adj or 0, nd)
    dilate = _tup(dilate or 1, nd)
    # conv_transpose with IOHW kernel: mxnet deconv weight is (in, out/g, *k)
    if num_group != 1:
        # grouped transpose conv: split and concat
        xs = jnp.split(data, num_group, axis=1)
        ws = jnp.split(weight, num_group, axis=0)
        outs = [_deconv1(x, w, stride, pad, adj, dilate, nd) for x, w in zip(xs, ws)]
        out = jnp.concatenate(outs, axis=1)
    else:
        out = _deconv1(data, weight, stride, pad, adj, dilate, nd)
    if bias is not None and not no_bias:
        out = out + bias.reshape((1, -1) + (1,) * nd)
    return out


def _deconv1(data, weight, stride, pad, adj, dilate, nd):
    pads = []
    for i in range(nd):
        k = (weight.shape[2 + i] - 1) * dilate[i] + 1
        lo = k - 1 - pad[i]
        hi = k - 1 - pad[i] + adj[i]
        pads.append((lo, hi))
    dn = lax.conv_dimension_numbers(data.shape, weight.shape[1:2] + weight.shape[0:1] + weight.shape[2:], _CONV_DIMS[nd])
    w = jnp.swapaxes(weight, 0, 1)  # (out, in, *k)
    w = jnp.flip(w, axis=tuple(range(2, 2 + nd)))
    return lax.conv_general_dilated(
        data, w, window_strides=(1,) * nd, padding=pads,
        lhs_dilation=stride, rhs_dilation=dilate, dimension_numbers=dn)


# ------------------------------------------------------------- pooling ----

@register()
def pooling(data, kernel=None, pool_type="max", global_pool=False,
            stride=None, pad=None, pooling_convention="valid",
            count_include_pad=True, layout=None):
    """Reference: src/operator/nn/pooling-inl.h → XLA reduce_window.
    layout NWC/NHWC/NDHWC pools over the middle (spatial) axes."""
    nd = data.ndim - 2
    channel_last = layout in _CHANNEL_LAST
    if global_pool:
        ax = tuple(range(1, data.ndim - 1)) if channel_last \
            else tuple(range(2, data.ndim))
        if pool_type == "max":
            return jnp.max(data, axis=ax, keepdims=True)
        return jnp.mean(data, axis=ax, keepdims=True)
    kernel = _tup(kernel, nd)
    stride = _tup(stride or 1, nd)
    pad = _tup(pad or 0, nd)
    sp = [data.shape[1 + i] if channel_last else data.shape[2 + i]
          for i in range(nd)]
    spads = tuple((p, p) for p in pad)
    if channel_last:
        window = (1,) + kernel + (1,)
        strides = (1,) + stride + (1,)
    else:
        window = (1, 1) + kernel
        strides = (1, 1) + stride
    if pooling_convention == "full":
        # ceil-mode: add extra high padding so last window fits
        extra = []
        for i in range(nd):
            size = sp[i] + 2 * pad[i] - kernel[i]
            rem = size % stride[i]
            extra.append(stride[i] - rem if rem else 0)
        spads = tuple((p, p + e) for p, e in zip(pad, extra))
    pads = ((0, 0),) + spads + ((0, 0),) if channel_last \
        else ((0, 0), (0, 0)) + spads
    if pool_type == "max":
        init = -jnp.inf if jnp.issubdtype(data.dtype, jnp.floating) else jnp.iinfo(data.dtype).min
        return lax.reduce_window(data, init, lax.max, window, strides, pads)
    if pool_type in ("avg", "sum"):
        s = lax.reduce_window(data, 0.0, lax.add, window, strides, pads)
        if pool_type == "sum":
            return s
        if count_include_pad:
            denom = 1
            for k in kernel:
                denom *= k
            return s / denom
        ones = jnp.ones_like(data)
        cnt = lax.reduce_window(ones, 0.0, lax.add, window, strides, pads)
        return s / cnt
    if pool_type == "lp":
        p2 = lax.reduce_window(jnp.square(data), 0.0, lax.add, window, strides, pads)
        return jnp.sqrt(p2)
    raise ValueError(f"unknown pool_type {pool_type}")


@register()
def adaptive_avg_pooling2d(data, output_size=1):
    """Reference: src/operator/contrib/adaptive_avg_pooling.cc."""
    if isinstance(output_size, int):
        output_size = (output_size, output_size)
    n, c, h, w = data.shape
    oh, ow = output_size
    x = data.reshape(n, c, oh, h // oh, ow, w // ow)
    return x.mean(axis=(3, 5))


# ---------------------------------------------------------- activations ---

@register()
def activation(data, act_type="relu"):
    """Reference: src/operator/nn/activation-inl.h."""
    if act_type == "relu":
        return jnp.maximum(data, 0)
    if act_type == "sigmoid":
        return jax.nn.sigmoid(data)
    if act_type == "tanh":
        return jnp.tanh(data)
    if act_type == "softrelu":
        return jax.nn.softplus(data)
    if act_type == "softsign":
        return data / (1 + jnp.abs(data))
    raise ValueError(f"unknown act_type {act_type}")


@register()
def leaky_relu(data, gamma=None, act_type="leaky", slope=0.25,
               lower_bound=0.125, upper_bound=0.334):
    """Reference: src/operator/leaky_relu-inl.h (leaky/prelu/elu/selu/gelu/rrelu)."""
    if act_type == "leaky":
        return jnp.where(data > 0, data, slope * data)
    if act_type == "prelu":
        g = gamma
        if g.ndim < data.ndim and g.ndim == 1:
            g = g.reshape((1, -1) + (1,) * (data.ndim - 2))
        return jnp.where(data > 0, data, g * data)
    if act_type == "elu":
        return jnp.where(data > 0, data, slope * jnp.expm1(data))
    if act_type == "selu":
        alpha, scale = 1.6732632423543772, 1.0507009873554805
        return scale * jnp.where(data > 0, data, alpha * jnp.expm1(data))
    if act_type == "gelu":
        return jax.nn.gelu(data, approximate=False)
    if act_type == "rrelu":
        mid = (lower_bound + upper_bound) / 2.0
        return jnp.where(data > 0, data, mid * data)
    raise ValueError(f"unknown act_type {act_type}")


@register()
def softmax(data, length=None, axis=-1, temperature=None, use_length=False,
            dtype=None):
    """Reference: src/operator/nn/softmax.cc — optional length masking
    (`use_length`), temperature, and output `dtype` (the reference
    accumulates in fp32 when dtype='float32' on half inputs; under XLA
    the jax.nn.softmax reduction is already fp32-accumulated, so dtype
    only selects the output type)."""
    if dtype is not None:
        data = data.astype(jnp.dtype(dtype))
    if temperature is not None and temperature != 1.0:
        data = data / temperature
    if length is not None and not use_length:
        # the reference softmax.cc CHECKs use_length when length is given;
        # silently unmasking would be a loud-data/quiet-bug situation
        raise ValueError("softmax: `length` provided without "
                         "use_length=True")
    if length is not None:
        pos = jnp.arange(data.shape[axis])
        shape = [1] * data.ndim
        shape[axis] = data.shape[axis]
        mask = pos.reshape(shape) < jnp.expand_dims(length, axis=tuple(
            range(length.ndim, data.ndim)))
        data = jnp.where(mask, data, -jnp.inf)
        out = jax.nn.softmax(data, axis=axis)
        return jnp.where(mask, out, 0.0)
    return jax.nn.softmax(data, axis=axis)


@register()
def log_softmax(data, axis=-1, temperature=None, dtype=None):
    """log(softmax(x)) along ``axis`` with optional temperature, computed
    stably (reference: softmax.cc log_softmax)."""
    if dtype is not None:
        data = data.astype(jnp.dtype(dtype))
    if temperature is not None and temperature != 1.0:
        data = data / temperature
    return jax.nn.log_softmax(data, axis=axis)


@register()
def softmin(data, axis=-1):
    """softmax of -x along ``axis`` (reference: softmax.cc softmin)."""
    return jax.nn.softmax(-data, axis=axis)


# ---------------------------------------------------------------- norms ---

@register()
def batch_norm(data, gamma, beta, moving_mean, moving_var, eps=1e-3,
               momentum=0.9, fix_gamma=True, use_global_stats=False,
               output_mean_var=False, axis=1, use_batch_stats=None):
    """Functional BatchNorm (reference: src/operator/nn/batch_norm.cc).

    Running-stat mutation is done by the caller (Gluon layer swap-on-write
    / Executor aux write-back), keeping this body pure/traceable.
    ``use_batch_stats`` None follows the ambient autograd train mode like
    the reference op's is_train flag (outside autograd.record the op
    normalizes with the moving statistics); True/False force it.
    """
    if use_batch_stats is None:
        from .. import autograd as _ag

        use_batch_stats = _ag.is_training()
    ax = tuple(i for i in range(data.ndim) if i != axis % data.ndim)
    bshape = [1] * data.ndim
    bshape[axis] = data.shape[axis]
    if fix_gamma:
        gamma = jnp.ones_like(gamma)
    # half-precision inputs: accumulate statistics in fp32 (bf16 variance
    # has ~3 significant digits — unusable for rsqrt), output back in the
    # input dtype; this is cuDNN's CUDNN_BATCHNORM_SPATIAL fp32-stat
    # behavior the reference relies on for fp16 training
    half = data.dtype in (jnp.bfloat16, jnp.float16)
    xf = data.astype(jnp.float32) if half else data
    if use_batch_stats and not use_global_stats:
        mean = jnp.mean(xf, axis=ax)
        var = jnp.var(xf, axis=ax)
    else:
        mean = moving_mean.astype(xf.dtype)
        var = moving_var.astype(xf.dtype)
    inv = lax.rsqrt(var + eps)
    out = (xf - mean.reshape(bshape)) * inv.reshape(bshape) * \
        gamma.astype(xf.dtype).reshape(bshape) + \
        beta.astype(xf.dtype).reshape(bshape)
    if half:
        out = out.astype(data.dtype)
    if output_mean_var:
        return out, mean, var
    return out


@jax.custom_vjp
def stored_residual(y):
    """``y`` itself; under differentiation, what a consumer keeps of it
    for its backward is ``y`` as a stored array, not the chain that made
    it.

    A norm's output feeds a product whose weight gradient is
    ``cotangent^T y``. XLA:TPU fuses that product with the optimizer's
    update of the weight, and left alone it clones the whole
    normalise-scale-shift into the fusion as the product's operand: the
    q|k|v gradient of the OPT cell then takes 1.12 ms where it takes 0.67
    with ``y`` stored (PERF.md section 5). The forward rule returns ``y``
    behind an ``optimization_barrier``; the cotangent passes through
    untouched (a plain barrier's transpose would put one on the
    cotangent and cost the dx product its epilogue). A forward-only trace
    sees the identity and lowers as without it. Counted once for each
    output stored in a differentiated trace:
    ``kernels.counters()["norm_out_stored"]``."""
    return y


def _stored_residual_fwd(y):
    from ..kernels import _count

    _count("norm_out_stored")
    return lax.optimization_barrier(y), None


stored_residual.defvjp(_stored_residual_fwd, lambda _, ct: (ct,))


@register()
def layer_norm(data, gamma, beta, axis=-1, eps=1e-5, output_mean_var=False):
    """Reference: src/operator/nn/layer_norm.cc."""
    mean = jnp.mean(data, axis=axis, keepdims=True)
    var = jnp.var(data, axis=axis, keepdims=True)
    out = (data - mean) * lax.rsqrt(var + eps)
    bshape = [1] * data.ndim
    bshape[axis] = data.shape[axis]
    out = stored_residual(out * gamma.reshape(bshape) + beta.reshape(bshape))
    if output_mean_var:
        return out, jnp.squeeze(mean, axis), jnp.squeeze(var, axis)
    return out


@register()
def instance_norm(data, gamma, beta, eps=1e-3):
    """Reference: src/operator/instance_norm.cc."""
    ax = tuple(range(2, data.ndim))
    mean = jnp.mean(data, axis=ax, keepdims=True)
    var = jnp.var(data, axis=ax, keepdims=True)
    out = (data - mean) * lax.rsqrt(var + eps)
    bshape = (1, -1) + (1,) * (data.ndim - 2)
    return out * gamma.reshape(bshape) + beta.reshape(bshape)


@register()
def group_norm(data, gamma, beta, num_groups=1, eps=1e-5):
    """Reference: src/operator/nn/group_norm.cc — gamma/beta are
    PER-GROUP (shape (num_groups,)), applied on the grouped view
    (group_norm-inl.h:163 new_param_shape[1]=num_groups)."""
    n, c = data.shape[:2]
    rest = data.shape[2:]
    x = data.reshape((n, num_groups, c // num_groups) + rest)
    ax = tuple(range(2, x.ndim))
    mean = jnp.mean(x, axis=ax, keepdims=True)
    var = jnp.var(x, axis=ax, keepdims=True)
    x = (x - mean) * lax.rsqrt(var + eps)
    gshape = (1, num_groups) + (1,) * (x.ndim - 2)
    x = x * gamma.reshape(gshape) + beta.reshape(gshape)
    return x.reshape(data.shape)


@register()
def lrn(data, alpha=1e-4, beta=0.75, knorm=2.0, nsize=5):
    """Local response norm (reference: src/operator/nn/lrn.cc)."""
    sq = jnp.square(data)
    half = nsize // 2
    pad = jnp.pad(sq, ((0, 0), (half, half), (0, 0), (0, 0)))
    acc = sum(pad[:, i:i + data.shape[1]] for i in range(nsize))
    return data / jnp.power(knorm + alpha / nsize * acc, beta)


# --------------------------------------------------------------- dropout --

@register()
def dropout(data, p=0.5, mode="training", axes=(), cudnn_off=False):
    """Reference: src/operator/nn/dropout-inl.h. Keys come from the ambient
    key provider (mxnet_tpu.random) so this stays pure under jit tracing."""
    from .. import autograd, random as mxrandom

    if p == 0 or (mode == "training" and not autograd.is_training()):
        return data
    key = mxrandom.next_key()
    shape = data.shape
    if axes:
        shape = tuple(1 if i in axes else s for i, s in enumerate(data.shape))
    keep = 1.0 - p
    mask = jax.random.bernoulli(key, keep, shape)
    return jnp.where(mask, data / keep, 0.0).astype(data.dtype)


# ------------------------------------------------------------ embedding ---

@register()
def embedding(data, weight, input_dim=0, output_dim=0, dtype="float32",
              sparse_grad=False):
    """Reference: src/operator/tensor/indexing_op.h (Embedding)."""
    return jnp.take(weight, data.astype(jnp.int32), axis=0)


# --------------------------------------------------------------- losses ---

@register()
def softmax_cross_entropy(data, label):
    """Reference: src/operator/loss_binary_op.cc."""
    return -jnp.sum(pick(jax.nn.log_softmax(data, axis=-1), label, axis=-1))


@register()
def softmax_output(data, label, grad_scale=1.0, ignore_label=-1.0,
                   multi_output=False, use_ignore=False, preserve_shape=False,
                   normalization="null", out_grad=False, smooth_alpha=0.0):
    """Legacy SoftmaxOutput op: forward = softmax (reference:
    src/operator/softmax_output.cc). The custom backward (y - label) is
    delivered through make_loss-style usage in Module; here forward only —
    Module wires the CE loss explicitly."""
    return jax.nn.softmax(data, axis=-1 if not multi_output else 1)


@register()
def make_loss(data, grad_scale=1.0, valid_thresh=0.0, normalization="null"):
    """Mark a symbol as a loss head: forward is identity, backward seeds
    gradient grad_scale (reference: make_loss.cc)."""
    return data


# --------------------------------------------------------------- sequence -

def _seq_mask(data, sequence_length, use_sequence_length, value, time_major=True):
    # data: (seq, batch, ...) when time_major
    if not use_sequence_length or sequence_length is None:
        return data
    t = data.shape[0]
    pos = jnp.arange(t)[:, None]
    mask = pos < sequence_length[None, :]
    mask = mask.reshape(mask.shape + (1,) * (data.ndim - 2))
    return jnp.where(mask, data, value)


@register()
def sequence_mask(data, sequence_length=None, use_sequence_length=False,
                  value=0.0, axis=0):
    """Reference: src/operator/sequence_mask.cc."""
    if axis == 1:
        data = jnp.swapaxes(data, 0, 1)
    out = _seq_mask(data, sequence_length, use_sequence_length, value)
    if axis == 1:
        out = jnp.swapaxes(out, 0, 1)
    return out


@register()
def sequence_last(data, sequence_length=None, use_sequence_length=False, axis=0):
    """Reference: src/operator/sequence_last.cc."""
    if axis == 1:
        data = jnp.swapaxes(data, 0, 1)
    if not use_sequence_length or sequence_length is None:
        out = data[-1]
    else:
        idx = (sequence_length - 1).astype(jnp.int32)
        out = jnp.take_along_axis(
            data, idx.reshape((1, -1) + (1,) * (data.ndim - 2)), axis=0)[0]
    return out


@register()
def sequence_reverse(data, sequence_length=None, use_sequence_length=False, axis=0):
    """Reference: src/operator/sequence_reverse.cc."""
    if not use_sequence_length or sequence_length is None:
        return jnp.flip(data, axis=0)
    t = data.shape[0]
    pos = jnp.arange(t)[:, None]
    rev_idx = jnp.where(pos < sequence_length[None, :],
                        sequence_length[None, :] - 1 - pos, pos)
    return jnp.take_along_axis(
        data, rev_idx.reshape(rev_idx.shape + (1,) * (data.ndim - 2)).astype(jnp.int32),
        axis=0)


@register()
def slice_channel(data, num_outputs, axis=1, squeeze_axis=False):
    """Alias of split: partition ``axis`` into num_outputs parts
    (reference: slice_channel.cc SliceChannel)."""
    parts = jnp.split(data, num_outputs, axis=axis)
    if squeeze_axis:
        parts = [jnp.squeeze(p, axis=axis) for p in parts]
    return tuple(parts)


# -------------------------------------------------------------- upsample --

@register()
def upsampling(data, scale=2, sample_type="nearest", num_args=1):
    """Reference: src/operator/nn/upsampling.cc (nearest)."""
    n, c, h, w = data.shape
    x = data.reshape(n, c, h, 1, w, 1)
    x = jnp.broadcast_to(x, (n, c, h, scale, w, scale))
    return x.reshape(n, c, h * scale, w * scale)


@register()
def bilinear_resize2d(data, height=None, width=None, scale_height=None,
                      scale_width=None, mode="size", align_corners=True):
    """Reference: src/operator/contrib/bilinear_resize.cc."""
    n, c, h, w = data.shape
    oh = height if height else int(h * scale_height)
    ow = width if width else int(w * scale_width)
    return jax.image.resize(data, (n, c, oh, ow), method="linear")


# ------------------------------------------------------------------ rnn ---

@register()
def rnn(data, parameters, state, state_cell=None, state_size=0, num_layers=1,
        mode="lstm", bidirectional=False, p=0.0, state_outputs=True,
        projection_size=None, sequence_length=None, use_sequence_length=False,
        lstm_state_clip_min=None, lstm_state_clip_max=None,
        lstm_state_clip_nan=False):
    """Fused multi-layer RNN/LSTM/GRU (reference: src/operator/rnn-inl.h,
    cuDNN path rnn-inl.h:447-482). TPU-native design: one ``lax.scan`` per
    layer/direction so XLA pipelines the time loop; parameters use the
    cuDNN-compatible packed layout (reference rnn_impl.h) for checkpoint
    interop: per layer/direction [W_i, W_h] then all biases [b_i, b_h].
    data: (seq_len, batch, input). state: (L*D, batch, H).
    """
    seq_len, batch, input_size = data.shape
    H = state_size
    D = 2 if bidirectional else 1
    ngates = {"rnn_relu": 1, "rnn_tanh": 1, "lstm": 4, "gru": 3}[mode]

    # unpack cuDNN-layout parameter vector
    offset = 0

    def take(n, shape):
        nonlocal offset
        w = lax.dynamic_slice(parameters, (offset,), (n,)).reshape(shape)
        offset += n
        return w

    Wi, Wh = [], []
    for layer in range(num_layers):
        for d in range(D):
            in_sz = input_size if layer == 0 else H * D
            Wi.append(take(ngates * H * in_sz, (ngates * H, in_sz)))
            Wh.append(take(ngates * H * H, (ngates * H, H)))
    bi, bh = [], []
    for layer in range(num_layers):
        for d in range(D):
            bi.append(take(ngates * H, (ngates * H,)))
            bh.append(take(ngates * H, (ngates * H,)))

    def cell_step(mode, x, h, c, wi, wh, bi_, bh_):
        gates = x @ wi.T + bi_ + h @ wh.T + bh_
        if mode == "lstm":
            i, f, g, o = jnp.split(gates, 4, axis=-1)
            i, f, o = jax.nn.sigmoid(i), jax.nn.sigmoid(f), jax.nn.sigmoid(o)
            g = jnp.tanh(g)
            c_new = f * c + i * g
            if lstm_state_clip_min is not None:
                c_new = jnp.clip(c_new, lstm_state_clip_min, lstm_state_clip_max)
            h_new = o * jnp.tanh(c_new)
            return h_new, c_new
        if mode == "gru":
            # mxnet/cudnn gru: gates order r, z, n
            xr, xz, xn = jnp.split(x @ wi.T + bi_, 3, axis=-1)
            hr, hz, hn = jnp.split(h @ wh.T + bh_, 3, axis=-1)
            r = jax.nn.sigmoid(xr + hr)
            z = jax.nn.sigmoid(xz + hz)
            n = jnp.tanh(xn + r * hn)
            h_new = (1 - z) * n + z * h
            return h_new, c
        act = jnp.tanh if mode == "rnn_tanh" else lambda v: jnp.maximum(v, 0)
        h_new = act(gates)
        return h_new, c

    h0 = state
    c0 = state_cell if state_cell is not None else jnp.zeros_like(state)
    x = data
    h_outs, c_outs = [], []
    idx = 0
    for layer in range(num_layers):
        dir_outs = []
        for d in range(D):
            wi, wh, bi_, bh_ = Wi[idx], Wh[idx], bi[idx], bh[idx]
            hd, cd = h0[idx], c0[idx]
            xs = x if d == 0 else jnp.flip(x, axis=0)

            def step(carry, xt, wi=wi, wh=wh, bi_=bi_, bh_=bh_):
                h, c = carry
                h2, c2 = cell_step(mode, xt, h, c, wi, wh, bi_, bh_)
                return (h2, c2), h2

            (hT, cT), ys = lax.scan(step, (hd, cd), xs)
            if d == 1:
                ys = jnp.flip(ys, axis=0)
            dir_outs.append(ys)
            h_outs.append(hT)
            c_outs.append(cT)
            idx += 1
        x = dir_outs[0] if D == 1 else jnp.concatenate(dir_outs, axis=-1)
        if p > 0 and layer < num_layers - 1:
            from .. import autograd, random as mxrandom

            if autograd.is_training():
                key = mxrandom.next_key()
                mask = jax.random.bernoulli(key, 1 - p, x.shape)
                x = jnp.where(mask, x / (1 - p), 0.0).astype(x.dtype)
    outputs = [x]
    if state_outputs:
        outputs.append(jnp.stack(h_outs))
        if mode == "lstm":
            outputs.append(jnp.stack(c_outs))
    return tuple(outputs) if len(outputs) > 1 else outputs[0]


@register()
def ctc_loss(data, label, data_lengths=None, label_lengths=None,
             use_data_lengths=False, use_label_lengths=False,
             blank_label="first"):
    """Connectionist temporal classification loss (reference:
    src/operator/nn/ctc_loss.cc over warpctc). The alpha recursion is a
    ``lax.scan`` over time — TPU-friendly log-space dynamic programming,
    differentiable end-to-end through JAX autodiff (no hand-written
    gradient kernel needed). data: (T, N, C) activations (softmax applied
    internally), label: (N, L). ``blank_label='first'`` reserves class 0
    for blank (labels 1..C-1, padding 0); ``'last'`` reserves class C-1
    (labels 0..C-2, padding -1) — ctc_loss-inl.h:174-186.
    """
    if blank_label not in ("first", "last"):
        raise ValueError(
            f"blank_label must be 'first' or 'last', got {blank_label!r}")
    T, N, C = data.shape
    L = label.shape[1]
    blank = 0 if blank_label == "first" else C - 1
    pad = 0 if blank_label == "first" else -1
    logp = jax.nn.log_softmax(data, axis=-1)
    lab = label.astype(jnp.int32)
    valid = lab != pad
    # pack non-pad labels contiguously (ctc_loss-inl.h
    # LabelTensorToPackedVector): a stable sort on the pad mask moves
    # valid entries to the front without dynamic shapes
    order = jnp.argsort(jnp.logical_not(valid), axis=1, stable=True)
    lab = jnp.take_along_axis(lab, order, axis=1)
    valid = jnp.take_along_axis(valid, order, axis=1)
    # extended label sequence with interleaved blanks: length 2L+1
    ext = jnp.full((N, 2 * L + 1), blank, dtype=jnp.int32)
    ext = ext.at[:, 1::2].set(jnp.where(valid, lab, blank))
    neg_inf = -1e30
    alpha0 = jnp.full((N, 2 * L + 1), neg_inf)
    alpha0 = alpha0.at[:, 0].set(logp[0, :, blank])
    alpha0 = alpha0.at[:, 1].set(
        jnp.take_along_axis(logp[0], ext[:, 1:2], axis=1)[:, 0])
    same_as_prev2 = jnp.concatenate(
        [jnp.ones((N, 2), dtype=bool), ext[:, 2:] == ext[:, :-2]], axis=1)

    def step(alpha, logp_t):
        a0 = alpha
        a1 = jnp.concatenate([jnp.full((N, 1), neg_inf), alpha[:, :-1]],
                             axis=1)
        a2 = jnp.concatenate([jnp.full((N, 2), neg_inf), alpha[:, :-2]],
                             axis=1)
        a2 = jnp.where(same_as_prev2, neg_inf, a2)
        m = jnp.maximum(jnp.maximum(a1, a2), a0)
        new = m + jnp.log(jnp.exp(a0 - m) + jnp.exp(a1 - m)
                          + jnp.exp(a2 - m))
        emit = jnp.take_along_axis(logp_t, ext, axis=1)
        return new + emit, new + emit

    _, alphas = lax.scan(step, alpha0, logp[1:])
    alphas = jnp.concatenate([alpha0[None], alphas], axis=0)  # (T, N, 2L+1)
    if use_data_lengths and data_lengths is not None:
        t_idx = (data_lengths.astype(jnp.int32) - 1)
    else:
        t_idx = jnp.full((N,), T - 1, dtype=jnp.int32)
    final = jnp.take_along_axis(
        alphas, t_idx[None, :, None], axis=0)[0]  # (N, 2L+1)
    if use_label_lengths and label_lengths is not None:
        ll = label_lengths.astype(jnp.int32)
    else:
        ll = jnp.sum(valid.astype(jnp.int32), axis=1)
        if blank_label == "first":
            # all-zero rows are ambiguous in 'first' mode (0 is both pad
            # and blank); the reference treats them as full-length labels.
            # In 'last' mode pad is -1, so ll==0 really means empty target.
            ll = jnp.where(ll == 0, L, ll)
    last = jnp.take_along_axis(final, (2 * ll)[:, None], axis=1)[:, 0]
    prev = jnp.take_along_axis(final, jnp.maximum(2 * ll - 1, 0)[:, None],
                               axis=1)[:, 0]
    # empty target: the only path is all-blank — alpha[T-1, 0] alone
    # (otherwise prev would double-count position 0)
    prev = jnp.where(ll > 0, prev, neg_inf)
    m = jnp.maximum(last, prev)
    return -(m + jnp.log(jnp.exp(last - m) + jnp.exp(prev - m)))


def rnn_param_size(num_layers, input_size, state_size, bidirectional, mode):
    """Total packed parameter count (reference: rnn-inl.h GetParamSize)."""
    ngates = {"rnn_relu": 1, "rnn_tanh": 1, "lstm": 4, "gru": 3}[mode]
    D = 2 if bidirectional else 1
    H = state_size
    size = 0
    for layer in range(num_layers):
        in_sz = input_size if layer == 0 else H * D
        size += D * ngates * H * (in_sz + H + 2)
    return size


@register(name="flash_attention")
def flash_attention_op(query, key, value, sm_scale=None, causal=False):
    """Blockwise Pallas attention over (B, H, S, D) (see
    mxnet_tpu/ops/flash_attention.py; NEW capability vs the reference —
    SURVEY §5.7)."""
    from ..ops.flash_attention import flash_attention

    return flash_attention(query, key, value, sm_scale=sm_scale,
                           causal=causal)
