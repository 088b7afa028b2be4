"""ResNet-50 v1 in plain jax.numpy: the reference of configuration
``resnet50_v1``.

He et al. 2015 (arXiv:1512.03385, Table 1) as MXNet's Gluon model zoo
builds it (python/mxnet/gluon/model_zoo/vision/resnet.py, BottleneckV1):
the stride sits on the block's first 1x1 convolution, the 1x1
convolutions carry a bias, the 3x3 and the projection do not, BatchNorm
has momentum 0.9 and eps 1e-5 and keeps a biased running variance.
Convolution weights are HWIO, the classifier's (in, out). Nothing of the
program is imported.

Leaves are listed in the order in which the network is built (stem, then
stage by stage and block by block, then the classifier), which is the
order ``benchmarks/models/resnet50_v1.py`` relies on.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from refcommon import Prec, softmax_xent

BN_MOMENTUM = 0.9
BN_EPS = 1e-5


def _blocks(cfg):
    """(stage, block, in_channels, channels, stride, projects)."""
    cin = cfg["stem_channels"]
    for si, (n, ch) in enumerate(zip(cfg["layers"], cfg["channels"])):
        for bi in range(n):
            stride = (1 if si == 0 else 2) if bi == 0 else 1
            yield si + 1, bi, cin, ch, stride, bi == 0
            cin = ch


def leaf_shapes(cfg):
    """Ordered {leaf: (shape, kind)}; kind is one of conv, bias, gamma,
    beta, mean, var, fc. ``mean``/``var`` are BatchNorm's running
    statistics: state, not trained."""
    out = {}

    def bn(name, c):
        out[name + ".gamma"] = ((c,), "gamma")
        out[name + ".beta"] = ((c,), "beta")
        out[name + ".mean"] = ((c,), "mean")
        out[name + ".var"] = ((c,), "var")

    c0 = cfg["stem_channels"]
    out["stem.w"] = ((7, 7, 3, c0), "conv")
    bn("stem.bn", c0)
    for s, b, cin, ch, _, proj in _blocks(cfg):
        p = f"s{s}.b{b}"
        mid = ch // 4
        out[p + ".c1.w"] = ((1, 1, cin, mid), "conv")
        out[p + ".c1.b"] = ((mid,), "bias")
        bn(p + ".n1", mid)
        out[p + ".c2.w"] = ((3, 3, mid, mid), "conv")
        bn(p + ".n2", mid)
        out[p + ".c3.w"] = ((1, 1, mid, ch), "conv")
        out[p + ".c3.b"] = ((ch,), "bias")
        bn(p + ".n3", ch)
        if proj:
            out[p + ".proj.w"] = ((1, 1, cin, ch), "conv")
            bn(p + ".projn", ch)
    out["fc.w"] = ((cfg["channels"][-1], cfg["classes"]), "fc")
    out["fc.b"] = ((cfg["classes"],), "bias")
    return out


def init(cfg, key):
    """(params, aux) in float32 from a PRNG key: He-normal convolutions,
    unit gammas, zero betas and biases, running statistics (0, 1)."""
    params, aux = {}, {}
    for i, (name, (shape, kind)) in enumerate(leaf_shapes(cfg).items()):
        if kind in ("conv", "fc"):
            fan_in = 1
            for d in shape[:-1]:
                fan_in *= d
            std = (2.0 / fan_in) ** 0.5 if kind == "conv" \
                else (1.0 / fan_in) ** 0.5
            params[name] = std * jax.random.normal(
                jax.random.fold_in(key, i), shape, jnp.float32)
        elif kind == "gamma":
            params[name] = jnp.ones(shape, jnp.float32)
        elif kind in ("beta", "bias"):
            params[name] = jnp.zeros(shape, jnp.float32)
        elif kind == "mean":
            aux[name] = jnp.zeros(shape, jnp.float32)
        else:
            aux[name] = jnp.ones(shape, jnp.float32)
    return params, aux


def _bn(x, name, params, aux, new_aux, train, prec):
    xf = x.astype(jnp.float32)
    if train:
        mean = jnp.mean(xf, axis=(0, 1, 2))
        var = jnp.var(xf, axis=(0, 1, 2))
        new_aux[name + ".mean"] = BN_MOMENTUM * aux[name + ".mean"] \
            + (1.0 - BN_MOMENTUM) * lax.stop_gradient(mean)
        new_aux[name + ".var"] = BN_MOMENTUM * aux[name + ".var"] \
            + (1.0 - BN_MOMENTUM) * lax.stop_gradient(var)
    else:
        mean, var = aux[name + ".mean"], aux[name + ".var"]
    out = (xf - mean) * lax.rsqrt(var + BN_EPS) * params[name + ".gamma"] \
        + params[name + ".beta"]
    return prec.store(out)


def _block(x, p, stride, proj, params, aux, train, prec):
    """One bottleneck; returns (y, this block's new running statistics)."""
    na = {}
    y = prec.store(prec.conv(x, params[p + ".c1.w"], stride, 0)
                   + params[p + ".c1.b"].astype(prec.act))
    y = jax.nn.relu(_bn(y, p + ".n1", params, aux, na, train, prec))
    y = prec.conv(y, params[p + ".c2.w"], 1, 1)
    y = jax.nn.relu(_bn(y, p + ".n2", params, aux, na, train, prec))
    y = prec.store(prec.conv(y, params[p + ".c3.w"], 1, 0)
                   + params[p + ".c3.b"].astype(prec.act))
    y = _bn(y, p + ".n3", params, aux, na, train, prec)
    if proj:
        r = prec.conv(x, params[p + ".proj.w"], stride, 0)
        r = _bn(r, p + ".projn", params, aux, na, train, prec)
    else:
        r = x
    return jax.nn.relu(prec.store(y + r)), na


def forward(cfg, params, aux, x, train, precision="float32"):
    """Logits (float32) and the new running statistics for NHWC images.
    Each bottleneck is rematerialised in the backward pass so that a
    float32 batch of the timed size fits one chip."""
    prec = Prec(precision)
    new_aux = {}
    x = prec.store(x)
    y = prec.conv(x, params["stem.w"], 2, 3)
    y = jax.nn.relu(_bn(y, "stem.bn", params, aux, new_aux, train, prec))
    y = lax.reduce_window(y, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                          [(0, 0), (1, 1), (1, 1), (0, 0)])
    for s, b, _, _, stride, proj in _blocks(cfg):
        p = f"s{s}.b{b}"
        keys = [k for k in list(params) + list(aux) if k.startswith(p + ".")]
        sub_p = {k: params[k] for k in keys if k in params}
        sub_a = {k: aux[k] for k in keys if k in aux}

        def run(y, sub_p, sub_a, p=p, stride=stride, proj=proj):
            return _block(y, p, stride, proj, sub_p, sub_a, train, prec)

        y, na = jax.checkpoint(run)(y, sub_p, sub_a)
        new_aux.update(na)
    y = prec.store(jnp.mean(y.astype(jnp.float32), axis=(1, 2)))
    logits = prec.matmul(y, params["fc.w"]).astype(jnp.float32) \
        + params["fc.b"]
    return logits, (new_aux if train else dict(aux))


def loss(cfg, params, aux, batch, precision="float32"):
    """(mean cross-entropy, new running statistics) of one training batch
    ``(images NHWC float32, labels)``."""
    x, y = batch
    logits, new_aux = forward(cfg, params, aux, x, True, precision)
    return softmax_xent(logits, y.astype(jnp.int32)), new_aux


def score(cfg, params, aux, x, precision="float32"):
    """Inference logits, normalising with the running statistics."""
    return forward(cfg, params, aux, x, False, precision)[0]
