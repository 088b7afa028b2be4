"""Flat C ABI tests (native/c_api.cc over mxnet_tpu/c_bridge.py).

Reference surface: include/mxnet/c_api.h + c_predict_api.h; the reference
exercises these through its frontend bindings, here we drive them through
ctypes exactly as an external C consumer would (plus one genuinely
standalone compiled C program for the deploy story).
"""
import ctypes
import os
import shutil
import struct
import subprocess
import sys

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu._native import build_c_api

i64 = ctypes.c_int64


@pytest.fixture(scope="module")
def capi():
    so = build_c_api()
    if so is None:
        pytest.skip("no toolchain to build libmxnet_c.so")
    lib = ctypes.CDLL(so)
    vp, c_int, u32 = ctypes.c_void_p, ctypes.c_int, ctypes.c_uint32
    lib.MXGetLastError.restype = ctypes.c_char_p
    lib.MXGetVersion.argtypes = [ctypes.POINTER(c_int)]
    lib.MXNDArrayCreate.argtypes = [ctypes.POINTER(i64), c_int, c_int,
                                    ctypes.POINTER(vp)]
    lib.MXNDArrayFree.argtypes = [vp]
    lib.MXNDArrayGetShape.argtypes = [vp, ctypes.POINTER(c_int),
                                      ctypes.POINTER(i64)]
    lib.MXNDArrayGetDType.argtypes = [vp, ctypes.POINTER(c_int)]
    lib.MXNDArraySyncCopyFromCPU.argtypes = [vp, vp, ctypes.c_size_t]
    lib.MXNDArraySyncCopyToCPU.argtypes = [vp, vp, ctypes.c_size_t]
    lib.MXImperativeInvoke.argtypes = [
        ctypes.c_char_p, c_int, ctypes.POINTER(vp), ctypes.POINTER(c_int),
        ctypes.POINTER(ctypes.POINTER(vp)), c_int,
        ctypes.POINTER(ctypes.c_char_p), ctypes.POINTER(ctypes.c_char_p)]
    lib.MXPredCreate.argtypes = [
        ctypes.c_char_p, vp, ctypes.c_size_t, c_int, c_int, u32,
        ctypes.POINTER(ctypes.c_char_p), ctypes.POINTER(u32),
        ctypes.POINTER(i64), ctypes.POINTER(vp)]
    lib.MXPredSetInput.argtypes = [vp, ctypes.c_char_p,
                                   ctypes.POINTER(ctypes.c_float), u32]
    lib.MXPredForward.argtypes = [vp]
    lib.MXPredGetOutputShape.argtypes = [vp, u32, ctypes.POINTER(c_int),
                                         ctypes.POINTER(i64)]
    lib.MXPredGetOutput.argtypes = [vp, u32,
                                    ctypes.POINTER(ctypes.c_float), u32]
    lib.MXPredFree.argtypes = [vp]
    return lib


def _err(lib):
    return lib.MXGetLastError().decode()


def test_version_and_error_empty(capi):
    v = ctypes.c_int()
    assert capi.MXGetVersion(ctypes.byref(v)) == 0
    assert v.value >= 10000


def test_ndarray_roundtrip(capi):
    shape = (i64 * 2)(3, 4)
    h = ctypes.c_void_p()
    assert capi.MXNDArrayCreate(shape, 2, 0, ctypes.byref(h)) == 0, _err(capi)
    ndim = ctypes.c_int()
    out_shape = (i64 * 8)()
    assert capi.MXNDArrayGetShape(h, ctypes.byref(ndim), out_shape) == 0
    assert ndim.value == 2 and tuple(out_shape[:2]) == (3, 4)
    dt = ctypes.c_int()
    assert capi.MXNDArrayGetDType(h, ctypes.byref(dt)) == 0
    assert dt.value == 0  # float32
    data = onp.arange(12, dtype="f").reshape(3, 4)
    assert capi.MXNDArraySyncCopyFromCPU(
        h, data.ctypes.data_as(ctypes.c_void_p), data.nbytes) == 0, _err(capi)
    back = onp.zeros_like(data)
    assert capi.MXNDArraySyncCopyToCPU(
        h, back.ctypes.data_as(ctypes.c_void_p), back.nbytes) == 0, _err(capi)
    onp.testing.assert_array_equal(back, data)
    assert capi.MXNDArrayFree(h) == 0


def test_imperative_invoke(capi):
    def make(vals):
        a = onp.asarray(vals, dtype="f")
        shape = (i64 * a.ndim)(*a.shape)
        h = ctypes.c_void_p()
        assert capi.MXNDArrayCreate(shape, a.ndim, 0, ctypes.byref(h)) == 0
        assert capi.MXNDArraySyncCopyFromCPU(
            h, a.ctypes.data_as(ctypes.c_void_p), a.nbytes) == 0
        return h, a

    ha, a = make([[1.0, 2.0], [3.0, 4.0]])
    hb, b = make([[10.0, 20.0], [30.0, 40.0]])
    ins = (ctypes.c_void_p * 2)(ha, hb)
    nout = ctypes.c_int()
    outs = ctypes.POINTER(ctypes.c_void_p)()
    assert capi.MXImperativeInvoke(
        b"broadcast_add", 2, ins, ctypes.byref(nout), ctypes.byref(outs),
        0, None, None) == 0, _err(capi)
    assert nout.value == 1
    res = onp.zeros((2, 2), dtype="f")
    assert capi.MXNDArraySyncCopyToCPU(
        outs[0], res.ctypes.data_as(ctypes.c_void_p), res.nbytes) == 0
    onp.testing.assert_allclose(res, a + b)
    assert capi.MXNDArrayWaitAll() == 0
    capi.MXNDArrayFree(ha)
    capi.MXNDArrayFree(hb)


def test_imperative_invoke_with_params(capi):
    a = onp.arange(6, dtype="f").reshape(2, 3)
    shape = (i64 * 2)(2, 3)
    h = ctypes.c_void_p()
    capi.MXNDArrayCreate(shape, 2, 0, ctypes.byref(h))
    capi.MXNDArraySyncCopyFromCPU(
        h, a.ctypes.data_as(ctypes.c_void_p), a.nbytes)
    ins = (ctypes.c_void_p * 1)(h)
    nout = ctypes.c_int()
    outs = ctypes.POINTER(ctypes.c_void_p)()
    keys = (ctypes.c_char_p * 1)(b"shape")
    vals = (ctypes.c_char_p * 1)(b"(3, 2)")
    assert capi.MXImperativeInvoke(
        b"reshape", 1, ins, ctypes.byref(nout), ctypes.byref(outs),
        1, keys, vals) == 0, _err(capi)
    ndim = ctypes.c_int()
    oshape = (i64 * 8)()
    capi.MXNDArrayGetShape(outs[0], ctypes.byref(ndim), oshape)
    assert tuple(oshape[:2]) == (3, 2)
    capi.MXNDArrayFree(h)


def test_invoke_unknown_op_sets_error(capi):
    nout = ctypes.c_int()
    outs = ctypes.POINTER(ctypes.c_void_p)()
    rc = capi.MXImperativeInvoke(
        b"definitely_not_an_op", 0, None, ctypes.byref(nout),
        ctypes.byref(outs), 0, None, None)
    assert rc == -1
    assert "definitely_not_an_op" in _err(capi)


@pytest.fixture(scope="module")
def exported_mlp(tmp_path_factory):
    """Export a small trained-ish MLP the way a deploy pipeline would:
    symbol json + reference-format params with arg:/aux: prefixes."""
    root = tmp_path_factory.mktemp("c_predict")
    from mxnet_tpu import sym

    x = sym.Variable("data")
    fc1 = sym.FullyConnected(x, name="fc1", num_hidden=16,
                             weight=sym.Variable("fc1_weight"),
                             bias=sym.Variable("fc1_bias"))
    act = sym.Activation(fc1, act_type="relu")
    fc2 = sym.FullyConnected(act, name="fc2", num_hidden=3,
                             weight=sym.Variable("fc2_weight"),
                             bias=sym.Variable("fc2_bias"))
    out = sym.softmax(fc2)
    rng = onp.random.RandomState(0)
    params = {
        "arg:fc1_weight": nd.array(rng.randn(16, 8).astype("f") * 0.1),
        "arg:fc1_bias": nd.array(rng.randn(16).astype("f") * 0.1),
        "arg:fc2_weight": nd.array(rng.randn(3, 16).astype("f") * 0.1),
        "arg:fc2_bias": nd.array(rng.randn(3).astype("f") * 0.1),
    }
    json_path = os.path.join(root, "mlp-symbol.json")
    params_path = os.path.join(root, "mlp-0000.params")
    with open(json_path, "w") as f:
        f.write(out.tojson())
    nd.save(params_path, params)
    xval = rng.rand(4, 8).astype("f")
    args = {"data": nd.array(xval)}
    args.update({k[4:]: v for k, v in params.items()})
    ex = out.bind(args=args)
    expect = ex.forward(is_train=False)[0].asnumpy()
    return json_path, params_path, xval, expect


def test_c_predict_api(capi, exported_mlp):
    json_path, params_path, xval, expect = exported_mlp
    with open(json_path) as f:
        sym_json = f.read().encode()
    with open(params_path, "rb") as f:
        param_bytes = f.read()
    keys = (ctypes.c_char_p * 1)(b"data")
    indptr = (ctypes.c_uint32 * 2)(0, 2)
    shp = (i64 * 2)(4, 8)
    h = ctypes.c_void_p()
    assert capi.MXPredCreate(
        sym_json, param_bytes, len(param_bytes), 1, 0, 1, keys, indptr,
        shp, ctypes.byref(h)) == 0, _err(capi)
    assert capi.MXPredSetInput(
        h, b"data", xval.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        xval.size) == 0, _err(capi)
    assert capi.MXPredForward(h) == 0, _err(capi)
    ndim = ctypes.c_int()
    oshape = (i64 * 8)()
    assert capi.MXPredGetOutputShape(
        h, 0, ctypes.byref(ndim), oshape) == 0, _err(capi)
    shape = tuple(oshape[:ndim.value])
    assert shape == expect.shape
    res = onp.zeros(shape, dtype="f")
    assert capi.MXPredGetOutput(
        h, 0, res.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        res.size) == 0, _err(capi)
    onp.testing.assert_allclose(res, expect, rtol=1e-5, atol=1e-6)
    assert capi.MXPredFree(h) == 0


C_PROGRAM = r"""
#include <stdio.h>
#include <stdint.h>
#include <string.h>
#include "mxnet_tpu/c_api.h"

int main(void) {
  int version = 0;
  if (MXGetVersion(&version) != 0 || version < 10000) return 1;
  int64_t shape[2] = {2, 3};
  NDArrayHandle h = NULL;
  if (MXNDArrayCreate(shape, 2, 0, &h) != 0) {
    fprintf(stderr, "create: %s\n", MXGetLastError());
    return 2;
  }
  float data[6] = {1, 2, 3, 4, 5, 6};
  if (MXNDArraySyncCopyFromCPU(h, data, sizeof(data)) != 0) return 3;
  NDArrayHandle ins[1] = {h};
  int nout = 0;
  NDArrayHandle* outs = NULL;
  const char* keys[1] = {"shape"};
  const char* vals[1] = {"(3, 2)"};
  if (MXImperativeInvoke("reshape", 1, ins, &nout, &outs, 1, keys, vals)
      != 0) {
    fprintf(stderr, "invoke: %s\n", MXGetLastError());
    return 4;
  }
  int ndim = 0;
  int64_t oshape[MX_MAX_DIM];
  if (MXNDArrayGetShape(outs[0], &ndim, oshape) != 0) return 5;
  if (ndim != 2 || oshape[0] != 3 || oshape[1] != 2) return 6;
  float back[6];
  if (MXNDArraySyncCopyToCPU(outs[0], back, sizeof(back)) != 0) return 7;
  if (memcmp(back, data, sizeof(back)) != 0) return 8;
  MXNDArrayFree(h);
  printf("C_OK\n");
  return 0;
}
"""


def test_standalone_c_program(capi, tmp_path):
    """The deploy story: a plain C program (no Python code) linking
    libmxnet_c drives the runtime end to end."""
    if shutil.which("gcc") is None:
        pytest.skip("no gcc")
    so = build_c_api()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    csrc = tmp_path / "main.c"
    csrc.write_text(C_PROGRAM)
    exe = tmp_path / "drive"
    subprocess.run(
        ["gcc", str(csrc), "-o", str(exe), f"-I{repo}/include",
         so, f"-Wl,-rpath,{os.path.dirname(so)}"],
        check=True, capture_output=True)
    env = dict(os.environ, PYTHONPATH=repo, JAX_PLATFORMS="cpu")
    proc = subprocess.run([str(exe)], env=env, capture_output=True,
                          text=True, timeout=240)
    assert proc.returncode == 0, (proc.stdout, proc.stderr)
    assert "C_OK" in proc.stdout


# ---------------------------------------------------------------------------
# Training surface: symbol compose + simple bind + forward/backward + kvstore
# (reference: c_api_symbolic.cc, c_api_executor.cc:189, MXKVStore*)
# ---------------------------------------------------------------------------

def _train_argtypes(lib):
    vp, c_int, u32 = ctypes.c_void_p, ctypes.c_int, ctypes.c_uint32
    cp = ctypes.c_char_p
    lib.MXSymbolCreateVariable.argtypes = [cp, ctypes.POINTER(vp)]
    lib.MXSymbolCreateAtomicSymbol.argtypes = [
        cp, u32, ctypes.POINTER(cp), ctypes.POINTER(cp), ctypes.POINTER(vp)]
    lib.MXSymbolCompose.argtypes = [vp, cp, u32, ctypes.POINTER(cp),
                                    ctypes.POINTER(vp)]
    lib.MXSymbolCreateFromJSON.argtypes = [cp, ctypes.POINTER(vp)]
    lib.MXSymbolSaveToJSON.argtypes = [vp, ctypes.POINTER(cp)]
    for f in (lib.MXSymbolListArguments, lib.MXSymbolListAuxiliaryStates,
              lib.MXSymbolListOutputs):
        f.argtypes = [vp, ctypes.POINTER(u32),
                      ctypes.POINTER(ctypes.POINTER(cp))]
    lib.MXSymbolFree.argtypes = [vp]
    lib.MXExecutorSimpleBind.argtypes = [
        vp, cp, u32, ctypes.POINTER(cp), ctypes.POINTER(u32),
        ctypes.POINTER(i64), ctypes.POINTER(vp)]
    lib.MXExecutorArgArray.argtypes = [vp, cp, cp, ctypes.POINTER(vp)]
    lib.MXExecutorForward.argtypes = [vp, ctypes.c_int]
    lib.MXExecutorOutputs.argtypes = [vp, ctypes.POINTER(c_int),
                                      ctypes.POINTER(ctypes.POINTER(vp))]
    lib.MXExecutorBackward.argtypes = [vp]
    lib.MXExecutorFree.argtypes = [vp]
    lib.MXKVStoreCreate.argtypes = [cp, ctypes.POINTER(vp)]
    lib.MXKVStoreSetOptimizer.argtypes = [vp, cp, u32, ctypes.POINTER(cp),
                                          ctypes.POINTER(cp)]
    for f in (lib.MXKVStoreInit,):
        f.argtypes = [vp, u32, ctypes.POINTER(c_int), ctypes.POINTER(vp)]
    for f in (lib.MXKVStorePush, lib.MXKVStorePull):
        f.argtypes = [vp, u32, ctypes.POINTER(c_int), ctypes.POINTER(vp),
                      ctypes.c_int]
    lib.MXKVStoreFree.argtypes = [vp]
    return lib


def test_symbol_compose_and_json_roundtrip(capi):
    lib = _train_argtypes(capi)
    vp, u32, cp = ctypes.c_void_p, ctypes.c_uint32, ctypes.c_char_p
    data = vp()
    assert lib.MXSymbolCreateVariable(b"data", ctypes.byref(data)) == 0
    fc = vp()
    keys = (cp * 1)(b"num_hidden")
    vals = (cp * 1)(b"4")
    assert lib.MXSymbolCreateAtomicSymbol(
        b"FullyConnected", 1, keys, vals, ctypes.byref(fc)) == 0, _err(capi)
    args = (vp * 1)(data)
    assert lib.MXSymbolCompose(fc, b"fc", 1, None, args) == 0, _err(capi)
    n = u32()
    names = ctypes.POINTER(cp)()
    assert lib.MXSymbolListArguments(fc, ctypes.byref(n),
                                     ctypes.byref(names)) == 0
    got = sorted(names[i].decode() for i in range(n.value))
    assert got == ["data", "fc_bias", "fc_weight"]
    js = cp()
    assert lib.MXSymbolSaveToJSON(fc, ctypes.byref(js)) == 0
    re = vp()
    assert lib.MXSymbolCreateFromJSON(js.value, ctypes.byref(re)) == 0
    assert lib.MXSymbolListOutputs(re, ctypes.byref(n),
                                   ctypes.byref(names)) == 0
    assert n.value == 1 and names[0].decode() == "fc_output"
    lib.MXSymbolFree(re)
    lib.MXSymbolFree(fc)
    lib.MXSymbolFree(data)


def test_c_training_loop_via_ctypes(capi):
    """The full training story through the flat ABI: compose an MLP,
    simple-bind, forward/backward, kvstore sgd updates — loss drops."""
    lib = _train_argtypes(capi)
    vp, u32, cp, c_int = (ctypes.c_void_p, ctypes.c_uint32, ctypes.c_char_p,
                          ctypes.c_int)
    data = vp(); label = vp()
    lib.MXSymbolCreateVariable(b"data", ctypes.byref(data))
    lib.MXSymbolCreateVariable(b"softmax_label", ctypes.byref(label))
    fc1 = vp()
    lib.MXSymbolCreateAtomicSymbol(b"FullyConnected", 1,
                                   (cp * 1)(b"num_hidden"), (cp * 1)(b"16"),
                                   ctypes.byref(fc1))
    assert lib.MXSymbolCompose(fc1, b"fc1", 1, None,
                               (vp * 1)(data)) == 0, _err(capi)
    act = vp()
    lib.MXSymbolCreateAtomicSymbol(b"Activation", 1, (cp * 1)(b"act_type"),
                                   (cp * 1)(b"relu"), ctypes.byref(act))
    assert lib.MXSymbolCompose(act, b"act", 1, None,
                               (vp * 1)(fc1)) == 0, _err(capi)
    fc2 = vp()
    lib.MXSymbolCreateAtomicSymbol(b"FullyConnected", 1,
                                   (cp * 1)(b"num_hidden"), (cp * 1)(b"2"),
                                   ctypes.byref(fc2))
    assert lib.MXSymbolCompose(fc2, b"fc2", 1, None,
                               (vp * 1)(act)) == 0, _err(capi)
    sm = vp()
    lib.MXSymbolCreateAtomicSymbol(b"SoftmaxOutput", 0, None, None,
                                   ctypes.byref(sm))
    assert lib.MXSymbolCompose(sm, b"softmax", 2, None,
                               (vp * 2)(fc2, label)) == 0, _err(capi)

    B, D = 64, 8
    ikeys = (cp * 2)(b"data", b"softmax_label")
    indptr = (u32 * 3)(0, 2, 3)
    shp = (i64 * 3)(B, D, B)
    ex = vp()
    assert lib.MXExecutorSimpleBind(sm, b"write", 2, ikeys, indptr, shp,
                                    ctypes.byref(ex)) == 0, _err(capi)

    rng = onp.random.RandomState(0)
    X = rng.randn(B, D).astype("f")
    y = (X[:, 0] > 0).astype("f")

    def arr(kind, name):
        h = vp()
        assert lib.MXExecutorArgArray(ex, kind.encode(), name.encode(),
                                      ctypes.byref(h)) == 0, _err(capi)
        return h

    def put(h, a):
        a = onp.ascontiguousarray(a)
        assert capi.MXNDArraySyncCopyFromCPU(
            h, a.ctypes.data_as(vp), a.nbytes) == 0, _err(capi)

    wnames = ["fc1_weight", "fc1_bias", "fc2_weight", "fc2_bias"]
    weights = [arr("arg", n) for n in wnames]
    grads = [arr("grad", n) for n in wnames]
    put(arr("arg", "data"), X)
    put(arr("arg", "softmax_label"), y)
    for h, shape in zip(weights, [(16, D), (16,), (2, 16), (2,)]):
        put(h, (rng.randn(*shape) * 0.1).astype("f"))

    kv = vp()
    assert lib.MXKVStoreCreate(b"local", ctypes.byref(kv)) == 0
    assert lib.MXKVStoreSetOptimizer(
        kv, b"sgd", 1, (cp * 1)(b"learning_rate"),
        (cp * 1)(b"0.01")) == 0, _err(capi)
    kkeys = (c_int * 4)(0, 1, 2, 3)
    assert lib.MXKVStoreInit(kv, 4, kkeys, (vp * 4)(*weights)) == 0, \
        _err(capi)

    def step():
        assert lib.MXExecutorForward(ex, 1) == 0, _err(capi)
        nout = c_int()
        outs = ctypes.POINTER(vp)()
        assert lib.MXExecutorOutputs(ex, ctypes.byref(nout),
                                     ctypes.byref(outs)) == 0
        probs = onp.zeros((B, 2), "f")
        assert capi.MXNDArraySyncCopyToCPU(
            outs[0], probs.ctypes.data_as(vp), probs.nbytes) == 0
        loss = -onp.log(probs[onp.arange(B), y.astype(int)] + 1e-9).mean()
        assert lib.MXExecutorBackward(ex) == 0, _err(capi)
        assert lib.MXKVStorePush(kv, 4, kkeys, (vp * 4)(*grads), 0) == 0
        assert lib.MXKVStorePull(kv, 4, kkeys, (vp * 4)(*weights), 0) == 0
        return loss

    first = step()
    last = None
    for _ in range(25):
        last = step()
    assert last < first * 0.5, (first, last)
    lib.MXKVStoreFree(kv)
    lib.MXExecutorFree(ex)
    for h in weights + grads:
        capi.MXNDArrayFree(h)


C_TRAIN_PROGRAM = r"""
#include <math.h>
#include <stdio.h>
#include <stdlib.h>
#include "mxnet_tpu/c_api.h"

#define B 64
#define D 8
#define H 16
#define CK(x) do { if ((x) != 0) { \
  fprintf(stderr, "%s\n", MXGetLastError()); return 1; } } while (0)

static unsigned lcg = 42u;
static float frand(void) {  /* uniform in [-1, 1) */
  lcg = lcg * 1664525u + 1013904223u;
  return ((lcg >> 8) / 8388608.0f) - 1.0f;
}

int main(void) {
  SymbolHandle data, label, fc1, act, fc2, sm;
  CK(MXSymbolCreateVariable("data", &data));
  CK(MXSymbolCreateVariable("softmax_label", &label));
  const char* kh = "num_hidden"; const char* ka = "act_type";
  const char* v16 = "16"; const char* v2 = "2"; const char* vr = "relu";
  CK(MXSymbolCreateAtomicSymbol("FullyConnected", 1, &kh, &v16, &fc1));
  CK(MXSymbolCompose(fc1, "fc1", 1, NULL, &data));
  CK(MXSymbolCreateAtomicSymbol("Activation", 1, &ka, &vr, &act));
  CK(MXSymbolCompose(act, "act", 1, NULL, &fc1));
  CK(MXSymbolCreateAtomicSymbol("FullyConnected", 1, &kh, &v2, &fc2));
  CK(MXSymbolCompose(fc2, "fc2", 1, NULL, &act));
  CK(MXSymbolCreateAtomicSymbol("SoftmaxOutput", 0, NULL, NULL, &sm));
  SymbolHandle smargs[2]; smargs[0] = fc2; smargs[1] = label;
  CK(MXSymbolCompose(sm, "softmax", 2, NULL, smargs));

  const char* ikeys[2] = {"data", "softmax_label"};
  uint32_t indptr[3] = {0, 2, 3};
  int64_t shp[3] = {B, D, B};
  ExecutorHandle ex;
  CK(MXExecutorSimpleBind(sm, "write", 2, ikeys, indptr, shp, &ex));

  float X[B * D], y[B];
  for (int i = 0; i < B; ++i) {
    for (int j = 0; j < D; ++j) X[i * D + j] = frand();
    y[i] = X[i * D] > 0.0f ? 1.0f : 0.0f;
  }
  NDArrayHandle hx, hy;
  CK(MXExecutorArgArray(ex, "arg", "data", &hx));
  CK(MXExecutorArgArray(ex, "arg", "softmax_label", &hy));
  CK(MXNDArraySyncCopyFromCPU(hx, X, sizeof(X)));
  CK(MXNDArraySyncCopyFromCPU(hy, y, sizeof(y)));

  const char* wn[4] = {"fc1_weight", "fc1_bias", "fc2_weight", "fc2_bias"};
  int wsize[4] = {H * D, H, 2 * H, 2};
  NDArrayHandle w[4], g[4];
  for (int i = 0; i < 4; ++i) {
    CK(MXExecutorArgArray(ex, "arg", wn[i], &w[i]));
    CK(MXExecutorArgArray(ex, "grad", wn[i], &g[i]));
    float buf[H * D];
    for (int j = 0; j < wsize[i]; ++j) buf[j] = 0.1f * frand();
    CK(MXNDArraySyncCopyFromCPU(w[i], buf, wsize[i] * sizeof(float)));
  }

  KVStoreHandle kv;
  CK(MXKVStoreCreate("local", &kv));
  const char* ok = "learning_rate"; const char* ov = "0.01";
  CK(MXKVStoreSetOptimizer(kv, "sgd", 1, &ok, &ov));
  int keys[4] = {0, 1, 2, 3};
  CK(MXKVStoreInit(kv, 4, keys, w));

  float first = -1.0f, loss = 0.0f;
  for (int step = 0; step < 25; ++step) {
    CK(MXExecutorForward(ex, 1));
    int nout = 0;
    NDArrayHandle* outs = NULL;
    CK(MXExecutorOutputs(ex, &nout, &outs));
    float probs[B * 2];
    CK(MXNDArraySyncCopyToCPU(outs[0], probs, sizeof(probs)));
    loss = 0.0f;
    for (int i = 0; i < B; ++i)
      loss -= logf(probs[i * 2 + (int)y[i]] + 1e-9f) / B;
    if (first < 0.0f) first = loss;
    CK(MXExecutorBackward(ex));
    CK(MXKVStorePush(kv, 4, keys, g, 0));
    CK(MXKVStorePull(kv, 4, keys, w, 0));
  }
  if (!(loss < first * 0.5f)) {
    fprintf(stderr, "loss did not halve: %f -> %f\n", first, loss);
    return 2;
  }
  printf("C_TRAIN_OK %f -> %f\n", first, loss);
  MXKVStoreFree(kv);
  MXExecutorFree(ex);
  return 0;
}
"""


def test_standalone_c_training_program(capi, tmp_path):
    """A plain C program (no Python source) composes the MLP, binds it,
    and trains with kvstore sgd until the loss halves — the reference's
    'any frontend can train through the C ABI' property."""
    if shutil.which("gcc") is None:
        pytest.skip("no gcc")
    so = build_c_api()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    csrc = tmp_path / "train.c"
    csrc.write_text(C_TRAIN_PROGRAM)
    exe = tmp_path / "ctrain"
    subprocess.run(
        ["gcc", str(csrc), "-o", str(exe), f"-I{repo}/include",
         so, "-lm", f"-Wl,-rpath,{os.path.dirname(so)}"],
        check=True, capture_output=True)
    env = dict(os.environ, PYTHONPATH=repo, JAX_PLATFORMS="cpu")
    proc = subprocess.run([str(exe)], env=env, capture_output=True,
                          text=True, timeout=300)
    assert proc.returncode == 0, (proc.stdout, proc.stderr)
    assert "C_TRAIN_OK" in proc.stdout


def test_misc_abi_surface(capi, exported_mlp):
    """MXPredReshape keeps weights; NDArray reshape/slice views; symbol
    attrs; kvstore metadata."""
    lib = _train_argtypes(capi)
    vp, u32, cp, c_int = (ctypes.c_void_p, ctypes.c_uint32, ctypes.c_char_p,
                          ctypes.c_int)
    lib.MXPredReshape.argtypes = [u32, ctypes.POINTER(cp),
                                  ctypes.POINTER(u32), ctypes.POINTER(i64),
                                  vp, ctypes.POINTER(vp)]
    lib.MXNDArrayReshape.argtypes = [vp, c_int, ctypes.POINTER(i64),
                                     ctypes.POINTER(vp)]
    lib.MXNDArraySlice.argtypes = [vp, i64, i64, ctypes.POINTER(vp)]
    lib.MXSymbolGetAttr.argtypes = [vp, cp, ctypes.POINTER(cp),
                                    ctypes.POINTER(c_int)]
    lib.MXSymbolSetAttr.argtypes = [vp, cp, cp]
    lib.MXKVStoreGetType.argtypes = [vp, ctypes.POINTER(cp)]
    lib.MXKVStoreGetRank.argtypes = [vp, ctypes.POINTER(c_int)]
    lib.MXKVStoreGetGroupSize.argtypes = [vp, ctypes.POINTER(c_int)]

    # predictor reshape keeps weights (batch 4 -> 2)
    json_path, params_path, xval, expect = exported_mlp
    with open(json_path) as f:
        sym_json = f.read().encode()
    with open(params_path, "rb") as f:
        param_bytes = f.read()
    keys = (cp * 1)(b"data")
    indptr = (u32 * 2)(0, 2)
    shp = (i64 * 2)(4, 8)
    h = vp()
    assert capi.MXPredCreate(sym_json, param_bytes, len(param_bytes), 1, 0,
                             1, keys, indptr, shp, ctypes.byref(h)) == 0
    shp2 = (i64 * 2)(2, 8)
    h2 = vp()
    assert lib.MXPredReshape(1, keys, indptr, shp2, h,
                             ctypes.byref(h2)) == 0, _err(capi)
    x2 = onp.ascontiguousarray(xval[:2])
    assert capi.MXPredSetInput(
        h2, b"data", x2.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        x2.size) == 0
    assert capi.MXPredForward(h2) == 0
    res = onp.zeros((2, 3), "f")
    assert capi.MXPredGetOutput(
        h2, 0, res.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        res.size) == 0
    onp.testing.assert_allclose(res, expect[:2], rtol=1e-5, atol=1e-6)
    capi.MXPredFree(h2)
    capi.MXPredFree(h)

    # ndarray reshape + slice
    a = vp()
    shape = (i64 * 2)(4, 3)
    assert capi.MXNDArrayCreate(shape, 2, 0, ctypes.byref(a)) == 0
    data = onp.arange(12, dtype="f")
    assert capi.MXNDArraySyncCopyFromCPU(a, data.ctypes.data_as(vp),
                                         data.nbytes) == 0
    r = vp()
    newshape = (i64 * 2)(3, 4)
    assert lib.MXNDArrayReshape(a, 2, newshape, ctypes.byref(r)) == 0
    nd_ = ctypes.c_int()
    oshape = (i64 * 8)()
    assert capi.MXNDArrayGetShape(r, ctypes.byref(nd_), oshape) == 0
    assert tuple(oshape[:2]) == (3, 4)
    s = vp()
    assert lib.MXNDArraySlice(a, 1, 3, ctypes.byref(s)) == 0
    assert capi.MXNDArrayGetShape(s, ctypes.byref(nd_), oshape) == 0
    assert tuple(oshape[:2]) == (2, 3)
    for x in (a, r, s):
        capi.MXNDArrayFree(x)

    # symbol attrs
    sym = vp()
    lib.MXSymbolCreateVariable(b"w", ctypes.byref(sym))
    assert lib.MXSymbolSetAttr(sym, b"__lr_mult__", b"2.5") == 0
    val = cp()
    ok = c_int()
    assert lib.MXSymbolGetAttr(sym, b"__lr_mult__", ctypes.byref(val),
                               ctypes.byref(ok)) == 0
    assert ok.value == 1 and val.value == b"2.5"
    assert lib.MXSymbolGetAttr(sym, b"missing", ctypes.byref(val),
                               ctypes.byref(ok)) == 0
    assert ok.value == 0
    lib.MXSymbolFree(sym)

    # kvstore metadata
    kv = vp()
    assert lib.MXKVStoreCreate(b"local", ctypes.byref(kv)) == 0
    t = cp()
    assert lib.MXKVStoreGetType(kv, ctypes.byref(t)) == 0
    assert t.value == b"local"
    rank = c_int(); size = c_int()
    assert lib.MXKVStoreGetRank(kv, ctypes.byref(rank)) == 0
    assert lib.MXKVStoreGetGroupSize(kv, ctypes.byref(size)) == 0
    assert rank.value == 0 and size.value >= 1
    lib.MXKVStoreFree(kv)


def test_attr_on_uncomposed_atomic_symbol(capi):
    """Reference ordering: SetAttr on an atomic symbol BEFORE Compose;
    the attr must survive composition."""
    lib = _train_argtypes(capi)
    vp, cp, c_int = ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int
    fc = vp()
    lib.MXSymbolCreateAtomicSymbol(b"FullyConnected", 1,
                                   (cp * 1)(b"num_hidden"), (cp * 1)(b"2"),
                                   ctypes.byref(fc))
    assert lib.MXSymbolSetAttr(fc, b"__lr_mult__", b"3.0") == 0, _err(capi)
    val = cp(); ok = c_int()
    assert lib.MXSymbolGetAttr(fc, b"__lr_mult__", ctypes.byref(val),
                               ctypes.byref(ok)) == 0
    assert ok.value == 1 and val.value == b"3.0"
    data = vp()
    lib.MXSymbolCreateVariable(b"data", ctypes.byref(data))
    assert lib.MXSymbolCompose(fc, b"fc", 1, None, (vp * 1)(data)) == 0
    assert lib.MXSymbolGetAttr(fc, b"__lr_mult__", ctypes.byref(val),
                               ctypes.byref(ok)) == 0
    assert ok.value == 1 and val.value == b"3.0"
    lib.MXSymbolFree(fc)
    lib.MXSymbolFree(data)


def test_c_ndarray_save_load_roundtrip(capi, tmp_path):
    """A C frontend can checkpoint what it trained: Save handles with
    names, Load them back, bytes identical (reference MXNDArraySave)."""
    lib = _train_argtypes(capi)
    vp, u32, cp = ctypes.c_void_p, ctypes.c_uint32, ctypes.c_char_p
    lib.MXNDArraySave.argtypes = [cp, u32, ctypes.POINTER(vp),
                                  ctypes.POINTER(cp)]
    lib.MXNDArrayLoad.argtypes = [cp, ctypes.POINTER(u32),
                                  ctypes.POINTER(ctypes.POINTER(vp)),
                                  ctypes.POINTER(u32),
                                  ctypes.POINTER(ctypes.POINTER(cp))]
    a = vp()
    shape = (i64 * 2)(2, 3)
    assert capi.MXNDArrayCreate(shape, 2, 0, ctypes.byref(a)) == 0
    data = onp.arange(6, dtype="f") * 1.5
    assert capi.MXNDArraySyncCopyFromCPU(a, data.ctypes.data_as(vp),
                                         data.nbytes) == 0
    fname = str(tmp_path / "ck.params").encode()
    keys = (cp * 1)(b"arg:w")
    assert lib.MXNDArraySave(fname, 1, (vp * 1)(a), keys) == 0, _err(capi)
    n = u32(); nn = u32()
    arrs = ctypes.POINTER(vp)()
    names = ctypes.POINTER(cp)()
    assert lib.MXNDArrayLoad(fname, ctypes.byref(n), ctypes.byref(arrs),
                             ctypes.byref(nn),
                             ctypes.byref(names)) == 0, _err(capi)
    assert n.value == 1 and nn.value == 1
    assert names[0] == b"arg:w"
    back = onp.zeros(6, "f")
    assert capi.MXNDArraySyncCopyToCPU(arrs[0], back.ctypes.data_as(vp),
                                       back.nbytes) == 0
    onp.testing.assert_allclose(back, data)
    # python side reads the same file (cross-surface interop)
    loaded = nd.load(str(tmp_path / "ck.params"))
    onp.testing.assert_allclose(loaded["arg:w"].asnumpy().ravel(), data)
    capi.MXNDArrayFree(a)


def test_c_ndarray_save_duplicate_keys(capi, tmp_path):
    """Duplicate names write sequentially like the reference list
    container — not silently collapsed through a dict."""
    import struct as _struct

    lib = _train_argtypes(capi)
    vp, cp = ctypes.c_void_p, ctypes.c_char_p
    arrs = []
    for val in (1.0, 2.0):
        a = vp()
        shape = (i64 * 1)(2)
        assert capi.MXNDArrayCreate(shape, 1, 0, ctypes.byref(a)) == 0
        d = onp.full(2, val, "f")
        capi.MXNDArraySyncCopyFromCPU(a, d.ctypes.data_as(vp), d.nbytes)
        arrs.append(a)
    fname = str(tmp_path / "dup.params")
    keys = (cp * 2)(b"w", b"w")
    assert lib.MXNDArraySave(fname.encode(), 2, (vp * 2)(*arrs),
                             keys) == 0, _err(capi)
    with open(fname, "rb") as f:
        buf = f.read()
    (count,) = _struct.unpack_from("<Q", buf, 16)
    assert count == 2  # both entries on disk
    # and MXNDArrayLoad returns BOTH entries (parallel arrays, unlike
    # the python dict view)
    u32 = ctypes.c_uint32
    n = u32(); nn = u32()
    la = ctypes.POINTER(vp)()
    ln = ctypes.POINTER(cp)()
    assert lib.MXNDArrayLoad(fname.encode(), ctypes.byref(n),
                             ctypes.byref(la), ctypes.byref(nn),
                             ctypes.byref(ln)) == 0, _err(capi)
    assert n.value == 2 and nn.value == 2
    assert ln[0] == b"w" and ln[1] == b"w"
    back = onp.zeros(2, "f")
    capi.MXNDArraySyncCopyToCPU(la[0], back.ctypes.data_as(vp), back.nbytes)
    onp.testing.assert_allclose(back, [1.0, 1.0])
    capi.MXNDArraySyncCopyToCPU(la[1], back.ctypes.data_as(vp), back.nbytes)
    onp.testing.assert_allclose(back, [2.0, 2.0])
    for a in arrs:
        capi.MXNDArrayFree(a)


def test_data_iter_c_abi(capi, tmp_path):
    """MXListDataIters + CSVIter through the C handle API (reference:
    c_api.cc MXDataIterCreateIter family)."""
    vp, c_int, u32 = ctypes.c_void_p, ctypes.c_int, ctypes.c_uint32
    lib = capi
    lib.MXListDataIters.argtypes = [
        ctypes.POINTER(u32), ctypes.POINTER(ctypes.POINTER(ctypes.c_char_p))]
    lib.MXDataIterCreateIter.argtypes = [
        ctypes.c_char_p, u32, ctypes.POINTER(ctypes.c_char_p),
        ctypes.POINTER(ctypes.c_char_p), ctypes.POINTER(vp)]
    lib.MXDataIterFree.argtypes = [vp]
    lib.MXDataIterNext.argtypes = [vp, ctypes.POINTER(c_int)]
    lib.MXDataIterBeforeFirst.argtypes = [vp]
    lib.MXDataIterGetData.argtypes = [vp, ctypes.POINTER(vp)]
    lib.MXDataIterGetLabel.argtypes = [vp, ctypes.POINTER(vp)]
    lib.MXDataIterGetPadNum.argtypes = [vp, ctypes.POINTER(c_int)]

    n = u32()
    names = ctypes.POINTER(ctypes.c_char_p)()
    assert lib.MXListDataIters(ctypes.byref(n), ctypes.byref(names)) == 0
    listed = [names[i].decode() for i in range(n.value)]
    assert "CSVIter" in listed and "ImageRecordIter" in listed

    data = onp.arange(24, dtype="f").reshape(8, 3)
    labels = onp.arange(8, dtype="f")
    dcsv = tmp_path / "d.csv"
    lcsv = tmp_path / "l.csv"
    dcsv.write_text("\n".join(",".join(str(v) for v in row)
                              for row in data) + "\n")
    lcsv.write_text("\n".join(str(v) for v in labels) + "\n")

    keys = (ctypes.c_char_p * 4)(b"data_csv", b"data_shape",
                                 b"label_csv", b"batch_size")
    vals = (ctypes.c_char_p * 4)(str(dcsv).encode(), b"(3,)",
                                 str(lcsv).encode(), b"4")
    it = vp()
    rc = lib.MXDataIterCreateIter(b"CSVIter", 4, keys, vals,
                                  ctypes.byref(it))
    assert rc == 0, _err(lib)

    seen_rows = []
    for _epoch in range(2):  # BeforeFirst resets for a second epoch
        while True:
            has = c_int()
            assert lib.MXDataIterNext(it, ctypes.byref(has)) == 0
            if not has.value:
                break
            d = vp()
            assert lib.MXDataIterGetData(it, ctypes.byref(d)) == 0, _err(lib)
            ndim = c_int()
            shape = (i64 * 8)()
            assert lib.MXNDArrayGetShape(d, ctypes.byref(ndim), shape) == 0
            dims = tuple(shape[i] for i in range(ndim.value))
            assert dims == (4, 3)
            buf = (ctypes.c_float * 12)()
            assert lib.MXNDArraySyncCopyToCPU(
                d, ctypes.cast(buf, vp), ctypes.sizeof(buf)) == 0
            seen_rows.append(onp.array(buf).reshape(4, 3).copy())
            lab = vp()
            assert lib.MXDataIterGetLabel(it, ctypes.byref(lab)) == 0, \
                _err(lib)
            pad = c_int()
            assert lib.MXDataIterGetPadNum(it, ctypes.byref(pad)) == 0
            assert pad.value == 0
            lib.MXNDArrayFree(d)
            lib.MXNDArrayFree(lab)
        assert lib.MXDataIterBeforeFirst(it) == 0
    got = onp.concatenate(seen_rows)
    assert got.shape == (16, 3)
    onp.testing.assert_allclose(got[:8], data, rtol=1e-6)
    onp.testing.assert_allclose(got[8:], data, rtol=1e-6)  # epoch 2
    lib.MXDataIterFree(it)


C_HYBRID_TRAIN_PROGRAM = r"""
#include <math.h>
#include <stdio.h>
#include <stdlib.h>
#include <string.h>
#include "mxnet_tpu/c_api.h"

#define B 32
#define D 8
#define H 16
#define NC 2
#define CK(x) do { if ((x) != 0) { \
  fprintf(stderr, "%s\n", MXGetLastError()); return 1; } } while (0)

static unsigned lcg = 7u;
static float frand(void) {
  lcg = lcg * 1664525u + 1013904223u;
  return ((lcg >> 8) / 8388608.0f) - 1.0f;
}

static NDArrayHandle mk(int ndim, const int64_t* shape, const float* src,
                        int n) {
  NDArrayHandle h = NULL;
  if (MXNDArrayCreate(shape, ndim, 0, &h) != 0) return NULL;
  if (src != NULL &&
      MXNDArraySyncCopyFromCPU(h, src, n * sizeof(float)) != 0) return NULL;
  return h;
}

int main(void) {
  /* profiler on from the start (reference: MXSetProcessProfilerConfig) */
  const char* pk[3] = {"filename", "profile_imperative", "aggregate_stats"};
  const char* pv[3] = {"c_hybrid_profile.json", "True", "True"};
  CK(MXSetProcessProfilerConfig(3, pk, pv));
  CK(MXSetProcessProfilerState(1));
  CK(MXRandomSeed(17));

  /* compose the MLP symbol and hybridize it as a CachedOp */
  SymbolHandle data, fc1, act, fc2;
  CK(MXSymbolCreateVariable("data", &data));
  const char* kh = "num_hidden"; const char* ka = "act_type";
  const char* v16 = "16"; const char* v2 = "2"; const char* vr = "relu";
  CK(MXSymbolCreateAtomicSymbol("FullyConnected", 1, &kh, &v16, &fc1));
  CK(MXSymbolCompose(fc1, "fc1", 1, NULL, &data));
  CK(MXSymbolCreateAtomicSymbol("Activation", 1, &ka, &vr, &act));
  CK(MXSymbolCompose(act, "act", 1, NULL, &fc1));
  CK(MXSymbolCreateAtomicSymbol("FullyConnected", 1, &kh, &v2, &fc2));
  CK(MXSymbolCompose(fc2, "fc2", 1, NULL, &act));
  CachedOpHandle cop;
  CK(MXCreateCachedOp(fc2, &cop));

  /* inputs in list_arguments order: data, fc1_w, fc1_b, fc2_w, fc2_b */
  float X[B * D], y[B];
  for (int i = 0; i < B; ++i) {
    float s = 0.0f;
    for (int j = 0; j < D; ++j) { X[i * D + j] = frand(); s += X[i * D + j]; }
    y[i] = s > 0.0f ? 1.0f : 0.0f;
  }
  int64_t shx[2] = {B, D};
  NDArrayHandle hx = mk(2, shx, X, B * D);
  if (hx == NULL) { fprintf(stderr, "%s\n", MXGetLastError()); return 1; }

  int wsize[4] = {H * D, H, NC * H, NC};
  int64_t wsh[4][2] = {{H, D}, {H, 1}, {NC, H}, {NC, 1}};
  int wnd[4] = {2, 1, 2, 1};
  NDArrayHandle w[4], g[4];
  float wbuf[4][H * D];
  for (int i = 0; i < 4; ++i) {
    for (int j = 0; j < wsize[i]; ++j) wbuf[i][j] = 0.2f * frand();
    w[i] = mk(wnd[i], wsh[i], wbuf[i], wsize[i]);
    g[i] = mk(wnd[i], wsh[i], NULL, 0);
    if (w[i] == NULL || g[i] == NULL) {
      fprintf(stderr, "%s\n", MXGetLastError()); return 1;
    }
  }
  uint32_t reqs[4] = {1, 1, 1, 1};  /* write */
  CK(MXAutogradMarkVariables(4, w, reqs, g));

  float first = -1.0f, loss = 0.0f, lr = 0.5f;
  for (int step = 0; step < 80; ++step) {
    int prev_rec = 0, prev_train = 0;
    CK(MXAutogradSetIsRecording(1, &prev_rec));
    CK(MXAutogradSetIsTraining(1, &prev_train));
    NDArrayHandle ins[5] = {hx, w[0], w[1], w[2], w[3]};
    int nout = 0; NDArrayHandle* outs = NULL;
    CK(MXInvokeCachedOp(cop, 5, ins, &nout, &outs));
    if (nout != 1) { fprintf(stderr, "nout=%d\n", nout); return 3; }

    float logits[B * NC], dlogits[B * NC];
    CK(MXNDArraySyncCopyToCPU(outs[0], logits, sizeof(logits)));
    loss = 0.0f;
    for (int i = 0; i < B; ++i) {
      float m = logits[i * NC] > logits[i * NC + 1] ? logits[i * NC]
                                                    : logits[i * NC + 1];
      float e0 = expf(logits[i * NC] - m), e1 = expf(logits[i * NC + 1] - m);
      float z = e0 + e1;
      float p[2] = {e0 / z, e1 / z};
      loss -= logf(p[(int)y[i]] + 1e-9f) / B;
      dlogits[i * NC] = (p[0] - (y[i] < 0.5f ? 1.0f : 0.0f)) / B;
      dlogits[i * NC + 1] = (p[1] - (y[i] < 0.5f ? 0.0f : 1.0f)) / B;
    }
    if (first < 0.0f) first = loss;

    /* recording only needs to cover the forward; stop it before
     * creating host-seeded arrays (in-place fills are untapeable) */
    CK(MXAutogradSetIsRecording(0, &prev_rec));
    CK(MXAutogradSetIsTraining(0, &prev_train));
    int64_t shl[2] = {B, NC};
    NDArrayHandle hg = mk(2, shl, dlogits, B * NC);
    if (hg == NULL) { fprintf(stderr, "%s\n", MXGetLastError()); return 1; }
    NDArrayHandle heads[1] = {outs[0]};
    NDArrayHandle hgs[1] = {hg};
    CK(MXAutogradBackward(1, heads, hgs, 0, 1));
    MXNDArrayFree(hg);

    /* sgd step: pull grads through MXNDArrayGetGrad, update on host */
    for (int i = 0; i < 4; ++i) {
      NDArrayHandle gi = NULL;
      CK(MXNDArrayGetGrad(w[i], &gi));
      float gb[H * D];
      CK(MXNDArraySyncCopyToCPU(gi, gb, wsize[i] * sizeof(float)));
      MXNDArrayFree(gi);
      for (int j = 0; j < wsize[i]; ++j) wbuf[i][j] -= lr * gb[j];
      CK(MXNDArraySyncCopyFromCPU(w[i], wbuf[i],
                                  wsize[i] * sizeof(float)));
    }
  }

  CK(MXSetProcessProfilerState(0));
  const char* stats = NULL;
  CK(MXAggregateProfileStatsPrint(&stats, 0));
  if (stats == NULL || strstr(stats, "fully_connected") == NULL) {
    fprintf(stderr, "profiler stats missing ops:\n%s\n",
            stats ? stats : "(null)");
    return 4;
  }
  CK(MXDumpProcessProfile(1));
  FILE* f = fopen("c_hybrid_profile.json", "r");
  if (f == NULL) { fprintf(stderr, "no profile dump\n"); return 5; }
  fclose(f);

  if (!(loss < first * 0.5f)) {
    fprintf(stderr, "loss did not halve: %f -> %f\n", first, loss);
    return 2;
  }
  printf("C_HYBRID_TRAIN_OK %f -> %f\n", first, loss);
  MXFreeCachedOp(cop);
  return 0;
}
"""


def test_standalone_c_hybridize_train_profile(capi, tmp_path):
    """VERDICT r4 item 7 done-criterion: a C program that hybridizes
    (CachedOp), trains (autograd record/backward over the C ABI), and
    dumps a profile (profiler config/state/dump/stats)."""
    if shutil.which("gcc") is None:
        pytest.skip("no gcc")
    so = build_c_api()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    csrc = tmp_path / "hybrid_train.c"
    csrc.write_text(C_HYBRID_TRAIN_PROGRAM)
    exe = tmp_path / "chybrid"
    subprocess.run(
        ["gcc", str(csrc), "-o", str(exe), f"-I{repo}/include",
         so, "-lm", f"-Wl,-rpath,{os.path.dirname(so)}"],
        check=True, capture_output=True)
    env = dict(os.environ, PYTHONPATH=repo, JAX_PLATFORMS="cpu")
    proc = subprocess.run([str(exe)], env=env, capture_output=True,
                          text=True, timeout=300, cwd=tmp_path)
    assert proc.returncode == 0, (proc.stdout, proc.stderr)
    assert "C_HYBRID_TRAIN_OK" in proc.stdout
    assert (tmp_path / "c_hybrid_profile.json").exists()


def test_cached_op_jit_cache_via_ctypes(capi):
    """Outside recording, repeated CachedOp invokes reuse one compiled
    callable per signature (the cache that makes it 'cached')."""
    import mxnet_tpu.c_bridge as cb
    from mxnet_tpu import sym as S

    x = S.var("data")
    net = S.FullyConnected(x, name="cfc", num_hidden=4)
    cop = cb.cached_op_create([net])
    a = nd.array(onp.ones((2, 3), "f"))
    pw = nd.array(onp.ones((4, 3), "f") * 0.1)
    pb = nd.array(onp.zeros((4,), "f"))
    o1 = cop([a, pw, pb])
    assert len(cop._jitted) == 1
    o2 = cop([a, pw, pb])
    assert len(cop._jitted) == 1
    onp.testing.assert_allclose(o1[0].asnumpy(), o2[0].asnumpy())
    b = nd.array(onp.ones((5, 3), "f"))
    cop([b, pw, pb])
    assert len(cop._jitted) == 2


def test_op_introspection_abi(capi):
    """MXListAllOpNames + MXSymbolGetAtomicSymbolInfo — the surface a
    frontend uses to autogenerate its op bindings (reference c_api.cc)."""
    lib = capi
    vp, u32, cp = ctypes.c_void_p, ctypes.c_uint32, ctypes.c_char_p
    lib.MXListAllOpNames.argtypes = [ctypes.POINTER(u32),
                                     ctypes.POINTER(ctypes.POINTER(cp))]
    n = u32()
    arr = ctypes.POINTER(cp)()
    assert lib.MXListAllOpNames(ctypes.byref(n), ctypes.byref(arr)) == 0
    names = [arr[i].decode() for i in range(n.value)]
    assert n.value > 300, n.value
    assert "convolution" in names and "fully_connected" in names

    lib.MXSymbolGetAtomicSymbolInfo.argtypes = [
        cp, ctypes.POINTER(cp), ctypes.POINTER(cp), ctypes.POINTER(u32),
        ctypes.POINTER(ctypes.POINTER(cp)),
        ctypes.POINTER(ctypes.POINTER(cp))]
    nm, desc = cp(), cp()
    na = u32()
    an = ctypes.POINTER(cp)()
    ad = ctypes.POINTER(cp)()
    assert lib.MXSymbolGetAtomicSymbolInfo(
        b"convolution", ctypes.byref(nm), ctypes.byref(desc),
        ctypes.byref(na), ctypes.byref(an), ctypes.byref(ad)) == 0
    assert nm.value == b"convolution"
    args = [an[i].decode() for i in range(na.value)]
    assert "data" in args and "kernel" in args
    defaults = [ad[i].decode() for i in range(na.value)]
    assert defaults[args.index("num_group")] == "1"
    # unknown op errors cleanly
    assert lib.MXSymbolGetAtomicSymbolInfo(
        b"no_such_op", ctypes.byref(nm), ctypes.byref(desc),
        ctypes.byref(na), ctypes.byref(an), ctypes.byref(ad)) == -1


def test_infer_shape_type_abi(capi):
    """MXSymbolInferShape/InferType over a composed MLP."""
    lib = capi
    vp, u32, cp = ctypes.c_void_p, ctypes.c_uint32, ctypes.c_char_p
    lib.MXSymbolInferShape.argtypes = [
        vp, u32, ctypes.POINTER(cp), ctypes.POINTER(u32),
        ctypes.POINTER(i64), ctypes.POINTER(u32),
        ctypes.POINTER(ctypes.POINTER(i64)),
        ctypes.POINTER(ctypes.POINTER(i64)),
        ctypes.POINTER(ctypes.POINTER(i64))]
    lib.MXSymbolInferType.argtypes = [
        vp, u32, ctypes.POINTER(cp), ctypes.POINTER(ctypes.c_int),
        ctypes.POINTER(u32), ctypes.POINTER(ctypes.POINTER(ctypes.c_int)),
        ctypes.POINTER(ctypes.POINTER(i64))]

    data = vp()
    fc = vp()
    assert capi.MXSymbolCreateVariable(b"data", ctypes.byref(data)) == 0
    kh, v8 = ctypes.c_char_p(b"num_hidden"), ctypes.c_char_p(b"8")
    assert capi.MXSymbolCreateAtomicSymbol(
        b"FullyConnected", 1, ctypes.byref(kh), ctypes.byref(v8),
        ctypes.byref(fc)) == 0
    assert capi.MXSymbolCompose(fc, b"fc", 1, None, ctypes.byref(data)) == 0

    keys = (cp * 1)(b"data")
    indptr = (u32 * 2)(0, 2)
    dims = (i64 * 2)(4, 16)
    total = u32()
    ndims = ctypes.POINTER(i64)()
    ddata = ctypes.POINTER(i64)()
    sect = ctypes.POINTER(i64)()
    assert lib.MXSymbolInferShape(
        fc, 1, keys, indptr, dims, ctypes.byref(total),
        ctypes.byref(ndims), ctypes.byref(ddata),
        ctypes.byref(sect)) == 0, _err(capi)
    n_args, n_outs, n_aux = sect[0], sect[1], sect[2]
    assert n_args == 3 and n_outs == 1 and n_aux == 0
    # walk the flattened dims: data(4,16), fc_weight(8,16), fc_bias(8)
    shapes = []
    off = 0
    for i in range(total.value):
        nd_ = ndims[i]
        if nd_ < 0:
            shapes.append(None)
        else:
            shapes.append(tuple(ddata[off + d] for d in range(nd_)))
            off += nd_
    assert shapes[0] == (4, 16)
    assert shapes[1] == (8, 16)
    assert shapes[2] == (8,)
    assert shapes[3] == (4, 8)  # output

    tkeys = (cp * 1)(b"data")
    tflags = (ctypes.c_int * 1)(0)  # 0 = float32
    ttotal = u32()
    ttypes = ctypes.POINTER(ctypes.c_int)()
    tsect = ctypes.POINTER(i64)()
    assert lib.MXSymbolInferType(
        fc, 1, tkeys, tflags, ctypes.byref(ttotal), ctypes.byref(ttypes),
        ctypes.byref(tsect)) == 0, _err(capi)
    assert ttotal.value == 4
    assert all(ttypes[i] == 0 for i in range(4))  # all float32


def test_nd_at_and_context_abi(capi):
    lib = capi
    vp, u32 = ctypes.c_void_p, ctypes.c_uint32
    lib.MXNDArrayAt.argtypes = [vp, u32, ctypes.POINTER(vp)]
    lib.MXNDArrayGetContext.argtypes = [vp, ctypes.POINTER(ctypes.c_int),
                                        ctypes.POINTER(ctypes.c_int)]
    shape = (i64 * 2)(3, 4)
    h = vp()
    assert capi.MXNDArrayCreate(shape, 2, 0, ctypes.byref(h)) == 0
    buf = onp.arange(12, dtype="f")
    assert capi.MXNDArraySyncCopyFromCPU(
        h, buf.ctypes.data_as(vp), buf.nbytes) == 0
    row = vp()
    assert lib.MXNDArrayAt(h, 1, ctypes.byref(row)) == 0
    out = onp.zeros(4, "f")
    assert capi.MXNDArraySyncCopyToCPU(
        row, out.ctypes.data_as(vp), out.nbytes) == 0
    onp.testing.assert_allclose(out, buf.reshape(3, 4)[1])
    dt, di = ctypes.c_int(), ctypes.c_int()
    assert lib.MXNDArrayGetContext(h, ctypes.byref(dt),
                                   ctypes.byref(di)) == 0
    assert dt.value in (1, 2)
    capi.MXNDArrayFree(row)
    capi.MXNDArrayFree(h)


def test_infer_shape_reports_aux_shapes(capi):
    """Aux states (BN moving stats) must come back with real shapes —
    frontends allocate them from MXSymbolInferShape (r5 review fix)."""
    import mxnet_tpu.c_bridge as cb

    data = cb.sym_var("data")
    bn = cb.sym_create_atomic("BatchNorm", [], [])
    cb.sym_compose(bn, "bn", [], [data])
    args, arg_shapes, out_shapes, auxs, aux_shapes = cb.sym_infer_shape(
        bn, ["data"], [(2, 4)])
    assert auxs == ["bn_moving_mean", "bn_moving_var"]
    assert aux_shapes == [(4,), (4,)], aux_shapes
