"""64-bit tensor-size story (reference: include/mxnet/libinfo.h:126
INT64_TENSOR_SIZE; tests/nightly/test_large_vector.py). The knob is
MXNET_INT64_TENSOR_SIZE=1 → JAX x64 mode. These tests exercise both sides:
the loud truncation warning when off, and real int64 arithmetic when on
(in a subprocess, since x64 must be set before first jax use).
"""
import os
import subprocess
import sys
import warnings

import numpy as onp
import pytest


def test_int64_request_warns_loudly():
    import mxnet_tpu as mx
    from mxnet_tpu.ndarray import ndarray as nd_mod

    if mx.nd.array([1]).data.dtype == onp.int64:
        pytest.skip("x64 already enabled in this process")
    nd_mod._warned_int64 = False
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        a = mx.nd.array([7], dtype="int64")
    msgs = [str(x.message) for x in w]
    assert any("MXNET_INT64_TENSOR_SIZE" in m for m in msgs), msgs
    # out-of-range values fail loudly rather than silently wrapping
    with pytest.raises(OverflowError):
        mx.nd.array([2 ** 40], dtype="int64")
    # warned once, not per call
    with warnings.catch_warnings(record=True) as w2:
        warnings.simplefilter("always")
        mx.nd.array([1], dtype="int64")
    assert not any("MXNET_INT64_TENSOR_SIZE" in str(x.message) for x in w2)


_CHILD = r"""
import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["MXNET_INT64_TENSOR_SIZE"] = "1"
import numpy as onp
import mxnet_tpu as mx

# int64 values beyond 2**31 survive round trips (large-vector analog)
a = mx.nd.array([2 ** 40, 2 ** 41], dtype="int64")
assert a.dtype == onp.int64, a.dtype
v = a.asnumpy()
assert v.tolist() == [2 ** 40, 2 ** 41], v
b = (a + a)
assert b.asnumpy().tolist() == [2 ** 41, 2 ** 42]
# arange/indexing keep int64 semantics
idx = mx.nd.array([1], dtype="int64")
took = a.take(idx)
assert took.asnumpy().tolist() == [2 ** 41]
# float64 honored too
f = mx.nd.array([1.0], dtype="float64")
assert f.dtype == onp.float64
# mx.np side
from mxnet_tpu import np as mnp
z = mnp.array([2 ** 40], dtype="int64")
assert int(z.asnumpy()[0]) == 2 ** 40
print("INT64-OK")
"""


def test_int64_mode_end_to_end():
    env = dict(os.environ)
    r = subprocess.run([sys.executable, "-c", _CHILD], env=env,
                       capture_output=True, text=True, timeout=300)
    assert "INT64-OK" in r.stdout, r.stdout + r.stderr
