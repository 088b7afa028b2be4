"""Single-file deploy bundles (reference: amalgamation/ — here the
bundle is generated jax source with embedded weights; the test runs it
in a subprocess with mxnet_tpu NOT importable, proving the deploy-site
dependency set is jax+numpy only)."""
import os
import subprocess
import sys

import numpy as onp

import mxnet_tpu as mx
from mxnet_tpu import nd, sym
from mxnet_tpu.tools.amalgamate import amalgamate


def _export_convnet():
    data = sym.Variable("data")
    c1 = sym.Convolution(data, name="c1", kernel=(3, 3), num_filter=4,
                         pad=(1, 1))
    bn = sym.BatchNorm(c1, name="bn1", fix_gamma=False)
    act = sym.Activation(bn, act_type="relu")
    pool = sym.Pooling(act, kernel=(2, 2), stride=(2, 2), pool_type="max")
    fc = sym.FullyConnected(sym.Flatten(pool), name="fc", num_hidden=3)
    out = sym.softmax(fc)
    rng = onp.random.RandomState(0)
    params = {
        "c1_weight": rng.randn(4, 1, 3, 3).astype("f") * 0.2,
        "c1_bias": rng.randn(4).astype("f") * 0.1,
        "bn1_gamma": rng.rand(4).astype("f") + 0.5,
        "bn1_beta": rng.randn(4).astype("f") * 0.1,
        "bn1_moving_mean": rng.randn(4).astype("f") * 0.1,
        "bn1_moving_var": rng.rand(4).astype("f") + 0.5,
        "fc_weight": rng.randn(3, 4 * 4 * 4).astype("f") * 0.1,
        "fc_bias": rng.randn(3).astype("f") * 0.1,
    }
    return out, params


def test_amalgamated_bundle_matches_framework(tmp_path):
    out, params = _export_convnet()
    x = onp.random.RandomState(1).rand(2, 1, 8, 8).astype("f")
    # framework reference output (inference semantics)
    args = {"data": nd.array(x)}
    args.update({k: nd.array(v) for k, v in params.items()
                 if "moving" not in k})
    aux = {k: nd.array(v) for k, v in params.items() if "moving" in k}
    ex = out.bind(args=args, aux_states=aux)
    want = ex.forward(is_train=False)[0].asnumpy()

    src = amalgamate(out.tojson(), params)
    bundle = tmp_path / "predict_model.py"
    bundle.write_text(src)
    driver = tmp_path / "drive.py"
    driver.write_text(
        "import sys, numpy as np\n"
        "import predict_model as m\n"
        "x = np.load(sys.argv[1])\n"
        "np.save(sys.argv[2], m.predict(x))\n"
        "assert 'mxnet_tpu' not in sys.modules, 'deploy leaked mxnet_tpu'\n")
    xin = tmp_path / "x.npy"
    onp.save(xin, x)
    yout = tmp_path / "y.npy"
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, str(driver), str(xin), str(yout)],
        cwd=tmp_path, env=env, capture_output=True, text=True,
        timeout=240)
    assert proc.returncode == 0, (proc.stdout, proc.stderr)
    got = onp.load(yout)
    onp.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_amalgamate_rejects_out_of_set_ops():
    import pytest

    data = sym.Variable("data")
    out = sym.LRN(data, nsize=3)
    with pytest.raises(ValueError, match="deploy op set"):
        amalgamate(out.tojson(), {})
