"""The reference user workflow end to end across subsystems: Module.fit
training → save_checkpoint (symbol.json + arg:/aux: params) → reload
three independent ways (Module.load, C-API predictor, amalgamated
single-file bundle) — all four prediction paths must agree exactly
(reference: example/image-classification save/deploy flow +
c_predict_api + amalgamation)."""
import os
import subprocess
import sys

import numpy as onp

import mxnet_tpu as mx
from mxnet_tpu import nd, sym, io
from mxnet_tpu.module import Module
from mxnet_tpu.test_utils import assert_almost_equal, with_seed


@with_seed(21)
def test_train_checkpoint_predict_amalgamate_agree(tmp_path):
    rs = onp.random.RandomState(0)
    X = rs.randn(192, 10).astype("f")
    y = (X[:, :5].sum(1) > X[:, 5:].sum(1)).astype("f")

    # 1. train through the symbolic path (BN included: aux states must
    # survive every reload below)
    data = sym.Variable("data")
    net = sym.FullyConnected(data, name="wf_fc1", num_hidden=16)
    net = sym.BatchNorm(net, name="wf_bn", fix_gamma=False)
    net = sym.Activation(net, act_type="relu")
    net = sym.FullyConnected(net, name="wf_fc2", num_hidden=2)
    out = sym.SoftmaxOutput(net, sym.Variable("softmax_label"),
                            name="softmax")
    mod = Module(out, context=mx.cpu())
    it = io.NDArrayIter(X, y, batch_size=64, shuffle=True)
    mod.fit(it, num_epoch=6, optimizer="sgd",
            optimizer_params={"learning_rate": 0.05, "momentum": 0.9})
    acc = dict(mod.score(io.NDArrayIter(X, y, batch_size=64), "acc"))
    assert acc["accuracy"] > 0.85, acc

    # 2. checkpoint in the reference layout
    prefix = str(tmp_path / "wf")
    mod.save_checkpoint(prefix, 6)
    assert os.path.isfile(prefix + "-symbol.json")
    assert os.path.isfile(prefix + "-0006.params")

    xq = X[:8]
    mod_batch = io.DataBatch(data=[nd.array(xq)])
    mod.forward(mod_batch, is_train=False)
    want = mod.get_outputs()[0].asnumpy()

    # 3a. reload through Module.load
    mod2 = Module.load(prefix, 6, context=mx.cpu())
    mod2.bind(data_shapes=[("data", (8, 10))], for_training=False,
              label_shapes=None)
    mod2.init_params()  # applies the checkpoint params loaded above
    mod2.forward(mod_batch, is_train=False)
    assert_almost_equal(mod2.get_outputs()[0].asnumpy(), want, rtol=1e-5,
                        atol=1e-6)

    # 3b. reload through the C-predictor surface
    from mxnet_tpu.c_bridge import CPredictor

    with open(prefix + "-symbol.json") as f:
        sym_json = f.read()
    with open(prefix + "-0006.params", "rb") as f:
        params_bytes = f.read()
    pred = CPredictor(sym_json, params_bytes,
                      input_shapes={"data": (8, 10)})
    pred.set_input("data", onp.ascontiguousarray(xq).tobytes())
    pred.forward()
    got_c = onp.frombuffer(pred.output_bytes(0), "f").reshape(8, 2)
    assert_almost_equal(got_c, want, rtol=1e-5, atol=1e-6)

    # 3c. reload through the amalgamated single-file bundle, run where
    # mxnet_tpu is NOT importable
    from mxnet_tpu.tools.amalgamate import amalgamate

    loaded = nd.load(prefix + "-0006.params")
    src = amalgamate(sym_json, {k: v.asnumpy() for k, v in loaded.items()})
    (tmp_path / "wf_bundle.py").write_text(src)
    drive = tmp_path / "drive.py"
    drive.write_text(
        "import sys, numpy as np\n"
        "import wf_bundle\n"
        "x = np.load(sys.argv[1])\n"
        "np.save(sys.argv[2], wf_bundle.predict(x))\n"
        "assert 'mxnet_tpu' not in sys.modules\n")
    onp.save(tmp_path / "xq.npy", xq)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, str(drive), str(tmp_path / "xq.npy"),
         str(tmp_path / "out.npy")],
        cwd=tmp_path, env=env, capture_output=True, text=True,
        timeout=240)
    assert proc.returncode == 0, (proc.stdout, proc.stderr)
    got_bundle = onp.load(tmp_path / "out.npy")
    assert_almost_equal(got_bundle, want, rtol=1e-5, atol=1e-6)
